"""Pallas TPU probe of a cascade's unfrozen structures, each tiled by its
own windows.

The cascade answers a query by probing Q0 plus every disk level.  The
wrapper (``ops.cascade_lookup``) hashes the batch once and sorts it once
by the canonical p-bit fingerprint: requotienting is monotone, so that
one order is sorted by every structure's quotient.

Each structure then groups the sorted queries by its *own* window blocks
(``qf_probe.window_tiles``) and runs the flat QF's single-window probe
kernel over them (``qf_probe.qf_probe_tiles``), so every tile fits its
window at any density.  A structure with ``nbw`` window blocks makes at
most ``ceil(B / T) + min(B, nbw - 1)`` tiles: a dense one about
``B / T`` full tiles, a sparse one about one tile per touched block.  A
single tiling of ``T`` consecutive queries shared by all structures
would span ``T`` times the deeper structures' mean gap between queries,
more than their window, and send them to their exact path.

Per-query answers come back as two int32 *bitmasks* in sorted order
(hit / overflow, bit l = structure l), so the wrapper unpermutes once
for any static depth L.  A query whose cluster outruns its window sets
its structure's overflow bit; the wrapper resolves it on that
structure's exact path.

Frozen (binary-fuse) levels are not probed here: their probe positions
hash the fingerprint, not monotone in it, so the wrapper folds them in
through their own position-sorted ``fuse_probe`` pass.
"""

from __future__ import annotations

import jax.numpy as jnp

from .qf_probe import qf_probe_tiles


def cascade_probe_tiles(
    level_planes,
    fq_levels,
    fr_levels,
    *,
    tile_t: int = 128,
    wblk: int = 1024,
    interpret: bool = True,
    with_stats: bool = False,
):
    """Probe every QF structure of a cascade in its own window tiles.

    ``level_planes`` is a list of ``(rem, occ, shf, con)`` plane tuples
    (one per structure, arbitrary sizes); ``fq_levels`` / ``fr_levels``
    hold each structure's quotient/remainder view of the SAME
    canonically sorted query batch (so every ``fq_levels[l]`` is
    ascending).  Returns ``(hit_mask, ovf_mask)`` int32 (B,) bitmask
    arrays — bit ``l`` of query ``i`` is structure ``l``'s
    verdict/overflow.  ``with_stats=True`` adds two int32 ``(L,)``
    counts: each structure's live tiles, and those of its tiles whose
    quotients outrun their window (a guard: 0 by construction).

    The ``pallas_call``s run under the ``cascade.kernel`` scope, so a
    trace can tell the kernel's time from its wrapper's.
    """
    if not level_planes:
        raise ValueError("cascade_probe_tiles needs at least one level")
    hit_mask = ovf_mask = jnp.zeros(fq_levels[0].shape, jnp.int32)
    tiles, unfit = [], []
    for lvl, (planes, fq, fr) in enumerate(zip(level_planes, fq_levels, fr_levels)):
        present, ovf, live, bad = qf_probe_tiles(
            *planes,
            fq,
            fr,
            tile_t=tile_t,
            wblk=wblk,
            interpret=interpret,
            scope="cascade.kernel",
        )
        hit_mask = hit_mask | (present << lvl)
        ovf_mask = ovf_mask | (ovf << lvl)
        tiles.append(live)
        unfit.append(bad)
    if not with_stats:
        return hit_mask, ovf_mask
    return hit_mask, ovf_mask, jnp.stack(tiles), jnp.stack(unfit)
