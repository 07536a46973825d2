"""Pallas TPU kernel: fused multi-level cascade membership probe.

The cascade answers a query by probing Q0 plus every disk level — the
reference path launches one windowed probe per level, re-reading the
sorted query tiles L times and paying L grid launches.  This kernel
fuses the whole unfrozen stack into ONE grid over the sorted queries:

* Requotienting is monotone, so sorting queries once by their p-bit
  canonical fingerprint sorts them by *every* level's quotient
  simultaneously — one sort serves all levels.
* The grid is one program per query tile.  For each of the L levels the
  program sees that level's own 2*wblk-slot window (aligned start
  scalar-prefetched per (tile, level), exactly ``qf_probe``'s
  two-consecutive-block scheme and lane-dense layout, just L of them),
  and runs the shared branch-free cluster decode
  (``qf_probe.window_decode``) per level.
* Per-query results come back as two int32 *bitmasks* (hit / overflow,
  bit l = level l), so the launch has a fixed two-output shape for any
  static depth L.

Frozen (binary-fuse) levels cannot join the fused grid — their probe
positions are hashes of the fingerprint, not monotone in it, so they
need their own position sort — and are folded in by the wrapper
(``ops.cascade_lookup``) via the existing 3-gather ``fuse_probe`` pass.

Tiles whose quotient span outruns a level's window flag that level's
overflow bit; the wrapper resolves flagged queries on the exact path,
per level, preserving bit-exactness with the per-level reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch, lanes
from .qf_probe import window_decode


def _make_kernel(L: int):
    """Kernel body for a static stack depth L.

    Ref layout (positional): 2L scalar-prefetch refs (blk_l, wbase_l
    interleaved), then 4 window refs per level (rem/meta, two
    consecutive blocks each), then fq/fr query tiles per level, then
    the two bitmask outputs.
    """

    def kernel(*refs):
        scalars = refs[: 2 * L]
        planes = refs[2 * L : 6 * L]
        queries = refs[6 * L : 8 * L]
        hit_o, ovf_o = refs[8 * L], refs[8 * L + 1]
        t = pl.program_id(0)

        T = queries[0].shape[-1]
        hitm = jnp.zeros((T, 1), jnp.int32)
        ovfm = jnp.zeros((T, 1), jnp.int32)
        for lvl in range(L):
            rem_a, rem_b, meta_a, meta_b = planes[4 * lvl : 4 * (lvl + 1)]
            present, ovf = window_decode(
                jnp.concatenate([rem_a[...], rem_b[...]], axis=0),
                jnp.concatenate([meta_a[...], meta_b[...]], axis=0),
                lanes.row_to_col(queries[2 * lvl][0]),
                lanes.row_to_col(queries[2 * lvl + 1][0]),
                scalars[2 * lvl + 1][t],
            )
            hitm = hitm | (present.astype(jnp.int32) << lvl)
            ovfm = ovfm | (ovf.astype(jnp.int32) << lvl)
        hit_o[0] = lanes.col_to_row(hitm)
        ovf_o[0] = lanes.col_to_row(ovfm)

    return kernel


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
    """Probe all QF levels of a cascade in one fused grid.

    ``level_planes`` is a list of ``(rem, occ, shf, con)`` plane tuples
    (one per level, arbitrary per-level sizes); ``fq_levels`` /
    ``fr_levels`` hold each level's quotient/remainder view of the SAME
    canonically sorted, tile-padded query batch (so every ``fq_levels[l]``
    is ascending).  Returns ``(hit_mask, ovf_mask)`` int32 (B,) bitmask
    arrays — bit ``l`` of query ``i`` is level ``l``'s verdict/overflow.
    ``with_stats=True`` adds an int32 ``(L,)`` count of the tiles whose
    quotients outrun each level's window (all their queries overflow).

    The ``pallas_call`` runs under the ``cascade.kernel`` scope, so a
    trace can tell the kernel's time from its wrapper's.
    """
    L = len(level_planes)
    if L < 1:
        raise ValueError("cascade_probe_tiles needs at least one level")
    B = fq_levels[0].shape[0]
    assert B % tile_t == 0
    n_tiles = B // tile_t

    scalars = []
    plane_args = []
    in_specs = []
    tile_mask = jnp.zeros((n_tiles,), jnp.int32)
    query_args = []

    for lvl, (rem, occ, shf, con) in enumerate(level_planes):
        fq3 = dispatch.query_tiles(fq_levels[lvl], tile_t)
        blk, wbase, fits = dispatch.window_base(
            fq3[:, 0, 0], fq3[:, 0, -1], rem.shape[0], wblk, margin=wblk // 4
        )
        scalars += [blk, wbase]
        tile_mask = tile_mask | ((~fits).astype(jnp.int32) << lvl)
        for plane in (rem, dispatch.meta_plane(occ, shf, con)):
            padded = dispatch.plane_blocks(plane, wblk)
            plane_args += [padded, padded]
            # index_map sees (t, s_0 .. s_{2L-1}); blk of level l is s[2l]
            in_specs += [
                dispatch.window_spec(wblk, 0, 2 * lvl),
                dispatch.window_spec(wblk, 1, 2 * lvl),
            ]
        query_args += [fq3, dispatch.query_tiles(fr_levels[lvl], tile_t)]

    qspec = dispatch.query_spec(tile_t)
    in_specs += [qspec, qspec] * L

    def launch(s, n):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 * L,
            grid=(n,),
            in_specs=in_specs,
            out_specs=[qspec, qspec],
        )
        args = (
            *(x[s : s + n] for x in scalars),
            *plane_args,
            *(x[s : s + n] for x in query_args),
        )
        with jax.named_scope("cascade.kernel"):
            return pl.pallas_call(
                _make_kernel(L),
                grid_spec=grid_spec,
                out_shape=[jax.ShapeDtypeStruct((n, 1, tile_t), jnp.int32)] * 2,
                interpret=interpret,
            )(*args)

    hit3, ovf3 = dispatch.concat_launches(
        launch(s, n) for s, n in dispatch.launches(n_tiles, 2 * L)
    )

    ovf3 = ovf3 | tile_mask[:, None, None]
    if not with_stats:
        return hit3.reshape(B), ovf3.reshape(B)
    unfit = jnp.stack([jnp.sum((tile_mask >> lvl) & 1) for lvl in range(L)])
    return hit3.reshape(B), ovf3.reshape(B), unfit.astype(jnp.int32)
