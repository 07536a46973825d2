"""Pallas TPU kernel: bulk quotient-filter membership probe.

The paper's lookup reads one *cluster* — one contiguous region — per
query (its whole point vs. the Bloom filter's k random reads).  The TPU
mapping (DESIGN.md §2): queries are sorted by quotient and grouped into
window-aligned tiles; each program serves up to T queries from a shared
2*WBLK-slot window of the filter whose aligned block is
scalar-prefetched per tile.  A tile holds consecutive sorted queries
that share one window block (``window_tiles``), so every tile fits its
window by construction however sparse the batch: a dense batch makes
about B / T tiles, a sparse one about one tile per touched block.
Sorted queries make neighbouring windows coalesce, so HBM traffic is a
linear stream over the touched region instead of random gathers.

In-window cluster decode is branch-free rank/select arithmetic (the
vectorized form of the paper's Fig. 3 walk).  Two prefix counts are
shared over the tile: O (occupied buckets) and S (run starts).  At any
unshifted slot b the runs started before b equal the occupied buckets
before b, so ``S - O`` just before an unshifted slot is one window
constant K: the query's run is the one with ordinal ``O[fq] + K``, and
the query hits if a slot of that run holds its remainder.

Layout: the window is two ``(wblk // 128, 128)`` lane-dense blocks, the
metadata bits are packed into one int32 plane (``dispatch.meta_plane``)
and each query tile is one ``(1, T)`` lane row, moved to a ``(T, 1)``
column so queries broadcast against window lanes (``lanes``).

A query whose cluster outruns its window (it starts before the window,
or its run may continue past it) raises a per-query overflow flag; the
wrapper (ops.py) resolves those on the exact path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import scan

from . import dispatch, lanes


def window_decode(rem, meta, fq, fr, base):
    """Branch-free cluster decode of one query tile against one window.

    ``rem`` / ``meta`` are the ``(rows, 128)`` int32 window planes
    (``meta`` packed by ``dispatch.meta_plane``), ``fq`` / ``fr`` the
    ``(T, 1)`` query columns, ``base`` the window's absolute start slot.
    Returns ``(present, ovf)`` bool ``(T, 1)`` — shared by the
    single-level and fused-cascade kernels.
    """
    rows, width = rem.shape
    occ = meta & 1
    shf = (meta >> 1) & 1
    con = (meta >> 2) & 1
    nonempty = (occ | shf) > 0
    start = jnp.where(nonempty & (con == 0), 1, 0)

    run = lanes.prefix_sum(start)  # run ordinal of every slot
    occ_cum = lanes.prefix_sum(occ)
    unshifted = shf == 0
    slot = lanes.flat_index(rem.shape)

    def reduce(f, x):
        return f(f(x, axis=1, keepdims=True), axis=0, keepdims=True)

    # first unshifted slot, and the window constant K = S - O just
    # before any unshifted slot (-1 when the window has none: then
    # every query overflows left)
    first_anchor = reduce(jnp.min, jnp.where(unshifted, slot, rows * width))
    k = reduce(
        jnp.max, jnp.where(unshifted, (run - start) - (occ_cum - occ), -1)
    )
    runs = jnp.where(nonempty, run, -1)
    last_run = runs[rows - 1 :, width - 1 :]  # (1, 1)
    run_total = run[rows - 1 :, width - 1 :]

    rel = fq - base  # (T, 1) in [0, rows * 128) when the tile fits
    g = lanes.gather(occ_cum * 2 + occ, rel)
    occ_q = (g & 1) > 0
    want = (g >> 1) + k  # ordinal of the query's run

    hit = jnp.zeros((fq.shape[0], width), jnp.bool_)
    for i in range(rows):
        hit = hit | ((runs[i : i + 1, :] == want) & (rem[i : i + 1, :] == fr))
    present = occ_q & jnp.any(hit, axis=1, keepdims=True)

    ovf_left = rel < first_anchor
    ovf_right = last_run == want  # the run may continue past the window
    ovf_nostart = ~ovf_left & (run_total < want)  # run starts past it
    ovf = occ_q & (ovf_left | ovf_right | ovf_nostart)
    return present, ovf


def _probe_kernel(
    live_ref, blk_ref, rem_a, rem_b, meta_a, meta_b, fq_ref, fr_ref, present_o, ovf_o
):
    t = pl.program_id(0)

    @pl.when(t < live_ref[0])  # tiles past the live count do no work
    def _():
        rem = jnp.concatenate([rem_a[...], rem_b[...]], axis=0)
        meta = jnp.concatenate([meta_a[...], meta_b[...]], axis=0)
        present, ovf = window_decode(
            rem,
            meta,
            lanes.row_to_col(fq_ref[0]),
            lanes.row_to_col(fr_ref[0]),
            blk_ref[t] * rem_a.shape[0] * lanes.LANES,
        )
        present_o[0] = lanes.col_to_row(present.astype(jnp.int32))
        ovf_o[0] = lanes.col_to_row(ovf.astype(jnp.int32))


def window_tiles(fq_sorted, fr_sorted, total: int, tile_t: int, wblk: int):
    """Group sorted queries into window-aligned tiles.

    Each query takes the window block ``g`` that
    ``dispatch.window_base`` gives a tile starting at it; a tile holds at
    most ``tile_t`` consecutive queries of one block.  Its quotients then
    lie in ``[g * wblk + margin, (g + 1) * wblk + margin)``, inside the
    window ``[g * wblk, (g + 2) * wblk)`` with a quarter block of room
    for run tails past the last query.

    Returns ``(fq3, fr3, dest, live)``: ``(n_tiles, 1, tile_t)`` int32
    query tiles, ``n_tiles = ceil(B / tile_t) + min(B, nbw - 1)`` being
    a static bound on the ``live`` tiles, which come first; and each
    query's flat position ``tile * tile_t + lane`` in them.  Padding
    lanes repeat the last query of their tile, so tiles stay sorted.
    """
    B = fq_sorted.shape[0]
    margin = wblk // 4
    nbw = -(-total // wblk) + 1
    g = jnp.clip((fq_sorted - margin) // wblk, 0, nbw - 2)
    i = jnp.arange(B, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_), g[1:] != g[:-1]])
    lane = (i - scan.cummax(jnp.where(first, i, 0))) % tile_t
    tile = scan.cumsum((lane == 0).astype(jnp.int32)) - 1
    dest = tile * tile_t + lane

    n_tiles = -(-B // tile_t) + min(B, nbw - 1)
    rows = jnp.stack([fq_sorted.astype(jnp.int32), fr_sorted.astype(jnp.int32)], 1)
    buf = (
        jnp.full((n_tiles * tile_t, 2), -1, jnp.int32)
        .at[dest]
        .set(rows, indices_are_sorted=True, unique_indices=True)
    )
    fq3, fr3 = (buf[:, j].reshape(n_tiles, 1, tile_t) for j in (0, 1))
    live_lane = fq3 >= 0  # quotients are never negative
    n_lanes = jnp.sum(live_lane, axis=2, keepdims=True)
    last = jax.lax.broadcasted_iota(jnp.int32, fq3.shape, 2) == n_lanes - 1
    pad = lambda x: jnp.where(
        live_lane, x, jnp.sum(jnp.where(last, x, 0), axis=2, keepdims=True)
    )
    return pad(fq3), pad(fr3), dest, tile[-1] + 1


def qf_probe_tiles(
    rem: jnp.ndarray,
    occ: jnp.ndarray,
    shf: jnp.ndarray,
    con: jnp.ndarray,
    fq_sorted: jnp.ndarray,
    fr_sorted: jnp.ndarray,
    *,
    tile_t: int = 128,
    wblk: int = 1024,
    interpret: bool = True,
):
    """Probe sorted queries in window-aligned tiles (``window_tiles``).

    Planes are any integer/bool dtype; ``fq_sorted`` must be ascending.
    ``wblk`` is a multiple of 128 (of 1024 for the TPU compiler's
    tiling).  Returns ``(present, overflow, tiles, tiles_unfit)``: int32
    ``(B,)`` answers and overflow flags in sorted order (the caller
    resolves overflowed queries on its exact path), the number of live
    tiles, and of those whose quotients outrun their window (a guard:
    0 by construction; such a tile would overflow all its queries).
    """
    total = rem.shape[0]
    fq3, fr3, dest, live = window_tiles(fq_sorted, fr_sorted, total, tile_t, wblk)
    n_tiles = fq3.shape[0]

    rem2 = dispatch.plane_blocks(rem, wblk)
    meta2 = dispatch.plane_blocks(dispatch.meta_plane(occ, shf, con), wblk)
    blk, _, tile_fits = dispatch.window_base(
        fq3[:, 0, 0], fq3[:, 0, -1], total, wblk, margin=wblk // 4
    )
    # the tiles past the live count keep the last live tile's blocks, so
    # their grid steps copy nothing
    last = jnp.maximum(live - 1, 0)
    blk = jnp.where(jnp.arange(n_tiles) < live, blk, blk[last])

    win = lambda off: dispatch.window_spec(wblk, off, blk_arg=1)
    qspec = pl.BlockSpec(
        (1, 1, tile_t),
        lambda t, live_ref, _: (jnp.maximum(jnp.minimum(t, live_ref[0] - 1), 0), 0, 0),
    )

    def launch(s, n):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            in_specs=[win(0), win(1), win(0), win(1), qspec, qspec],
            out_specs=[qspec, qspec],
        )
        return pl.pallas_call(
            _probe_kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((n, 1, tile_t), jnp.int32)] * 2,
            interpret=interpret,
        )(
            jnp.clip(live - s, 0, n).reshape(1).astype(jnp.int32),
            blk[s : s + n],
            rem2,
            rem2,
            meta2,
            meta2,
            fq3[s : s + n],
            fr3[s : s + n],
        )

    # a launch prefetches one block a tile and its live count: at most
    # two words a tile
    present3, ovf3 = dispatch.concat_launches(
        launch(s, n) for s, n in dispatch.launches(n_tiles, 2)
    )
    ovf3 = ovf3 | (~tile_fits[:, None, None]).astype(jnp.int32)
    code = (present3 | (ovf3 << 1)).reshape(-1)[dest]
    return code & 1, code >> 1, live, jnp.sum(~tile_fits).astype(jnp.int32)
