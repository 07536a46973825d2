"""Pallas TPU kernel: bulk quotient-filter membership probe.

The paper's lookup reads one *cluster* — one contiguous region — per
query (its whole point vs. the Bloom filter's k random reads).  The TPU
mapping (DESIGN.md §2): queries are sorted by quotient and tiled; each
program serves T queries from a shared 2*WBLK-slot window of the filter
whose aligned start is scalar-prefetched per tile.  Sorted queries make
neighbouring windows coalesce, so HBM traffic is a linear stream over
the touched region instead of random gathers.

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

Queries whose tile span or cluster exceeds the window raise a per-query
overflow flag; the wrapper (ops.py) resolves those on the exact path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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
    blk_ref, wbase_ref, rem_a, rem_b, meta_a, meta_b, fq_ref, fr_ref, present_o, ovf_o
):
    t = pl.program_id(0)
    rem = jnp.concatenate([rem_a[...], rem_b[...]], axis=0)
    meta = jnp.concatenate([meta_a[...], meta_b[...]], axis=0)
    present, ovf = window_decode(
        rem,
        meta,
        lanes.row_to_col(fq_ref[0]),
        lanes.row_to_col(fr_ref[0]),
        wbase_ref[t],
    )
    present_o[0] = lanes.col_to_row(present.astype(jnp.int32))
    ovf_o[0] = lanes.col_to_row(ovf.astype(jnp.int32))


def tile_windows(fq3: jnp.ndarray, total: int, wblk: int):
    """``(blk, wbase, fits)`` of each ``dispatch.query_tiles`` tile of
    sorted quotients over a ``total``-slot table: its window, from its
    first and last quotient with room for the run tail past the last
    query, and whether the tile fits it (``dispatch.window_base``)."""
    return dispatch.window_base(
        fq3[:, 0, 0], fq3[:, 0, -1], total, wblk, margin=wblk // 4
    )


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
    """Probe sorted queries. Returns (present, overflow) int32 (B,).

    Planes are any integer/bool dtype; fq_sorted must be ascending,
    padded to a multiple of tile_t (duplicate-last padding preserves
    sortedness).  ``wblk`` is a multiple of 128 (of 1024 for the TPU
    compiler's tiling).  Tiles whose quotient span exceeds the window
    report overflow for all their queries (handled by the caller's
    exact path).
    """
    total = rem.shape[0]
    B = fq_sorted.shape[0]
    assert B % tile_t == 0
    n_tiles = B // tile_t

    rem2 = dispatch.plane_blocks(rem, wblk)
    meta2 = dispatch.plane_blocks(dispatch.meta_plane(occ, shf, con), wblk)
    fq3 = dispatch.query_tiles(fq_sorted, tile_t)
    fr3 = dispatch.query_tiles(fr_sorted, tile_t)

    blk, wbase, tile_fits = tile_windows(fq3, total, wblk)

    win = lambda off: dispatch.window_spec(wblk, off)
    qspec = dispatch.query_spec(tile_t)

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
            blk[s : s + n],
            wbase[s : s + n],
            rem2,
            rem2,
            meta2,
            meta2,
            fq3[s : s + n],
            fr3[s : s + n],
        )

    present3, ovf3 = dispatch.concat_launches(
        launch(s, n) for s, n in dispatch.launches(n_tiles, 2)
    )

    ovf3 = ovf3 | (~tile_fits[:, None, None]).astype(jnp.int32)
    return present3.reshape(B), ovf3.reshape(B)
