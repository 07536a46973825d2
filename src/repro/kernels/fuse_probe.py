"""Pallas TPU kernel: batched binary-fuse (3-gather) membership probe.

A frozen level answers a query with exactly three cell reads — one per
consecutive segment ``start .. start+2`` — xor'd against the query's
fingerprint.  The TPU mapping mirrors ``qf_probe``: queries are sorted
by their first position and tiled; each program serves T queries from a
shared 2*wblk-cell window of the table whose aligned start is
scalar-prefetched per tile.  Because the three touched segments are
*consecutive*, one window covers all three gathers for every query in
the tile — sorted queries turn the probe into a single linear pass over
the table instead of 3B random gathers.

The gathers themselves are branch-free one-hot contractions
(``lanes.gather``: a (T x 128) iota compare per window row), the same
trick the QF probe kernel uses for its cluster decode, over the same
lane-dense window and query-row layout.  Queries whose window residency
fails (tile spans more segments than the window holds) flag overflow
and the wrapper (ops.py) resolves them on the reference path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch, lanes


def _fuse_probe_kernel(
    blk_ref, wbase_ref, tab_a, tab_b, p0_ref, p1_ref, p2_ref, fp_ref, hit_o
):
    base = wbase_ref[pl.program_id(0)]
    w = jnp.concatenate([tab_a[...], tab_b[...]], axis=0)  # int32 cells

    def cell(p_ref):  # w[p - base] for the tile's (T, 1) position column
        return lanes.gather(w, lanes.row_to_col(p_ref[0]) - base)

    got = cell(p0_ref) ^ cell(p1_ref) ^ cell(p2_ref)
    hit = got == lanes.row_to_col(fp_ref[0])
    hit_o[0] = lanes.col_to_row(hit.astype(jnp.int32))


def fuse_probe_tiles(
    table: jnp.ndarray,
    p0_sorted: jnp.ndarray,
    p1_sorted: jnp.ndarray,
    p2_sorted: jnp.ndarray,
    fp_sorted: jnp.ndarray,
    *,
    tile_t: int = 128,
    wblk: int = 2048,
    interpret: bool = True,
):
    """Probe position-sorted queries. Returns (hit, overflow) int32 (B,).

    ``table`` is the int32 bit-pattern of the uint32 cell plane;
    ``p0_sorted`` must be ascending and padded to a multiple of
    ``tile_t`` (duplicate-last padding preserves sortedness).  Tiles
    whose third-segment reach exceeds the 2*wblk window report overflow
    for all their queries (resolved by the caller's reference path).
    """
    total = table.shape[0]
    B = p0_sorted.shape[0]
    assert B % tile_t == 0
    n_tiles = B // tile_t

    tab2 = dispatch.plane_blocks(table, wblk)
    p0, p1, p2, fp3 = (
        dispatch.query_tiles(x, tile_t)
        for x in (p0_sorted, p1_sorted, p2_sorted, fp_sorted)
    )

    # all three positions of every query must land inside [wbase, wbase+2*wblk)
    reach = jnp.maximum(jnp.max(p1, axis=(1, 2)), jnp.max(p2, axis=(1, 2)))
    blk, wbase, tile_fits = dispatch.window_base(p0[:, 0, 0], reach, total, wblk)

    qspec = dispatch.query_spec(tile_t)

    def launch(s, n):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            in_specs=[dispatch.window_spec(wblk, 0), dispatch.window_spec(wblk, 1)]
            + [qspec] * 4,
            out_specs=[qspec],
        )
        return pl.pallas_call(
            _fuse_probe_kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((n, 1, tile_t), jnp.int32)],
            interpret=interpret,
        )(
            blk[s : s + n],
            wbase[s : s + n],
            tab2,
            tab2,
            *(x[s : s + n] for x in (p0, p1, p2, fp3)),
        )

    (hit3,) = dispatch.concat_launches(
        launch(s, n) for s, n in dispatch.launches(n_tiles, 2)
    )

    ovf3 = jnp.broadcast_to((~tile_fits[:, None, None]).astype(jnp.int32), hit3.shape)
    return hit3.reshape(B), ovf3.reshape(B)
