"""Pallas TPU kernels: blocked-Bloom bin insert + contains.

The blocked Bloom filter's whole design point is that all k probes of a
key land inside one ``block_bits``-sized bin (one cache line / flash
page) — the layout of SNIPPETS.md's BlockBloomFilter (64-byte bins) and
the paper's buffered Bloom variant.  That locality is exactly what the
window-prefetch scheme rewards:

* **contains** — queries sorted by bin share a 2*wblk-cell window whose
  aligned start is scalar-prefetched per tile; each of the k probes is
  a branch-free one-hot gather in the window, AND-reduced.  Tiles whose
  bins outrun the window flag overflow (wrapper resolves exactly).
* **insert** — the write side mirrors ``qf_build``: ALL k*B touched
  cell indices are sorted, so the items landing in an S-cell output
  tile are one contiguous range whose item-block is scalar-prefetched;
  the kernel reduces one-hot match matrices (``lanes.scatter_rows``)
  into per-cell hit COUNTS.  Counts compose with any cell plane: ``cells + counts``
  (counting), ``cells | (counts > 0)`` (plain bits), ``cells - counts``
  (counting delete) — and because the aggregation is commutative, tiles
  whose bins are denser than the item window simply fall back to a
  scatter recount without affecting the rest (see ``ops.bloom_counts``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch, lanes


def _make_probe_kernel(k: int):
    def kernel(*refs):
        # refs: blk, wbase, cell_a, cell_b, idx_0 .. idx_{k-1}, hit_o
        blk_ref, wbase_ref, cell_a, cell_b = refs[:4]
        idx_refs = refs[4 : 4 + k]
        hit_o = refs[4 + k]
        base = wbase_ref[pl.program_id(0)]
        w = jnp.concatenate([cell_a[...], cell_b[...]], axis=0)  # cells

        hit = None
        for j in range(k):
            val = lanes.gather(w, lanes.row_to_col(idx_refs[j][0]) - base)
            hit = val > 0 if hit is None else hit & (val > 0)
        hit_o[0] = lanes.col_to_row(hit.astype(jnp.int32))

    return kernel


def bloom_probe_tiles(
    cells: jnp.ndarray,
    idx_sorted: jnp.ndarray,
    *,
    tile_t: int = 128,
    wblk: int = 4096,
    interpret: bool = True,
):
    """AND-of-k probe of bin-sorted queries. Returns (hit, ovf) int32 (B,).

    ``cells`` is the cell plane; ``idx_sorted`` is (B, k) cell indices
    with rows ordered by their minimum index (bin order) and B a
    multiple of ``tile_t``.  Tiles whose index span exceeds the 2*wblk
    window report overflow for all their queries.
    """
    total = cells.shape[0]
    B, k = idx_sorted.shape
    assert B % tile_t == 0
    n_tiles = B // tile_t

    cells2 = dispatch.plane_blocks(cells, wblk)
    idx3 = idx_sorted.reshape(n_tiles, tile_t, k)
    mn = jnp.min(idx3, axis=(1, 2))
    mx = jnp.max(idx3, axis=(1, 2))
    blk, wbase, fits = dispatch.window_base(mn, mx, total, wblk)

    qspec = dispatch.query_spec(tile_t)
    idx_args = [
        dispatch.query_tiles(idx_sorted[:, j], tile_t) for j in range(k)
    ]

    def launch(s, n):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            in_specs=[dispatch.window_spec(wblk, 0), dispatch.window_spec(wblk, 1)]
            + [qspec] * k,
            out_specs=[qspec],
        )
        return pl.pallas_call(
            _make_probe_kernel(k),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((n, 1, tile_t), jnp.int32)],
            interpret=interpret,
        )(
            blk[s : s + n],
            wbase[s : s + n],
            cells2,
            cells2,
            *(x[s : s + n] for x in idx_args),
        )

    (hit3,) = dispatch.concat_launches(
        launch(s, n) for s, n in dispatch.launches(n_tiles, 2)
    )

    ovf3 = jnp.broadcast_to((~fits[:, None, None]).astype(jnp.int32), hit3.shape)
    return hit3.reshape(B), ovf3.reshape(B)


def _count_kernel(blk_ref, first_ref, idx_a, idx_b, cnt_o):
    base = dispatch.tile_base(first_ref, cnt_o)
    idx = jnp.concatenate([idx_a[...], idx_b[...]], axis=0)
    lanes.scatter_rows(idx, [jnp.ones_like(idx)], [cnt_o], base)


def bloom_count_tiles(
    idx_flat_sorted: jnp.ndarray,
    ncells: int,
    *,
    block_s: int = 1024,
    interpret: bool = True,
):
    """Aggregate ascending cell indices into per-cell counts, tiled.

    Returns ``(counts, fits)``: counts is int32 (n_tiles * block_s,)
    (slice to ``ncells``); ``fits`` is bool (n_tiles,), False where a
    tile's item range exceeded its two prefetched item blocks (denser
    than 2*block_s items — the caller recounts those tiles by scatter).
    Sentinel indices (>= n_tiles * block_s, e.g. INT32_MAX for masked
    keys) never land in any tile.  ``block_s`` is a multiple of 128.
    """
    S = block_s
    n_tiles = -(-ncells // S)
    n = idx_flat_sorted.shape[0]
    n_blocks = -(-n // S) + 1
    pad = n_blocks * S - n
    idx_p = jnp.concatenate(
        [idx_flat_sorted, jnp.full((pad,), jnp.int32(2**31 - 1))]
    )
    idx2 = idx_p.reshape(-1, lanes.LANES)

    tile_base = jnp.arange(n_tiles, dtype=jnp.int32) * S
    starts = jnp.searchsorted(idx_p, tile_base)
    ends = jnp.searchsorted(idx_p, tile_base + S)
    blk = jnp.minimum(starts // S, n_blocks - 2).astype(jnp.int32)
    fits = ends <= (blk + 2) * S

    (cnt2,) = dispatch.concat_launches(
        dispatch.launch_tiles(_count_kernel, blk, [idx2], S, 1, interpret, s, n)
        for s, n in dispatch.launches(n_tiles, 1)
    )
    return cnt2.reshape(n_tiles * S), fits
