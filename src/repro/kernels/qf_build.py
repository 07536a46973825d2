"""Pallas TPU kernel: bulk quotient-filter build (slot-plane emit).

The bulk-parallel QF write path (DESIGN.md §2) is: sort fingerprints,
compute probe positions with one cummax scan, then *materialize* the
slot planes — a streaming, bandwidth-bound scatter of n items into
m + slack slots.  This kernel tiles that materialization:

grid = one program per S-slot output tile.  Because probe positions are
strictly increasing, the items landing in an S-slot tile are a
contiguous range of at most S items, whose location is scalar-prefetched
(`blk[t]` = item-block index).  Each program loads two consecutive
S-item blocks (covering any alignment) and, for every 128-item row and
every 128-slot output row, reduces a (128 x 128) match matrix
``pos - row_base == lane`` onto the output row — pure VPU work, no
data-dependent control flow, VMEM-resident.  Items and slots are
lane-dense ``(S // 128, 128)`` blocks; S is a multiple of 1024 for the
TPU compiler's (8, 128) tiling.

The is_occupied plane is a trivial one-line scatter handled by the
wrapper (ops.py); this kernel emits the payload planes (remainder +
is_shifted/is_continuation), which dominate bytes.
"""

from __future__ import annotations

import jax.numpy as jnp

from . import dispatch, lanes


def _build_kernel(
    blk_ref, first_ref, pos_a, pos_b, fr_a, fr_b, mb_a, mb_b, rem_o, meta_o
):
    base = dispatch.tile_base(first_ref, rem_o)
    cat = lambda a, b: jnp.concatenate([a[...], b[...]], axis=0)
    lanes.scatter_rows(
        cat(pos_a, pos_b), [cat(fr_a, fr_b), cat(mb_a, mb_b)], [rem_o, meta_o], base
    )


def qf_build_planes(
    pos: jnp.ndarray,
    fr: jnp.ndarray,
    meta_bits: jnp.ndarray,
    total_slots: int,
    *,
    block_s: int = 1024,
    interpret: bool = True,
):
    """Scatter items (pos strictly increasing, INT32_MAX padding) into
    (rem, meta) planes of length total_slots.

    meta_bits packs is_continuation | is_shifted << 1 per item.
    ``block_s`` is a multiple of 128.
    """
    S = block_s
    n_tiles = -(-total_slots // S)
    t_pad = n_tiles * S

    # pad item arrays to a whole number of S-blocks plus one sentinel block
    n = pos.shape[0]
    n_blocks = -(-n // S) + 1
    pad = n_blocks * S - n
    pos_p = jnp.concatenate([pos, jnp.full((pad,), jnp.int32(2**31 - 1))])
    rows = lambda x: jnp.concatenate(
        [x.astype(jnp.int32), jnp.zeros((pad,), jnp.int32)]
    ).reshape(-1, lanes.LANES)
    pos2 = pos_p.reshape(-1, lanes.LANES)
    fr2 = rows(fr)
    mb2 = rows(meta_bits)

    # scalar prefetch: first item-block feeding each output tile
    starts = jnp.searchsorted(pos_p, jnp.arange(n_tiles, dtype=jnp.int32) * S)
    blk = jnp.minimum(starts // S, n_blocks - 2).astype(jnp.int32)

    rem2, meta2 = dispatch.concat_launches(
        dispatch.launch_tiles(
            _build_kernel, blk, [pos2, fr2, mb2], S, 2, interpret, s, n
        )
        for s, n in dispatch.launches(n_tiles, 1)
    )
    rem = rem2.reshape(t_pad)[:total_slots]
    meta = meta2.reshape(t_pad)[:total_slots]
    return rem, meta
