"""Pure-jnp oracles for the Pallas kernels.

Deliberately independent re-implementations (no imports from
``repro.core``) so kernel-vs-ref is a genuine cross-check; tests
additionally compare both against ``repro.core.quotient_filter``.
"""

from __future__ import annotations

import jax.numpy as jnp

INT32_MAX = jnp.int32(2**31 - 1)


def build_ref(total_slots: int, pos, fq, fr, con_bits, shf_bits):
    """Scatter sorted items into slot planes.

    pos: int32 (n,) strictly increasing probe positions (sentinel
    INT32_MAX for padding), fq: bucket ids, fr: remainders (int32
    bit-pattern), con/shf: per-item metadata bits.
    Returns (rem, meta, occ): meta = occ-less packed bits con | shf<<1.
    """
    t = total_slots
    rem = jnp.zeros((t,), jnp.int32).at[pos].set(fr, mode="drop")
    meta = (
        jnp.zeros((t,), jnp.int32)
        .at[pos]
        .set(
            con_bits.astype(jnp.int32) | (shf_bits.astype(jnp.int32) << 1),
            mode="drop",
        )
    )
    occ = jnp.zeros((t,), jnp.int32).at[fq].max(1, mode="drop")
    return rem, meta, occ


def probe_ref(rem, occ, shf, con, fq, fr, window: int):
    """Windowed cluster-decode membership (paper Fig. 3, vectorized).

    rem/occ/shf/con: full slot planes; fq (B,) int32 quotients; fr (B,)
    int32 remainders. Returns (present bool (B,), overflow bool (B,)).
    """
    t = rem.shape[0]
    W = window
    wtot = 2 * W
    js = jnp.arange(wtot, dtype=jnp.int32)
    base = fq - W
    idx = base[:, None] + js[None, :]
    valid = (idx >= 0) & (idx < t)
    idxc = jnp.clip(idx, 0, t - 1)

    w_occ = jnp.where(valid, occ[idxc] > 0, False)
    w_shf = jnp.where(valid, shf[idxc] > 0, False)
    w_con = jnp.where(valid, con[idxc] > 0, False)
    w_rem = jnp.where(valid, rem[idxc], 0)
    nonempty = w_occ | w_shf

    occ_q = w_occ[:, W]
    cand = jnp.where((~w_shf) & (js <= W)[None, :], js[None, :], -1)
    b = jnp.max(cand, axis=1)
    ovf_left = b < 0

    sel = w_occ & (js[None, :] >= b[:, None]) & (js <= W)[None, :]
    R = jnp.sum(sel, axis=1, dtype=jnp.int32)

    run_start = nonempty & ~w_con
    cum = jnp.cumsum(run_start.astype(jnp.int32), axis=1)
    cum_before = jnp.where(
        b > 0,
        jnp.take_along_axis(cum, jnp.maximum(b - 1, 0)[:, None], axis=1)[:, 0],
        0,
    )
    C = cum_before + R

    in_run = (cum == C[:, None]) & nonempty
    present = occ_q & jnp.any(in_run & (w_rem == fr[:, None]), axis=1)
    ovf_right = in_run[:, -1]
    ovf_nostart = occ_q & ~ovf_left & (cum[:, -1] < C)
    overflow = occ_q & (ovf_left | ovf_right | ovf_nostart)
    return present, overflow


def fuse_probe_ref(table, p0, p1, p2, fp):
    """Binary-fuse membership oracle: three gathers + xor + compare.

    table: uint32 (slots,) fingerprint cells; p0/p1/p2: int32 (B,) cell
    positions (already hashed — one per consecutive segment); fp: uint32
    (B,) stored fingerprints.  Returns present bool (B,).  The caller
    owns the empty-table (n == 0) guard.
    """
    return (table[p0] ^ table[p1] ^ table[p2]) == fp


def bloom_probe_ref(cells, idx):
    """Blocked-Bloom membership oracle: AND of k direct gathers.

    cells: int32 (ncells,) cell plane; idx: int32 (B, k) cell indices.
    Returns present bool (B,).
    """
    return jnp.all(cells[idx] > 0, axis=1)


def bloom_count_ref(idx_flat, ncells: int):
    """Per-cell increment counts from flat cell indices.

    Sentinel / out-of-range indices (e.g. INT32_MAX for masked keys)
    contribute nothing.  Returns int32 (ncells,).
    """
    return jnp.zeros((ncells,), jnp.int32).at[idx_flat].add(1, mode="drop")


def cascade_probe_ref(level_planes, fq_levels, fr_levels, window: int):
    """Multi-level cascade probe oracle: per-level windowed decode
    composed into (hit, ovf) int32 bitmasks (bit l = level l), matching
    ``cascade_probe_tiles``' output contract.
    """
    B = fq_levels[0].shape[0]
    hit = jnp.zeros((B,), jnp.int32)
    ovf = jnp.zeros((B,), jnp.int32)
    for lvl, (rem, occ, shf, con) in enumerate(level_planes):
        p, o = probe_ref(rem, occ, shf, con, fq_levels[lvl], fr_levels[lvl], window)
        hit = hit | (p.astype(jnp.int32) << lvl)
        ovf = ovf | (o.astype(jnp.int32) << lvl)
    return hit, ovf
