"""Bulk-parallel quotient filter (the paper's core contribution, §3).

A QF stores p-bit fingerprints, p = q + r, in ``m = 2**q`` buckets using
quotienting [Knuth; Cleary'84]: the quotient f_q picks the bucket, the
r-bit remainder f_r is stored, and three metadata bit-planes
(is_occupied / is_continuation / is_shifted) make the linear-probed
table exactly decodable.

TPU adaptation (see DESIGN.md §2).  The paper's item-at-a-time shifted
insert is a data-dependent scalar loop — hostile to the TPU execution
model.  We exploit the paper's own observation that a QF *is* a sorted
multiset of fingerprints:

* ``build_sorted``: for sorted quotients ``qs[i]`` the linear-probe
  position obeys ``pos[i] = max(pos[i-1] + 1, qs[i])``, which
  closed-forms to ``pos[i] = i + cummax(qs[i] - i)`` — an associative
  scan.  Metadata bits follow elementwise and everything is scattered in
  one pass.  O(n) work, fully parallel.
* ``extract``: inverse decode via rank/select prefix sums — again O(m)
  parallel.  ``build(extract(s)) == s`` exactly.
* inserts/deletes/merges/resizes are all expressed through these two
  bulk ops, i.e. *every* write is a sequential streaming pass — the
  paper's "cache your hash" locality argument taken to its bulk-
  synchronous limit.
* lookups: the paper's cluster walk becomes a fixed-width windowed
  decode (``lookup``) — one contiguous W-slot window per query, the
  TPU analogue of "one cluster = one SSD page".  An exact
  binary-search path over the decoded fingerprints (``lookup_exact``)
  serves as oracle and overflow fallback.

Layout change vs paper: the three metadata bits are stored as separate
bit-planes rather than interleaved 3-bit fields (identical space,
vectorizes decode), and the table does not wrap around — a small slack
region absorbs the final cluster (the paper's whp cluster-length bound,
§3 Fact, sizes it).  ``state.overflow`` flags the (never observed in
tests) violation.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import scan
from .fingerprint import fingerprint

INT32_MAX = jnp.int32(2**31 - 1)
UINT32_MAX = jnp.uint32(0xFFFFFFFF)


class QFConfig(NamedTuple):
    """Static quotient-filter configuration (hashable; jit-static)."""

    q: int  # log2 number of buckets
    r: int  # remainder bits; false-positive rate ~= load * 2**-r
    slack: int = 1024  # extra slots past 2**q absorbing the last cluster
    seed: int = 0
    max_load: float = 0.75  # paper's recommended operating point

    @property
    def m(self) -> int:
        return 1 << self.q

    @property
    def total_slots(self) -> int:
        return self.m + self.slack

    @property
    def capacity(self) -> int:
        return int(self.m * self.max_load)

    @property
    def bits_per_slot(self) -> int:
        return self.r + 3

    @property
    def size_bytes(self) -> int:
        """Modeled size of the packed structure (r+3 bits per slot)."""
        return (self.total_slots * self.bits_per_slot + 7) // 8


class QFState(NamedTuple):
    """Device state. Planes have length cfg.total_slots."""

    rem: jnp.ndarray  # uint32 remainders
    occ: jnp.ndarray  # bool  is_occupied   (indexed by bucket)
    shf: jnp.ndarray  # bool  is_shifted    (indexed by slot)
    con: jnp.ndarray  # bool  is_continuation (indexed by slot)
    n: jnp.ndarray  # int32 scalar, number of stored fingerprints
    overflow: jnp.ndarray  # bool scalar, slack exhausted (should stay False)


def empty(cfg: QFConfig) -> QFState:
    t = cfg.total_slots
    return QFState(
        rem=jnp.zeros((t,), jnp.uint32),
        occ=jnp.zeros((t,), jnp.bool_),
        shf=jnp.zeros((t,), jnp.bool_),
        con=jnp.zeros((t,), jnp.bool_),
        n=jnp.zeros((), jnp.int32),
        overflow=jnp.zeros((), jnp.bool_),
    )


def load(cfg: QFConfig, state: QFState) -> jnp.ndarray:
    """Load factor alpha = n / m."""
    return state.n.astype(jnp.float32) / cfg.m


# ---------------------------------------------------------------------------
# Fingerprints
# ---------------------------------------------------------------------------


def fingerprints(cfg: QFConfig, keys: jnp.ndarray):
    """Hash keys to (quotient, remainder) for this filter."""
    return fingerprint(keys, cfg.q, cfg.r, cfg.seed)


def _pad_sort(fq: jnp.ndarray, fr: jnp.ndarray, valid: jnp.ndarray):
    """Sort (fq, fr) lexicographically, pushing invalid entries to the end."""
    fq = jnp.where(valid, fq, INT32_MAX)
    fr = jnp.where(valid, fr, UINT32_MAX)
    # equal (fq, fr) pairs are identical, so an unstable sort returns
    # the same arrays (and compiles far faster on TPU)
    fq, fr = jax.lax.sort((fq, fr), num_keys=2, is_stable=False)
    return fq, fr


# ---------------------------------------------------------------------------
# Bulk build: sorted fingerprints -> slot planes
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=0)
def build_sorted(cfg: QFConfig, fq: jnp.ndarray, fr: jnp.ndarray, n) -> QFState:
    """Build a QF from lexicographically sorted (fq, fr), first ``n`` valid.

    Padding entries must sort after all valid ones (fq == INT32_MAX).
    """
    t = cfg.total_slots
    nn = jnp.asarray(n, jnp.int32)
    idx = jnp.arange(fq.shape[0], dtype=jnp.int32)
    valid = idx < nn

    # Linear-probe positions: pos[i] = max(pos[i-1] + 1, fq[i])
    #                                = i + cummax(fq[i] - i)          (scan)
    # The padding sentinel must stay out of the subtraction: -INT32_MAX - idx
    # wraps for idx >= 2, so the difference is formed for valid rows only.
    pos = idx + scan.cummax(jnp.where(valid, fq - idx, -INT32_MAX))
    overflow = jnp.any(valid & (pos >= t))
    spos = jnp.where(valid, pos, INT32_MAX)  # scatter-drop for padding

    con_bits = valid & (idx > 0) & (fq == jnp.roll(fq, 1))
    shf_bits = valid & (pos != fq)

    # positions and quotients ascend (padding last), so every scatter
    # here is a sorted one
    put = dict(mode="drop", indices_are_sorted=True)
    rem = jnp.zeros((t,), jnp.uint32).at[spos].set(fr, **put)
    shf = jnp.zeros((t,), jnp.bool_).at[spos].set(shf_bits, **put)
    con = jnp.zeros((t,), jnp.bool_).at[spos].set(con_bits, **put)
    occ_at = jnp.where(valid, fq, INT32_MAX)
    occ = jnp.zeros((t,), jnp.bool_).at[occ_at].set(True, **put)
    return QFState(rem=rem, occ=occ, shf=shf, con=con, n=nn, overflow=overflow)


def _compact(keep: jnp.ndarray, values: jnp.ndarray, fill) -> jnp.ndarray:
    """Stream compaction: ``values[keep]`` moved to the front, ``fill``
    after them.

    Kept element k (0-based rank) lands at odd index ``2k + 1`` of a
    twice-as-long buffer and every dropped element at the even index
    just before the next kept one, so the scatter indices ascend — a
    sorted scatter, which the TPU compiles and runs far faster than an
    arbitrary one.  The odd half is the compacted stream.
    """
    t = keep.shape[0]
    kept = keep.astype(jnp.int32)
    dest = 2 * scan.cumsum(kept) - kept
    out = jnp.full((2 * t,), fill, values.dtype)
    out = out.at[dest].set(values, mode="drop", indices_are_sorted=True)
    return out[1::2]


def _slot_fingerprints(cfg: QFConfig, state: QFState):
    """Each slot's stored fingerprint ``(fq, fr)`` in slot order, which
    is sorted order, with sentinels in the empty slots."""
    t = cfg.total_slots
    nonempty = state.occ | state.shf  # continuation implies shifted
    run_start = nonempty & ~state.con
    # run_id: 1-indexed run ordinal for every slot in a run
    run_id = scan.cumsum(run_start.astype(jnp.int32))
    # bucket of the j-th run = index of the j-th set is_occupied bit
    # (the select half of rank/select; a missing rank reads t, as
    # searchsorted(occ_cum, j, 'left') would)
    slot = jnp.arange(t, dtype=jnp.int32)
    select = _compact(state.occ, slot, t)
    bucket_of_run = select[jnp.clip(run_id - 1, 0, t - 1)]
    fq_slot = jnp.where(nonempty, bucket_of_run, INT32_MAX)
    fr_slot = jnp.where(nonempty, state.rem, UINT32_MAX)
    return fq_slot, fr_slot, nonempty


@functools.partial(jax.jit, static_argnums=0)
def extract(cfg: QFConfig, state: QFState):
    """Decode the filter back to sorted fingerprints.

    Returns (fq, fr, n): padded (total_slots,) arrays whose first n
    entries are the sorted fingerprint multiset (padding = sentinels).
    Pure rank/select prefix arithmetic — a single sequential pass.
    """
    fq_slot, fr_slot, nonempty = _slot_fingerprints(cfg, state)
    fq_out = _compact(nonempty, fq_slot, INT32_MAX)
    fr_out = _compact(nonempty, fr_slot, UINT32_MAX)
    return fq_out, fr_out, state.n


# ---------------------------------------------------------------------------
# Lookup
# ---------------------------------------------------------------------------


def _range_bsearch(rs, lo, hi, v, right: bool):
    """Vectorized binary search of v in rs[lo:hi] (per-query ranges)."""
    import math

    iters = max(1, math.ceil(math.log2(max(2, rs.shape[0]))) + 1)

    def body(_, carry):
        lo, hi = carry
        mid = (lo + hi) >> 1
        x = rs[jnp.clip(mid, 0, rs.shape[0] - 1)]
        go_right = (x < v) | ((x == v) & right)
        active = lo < hi
        lo2 = jnp.where(active & go_right, mid + 1, lo)
        hi2 = jnp.where(active & ~go_right, mid, hi)
        return lo2, hi2

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return lo


def lex_searchsorted(qs, rs, fq, fr, side: str = "left"):
    """Rank of (fq, fr) in the lexicographically sorted (qs, rs)."""
    lo = jnp.searchsorted(qs, fq, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(qs, fq, side="right").astype(jnp.int32)
    return _range_bsearch(rs, lo, hi, fr, right=(side == "right"))


@functools.partial(jax.jit, static_argnums=0)
def lookup_exact(cfg: QFConfig, state: QFState, fq: jnp.ndarray, fr: jnp.ndarray):
    """Oracle lookup: decode + binary search. O(m) decode per batch."""
    qs, rs, _ = extract(cfg, state)
    lo = lex_searchsorted(qs, rs, fq, fr, "left")
    qh = qs[jnp.clip(lo, 0, qs.shape[0] - 1)]
    rh = rs[jnp.clip(lo, 0, rs.shape[0] - 1)]
    return (qh == fq) & (rh == fr)


def _window_decode(cfg: QFConfig, state: QFState, fq, fr, W: int):
    """One windowed-decode pass. Returns (present, overflow_flag)."""
    B = fq.shape[0]
    t = cfg.total_slots
    wtot = 2 * W
    js = jnp.arange(wtot, dtype=jnp.int32)
    base = fq - W
    idx = base[:, None] + js[None, :]
    valid = (idx >= 0) & (idx < t)
    idxc = jnp.clip(idx, 0, t - 1)

    occ = jnp.where(valid, state.occ[idxc], False)
    shf = jnp.where(valid, state.shf[idxc], False)
    con = jnp.where(valid, state.con[idxc], False)
    rem = jnp.where(valid, state.rem[idxc], jnp.uint32(0))
    nonempty = occ | shf

    occ_q = occ[:, W]  # is_occupied(A[f_q])

    # cluster/anchor start b: largest j <= W with !is_shifted
    cand = jnp.where((~shf) & (js <= W)[None, :], js[None, :], -1)
    b = jnp.max(cand, axis=1)
    ovf_left = b < 0

    # R = #occupied buckets in [b, fq]
    sel = occ & (js[None, :] >= b[:, None]) & (js <= W)[None, :]
    R = jnp.sum(sel, axis=1, dtype=jnp.int32)

    run_start = nonempty & ~con
    cum = jnp.cumsum(run_start.astype(jnp.int32), axis=1)
    cum_before = jnp.where(
        b > 0, jnp.take_along_axis(cum, jnp.maximum(b - 1, 0)[:, None], axis=1)[:, 0], 0
    )
    C = cum_before + R

    in_run = (cum == C[:, None]) & nonempty
    present = occ_q & jnp.any(in_run & (rem == fr[:, None]), axis=1)

    ovf_right = in_run[:, -1]  # run may continue past the window
    ovf_nostart = occ_q & ~ovf_left & (cum[:, -1] < C)  # run start past window
    overflow = occ_q & (ovf_left | ovf_right | ovf_nostart)
    return present, overflow


@functools.partial(jax.jit, static_argnums=(0, 4))
def lookup(
    cfg: QFConfig, state: QFState, fq: jnp.ndarray, fr: jnp.ndarray, window: int = 256
):
    """MAY-CONTAIN for a batch of fingerprints (paper Fig. 3, vectorized).

    Fast path: one contiguous ``2*window``-slot decode per query (the
    TPU analogue of the paper's single-page cluster access).  Queries
    whose cluster exceeds the window (whp-rare; paper §3 Fact) retry at
    4x the window, then fall back to the exact decode path.
    """
    present, ovf = _window_decode(cfg, state, fq, fr, window)

    def retry(args):
        present, ovf = args
        p2, o2 = _window_decode(cfg, state, fq, fr, min(4 * window, cfg.m))
        present = jnp.where(ovf, p2, present)

        def exact(args):
            present, o2 = args
            pe = lookup_exact(cfg, state, fq, fr)
            return jnp.where(o2, pe, present)

        return jax.lax.cond(
            jnp.any(o2), exact, lambda a: a[0], (present, ovf & o2)
        )

    return jax.lax.cond(jnp.any(ovf), retry, lambda a: a[0], (present, ovf))


def contains(cfg: QFConfig, state: QFState, keys: jnp.ndarray, window: int = 256):
    """Key-level MAY-CONTAIN."""
    fq, fr = fingerprints(cfg, keys)
    return lookup(cfg, state, fq, fr, window)


# ---------------------------------------------------------------------------
# Bulk mutation: insert / delete / merge / resize
# ---------------------------------------------------------------------------


def merge_sorted_with(
    cfg: QFConfig, state: QFState, fq, fr, k, build, batch_valid=None
) -> QFState:
    """insert_batch body with a pluggable build pass (reference or kernel).

    The batch is sorted together with the table, so it need not be
    sorted itself: ``batch_valid`` marks its ``k`` valid rows (default:
    the first ``k``).  The table joins the sort slot by slot, empty
    slots as sentinels, so it is never compacted first.
    """
    qs, rs, nonempty = _slot_fingerprints(cfg, state)
    allq = jnp.concatenate([qs, fq])
    allr = jnp.concatenate([rs, fr])
    if batch_valid is None:
        batch_valid = jnp.arange(fq.shape[0]) < jnp.asarray(k)
    valid = jnp.concatenate([nonempty, batch_valid])
    allq, allr = _pad_sort(allq, allr, valid)
    new = build(cfg, allq, allr, state.n + jnp.asarray(k, jnp.int32))
    return new._replace(overflow=new.overflow | state.overflow)


@functools.partial(jax.jit, static_argnums=0)
def insert_batch(cfg: QFConfig, state: QFState, fq, fr, valid) -> QFState:
    """Insert the fingerprints where ``valid`` is set (merge + rebuild).

    This is the paper's merge-sort write path: one streaming pass over
    the filter — sequential I/O in the paper, sequential HBM traffic
    here.  The batch needs no order.  Duplicates are kept (QF is a
    multiset).
    """
    k = jnp.sum(valid, dtype=jnp.int32)
    return merge_sorted_with(cfg, state, fq, fr, k, build_sorted, batch_valid=valid)


def insert(cfg: QFConfig, state: QFState, keys: jnp.ndarray, k=None) -> QFState:
    """Insert a batch of keys (k = valid count; default all)."""
    if k is None:
        k = keys.shape[0]
    fq, fr = fingerprints(cfg, keys)
    return insert_batch(cfg, state, fq, fr, jnp.arange(keys.shape[0]) < k)


@functools.partial(jax.jit, static_argnums=0)
def delete_sorted(cfg: QFConfig, state: QFState, fq, fr, k) -> QFState:
    """Delete (one copy of) each of k sorted fingerprints — multiset diff."""
    qs, rs, n = extract(cfg, state)
    idx = jnp.arange(qs.shape[0], dtype=jnp.int32)
    valid = idx < n
    # occurrence rank of element i among equal fingerprints: distance to
    # the start of its run in the sorted stream
    new_run = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), (qs[1:] != qs[:-1]) | (rs[1:] != rs[:-1])]
    )
    rank = idx - scan.cummax(jnp.where(new_run, idx, 0))
    # how many copies of this fingerprint are being deleted
    dlo = lex_searchsorted(fq, fr, qs, rs, "left")
    dhi = lex_searchsorted(fq, fr, qs, rs, "right")
    ndel = jnp.minimum(dhi, jnp.asarray(k, jnp.int32)) - jnp.minimum(
        dlo, jnp.asarray(k, jnp.int32)
    )
    keep = valid & (rank >= ndel)
    # dropping entries keeps the stream sorted: compact, no re-sort
    qs2 = _compact(keep, qs, INT32_MAX)
    rs2 = _compact(keep, rs, UINT32_MAX)
    return build_sorted(cfg, qs2, rs2, jnp.sum(keep, dtype=jnp.int32))


def delete(cfg: QFConfig, state: QFState, keys: jnp.ndarray, k=None) -> QFState:
    if k is None:
        k = keys.shape[0]
    fq, fr = fingerprints(cfg, keys)
    idx = jnp.arange(keys.shape[0])
    fq, fr = _pad_sort(fq, fr, idx < jnp.asarray(k))
    return delete_sorted(cfg, state, fq, fr, k)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def merge(
    cfg_out: QFConfig,
    cfg_a: QFConfig,
    cfg_b: QFConfig,
    sa: QFState,
    sb: QFState,
) -> QFState:
    """Merge two QFs into a (usually larger) output QF (paper Fig. 5).

    Requires identical fingerprint width: q + r must match across all
    three configs; quotients are re-derived by moving bits between
    quotient and remainder, which preserves sort order.
    """
    pa, pb, po = cfg_a.q + cfg_a.r, cfg_b.q + cfg_b.r, cfg_out.q + cfg_out.r
    if not (pa == pb == po):
        raise ValueError("merge requires equal fingerprint width q + r")
    qa, ra, na = extract(cfg_a, sa)
    qb, rb, nb = extract(cfg_b, sb)
    qa, ra = _requotient(qa, ra, cfg_a, cfg_out)
    qb, rb = _requotient(qb, rb, cfg_b, cfg_out)
    allq = jnp.concatenate([qa, qb])
    allr = jnp.concatenate([ra, rb])
    valid = jnp.concatenate(
        [jnp.arange(qa.shape[0]) < na, jnp.arange(qb.shape[0]) < nb]
    )
    allq, allr = _pad_sort(allq, allr, valid)
    out = build_sorted(cfg_out, allq, allr, na + nb)
    return out._replace(overflow=out.overflow | sa.overflow | sb.overflow)


def _requotient(fq, fr, cfg_in: QFConfig, cfg_out: QFConfig):
    """Move bits between quotient and remainder: (q, r) -> (q', r').

    Monotone w.r.t. lexicographic order, so sortedness is preserved.
    """
    dq = cfg_out.q - cfg_in.q
    if dq == 0:
        return fq, fr
    if dq > 0:  # grow quotient: steal top dq bits of remainder
        top = (fr >> jnp.uint32(cfg_in.r - dq)).astype(jnp.int32)
        fq2 = jnp.where(
            fq == INT32_MAX, INT32_MAX, (fq << dq) | top
        )
        fr2 = jnp.where(
            fq == INT32_MAX,
            UINT32_MAX,
            (fr << jnp.uint32(dq))
            & jnp.uint32((1 << cfg_in.r) - 1 if cfg_in.r < 32 else 0xFFFFFFFF),
        )
        # keep remainder left-aligned in r_out bits: r_out = r_in - dq
        fr2 = fr2 >> jnp.uint32(cfg_in.r - cfg_out.r)
        return fq2, fr2
    # shrink quotient: donate low |dq| quotient bits to the remainder top
    k = -dq
    lowbits = (fq & ((1 << k) - 1)).astype(jnp.uint32)
    fq2 = jnp.where(fq == INT32_MAX, INT32_MAX, fq >> k)
    fr2 = jnp.where(
        fq == INT32_MAX, UINT32_MAX, (lowbits << jnp.uint32(cfg_in.r)) | fr
    )
    return fq2, fr2


def multi_merge(cfg_out: QFConfig, parts, build=None) -> QFState:
    """Merge any number of (cfg, state) QFs into one output QF.

    One decode pass per input + one sort + one build — the k-way
    analogue of the paper's merge, used by the cascade filter when it
    collapses levels Q_0..Q_i into Q_i' (paper §4, Fig. 5).  ``build``
    swaps the bandwidth-bound rebuild pass (default :func:`build_sorted`;
    the Pallas kernel path passes ``kernels.ops.build_sorted``).
    """
    if build is None:
        build = build_sorted
    p_out = cfg_out.q + cfg_out.r
    qs_all, rs_all, valid_all, n_total = [], [], [], jnp.zeros((), jnp.int32)
    overflow = jnp.zeros((), jnp.bool_)
    for cfg, state in parts:
        if cfg.q + cfg.r != p_out:
            raise ValueError("multi_merge requires equal fingerprint width")
        fq, fr, n = extract(cfg, state)
        fq, fr = _requotient(fq, fr, cfg, cfg_out)
        qs_all.append(fq)
        rs_all.append(fr)
        valid_all.append(jnp.arange(fq.shape[0]) < n)
        n_total = n_total + n
        overflow = overflow | state.overflow
    allq = jnp.concatenate(qs_all)
    allr = jnp.concatenate(rs_all)
    valid = jnp.concatenate(valid_all)
    allq, allr = _pad_sort(allq, allr, valid)
    out = build(cfg_out, allq, allr, n_total)
    # an input whose slack had overflowed may already have lost entries;
    # the union must keep reporting that (as qf.merge does)
    return out._replace(overflow=out.overflow | overflow)


def merge_streams(aq, ar, na, bq, br, nb):
    """Merge two lexicographically sorted fingerprint streams in O(n).

    Both inputs follow the extract/_pad_sort convention: sorted valid
    prefix (``na``/``nb`` entries) followed by sentinel padding.  The
    output stream has length ``len(a) + len(b)`` with the ``na + nb``
    valid entries sorted first — computed by rank arithmetic
    (``searchsorted`` + scatter), skipping the ``lax.sort`` that
    dominates ``multi_merge``.  Used by the incremental-resize finish
    pass, where one input (the in-flight buffer) is much smaller than
    the other (the freshly built table).
    """
    la, lb = aq.shape[0], bq.shape[0]
    ia = jnp.arange(la, dtype=jnp.int32)
    ib = jnp.arange(lb, dtype=jnp.int32)
    # ties break a-before-b: a ranks 'left' into b, b ranks 'right' into a
    ra = ia + lex_searchsorted(bq, br, aq, ar, "left")
    rb = ib + lex_searchsorted(aq, ar, bq, br, "right")
    # sentinel padding would collide: route it to the tail deterministically
    ra = jnp.where(ia < na, ra, nb + ia)
    rb = jnp.where(ib < nb, rb, la + ib)
    out_q = jnp.full((la + lb,), INT32_MAX, jnp.int32)
    out_r = jnp.full((la + lb,), UINT32_MAX, jnp.uint32)
    out_q = out_q.at[ra].set(aq).at[rb].set(bq)
    out_r = out_r.at[ra].set(ar).at[rb].set(br)
    return out_q, out_r


def merge_streams_many(parts):
    """Fold any number of sorted streams into one, sort-free.

    ``parts`` is a sequence of ``(fq, fr, n)`` streams in the
    extract/_pad_sort convention (same (q, r) split).  Pairwise
    :func:`merge_streams` folds keep every pass rank arithmetic —
    the k-way analogue used where ``multi_merge`` would pay a
    ``lax.sort`` over the concatenation.  Returns ``(fq, fr, n)`` with
    length ``sum(len(part))``.
    """
    parts = list(parts)
    if not parts:
        raise ValueError("merge_streams_many needs at least one stream")
    aq, ar, na = parts[0]
    na = jnp.asarray(na, jnp.int32)
    for bq, br, nb in parts[1:]:
        nb = jnp.asarray(nb, jnp.int32)
        aq, ar = merge_streams(aq, ar, na, bq, br, nb)
        na = na + nb
    return aq, ar, na


def resize(
    cfg: QFConfig, state: QFState, new_q: int, build=None
) -> tuple[QFConfig, QFState]:
    """Dynamically resize (paper §3 'Resizing'): borrow/steal one or more
    bits between remainder and quotient, preserving all fingerprints.

    A host-level structural op — the slot-plane shapes change — but the
    requotient + rebuild body is one streaming device pass.  ``build``
    swaps the rebuild pass (reference vs Pallas kernel), as in
    :func:`multi_merge`.
    """
    if build is None:
        build = build_sorted
    new_cfg = cfg._replace(q=new_q, r=cfg.q + cfg.r - new_q)
    qs, rs, n = extract(cfg, state)
    qs, rs = _requotient(qs, rs, cfg, new_cfg)
    pad = new_cfg.total_slots - qs.shape[0]
    if pad > 0:
        qs = jnp.concatenate([qs, jnp.full((pad,), INT32_MAX, jnp.int32)])
        rs = jnp.concatenate([rs, jnp.full((pad,), UINT32_MAX, jnp.uint32)])
    elif pad < 0:
        # shrinking: all valid entries must fit; sort pushes pads last
        qs, rs = _pad_sort(qs, rs, jnp.arange(qs.shape[0]) < n)
        qs, rs = qs[: new_cfg.total_slots], rs[: new_cfg.total_slots]
    new = build(new_cfg, qs, rs, n)
    return new_cfg, new._replace(overflow=new.overflow | state.overflow)


# ---------------------------------------------------------------------------
# Item-at-a-time parity wrappers (paper semantics; used by tests)
# ---------------------------------------------------------------------------


def insert_one(cfg: QFConfig, state: QFState, key) -> QFState:
    return insert(cfg, state, jnp.asarray([key]))


def delete_one(cfg: QFConfig, state: QFState, key) -> QFState:
    return delete(cfg, state, jnp.asarray([key]))


def contains_one(cfg: QFConfig, state: QFState, key) -> jnp.ndarray:
    return contains(cfg, state, jnp.asarray([key]))[0]
