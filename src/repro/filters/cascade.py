"""Cascade filter, functional (paper §4's insert-optimized on-flash AMQ).

COLA-style hierarchy: RAM quotient filter Q0 plus a *fixed-depth* stack
of on-"disk" QFs whose capacities grow geometrically with the fanout.
The legacy ``core.cascade_filter`` dataclass drives merges from Python
(``int(state.n)`` sync per batch, lazily allocated levels); here the
level stack is a static-depth tuple inside one pytree state, and the
merge-down decision is a ``jax.lax.switch`` over device counts:

* target = smallest level i such that |Q0| + |Q1..Qi| fits level i's
  capacity (the paper's collapse rule);
* branch i k-way-merges Q0..Qi into a fresh Qi in one streaming pass
  (``qf.multi_merge``) and empties everything above it;
* branch L (no fit / Q0 not full) is the identity.

Everything — including the modeled I/O schedule in ``IOCounters`` — is
device arithmetic, so a full ingest loop compiles into one
``jax.lax.scan`` with zero host transfers.  If Q0 fills and no level
fits (undersized ``levels``), Q0 keeps absorbing into its slack and its
``overflow`` flag eventually trips — sized like the legacy default
(``levels >= log_b(n_total / capacity(Q0))``) this never happens, and
the depth is no longer a hard ceiling: ``needs_resize`` flags the
approaching saturation on device and ``grow`` deepens the stack by one
level (a host-level structural step; the façade's ``auto_grow`` ingest
driver composes the two).

**Frozen cold tier** (``frozen_below=k``): levels at depth >= k are
demoted to binary-fuse form (``repro.core.fuse_filter``) — a level is
write-once between merge-downs, so immutability costs nothing there,
and the fuse table is ~20-30% smaller than the QF at the same fp-rate
target with a fixed 3-read probe.  A merge-down whose target is frozen
peels the merged stream into a fuse table; a later merge that
*consumes* a frozen level re-expands it from its retained sorted
fingerprint run, so merge/grow/shrink/``auto_scale`` keep composing
and membership stays exact across demote -> probe -> re-expand ->
merge.  Peeling is device-resident (``fuse.freeze_stream`` hides the
data-dependent rounds in ``while_loop`` carries), so a frozen cascade's
insert/merge-down runs under the same zero-sync ``lax.switch`` as the
all-QF stack.  Deletes are refused (``UnsupportedOpError``): a fuse
table cannot unlink a key.  ``cost_model.recommend_frozen_below`` picks
k from the geometry.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import cost_model
from repro.core import fuse_filter as fuse
from repro.core import quotient_filter as qf

from . import iostats, qf_filter
from .iostats import IOCounters
from .registry import FilterImpl, UnsupportedOpError, register


class CascadeConfig(NamedTuple):
    ram_q: int  # log2 buckets of Q0
    p: int  # fingerprint bits (q + r at every level)
    fanout: int = 2  # power of two; level i has q = ram_q + (i+1)*log2(fanout)
    levels: int = 4  # static level-stack depth
    seed: int = 0
    max_load: float = 0.75
    backend: str = "reference"
    shrink_load: float = 0.5  # low watermark vs the one-shallower stack
    frozen_below: Optional[int] = None  # demote levels >= this depth to fuse form
    fuse_bits: Optional[int] = None  # frozen cell width override (default: match QF fp)

    @property
    def lb(self) -> int:
        return int(math.log2(self.fanout))

    def is_frozen(self, i: int) -> bool:
        return self.frozen_below is not None and i >= self.frozen_below

    def fuse_cfg(self, i: int) -> fuse.FuseConfig:
        """Frozen geometry of level i: sized for the level's design
        capacity, cell width matching the QF level's fp-rate target."""
        lvl = self.level_cfg(i)
        fp_bits = self.fuse_bits or cost_model.fuse_fp_bits_for(lvl.r, self.max_load)
        return fuse.make_config(lvl.capacity, self.p, fp_bits=fp_bits, seed=self.seed)

    def level_size_bytes(self, i: int) -> int:
        """Probe-structure bytes of level i (fuse table when frozen)."""
        return (
            self.fuse_cfg(i).size_bytes
            if self.is_frozen(i)
            else self.level_cfg(i).size_bytes
        )

    @property
    def cold_run_bytes(self) -> int:
        """Sequential-only re-expansion runs of the frozen levels —
        merge-path bytes, never touched by probes."""
        return sum(
            self.fuse_cfg(i).run_bytes
            for i in range(self.levels)
            if self.is_frozen(i)
        )

    def _cfg(self, q: int) -> qf.QFConfig:
        return qf.QFConfig(
            q=q,
            r=self.p - q,
            slack=max(1024, (1 << q) // 64),
            seed=self.seed,
            max_load=self.max_load,
        )

    @property
    def q0_cfg(self) -> qf.QFConfig:
        return self._cfg(self.ram_q)

    def level_cfg(self, i: int) -> qf.QFConfig:
        return self._cfg(self.ram_q + (i + 1) * self.lb)

    @property
    def size_bytes(self) -> int:
        return self.q0_cfg.size_bytes + sum(
            self.level_size_bytes(i) for i in range(self.levels)
        )


class CascadeState(NamedTuple):
    q0: qf.QFState
    levels: tuple  # length cfg.levels, element i sized by cfg.level_cfg(i)
    io: IOCounters


def _empty_level(cfg: CascadeConfig, i: int):
    if cfg.is_frozen(i):
        return fuse.empty(cfg.fuse_cfg(i))
    return qf.empty(cfg.level_cfg(i))


def make(**spec):
    cfg = CascadeConfig(**spec)
    _check_geometry(cfg)
    qf_filter._check_backend(cfg)
    return cfg, CascadeState(
        q0=qf.empty(cfg.q0_cfg),
        levels=tuple(_empty_level(cfg, i) for i in range(cfg.levels)),
        io=iostats.zeros(),
    )


# ---------------------------------------------------------------------------
# Frozen-tier plumbing: canonical streams in, fuse/QF levels out
# ---------------------------------------------------------------------------


def _canon_cfg(cfg: CascadeConfig) -> qf.QFConfig:
    """The canonical (q, r) split all cross-level streams are carried in
    (``fuse.canonical_split``); only q/r are read — never materialized."""
    qc, rc = fuse.canonical_split(cfg.p)
    return qf.QFConfig(q=qc, r=rc, slack=0, seed=cfg.seed, max_load=cfg.max_load)


def _level_stream(cfg: CascadeConfig, state: CascadeState, i: int):
    """Level i as a sorted canonical fingerprint stream ``(fq, fr, n)``.

    QF levels decode + requotient (order-preserving); frozen levels
    stream their retained run directly — the re-expansion path.
    """
    s = state.levels[i]
    if cfg.is_frozen(i):
        return fuse.extract_run(cfg.fuse_cfg(i), s)
    c = cfg.level_cfg(i)
    fq, fr, n = qf.extract(c, s)
    fq, fr = qf._requotient(fq, fr, c, _canon_cfg(cfg))
    return fq, fr, n


def _slot_stream(cfg: CascadeConfig, c: qf.QFConfig, s):
    """A QF's fingerprints slot by slot in the canonical split, with the
    mask of stored slots — no compaction, so not yet one sorted run."""
    fq, fr, stored = qf._slot_fingerprints(c, s)
    fq, fr = qf._requotient(fq, fr, c, _canon_cfg(cfg))
    return fq, fr, stored


def _q0_stream(cfg: CascadeConfig, state: CascadeState):
    fq, fr, n = qf.extract(cfg.q0_cfg, state.q0)
    fq, fr = qf._requotient(fq, fr, cfg.q0_cfg, _canon_cfg(cfg))
    return fq, fr, n


def _build_level(cfg: CascadeConfig, i: int, allq, allr, total: int, overflow: bool):
    """Materialize level i from a sorted canonical stream (host-level)."""
    if cfg.is_frozen(i):
        st = fuse.freeze(cfg.fuse_cfg(i), allq, allr, total)
        return st._replace(overflow=st.overflow | jnp.asarray(overflow))
    tgt = cfg.level_cfg(i)
    tq, tr = qf._requotient(allq, allr, _canon_cfg(cfg), tgt)
    built = qf_filter.build_fn(cfg)(tgt, tq, tr, jnp.asarray(total, jnp.int32))
    return built._replace(overflow=built.overflow | jnp.asarray(overflow))


def _level_read_bytes(cfg: CascadeConfig, i: int) -> float:
    """Merge-path read cost of consuming level i: QF levels stream their
    table; frozen levels stream only their run (the table is not read)."""
    return (
        cfg.fuse_cfg(i).run_bytes if cfg.is_frozen(i) else cfg.level_cfg(i).size_bytes
    )


def _level_write_bytes(cfg: CascadeConfig, i: int) -> float:
    return cfg.level_size_bytes(i) + (
        cfg.fuse_cfg(i).run_bytes if cfg.is_frozen(i) else 0
    )


def _build_level_traced(cfg: CascadeConfig, i: int, allq, allr, total):
    """Materialize level i from a sorted canonical stream, traceable
    (``total`` may be a device scalar).  Frozen targets peel on device
    (:func:`fuse.freeze_stream`); a stream that exceeds the frozen
    capacity or refuses to peel sets the level's ``overflow`` flag."""
    if cfg.is_frozen(i):
        return fuse.freeze_stream(cfg.fuse_cfg(i), allq, allr, total)
    tgt = cfg.level_cfg(i)
    tq, tr = qf._requotient(allq, allr, _canon_cfg(cfg), tgt)
    return qf_filter.build_fn(cfg)(tgt, tq, tr, jnp.asarray(total, jnp.int32))


@jax.named_scope("cascade.collapse")
def _collapse_into(cfg: CascadeConfig, state: CascadeState, i: int) -> CascadeState:
    """Merge Q0..Q_i into a fresh Q_i; levels above i empty (paper Fig. 5).

    Every participant joins in the canonical split — a QF as its slot
    stream, a frozen level as its retained run — and one unstable sort
    over the concatenation orders them, as the flat QF's insert does
    (equal fingerprints are identical, so their order cannot matter).
    A rank-arithmetic fold (``merge_streams_many``) would binary-search
    every element of every level, one gather per step, which on a TPU
    costs far more than the sort.  A frozen target peels on device, so
    the whole collapse — demotions included — stays inside the
    ``lax.switch`` branch."""
    parts = [_slot_stream(cfg, cfg.q0_cfg, state.q0)]
    total = state.q0.n
    for j in range(i + 1):
        s = state.levels[j]
        if cfg.is_frozen(j):
            fq, fr, n = fuse.extract_run(cfg.fuse_cfg(j), s)
            parts.append((fq, fr, jnp.arange(fq.shape[0]) < n))
        else:
            parts.append(_slot_stream(cfg, cfg.level_cfg(j), s))
        total = total + s.n
    allq, allr = qf._pad_sort(
        *(jnp.concatenate([part[k] for part in parts]) for k in range(3))
    )
    overflow = state.q0.overflow
    for j in range(i + 1):
        overflow = overflow | state.levels[j].overflow
    merged = _build_level_traced(cfg, i, allq, allr, total)
    merged = merged._replace(overflow=merged.overflow | overflow)
    # I/O: stream each participating non-empty disk level in, target out
    read = jnp.zeros((), jnp.float32)
    for j in range(i + 1):
        read = read + jnp.where(
            state.levels[j].n > 0,
            jnp.float32(_level_read_bytes(cfg, j)),
            jnp.float32(0),
        )
    io = state.io._replace(
        seq_read_bytes=state.io.seq_read_bytes + read,
        seq_write_bytes=state.io.seq_write_bytes
        + jnp.float32(_level_write_bytes(cfg, i)),
        flushes=state.io.flushes + 1,
        merges=state.io.merges + 1,
    )
    new_levels = tuple(
        _empty_level(cfg, j) if j < i else (merged if j == i else state.levels[j])
        for j in range(cfg.levels)
    )
    return CascadeState(q0=qf.empty(cfg.q0_cfg), levels=new_levels, io=io)


def _maybe_collapse(cfg: CascadeConfig, state: CascadeState, full) -> CascadeState:
    """lax.switch on the collapse target (branch cfg.levels = identity)."""
    L = cfg.levels
    ns = jnp.stack([s.n for s in state.levels])
    cum = state.q0.n + jnp.cumsum(ns)
    caps = jnp.asarray([cfg.level_cfg(i).capacity for i in range(L)], jnp.int32)
    fits = cum <= caps
    target = jnp.argmax(fits).astype(jnp.int32)  # first fitting level
    branch = jnp.where(full & jnp.any(fits), target, jnp.int32(L))

    def mk(i):
        return lambda s: _collapse_into(cfg, s, i)

    return jax.lax.switch(branch, [mk(i) for i in range(L)] + [lambda s: s], state)


@functools.partial(jax.jit, static_argnums=0)
def _insert_impl(cfg: CascadeConfig, state, keys, k) -> CascadeState:
    with jax.named_scope("cascade.q0"):
        q0 = qf_filter.insert_keys(cfg.q0_cfg, cfg.backend, state.q0, keys, k)
    state = state._replace(q0=q0)
    full = qf.load(cfg.q0_cfg, q0) >= cfg.max_load
    return _maybe_collapse(cfg, state, full)


def insert(cfg: CascadeConfig, state, keys, k=None) -> CascadeState:
    """Insert a batch; merge-downs (frozen demotions included) happen
    inside one jitted program — the eager façade call costs one
    dispatch, not a re-trace of the ``lax.switch`` collapse branches."""
    if k is None:
        k = keys.shape[0]
    return _insert_impl(cfg, state, keys, jnp.asarray(k, jnp.int32))


def _structures(cfg, state):
    yield cfg.q0_cfg, state.q0
    for i in range(cfg.levels):
        yield cfg.level_cfg(i), state.levels[i]


def _level_contains(cfg: CascadeConfig, state, i: int, keys):
    s = state.levels[i]
    if cfg.is_frozen(i):
        fc = cfg.fuse_cfg(i)
        if cfg.backend == "pallas":
            from repro.kernels import ops as kernel_ops

            return kernel_ops.fuse_contains(fc, s, keys)
        return fuse.contains(fc, s, keys)  # carries its own n > 0 guard
    c = cfg.level_cfg(i)
    return jax.lax.cond(
        s.n > 0,
        lambda: qf_filter.contains_keys(c, cfg.backend, s, keys),
        lambda: jnp.zeros(keys.shape[0], jnp.bool_),
    )


def _fused_level_hits(cfg: CascadeConfig, state, keys, with_stats=False):
    """Per-structure hits from ONE probe program over the stack.

    Q0 and the unfrozen levels hash and sort once, then each probes the
    sorted batch in its own window-aligned tiles; frozen levels fold in
    via their 3-gather pass (``ops.cascade_lookup``).  Returns ``(q0_hit, [hit per
    level])`` so ``contains`` can OR and ``probe`` can keep the paper's
    top-down read accounting without a second pass; ``with_stats``
    appends the probe's counters (:func:`contains_stats`).
    """
    from repro.kernels import ops as kernel_ops

    qf_ix = [i for i in range(cfg.levels) if not cfg.is_frozen(i)]
    fz_ix = [i for i in range(cfg.levels) if cfg.is_frozen(i)]
    out = kernel_ops.cascade_lookup(
        (cfg.q0_cfg,) + tuple(cfg.level_cfg(i) for i in qf_ix),
        (state.q0,) + tuple(state.levels[i] for i in qf_ix),
        tuple(cfg.fuse_cfg(i) for i in fz_ix),
        tuple(state.levels[i] for i in fz_ix),
        keys,
        with_stats=with_stats,
    )
    hits, stats = out if with_stats else (out, None)
    per_level = dict(zip(qf_ix + fz_ix, hits[1:]))
    lvl_hits = [per_level[i] for i in range(cfg.levels)]
    if not with_stats:
        return hits[0], lvl_hits
    # one count per structure, Q0 first; a frozen level's pass counts none
    at = jnp.asarray([0] + [1 + i for i in qf_ix])
    for key in ("tiles", "tiles_unfit", "queries_exact"):
        stats[key] = jnp.zeros((1 + cfg.levels,), jnp.int32).at[at].set(stats[key])
    return hits[0], lvl_hits, stats


@functools.partial(jax.jit, static_argnums=0)
def contains(cfg: CascadeConfig, state, keys):
    """Membership across the stack in one jitted program (the per-level
    ``lax.cond`` guards would otherwise re-trace on every eager call)."""
    if cfg.backend == "pallas":
        q0_hit, lvl_hits = _fused_level_hits(cfg, state, keys)
        hit = q0_hit
        for h in lvl_hits:
            hit = hit | h
        return hit
    hit = jax.lax.cond(
        state.q0.n > 0,
        lambda: qf_filter.contains_keys(cfg.q0_cfg, cfg.backend, state.q0, keys),
        lambda: jnp.zeros(keys.shape[0], jnp.bool_),
    )
    for i in range(cfg.levels):
        hit = hit | _level_contains(cfg, state, i, keys)
    return hit


def contains_stats(cfg: CascadeConfig, state, keys):
    """``(hits, stats)``: ``contains`` and the counters of its probe,
    from the same program (README "Observability"): scalars ``queries``
    and ``exact_passes`` (whole-structure decodes), and ``(1 + levels,)``
    arrays ``tiles`` (live window-aligned query tiles), ``tiles_unfit``
    and ``queries_exact``, one count per structure, Q0 first (a frozen
    level counts none).  Only the pallas backend runs the probe that
    they count."""
    if cfg.backend != "pallas":
        raise UnsupportedOpError(
            "cascade", "contains_stats", "the counters count backend='pallas'"
        )
    return _contains_stats(cfg, state, keys)


@functools.partial(jax.jit, static_argnums=0)
def _contains_stats(cfg: CascadeConfig, state, keys):
    q0_hit, lvl_hits, stats = _fused_level_hits(cfg, state, keys, with_stats=True)
    return functools.reduce(jnp.logical_or, lvl_hits, q0_hit), stats


@functools.partial(jax.jit, static_argnums=0)
def probe(cfg: CascadeConfig, state, keys):
    """Lookup with the paper's schedule: per query still unresolved at a
    non-empty disk level, one random page read (QF cluster) or
    ``cost_model.FUSE_PROBE_READS`` independent gathers (frozen level),
    top-down short-circuit.  Matches ``cost_model.cascade_probe_reads``.

    The modeled I/O schedule stays top-down-sequential either way; under
    the pallas backend the *device* work is the one probe program of
    ``_fused_level_hits`` and the schedule is re-derived from its
    per-level hits."""
    if cfg.backend == "pallas":
        hit, lvl_hits = _fused_level_hits(cfg, state, keys)
    else:
        hit = qf_filter.contains_keys(cfg.q0_cfg, cfg.backend, state.q0, keys)
    reads = jnp.zeros((), jnp.int32)
    for i in range(cfg.levels):
        s = state.levels[i]
        pending = ~hit
        nonempty = s.n > 0
        per_query = (
            cost_model.FUSE_PROBE_READS
            if cfg.is_frozen(i)
            else cost_model.QF_PROBE_READS
        )
        reads = reads + jnp.where(
            nonempty,
            per_query * jnp.sum(pending, dtype=jnp.int32),
            jnp.int32(0),
        )
        level_hit = (
            lvl_hits[i]
            if cfg.backend == "pallas"
            else _level_contains(cfg, state, i, keys)
        )
        hit = hit | (pending & level_hit)
    io = state.io._replace(rand_page_reads=state.io.rand_page_reads + reads)
    return state._replace(io=io), hit


def delete(cfg: CascadeConfig, state, keys, k=None) -> CascadeState:
    """Remove one copy per key from the topmost structure holding it.

    Duplicate-safe: the j-th batch occurrence of a key targets the j-th
    stored copy in top-down order, so a batch can delete more copies of
    a key than any single level holds.

    Disk-level deletes are charged to ``IOCounters`` under the same
    schedule as ``probe``: one random page read per key targeted at a
    non-empty level (the cluster must be fetched) and one random page
    write per copy actually removed; Q0 deletes are RAM-only and free."""
    if cfg.frozen_below is not None:
        raise UnsupportedOpError(
            "cascade",
            "delete",
            "frozen_below cascades cannot unlink keys from demoted "
            "(binary-fuse) levels; use an all-QF cascade when the cold "
            "tier must support deletes",
        )
    if k is None:
        k = keys.shape[0]
    return _delete_impl(cfg, state, keys, jnp.asarray(k, jnp.int32))


@functools.partial(jax.jit, static_argnums=0)
def _delete_impl(cfg: CascadeConfig, state, keys, k) -> CascadeState:
    valid = qf_filter.valid_mask(keys, k)
    structures = [(cfg.q0_cfg, state.q0)] + [
        (cfg.level_cfg(i), state.levels[i]) for i in range(cfg.levels)
    ]
    fq0, fr0 = qf.fingerprints(cfg.q0_cfg, keys)
    rank = qf_filter.batch_occurrence_rank(fq0, fr0, valid)
    cum = jnp.zeros(keys.shape[0], jnp.int32)
    out = []
    reads = jnp.zeros((), jnp.int32)
    writes = jnp.zeros((), jnp.int32)
    for lvl, (c, s) in enumerate(structures):
        fq, fr = qf.fingerprints(c, keys)
        cnt = qf_filter.multiplicity(c, s, fq, fr)
        todel = valid & (rank >= cum) & (rank < cum + cnt)
        new = qf_filter.delete_masked(c, s, fq, fr, todel)
        if lvl > 0:  # disk-resident level
            reads = reads + jnp.where(
                s.n > 0, jnp.sum(todel, dtype=jnp.int32), jnp.int32(0)
            )
            writes = writes + (s.n - new.n)
        out.append(new)
        cum = cum + cnt
    io = state.io._replace(
        rand_page_reads=state.io.rand_page_reads + reads,
        rand_page_writes=state.io.rand_page_writes + writes,
    )
    return CascadeState(q0=out[0], levels=tuple(out[1:]), io=io)


def merge(cfg: CascadeConfig, sa, sb) -> CascadeState:
    """Union of two cascades (same cfg) as ONE streaming pass into the
    smallest level that fits the combined count (paper Fig. 5's k-way
    merge).

    The previous component-wise merge overflowed a level whenever the
    two inputs' same-index levels were each more than half full — the
    collapse trigger only looked at Q0's load.  Choosing the target by
    the *total* count can never oversubscribe a level that fits; if even
    the bottom level cannot hold the union, the merge streams into the
    bottom anyway and the ``overflow`` flag reports the (physically
    unavoidable) oversubscription — ``grow``/``resize`` the inputs
    first.

    All 2L + 2 components stream in the canonical split and fold by
    rank arithmetic (``merge_streams_many`` — sort-free); each
    ``lax.switch`` branch re-splits elementwise and rebuilds at its
    target geometry.  Frozen targets peel on device
    (``fuse.freeze_stream``), frozen inputs re-expand from their
    retained runs, so frozen and all-QF cascades share this one
    device-resident path.
    """
    L = cfg.levels
    parts = [_q0_stream(cfg, sa), _q0_stream(cfg, sb)]
    for j in range(L):
        parts.append(_level_stream(cfg, sa, j))
        parts.append(_level_stream(cfg, sb, j))
    allq, allr, total = qf.merge_streams_many(parts)
    overflow = sa.q0.overflow | sb.q0.overflow
    for s in (sa, sb):
        for lv in s.levels:
            overflow = overflow | lv.overflow

    read = jnp.zeros((), jnp.float32)
    for j in range(L):
        for s in (sa.levels[j], sb.levels[j]):
            read = read + jnp.where(
                s.n > 0, jnp.float32(_level_read_bytes(cfg, j)), jnp.float32(0)
            )
    io = iostats.add(sa.io, sb.io)
    io = io._replace(seq_read_bytes=io.seq_read_bytes + read, merges=io.merges + 1)

    caps = jnp.asarray([cfg.level_cfg(i).capacity for i in range(L)], jnp.int32)
    fits = total <= caps
    branch = jnp.where(jnp.any(fits), jnp.argmax(fits), L - 1).astype(jnp.int32)

    def mk(i):
        def build_at(args):
            allq, allr, io = args
            merged = _build_level_traced(cfg, i, allq, allr, total)
            merged = merged._replace(overflow=merged.overflow | overflow)
            io2 = io._replace(
                seq_write_bytes=io.seq_write_bytes
                + jnp.float32(_level_write_bytes(cfg, i))
            )
            levels = tuple(
                merged if j == i else _empty_level(cfg, j) for j in range(L)
            )
            return CascadeState(q0=qf.empty(cfg.q0_cfg), levels=levels, io=io2)

        return build_at

    return jax.lax.switch(branch, [mk(i) for i in range(L)], (allq, allr, io))


def _restream_host(new_cfg: CascadeConfig, parts, io, overflow):
    """Collapse canonical streams into the smallest fitting level of
    ``new_cfg`` (host-level; the tail of the geometry-changing resize).
    ``parts`` is a list of ``(fq, fr, n)`` canonical streams."""
    L = new_cfg.levels
    total = int(jax.device_get(sum(p[2] for p in parts)))  # one batched sync
    target = next(
        (i for i in range(L) if total <= new_cfg.level_cfg(i).capacity), L - 1
    )
    if new_cfg.is_frozen(target) and total > new_cfg.fuse_cfg(target).capacity:
        raise ValueError(
            f"union of {total} keys exceeds the bottom frozen level's "
            f"capacity {new_cfg.fuse_cfg(target).capacity}; grow/resize first"
        )
    allq, allr, _ = qf.merge_streams_many(parts)
    merged = _build_level(new_cfg, target, allq, allr, total, overflow)
    io = io._replace(
        seq_write_bytes=io.seq_write_bytes
        + jnp.float32(_level_write_bytes(new_cfg, target)),
        merges=io.merges + 1,
    )
    levels = tuple(
        merged if j == target else _empty_level(new_cfg, j) for j in range(L)
    )
    return CascadeState(q0=qf.empty(new_cfg.q0_cfg), levels=levels, io=io)


def _all_streams(cfg: CascadeConfig, state: CascadeState):
    """Every component of one cascade as canonical streams, plus the
    merge-path read bytes and the or'd overflow flag (host values)."""
    ns, ovf = jax.device_get(
        (
            jnp.stack([s.n for s in state.levels]),
            jnp.stack([state.q0.overflow] + [s.overflow for s in state.levels]),
        )
    )  # one batched sync for the whole walk, not 2L+1 scalar pulls
    parts = [_q0_stream(cfg, state)]
    overflow = ovf.any()
    read = 0.0
    for j in range(cfg.levels):
        parts.append(_level_stream(cfg, state, j))
        if ns[j] > 0:
            read += _level_read_bytes(cfg, j)
    return parts, read, overflow


def needs_resize(cfg: CascadeConfig, state):
    """Device predicate: a full Q0 could fail to collapse anywhere —
    i.e. Q0's capacity plus everything already on disk no longer fits
    the bottom level (the paper's ``levels >= log_b(n/cap0)`` sizing).
    Q0's *actual* count is taken when it exceeds the design capacity
    (a large batch can overshoot into the slack), so the predicate
    cannot report False while a collapse is already impossible."""
    ns = jnp.stack([s.n for s in state.levels])
    q0_worst = jnp.maximum(state.q0.n, jnp.int32(cfg.q0_cfg.capacity))
    return q0_worst + jnp.sum(ns) > jnp.int32(cfg.level_cfg(cfg.levels - 1).capacity)


def _check_geometry(cfg: CascadeConfig) -> None:
    if cfg.fanout < 2 or (cfg.fanout & (cfg.fanout - 1)):
        raise ValueError("fanout must be a power of two >= 2")
    if cfg.levels < 1:
        raise ValueError("need at least one disk level")
    if cfg.ram_q + (cfg.levels) * cfg.lb >= cfg.p:
        raise ValueError("fingerprint bits p too small for the deepest level")
    if cfg.frozen_below is not None:
        if cfg.frozen_below < 0:
            raise ValueError("frozen_below must be a depth >= 0")
        fuse.canonical_split(cfg.p)  # frozen levels carry canonical streams
        for i in range(cfg.frozen_below, cfg.levels):
            cfg.fuse_cfg(i)  # validates the per-level fuse geometry


def grow(cfg: CascadeConfig, state):
    """Deepen the level stack by one (host-level structural op).

    The new bottom level starts empty, so no data moves — growth cost
    is deferred to the collapse that eventually fills it (charged there
    as usual).  Requires fingerprint headroom: the new deepest level
    still needs r >= 1 remainder bits.
    """
    new_cfg = cfg._replace(levels=cfg.levels + 1)
    _check_geometry(new_cfg)
    return new_cfg, CascadeState(
        q0=state.q0,
        levels=state.levels + (_empty_level(new_cfg, cfg.levels),),
        io=state.io._replace(resizes=state.io.resizes + 1),
    )


def needs_shrink(cfg: CascadeConfig, state):
    """Device predicate: the deepest level is empty AND the rest of the
    hierarchy (with Q0 at its worst-case design fill, mirroring
    ``needs_resize``) fits the one-shallower stack at the low
    watermark — popping the level then cannot immediately re-trip
    ``needs_resize``."""
    if cfg.levels <= 1:
        return jnp.zeros((), jnp.bool_)
    ns = jnp.stack([s.n for s in state.levels])
    q0_worst = jnp.maximum(state.q0.n, jnp.int32(cfg.q0_cfg.capacity))
    total = q0_worst + jnp.sum(ns)
    fits = total <= jnp.int32(
        cfg.shrink_load * cfg.level_cfg(cfg.levels - 2).capacity
    )
    return (state.levels[-1].n == 0) & fits


def shrink(cfg: CascadeConfig, state):
    """Pop the (empty) deepest level — the inverse of ``grow``, and
    like it free: no data moves, only the static stack depth changes."""
    if cfg.levels <= 1:
        raise ValueError("cannot shrink a single-level cascade")
    if int(state.levels[-1].n) != 0:
        raise ValueError("deepest level is non-empty; collapse/delete first")
    new_cfg = cfg._replace(levels=cfg.levels - 1)
    return new_cfg, CascadeState(
        q0=state.q0,
        levels=state.levels[:-1],
        io=state.io._replace(resizes=state.io.resizes + 1),
    )


def resize(cfg: CascadeConfig, state, levels: int = None, fanout: int = None):
    """Re-shape the hierarchy: deepen the stack and/or widen the fanout.

    Deepening with the fanout unchanged appends empty levels (free).
    Any other geometry change re-streams the whole cascade once into
    the smallest new level that fits the total count (one sequential
    pass, charged to ``IOCounters``).
    """
    new_cfg = cfg._replace(
        levels=cfg.levels if levels is None else levels,
        fanout=cfg.fanout if fanout is None else fanout,
    )
    _check_geometry(new_cfg)
    if new_cfg.fanout == cfg.fanout and new_cfg.levels >= cfg.levels:
        extra = tuple(
            _empty_level(new_cfg, i) for i in range(cfg.levels, new_cfg.levels)
        )
        return new_cfg, CascadeState(
            q0=state.q0,
            levels=state.levels + extra,
            io=state.io._replace(resizes=state.io.resizes + 1),
        )
    if cfg.frozen_below is not None:
        # frozen levels re-expand from their runs; one host re-stream
        parts, read, overflow = _all_streams(cfg, state)
        io = state.io._replace(
            seq_read_bytes=state.io.seq_read_bytes + jnp.float32(read),
            resizes=state.io.resizes + 1,
        )
        return new_cfg, _restream_host(new_cfg, parts, io, overflow)
    # geometry change: one streaming pass into the smallest fitting level
    total = int(state.q0.n) + sum(int(s.n) for s in state.levels)
    target = next(
        (
            i
            for i in range(new_cfg.levels)
            if total <= new_cfg.level_cfg(i).capacity
        ),
        new_cfg.levels - 1,
    )
    parts = [(cfg.q0_cfg, state.q0)] + [
        (cfg.level_cfg(j), state.levels[j]) for j in range(cfg.levels)
    ]
    tgt = new_cfg.level_cfg(target)
    merged = qf.multi_merge(tgt, parts, build=qf_filter.build_fn(cfg))
    read = jnp.zeros((), jnp.float32)
    for j in range(cfg.levels):
        read = read + jnp.where(
            state.levels[j].n > 0,
            jnp.float32(cfg.level_cfg(j).size_bytes),
            jnp.float32(0),
        )
    io = state.io._replace(
        seq_read_bytes=state.io.seq_read_bytes + read,
        seq_write_bytes=state.io.seq_write_bytes + tgt.size_bytes,
        resizes=state.io.resizes + 1,
        merges=state.io.merges + 1,
    )
    new_levels = tuple(
        merged if j == target else qf.empty(new_cfg.level_cfg(j))
        for j in range(new_cfg.levels)
    )
    return new_cfg, CascadeState(
        q0=qf.empty(new_cfg.q0_cfg), levels=new_levels, io=io
    )


def stats(cfg: CascadeConfig, state):
    ns = jnp.stack([s.n for s in state.levels])
    out = {
        "n": state.q0.n + jnp.sum(ns),
        "q0_load": qf.load(cfg.q0_cfg, state.q0),
        "level_counts": ns,
        "nonempty_levels": jnp.sum((ns > 0).astype(jnp.int32)),
        "overflow": state.q0.overflow
        | jnp.any(jnp.stack([s.overflow for s in state.levels])),
        "size_bytes": cfg.size_bytes,
        **state.io._asdict(),
    }
    if cfg.frozen_below is not None:
        frozen = [i for i in range(cfg.levels) if cfg.is_frozen(i)]
        out["frozen_levels"] = len(frozen)
        out["frozen_size_bytes"] = sum(cfg.level_size_bytes(i) for i in frozen)
        out["cold_run_bytes"] = cfg.cold_run_bytes
    return out


IMPL = register(
    FilterImpl(
        name="cascade",
        paper_section="§4 (cascade filter: COLA-style QF hierarchy on flash)",
        cfg_cls=CascadeConfig,
        make=make,
        insert=insert,
        contains=contains,
        contains_stats=contains_stats,
        stats=stats,
        delete=delete,
        merge=merge,
        probe=probe,
        needs_resize=needs_resize,
        grow=grow,
        resize=resize,
        needs_shrink=needs_shrink,
        shrink=shrink,
        can_delete=lambda cfg: cfg.frozen_below is None,
        op_hints={
            "delete": "frozen_below cascades cannot unlink keys from "
            "demoted (binary-fuse) levels"
        },
    )
)
