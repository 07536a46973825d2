"""jit'd wrappers binding the Pallas kernels to the core filter states.

Every op dispatches on a kernel *mode* (see :mod:`.dispatch`):

* ``"mosaic"``    — compiled Pallas kernel (TPU).
* ``"interpret"`` — Pallas interpreter; validation only.
* ``"xla"``       — bit-exact kernel-equivalent jnp lowering; the
  deployed path on CPU/GPU, where interpret-mode tiling would only add
  overhead.

``mode=None`` auto-selects (Mosaic on TPU, XLA elsewhere,
``REPRO_KERNEL_MODE`` env override); the legacy ``interpret=`` bool is
still honored (True -> "interpret", False -> "mosaic").  All three
modes return identical results — parity is enforced by
``tests/test_kernels.py`` and the perf gate's ``kernelratio_*`` rows
keep the deployed mode at-or-under the reference cost.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import fuse_filter as ffc
from repro.core import quotient_filter as qf
from repro.core import scan
from . import dispatch
from .bloom_block import bloom_count_tiles, bloom_probe_tiles
from .cascade_probe import cascade_probe_tiles
from .fuse_probe import fuse_probe_tiles
from .qf_build import qf_build_planes
from .qf_probe import qf_probe_tiles

INT32_MAX = jnp.int32(2**31 - 1)


# ---------------------------------------------------------------------------
# QF bulk build
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=("mode", "block_s")
)
def _build_sorted(cfg, fq, fr, n, *, mode, block_s):
    if dispatch.is_pallas(mode):
        t = cfg.total_slots
        nn = jnp.asarray(n, jnp.int32)
        idx = jnp.arange(fq.shape[0], dtype=jnp.int32)
        valid = idx < nn

        # sentinel stays out of the subtraction (-INT32_MAX - idx wraps
        # for idx >= 2)
        pos = idx + scan.cummax(jnp.where(valid, fq - idx, -INT32_MAX))
        overflow = jnp.any(valid & (pos >= t))
        spos = jnp.where(valid, pos, INT32_MAX)
        con_b = valid & (idx > 0) & (fq == jnp.roll(fq, 1))
        shf_b = valid & (pos != fq)
        meta_bits = con_b.astype(jnp.int32) | (shf_b.astype(jnp.int32) << 1)

        rem_i32, meta = qf_build_planes(
            spos,
            fr,
            meta_bits,
            t,
            block_s=block_s,
            interpret=dispatch.pallas_interpret(mode),
        )
        occ = (
            jnp.zeros((t,), jnp.bool_)
            .at[jnp.where(valid, fq, INT32_MAX)]
            .set(True, mode="drop", indices_are_sorted=True)
        )
        return qf.QFState(
            rem=rem_i32.astype(jnp.uint32),
            occ=occ,
            shf=(meta >> 1) > 0,
            con=(meta & 1) > 0,
            n=nn,
            overflow=overflow,
        )
    # xla mode: the reference scatter IS the kernel-equivalent lowering
    # (same closed-form positions, plane-at-a-time writes)
    return qf.build_sorted(cfg, fq, fr, n)


# The scopes of the kernel paths open outside their jitted wrappers, so
# a kernel's op keeps the wrapper's name (``%_build_sorted.N``,
# ``%_lookup.N``) in the compiled program: traces find kernels by it.
@jax.named_scope("qf.build")
def build_sorted(
    cfg: qf.QFConfig,
    fq: jnp.ndarray,
    fr: jnp.ndarray,
    n,
    *,
    mode: str | None = None,
    interpret: bool | None = None,
    block_s: int = 1024,
) -> qf.QFState:
    """Mode-dispatched equivalent of ``quotient_filter.build_sorted``.

    Probe positions and metadata bits are one cheap scan in jnp; the
    bandwidth-bound plane materialization runs in the Pallas kernel
    (pallas modes) or as the reference jnp scatter (xla mode).
    """
    if cfg.r > 31:
        raise ValueError("kernel path packs remainders in int32 lanes (r <= 31)")
    return _build_sorted(
        cfg, fq, fr, n, mode=dispatch.resolve(mode, interpret), block_s=block_s
    )


# ---------------------------------------------------------------------------
# QF bulk probe
# ---------------------------------------------------------------------------


def _probe_stats(**counts):
    """The probe's counters as int32 device scalars."""
    return {k: jnp.asarray(v, jnp.int32) for k, v in counts.items()}


@functools.partial(
    jax.jit,
    static_argnums=(0,),
    static_argnames=("mode", "tile_t", "wblk", "with_stats"),
)
def _lookup(cfg, state, fq, fr, *, mode, tile_t, wblk, with_stats):
    if not dispatch.is_pallas(mode):
        # xla mode: decode the table once, binary-search the batch —
        # O(m + B log m) vs the reference's O(B * window) per-query
        # cluster decode; same exact-membership answer
        with jax.named_scope("qf.exact"):
            present = qf.lookup_exact(cfg, state, fq, fr)
        if not with_stats:
            return present
        B = fq.shape[0]
        return present, _probe_stats(
            queries=B, tiles=0, tiles_unfit=0, queries_exact=B, exact_passes=1
        )

    B0 = fq.shape[0]
    osort = dispatch.sorted_tile_order(fq, tile_t)
    present_s, ovf_s, tiles, tiles_unfit = qf_probe_tiles(
        state.rem.astype(jnp.int32),
        state.occ.astype(jnp.int32),
        state.shf.astype(jnp.int32),
        state.con.astype(jnp.int32),
        fq[osort],
        fr[osort],
        tile_t=tile_t,
        wblk=wblk,
        interpret=dispatch.pallas_interpret(mode),
    )
    # the padding copies of the last query may spill into the next tile,
    # which shares its window, so every copy still carries the same values
    present, ovf = dispatch.unpermute(osort, B0, present_s, ovf_s)

    @jax.named_scope("qf.exact")
    def resolve(args):
        present, ovf = args
        exact = qf.lookup_exact(cfg, state, fq, fr)
        return jnp.where(ovf > 0, exact, present > 0)

    exact_pass = jnp.any(ovf > 0)
    present = jax.lax.cond(exact_pass, resolve, lambda a: a[0] > 0, (present, ovf))
    if not with_stats:
        return present
    return present, _probe_stats(
        queries=B0,
        tiles=tiles,
        tiles_unfit=tiles_unfit,
        queries_exact=jnp.sum(ovf > 0),
        exact_passes=exact_pass,
    )


@jax.named_scope("qf.probe")  # outside the jitted wrapper, as qf.build
def lookup(
    cfg: qf.QFConfig,
    state: qf.QFState,
    fq: jnp.ndarray,
    fr: jnp.ndarray,
    *,
    mode: str | None = None,
    interpret: bool | None = None,
    tile_t: int = 128,
    wblk: int = 1024,
    with_stats: bool = False,
):
    """Mode-dispatched MAY-CONTAIN; overflows resolve on the exact path.

    ``with_stats=True`` returns ``(present, stats)``, ``stats`` a dict
    of int32 device scalars computed in the same program: ``queries``;
    ``tiles`` (live window-aligned query tiles probed); ``tiles_unfit``
    (tiles whose quotients outrun their window: a guard, 0 by
    construction); ``queries_exact`` (answered by the exact path: their
    cluster outruns the window) and ``exact_passes`` (0 or 1 whole-table
    decodes).  In
    xla mode every query is answered exactly, with no tiles.
    """
    return _lookup(
        cfg,
        state,
        fq,
        fr,
        mode=dispatch.resolve(mode, interpret),
        tile_t=tile_t,
        wblk=wblk,
        with_stats=with_stats,
    )


def contains(cfg: qf.QFConfig, state: qf.QFState, keys: jnp.ndarray, **kw):
    fq, fr = qf.fingerprints(cfg, keys)
    return lookup(cfg, state, fq, fr, **kw)


# ---------------------------------------------------------------------------
# Binary-fuse (3-gather) probe
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=("mode", "tile_t", "wblk")
)
def _fuse_lookup(cfg, state, fq, fr, *, mode, tile_t, wblk):
    p0, p1, p2, fp = ffc.fuse_hash(cfg, fq, fr, state.fuse_seed)
    if not dispatch.is_pallas(mode):
        # xla mode: the 3-gather is already one contiguous-window read
        # per segment triple — gather directly
        present = (state.table[p0] ^ state.table[p1] ^ state.table[p2]) == fp
        return (state.n > 0) & present

    B0 = p0.shape[0]
    osort = dispatch.sorted_tile_order(p0, tile_t)

    hit_s, ovf_s = fuse_probe_tiles(
        state.table.astype(jnp.int32),
        p0[osort],
        p1[osort],
        p2[osort],
        fp[osort],
        tile_t=tile_t,
        wblk=wblk,
        interpret=dispatch.pallas_interpret(mode),
    )
    hit, ovf = dispatch.unpermute(osort, B0, hit_s, ovf_s)

    def resolve(args):
        hit, ovf = args
        exact = (state.table[p0] ^ state.table[p1] ^ state.table[p2]) == fp
        return jnp.where(ovf > 0, exact, hit > 0)

    present = jax.lax.cond(
        jnp.any(ovf > 0), resolve, lambda a: a[0] > 0, (hit, ovf)
    )
    return (state.n > 0) & present


def fuse_lookup(
    cfg: ffc.FuseConfig,
    state: ffc.FuseState,
    fq: jnp.ndarray,
    fr: jnp.ndarray,
    *,
    mode: str | None = None,
    interpret: bool | None = None,
    tile_t: int = 128,
    wblk: int = 2048,
):
    """Mode-dispatched binary-fuse MAY-CONTAIN for canonical fingerprints.

    Pallas modes sort queries by first position so tile windows stream
    the table; tiles that outrun their window fall back to the reference
    3-gather.  XLA mode gathers directly.
    """
    return _fuse_lookup(
        cfg,
        state,
        fq,
        fr,
        mode=dispatch.resolve(mode, interpret),
        tile_t=tile_t,
        wblk=wblk,
    )


@functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=("mode", "tile_t", "wblk")
)
def _fuse_contains_impl(cfg, state, keys, *, mode, tile_t, wblk):
    fq, fr = ffc.key_fingerprints(cfg, keys)
    return _fuse_lookup(cfg, state, fq, fr, mode=mode, tile_t=tile_t, wblk=wblk)


def fuse_contains(
    cfg: ffc.FuseConfig,
    state: ffc.FuseState,
    keys: jnp.ndarray,
    *,
    mode: str | None = None,
    interpret: bool | None = None,
    tile_t: int = 128,
    wblk: int = 2048,
):
    """Key-level fuse probe: hash + lookup under ONE jitted program (the
    ~30-op fingerprint hash costs milliseconds dispatched eagerly)."""
    return _fuse_contains_impl(
        cfg,
        state,
        keys,
        mode=dispatch.resolve(mode, interpret),
        tile_t=tile_t,
        wblk=wblk,
    )


# ---------------------------------------------------------------------------
# Cascade probe: every structure of the stack in one program
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnums=(0, 1),
    static_argnames=("mode", "tile_t", "wblk", "with_stats"),
)
def _cascade_lookup(
    qf_cfgs, fuse_cfgs, qf_states, fuse_states, keys, *, mode, tile_t, wblk, with_stats
):
    p = qf_cfgs[0].q + qf_cfgs[0].r
    seed = qf_cfgs[0].seed
    for c in qf_cfgs:
        if c.q + c.r != p or c.seed != seed:
            raise ValueError("cascade levels must share fingerprint bits and seed")
    for c in fuse_cfgs:
        if c.p != p or c.seed != seed:
            raise ValueError("frozen levels must share fingerprint bits and seed")

    # hash ONCE in the canonical split; every level's (fq, fr) view is a
    # bit re-split of the same p-bit fingerprint (requotient), so the
    # probe never re-hashes per level the way the reference does
    qc, rc = ffc.canonical_split(p)
    canon = qf.QFConfig(q=qc, r=rc, slack=0, seed=seed)
    fqc, frc = qf.fingerprints(canon, keys)

    qf_hits = []
    L = len(qf_cfgs)
    B0 = keys.shape[0]
    if not dispatch.is_pallas(mode):
        for c, s in zip(qf_cfgs, qf_states):
            fq, fr = qf._requotient(fqc, frc, canon, c)
            qf_hits.append((s.n > 0) & qf.lookup_exact(c, s, fq, fr))
        stats = _probe_stats(queries=B0, exact_passes=L)
        stats["tiles"] = jnp.zeros((L,), jnp.int32)
        stats["tiles_unfit"] = jnp.zeros((L,), jnp.int32)
        stats["queries_exact"] = jnp.full((L,), B0, jnp.int32)
    else:
        # one canonical-fingerprint sort serves every structure:
        # requotient is monotone, so the sorted batch is sorted by each
        # structure's quotient too
        iota = jnp.arange(B0, dtype=jnp.int32)
        fqc_s, frc_s, perm = jax.lax.sort(
            (fqc, frc, iota), num_keys=3, is_stable=False
        )
        planes, fq_lv, fr_lv = [], [], []
        for c, s in zip(qf_cfgs, qf_states):
            fq, fr = qf._requotient(fqc_s, frc_s, canon, c)
            fq_lv.append(fq)
            fr_lv.append(fr)
            planes.append(
                (
                    s.rem.astype(jnp.int32),
                    s.occ.astype(jnp.int32),
                    s.shf.astype(jnp.int32),
                    s.con.astype(jnp.int32),
                )
            )
        hitm_s, ovfm_s, tiles, tiles_unfit = cascade_probe_tiles(
            planes,
            fq_lv,
            fr_lv,
            tile_t=tile_t,
            wblk=wblk,
            interpret=dispatch.pallas_interpret(mode),
            with_stats=True,
        )
        hitm, ovfm = dispatch.unpermute(perm, B0, hitm_s, ovfm_s)

        queries_exact, exact_pass = [], []
        for lvl, (c, s) in enumerate(zip(qf_cfgs, qf_states)):
            hit_l = ((hitm >> lvl) & 1) > 0
            ovf_l = ((ovfm >> lvl) & 1) > 0

            @jax.named_scope("cascade.exact")
            def resolve(args, c=c, s=s):
                hit_l, ovf_l = args
                fq, fr = qf._requotient(fqc, frc, canon, c)
                exact = qf.lookup_exact(c, s, fq, fr)
                return jnp.where(ovf_l, exact, hit_l)

            exact_pass.append(jnp.any(ovf_l))
            queries_exact.append(jnp.sum(ovf_l, dtype=jnp.int32))
            hit_l = jax.lax.cond(
                exact_pass[-1], resolve, lambda a: a[0], (hit_l, ovf_l)
            )
            qf_hits.append((s.n > 0) & hit_l)
        stats = _probe_stats(
            queries=B0,
            exact_passes=jnp.sum(jnp.stack(exact_pass), dtype=jnp.int32),
        )
        stats["tiles"] = tiles
        stats["tiles_unfit"] = tiles_unfit
        stats["queries_exact"] = jnp.stack(queries_exact)

    # frozen levels: their probe positions hash the fingerprint (not
    # monotone in it), so they keep their own position-sorted 3-gather
    # pass instead of the window-tiled QF probe
    fuse_hits = [
        fuse_lookup(c, s, fqc, frc, mode=mode)
        for c, s in zip(fuse_cfgs, fuse_states)
    ]
    hits = tuple(qf_hits) + tuple(fuse_hits)
    return (hits, stats) if with_stats else hits


@jax.named_scope("cascade.probe")  # outside the jitted wrapper, as qf.probe
def cascade_lookup(
    qf_cfgs,
    qf_states,
    fuse_cfgs,
    fuse_states,
    keys: jnp.ndarray,
    *,
    mode: str | None = None,
    interpret: bool | None = None,
    tile_t: int = 128,
    wblk: int = 1024,
    with_stats: bool = False,
):
    """Probe a whole cascade stack in one program, hashing and sorting
    the batch once.

    ``qf_cfgs``/``qf_states`` are the unfrozen structures top-down (Q0
    first), ``fuse_cfgs``/``fuse_states`` the frozen levels; all must
    share the fingerprint width ``p`` and seed.  Returns one bool (B,)
    hit array per structure, QF structures first, in argument order —
    the caller ORs (contains) or schedules (probe I/O accounting) them.

    ``with_stats=True`` returns ``(hits, stats)``, int32 counters of the
    QF structures' probe computed in the same program: scalars
    ``queries`` and ``exact_passes`` (whole-structure decodes), and
    ``(len(qf_cfgs),)`` arrays ``tiles`` (that structure's live
    window-aligned query tiles), ``tiles_unfit`` (of those, tiles whose
    quotients outrun their window: a guard, 0 by construction) and
    ``queries_exact`` (queries that structure answered on its exact
    path).  In xla mode every structure answers every query exactly,
    with no tiles.
    """
    return _cascade_lookup(
        tuple(qf_cfgs),
        tuple(fuse_cfgs),
        tuple(qf_states),
        tuple(fuse_states),
        keys,
        mode=dispatch.resolve(mode, interpret),
        tile_t=tile_t,
        wblk=wblk,
        with_stats=with_stats,
    )


# ---------------------------------------------------------------------------
# Chunked / span append (incremental migration)
# ---------------------------------------------------------------------------


def _span_math(cfg, fq, fr, k, last_pos, last_fq):
    """Closed-form append positions for a carried sorted span.

    The probe recurrence ``pos[i] = max(pos[i-1] + 1, fq[i])``
    closed-forms to ``i + max(last_pos + 1, cummax(fq - i))`` over the
    whole span at once — chunk boundaries are irrelevant to the math,
    which is what lets a multi-chunk drain run as ONE pass.
    """
    t = cfg.total_slots
    kk = jnp.asarray(k, jnp.int32)
    idx = jnp.arange(fq.shape[0], dtype=jnp.int32)
    valid = idx < kk

    d = jnp.where(valid, fq - idx, -INT32_MAX)
    pos = idx + jnp.maximum(last_pos + 1, scan.cummax(d))
    overflow = jnp.any(valid & (pos >= t))
    spos = jnp.where(valid, pos, INT32_MAX)

    prev_fq = jnp.roll(fq, 1).at[0].set(last_fq)
    con_bits = valid & (fq == prev_fq)
    shf_bits = valid & (pos != fq)

    last = jnp.clip(kk - 1, 0, fq.shape[0] - 1)
    new_last_pos = jnp.where(kk > 0, pos[last], last_pos)
    new_last_fq = jnp.where(kk > 0, fq[last], last_fq)
    return kk, valid, spos, con_bits, shf_bits, overflow, new_last_pos, new_last_fq


@functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=("mode", "block_s")
)
def _build_span(cfg, state, fq, fr, k, last_pos, last_fq, *, mode, block_s):
    kk, valid, spos, con_bits, shf_bits, overflow, nlp, nlf = _span_math(
        cfg, fq, fr, k, last_pos, last_fq
    )
    occ = state.occ.at[jnp.where(valid, fq, INT32_MAX)].set(
        True, mode="drop", indices_are_sorted=True
    )

    if dispatch.is_pallas(mode):
        # kernel-resident append: positions strictly increase past every
        # slot the partial state has written, so the freshly emitted
        # planes and the existing ones touch DISJOINT slots (all-zero on
        # the other side) and OR-merge exactly
        meta_bits = con_bits.astype(jnp.int32) | (shf_bits.astype(jnp.int32) << 1)
        rem_k, meta_k = qf_build_planes(
            spos,
            fr,
            meta_bits,
            cfg.total_slots,
            block_s=block_s,
            interpret=dispatch.pallas_interpret(mode),
        )
        new = qf.QFState(
            rem=state.rem | rem_k.astype(jnp.uint32),
            occ=occ,
            shf=state.shf | ((meta_k >> 1) > 0),
            con=state.con | ((meta_k & 1) > 0),
            n=state.n + kk,
            overflow=state.overflow | overflow,
        )
    else:
        new = qf.QFState(
            rem=state.rem.at[spos].set(fr, mode="drop", indices_are_sorted=True),
            occ=occ,
            shf=state.shf.at[spos].set(shf_bits, mode="drop", indices_are_sorted=True),
            con=state.con.at[spos].set(con_bits, mode="drop", indices_are_sorted=True),
            n=state.n + kk,
            overflow=state.overflow | overflow,
        )
    return new, nlp, nlf


def build_span(
    cfg: qf.QFConfig,
    state: qf.QFState,
    fq: jnp.ndarray,
    fr: jnp.ndarray,
    k,
    last_pos,
    last_fq,
    *,
    mode: str | None = None,
    interpret: bool | None = None,
    block_s: int = 1024,
):
    """Append a bounded sorted span (first ``k`` rows valid) to a
    partially built QF in one pass — the multi-chunk form of
    ``build_chunk``, bit-identical to folding the span in chunk by
    chunk (the carried scan closed-forms over any span length).

    Same contract as ``build_chunk``: ``state`` holds exactly the
    entries appended so far in sorted order, ``(last_pos, last_fq)``
    carry across calls.  Under the pallas modes the plane
    materialization runs as the tiled build grid (one launch for the
    whole span); xla mode scatters directly.  Returns
    ``(state, last_pos, last_fq)``.
    """
    return _build_span(
        cfg,
        state,
        fq,
        fr,
        k,
        last_pos,
        last_fq,
        mode=dispatch.resolve(mode, interpret),
        block_s=block_s,
    )


@functools.partial(jax.jit, static_argnums=(0,))
def build_chunk(
    cfg: qf.QFConfig,
    state: qf.QFState,
    fq: jnp.ndarray,
    fr: jnp.ndarray,
    k,
    last_pos,
    last_fq,
):
    """Chunked build-plane entry: append one bounded sorted chunk to a
    partially built QF (the per-insert incremental-resize step).

    ``state`` must hold exactly the entries appended so far, built in
    sorted fingerprint order; ``(last_pos, last_fq)`` carry the probe
    scan across chunk boundaries (both -1 before the first chunk).  The
    first ``k`` rows of ``(fq, fr)`` are valid and sorted, and every
    fingerprint sorts at-or-after the carried ``last_fq``.  Appending
    chunk by chunk reproduces ``build_sorted`` of the full prefix
    bit-for-bit: the probe recurrence ``pos[i] = max(pos[i-1] + 1,
    fq[i])`` closed-forms to ``i + max(last_pos + 1, cummax(fq - i))``,
    so positions strictly increase and chunks never overwrite.

    O(chunk) work — a handful of scattered single-slot writes, the
    right shape for the per-insert path on every backend.  Multi-chunk
    drains (``finish``) route through :func:`build_span`, which runs
    the same math as one tiled kernel grid / one fused scatter instead
    of a host loop of these.

    Returns ``(state, last_pos, last_fq)`` with the carries advanced.
    """
    kk, valid, spos, con_bits, shf_bits, overflow, nlp, nlf = _span_math(
        cfg, fq, fr, k, last_pos, last_fq
    )
    new = qf.QFState(
        rem=state.rem.at[spos].set(fr, mode="drop"),
        occ=state.occ.at[jnp.where(valid, fq, INT32_MAX)].set(True, mode="drop"),
        shf=state.shf.at[spos].set(shf_bits, mode="drop"),
        con=state.con.at[spos].set(con_bits, mode="drop"),
        n=state.n + kk,
        overflow=state.overflow | overflow,
    )
    return new, nlp, nlf


# ---------------------------------------------------------------------------
# Blocked-Bloom bin ops
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("ncells", "mode", "block_s")
)
def _bloom_counts(idx_flat, *, ncells, mode, block_s):
    if not dispatch.is_pallas(mode):
        return (
            jnp.zeros((ncells,), jnp.int32)
            .at[idx_flat]
            .add(1, mode="drop")
        )
    sidx = jnp.sort(idx_flat)
    counts_k, fits = bloom_count_tiles(
        sidx, ncells, block_s=block_s, interpret=dispatch.pallas_interpret(mode)
    )
    n_tiles = fits.shape[0]
    t_pad = n_tiles * block_s

    def resolve(counts_k):
        # hot tiles (bins denser than the item window) recount by
        # scatter; insert is a commutative aggregation, so a per-tile
        # mix of kernel and scatter counts is exact
        ref = (
            jnp.zeros((t_pad,), jnp.int32)
            .at[sidx]
            .add(1, mode="drop")
            .reshape(n_tiles, block_s)
        )
        ck = counts_k.reshape(n_tiles, block_s)
        return jnp.where(fits[:, None], ck, ref).reshape(t_pad)

    counts = jax.lax.cond(
        jnp.all(fits), lambda c: c, resolve, counts_k
    )
    return counts[:ncells]


def bloom_counts(
    idx_flat: jnp.ndarray,
    ncells: int,
    *,
    mode: str | None = None,
    interpret: bool | None = None,
    block_s: int = 1024,
):
    """Aggregate a flat batch of cell indices into an int32 counts plane.

    The shared write-side primitive of the Bloom family: insert is
    ``cells + counts`` (counting) or ``cells | (counts > 0)`` (plain),
    delete is ``cells - counts`` — all commutative, so the kernel's
    per-tile aggregation composes exactly with the scatter fallback.
    Out-of-range indices (masked keys) drop.
    """
    return _bloom_counts(
        idx_flat,
        ncells=ncells,
        mode=dispatch.resolve(mode, interpret),
        block_s=block_s,
    )


@functools.partial(jax.jit, static_argnames=("mode", "tile_t", "wblk"))
def _bloom_probe(cells, idx, *, mode, tile_t, wblk):
    if not dispatch.is_pallas(mode):
        return jnp.all(cells[idx] > 0, axis=1)

    B0 = idx.shape[0]
    # blocked layout: all k probes of a key share one bin, so sorting by
    # the per-key min makes tile windows contiguous bin ranges
    osort = dispatch.sorted_tile_order(jnp.min(idx, axis=1), tile_t)
    hit_s, ovf_s = bloom_probe_tiles(
        cells.astype(jnp.int32),
        idx[osort],
        tile_t=tile_t,
        wblk=wblk,
        interpret=dispatch.pallas_interpret(mode),
    )
    hit, ovf = dispatch.unpermute(osort, B0, hit_s, ovf_s)

    def resolve(args):
        hit, ovf = args
        exact = jnp.all(cells[idx] > 0, axis=1)
        return jnp.where(ovf > 0, exact, hit > 0)

    return jax.lax.cond(
        jnp.any(ovf > 0), resolve, lambda a: a[0] > 0, (hit, ovf)
    )


def bloom_probe(
    cells: jnp.ndarray,
    idx: jnp.ndarray,
    *,
    mode: str | None = None,
    interpret: bool | None = None,
    tile_t: int = 128,
    wblk: int = 4096,
):
    """AND-of-k membership over a cell plane for (B, k) cell indices.

    Pallas modes tile bin-sorted queries over prefetched cell windows
    (the blocked-Bloom read path); xla mode gathers directly.  Queries
    whose bins outrun their tile window resolve on the exact gather.
    """
    return _bloom_probe(
        cells,
        idx,
        mode=dispatch.resolve(mode, interpret),
        tile_t=tile_t,
        wblk=wblk,
    )
