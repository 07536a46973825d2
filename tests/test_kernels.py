"""Pallas kernel validation: interpret-mode vs pure-jnp oracles.

Sweeps (q, r, n, tile sizes); asserts exact equality (integer data
structures — no tolerance needed) against ref.py and repro.core.
Kernel-exercising tests pin ``mode="interpret"`` explicitly: on CPU the
auto-resolved mode is the XLA lowering, which would silently skip the
kernel bodies.  The xla lowerings get their own parity sweep below.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import fuse_filter as fuse
from repro.core import quotient_filter as qf
from repro.kernels import dispatch, ops, ref
from repro.kernels.qf_build import qf_build_planes
from repro.kernels.qf_probe import qf_probe_tiles


def _mkfilter(q, r, n, seed=0, max_load=1.0, slack=1024):
    cfg = qf.QFConfig(q=q, r=r, slack=slack, max_load=max_load)
    rng = np.random.default_rng(seed)
    keys = jnp.asarray(rng.integers(0, 2**32, size=n, dtype=np.int64).astype(np.uint32))
    st = qf.insert(cfg, qf.empty(cfg), keys)
    return cfg, st, keys, rng


@pytest.mark.parametrize(
    "q,r,n", [(8, 8, 100), (10, 12, 700), (12, 6, 3000), (14, 16, 12000)]
)
@pytest.mark.parametrize("block_s", [128, 256])
def test_build_kernel_matches_core(q, r, n, block_s):
    cfg, st_ref, keys, _ = _mkfilter(q, r, n)
    fq, fr = qf.fingerprints(cfg, keys)
    fq, fr = qf._pad_sort(fq, fr, jnp.ones(fq.shape, bool))
    st_ker = ops.build_sorted(cfg, fq, fr, n, mode="interpret", block_s=block_s)
    for name, a, b in zip(st_ref._fields, st_ref, st_ker):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


def test_build_kernel_matches_ref_oracle():
    """Kernel vs the independent ref.py scatter oracle."""
    cfg, st, keys, _ = _mkfilter(10, 10, 600)
    fq, fr = qf.fingerprints(cfg, keys)
    fq, fr = qf._pad_sort(fq, fr, jnp.ones(fq.shape, bool))
    idx = jnp.arange(fq.shape[0], dtype=jnp.int32)
    pos = idx + jax.lax.cummax(fq - idx)
    con_b = (idx > 0) & (fq == jnp.roll(fq, 1)) & (fq < 2**30)
    shf_b = (pos != fq) & (fq < 2**30)
    spos = jnp.where(fq < 2**30, pos, jnp.int32(2**31 - 1))
    rem_ref, meta_ref, _ = ref.build_ref(
        cfg.total_slots, spos, fq, fr.astype(jnp.int32), con_b, shf_b
    )
    meta_bits = con_b.astype(jnp.int32) | (shf_b.astype(jnp.int32) << 1)
    rem_ker, meta_ker = qf_build_planes(spos, fr, meta_bits, cfg.total_slots)
    np.testing.assert_array_equal(np.asarray(rem_ref), np.asarray(rem_ker))
    np.testing.assert_array_equal(np.asarray(meta_ref), np.asarray(meta_ker))


@pytest.mark.parametrize(
    "q,r,n,load", [(8, 8, 180, 0.7), (10, 10, 900, 0.9), (12, 12, 2000, 0.5)]
)
@pytest.mark.parametrize("tile_t,wblk", [(128, 1024), (256, 512)])
def test_probe_kernel_matches_exact(q, r, n, load, tile_t, wblk):
    cfg, st, keys, rng = _mkfilter(q, r, n, max_load=load)
    probes = jnp.concatenate(
        [
            keys,
            jnp.asarray(
                rng.integers(0, 2**32, size=2 * n, dtype=np.int64).astype(np.uint32)
            ),
        ]
    )
    fq, fr = qf.fingerprints(cfg, probes)
    exact = qf.lookup_exact(cfg, st, fq, fr)
    got = ops.lookup(cfg, st, fq, fr, mode="interpret", tile_t=tile_t, wblk=wblk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exact))


def test_probe_kernel_matches_ref_oracle():
    """Kernel windowed decode vs the independent ref.py window oracle,
    on queries whose tiles fit (non-overflow path)."""
    cfg, st, keys, rng = _mkfilter(10, 8, 500, max_load=0.6)
    fq, fr = qf.fingerprints(cfg, keys)
    order = jnp.argsort(fq)
    fq_s, fr_s = fq[order], fr[order]
    pad = (-fq_s.shape[0]) % 128
    fq_s = jnp.concatenate([fq_s, jnp.repeat(fq_s[-1:], pad)])
    fr_s = jnp.concatenate([fr_s, jnp.repeat(fr_s[-1:], pad)])
    present, ovf, _, _ = qf_probe_tiles(
        st.rem.astype(jnp.int32),
        st.occ.astype(jnp.int32),
        st.shf.astype(jnp.int32),
        st.con.astype(jnp.int32),
        fq_s,
        fr_s,
        tile_t=128,
        wblk=1024,
    )
    ref_present, ref_ovf = ref.probe_ref(
        st.rem.astype(jnp.int32),
        st.occ.astype(jnp.int32),
        st.shf.astype(jnp.int32),
        st.con.astype(jnp.int32),
        fq_s,
        fr_s.astype(jnp.int32),
        window=256,
    )
    ok = ~(np.asarray(ovf) > 0) & ~np.asarray(ref_ovf)
    np.testing.assert_array_equal(
        np.asarray(present)[ok] > 0, np.asarray(ref_present)[ok]
    )
    assert ok.mean() > 0.95  # overflow must be rare at this load


@pytest.mark.parametrize("dtype", [jnp.uint32, jnp.int32, jnp.uint16])
def test_key_dtypes(dtype):
    cfg = qf.QFConfig(q=10, r=10, slack=512)
    keys = jnp.arange(500, dtype=dtype)
    st = qf.insert(cfg, qf.empty(cfg), keys)
    assert bool(ops.contains(cfg, st, keys, mode="interpret").all())


def test_high_load_overflow_fallback():
    """At 95% load, clusters exceed any window — the exact fallback
    inside the kernel wrapper must keep answers correct."""
    cfg, st, keys, rng = _mkfilter(9, 12, 486, max_load=0.95)
    probes = jnp.concatenate(
        [
            keys,
            jnp.asarray(
                rng.integers(0, 2**32, size=1000, dtype=np.int64).astype(np.uint32)
            ),
        ]
    )
    fq, fr = qf.fingerprints(cfg, probes)
    exact = qf.lookup_exact(cfg, st, fq, fr)
    got = ops.lookup(cfg, st, fq, fr, mode="interpret", tile_t=128, wblk=256)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exact))


# ---------------------------------------------------------------------------
# Mode dispatch (PR 7): auto-selection, env pin, legacy interpret flag
# ---------------------------------------------------------------------------


class TestDispatch:
    def test_default_mode_is_platform_dependent(self, monkeypatch):
        monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
        want = "mosaic" if jax.default_backend() == "tpu" else "xla"
        assert dispatch.default_mode() == want
        assert dispatch.resolve() == want

    def test_env_var_pins_mode(self, monkeypatch):
        monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
        assert dispatch.default_mode() == "interpret"
        assert dispatch.resolve() == "interpret"
        # per-call override still wins over the env pin
        assert dispatch.resolve(mode="xla") == "xla"
        monkeypatch.setenv("REPRO_KERNEL_MODE", "bogus")
        with pytest.raises(ValueError):
            dispatch.default_mode()

    def test_legacy_interpret_flag_maps_to_modes(self):
        assert dispatch.resolve(interpret=True) == "interpret"
        assert dispatch.resolve(interpret=False) == "mosaic"
        with pytest.raises(ValueError):
            dispatch.resolve(mode="fast")

    def test_env_pin_reaches_ops_without_stale_cache(self, monkeypatch):
        """Mode resolution happens outside jit, so flipping the env var
        between calls must actually change the executed lowering."""
        cfg, st, keys, _ = _mkfilter(8, 8, 100)
        fq, fr = qf.fingerprints(cfg, keys)
        monkeypatch.setenv("REPRO_KERNEL_MODE", "xla")
        a = ops.lookup(cfg, st, fq, fr)
        monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
        b = ops.lookup(cfg, st, fq, fr)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# XLA lowering parity: the deployed CPU/GPU path must be bit-identical
# to both the reference ops and the interpreted kernels
# ---------------------------------------------------------------------------


class TestXlaLowering:
    def test_build_matches_reference(self):
        cfg, st_ref, keys, _ = _mkfilter(10, 12, 700)
        fq, fr = qf.fingerprints(cfg, keys)
        fq, fr = qf._pad_sort(fq, fr, jnp.ones(fq.shape, bool))
        st_xla = ops.build_sorted(cfg, fq, fr, 700, mode="xla")
        for name, a, b in zip(st_ref._fields, st_ref, st_xla):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)

    def test_lookup_matches_exact(self):
        cfg, st, keys, rng = _mkfilter(10, 10, 900, max_load=0.9)
        extra = rng.integers(0, 2**32, 2000, np.int64).astype(np.uint32)
        probes = jnp.concatenate([keys, jnp.asarray(extra)])
        fq, fr = qf.fingerprints(cfg, probes)
        np.testing.assert_array_equal(
            np.asarray(ops.lookup(cfg, st, fq, fr, mode="xla")),
            np.asarray(qf.lookup_exact(cfg, st, fq, fr)),
        )

    def test_fuse_lookup_matches_reference(self):
        rng = np.random.default_rng(3)
        keys = jnp.asarray(rng.integers(0, 2**32, 4000, np.int64).astype(np.uint32))
        fc = fuse.make_config(6000, 26, fp_bits=16)
        qc, rc = fuse.canonical_split(26)
        canon = qf.QFConfig(q=qc, r=rc, slack=0)
        fq, fr = qf.fingerprints(canon, keys)
        fq, fr = qf._pad_sort(fq, fr, jnp.ones(fq.shape, bool))
        st = fuse.freeze(fc, fq, fr, keys.shape[0])
        probes = jnp.asarray(rng.integers(0, 2**32, 3000, np.int64).astype(np.uint32))
        pq, pr = qf.fingerprints(canon, probes)
        want = fuse.contains(fc, st, probes)
        for mode in ("xla", "interpret"):
            got = ops.fuse_lookup(fc, st, pq, pr, mode=mode)
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want), err_msg=mode
            )


# ---------------------------------------------------------------------------
# Fused multi-level cascade probe (PR 7 tentpole)
# ---------------------------------------------------------------------------


def _grown_cascade(frozen_below, seed=7, n=3000, backend="pallas"):
    """A cascade ingested far enough that several levels are non-empty."""
    from repro import filters

    cfg, st = filters.make(
        "cascade",
        ram_q=8,
        p=26,
        fanout=2,
        levels=3,
        backend=backend,
        frozen_below=frozen_below,
    )
    rng = np.random.default_rng(seed)
    keys = jnp.asarray(rng.integers(0, 2**32, n, np.int64).astype(np.uint32))
    for i in range(0, n, 128):
        st = filters.insert(cfg, st, keys[i : i + 128])
    probes = jnp.asarray(rng.integers(0, 2**32, 2048, np.int64).astype(np.uint32))
    return cfg, st, keys, probes


def _per_level_reference(cfg, st, keys):
    """Per-structure hits via the unfused reference path (same guards)."""
    from repro.filters import cascade as cas

    ref_cfg = cfg._replace(backend="reference")
    q0 = jax.lax.cond(
        st.q0.n > 0,
        lambda: qf.contains(cfg.q0_cfg, st.q0, keys, 256),
        lambda: jnp.zeros(keys.shape[0], jnp.bool_),
    )
    return q0, [
        cas._level_contains(ref_cfg, st, i, keys) for i in range(cfg.levels)
    ]


class TestFusedCascadeProbe:
    @pytest.mark.parametrize("frozen_below", [None, 1, 0])
    def test_fused_hits_match_per_level_reference(self, frozen_below):
        cfg, st, keys, probes = _grown_cascade(frozen_below)
        from repro.filters import cascade as cas

        for batch in (probes, keys[:1024]):
            want_q0, want_lvls = _per_level_reference(cfg, st, batch)
            got_q0, got_lvls = cas._fused_level_hits(cfg, st, batch)
            np.testing.assert_array_equal(np.asarray(got_q0), np.asarray(want_q0))
            for i, (g, w) in enumerate(zip(got_lvls, want_lvls)):
                np.testing.assert_array_equal(
                    np.asarray(g), np.asarray(w), err_msg=f"level {i}"
                )

    @pytest.mark.parametrize("frozen_below", [None, 1])
    def test_contains_and_probe_match_reference_backend(self, frozen_below):
        from repro import filters

        cfg, st, keys, probes = _grown_cascade(frozen_below)
        ref_cfg = cfg._replace(backend="reference")
        for batch in (probes, keys):
            np.testing.assert_array_equal(
                np.asarray(filters.contains(cfg, st, batch)),
                np.asarray(filters.contains(ref_cfg, st, batch)),
            )
        st_p, hit_p = filters.probe(cfg, st, probes)
        st_r, hit_r = filters.probe(ref_cfg, st, probes)
        np.testing.assert_array_equal(np.asarray(hit_p), np.asarray(hit_r))
        # the modeled top-down read schedule must not drift either
        assert int(st_p.io.rand_page_reads) == int(st_r.io.rand_page_reads)

    def test_interpret_kernel_matches_xla_lowering(self):
        """The fused Pallas grid (interpret) vs the xla lowering — the
        two deployed lowerings must agree structure-by-structure."""
        cfg, st, keys, probes = _grown_cascade(1, n=2000)
        qf_ix = [i for i in range(cfg.levels) if not cfg.is_frozen(i)]
        fz_ix = [i for i in range(cfg.levels) if cfg.is_frozen(i)]
        args = (
            (cfg.q0_cfg,) + tuple(cfg.level_cfg(i) for i in qf_ix),
            (st.q0,) + tuple(st.levels[i] for i in qf_ix),
            tuple(cfg.fuse_cfg(i) for i in fz_ix),
            tuple(st.levels[i] for i in fz_ix),
        )
        for batch in (probes, keys[:512]):
            a = ops.cascade_lookup(*args, batch, mode="interpret")
            b = ops.cascade_lookup(*args, batch, mode="xla")
            for i, (x, y) in enumerate(zip(a, b)):
                np.testing.assert_array_equal(
                    np.asarray(x), np.asarray(y), err_msg=f"structure {i}"
                )

    def test_window_overflow_fallback_tiles(self):
        """A tiny window forces whole tiles onto the exact-resolve
        fallback; answers must stay bit-exact."""
        cfg, st, keys, probes = _grown_cascade(None, n=2500)
        qf_cfgs = (cfg.q0_cfg,) + tuple(cfg.level_cfg(i) for i in range(cfg.levels))
        qf_states = (st.q0,) + tuple(st.levels)
        for batch in (probes, keys[:1024]):
            want = ops.cascade_lookup(qf_cfgs, qf_states, (), (), batch, mode="xla")
            got = ops.cascade_lookup(
                qf_cfgs, qf_states, (), (), batch, mode="interpret", wblk=128
            )
            for i, (x, y) in enumerate(zip(got, want)):
                np.testing.assert_array_equal(
                    np.asarray(x), np.asarray(y), err_msg=f"structure {i}"
                )

    def test_rejects_mismatched_seeds(self):
        cfg, st, keys, probes = _grown_cascade(None, n=500)
        qf_cfgs = (cfg.q0_cfg, cfg.level_cfg(0)._replace(seed=99))
        with pytest.raises(ValueError):
            ops.cascade_lookup(qf_cfgs, (st.q0, st.levels[0]), (), (), probes)


# ---------------------------------------------------------------------------
# Blocked-Bloom bin kernels (PR 7 tentpole)
# ---------------------------------------------------------------------------


class TestBloomBinKernels:
    def _idx(self, seed, n, ncells, k=4, nblocks=32):
        """(n, k) indices with blocked locality over ``nblocks`` bins."""
        rng = np.random.default_rng(seed)
        blk = rng.integers(0, nblocks, n)
        span = ncells // nblocks
        inner = rng.integers(0, span, (n, k))
        return jnp.asarray((blk[:, None] * span + inner).astype(np.int32))

    @pytest.mark.parametrize("block_s", [256, 512])
    def test_counts_match_scatter(self, block_s):
        ncells = 1 << 13
        idx = self._idx(0, 3000, ncells).reshape(-1)
        want = ops.bloom_counts(idx, ncells, mode="xla")
        got = ops.bloom_counts(idx, ncells, mode="interpret", block_s=block_s)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_counts_dense_bins_fall_back_exactly(self):
        """Hammer two bins so their tiles outrun the item window — the
        per-tile scatter recount must splice in bit-exactly."""
        ncells = 1 << 12
        rng = np.random.default_rng(1)
        hot = rng.integers(0, 256, 6000).astype(np.int32)  # ~23 items/cell
        cold = self._idx(2, 1000, ncells).reshape(-1)
        idx = jnp.concatenate([jnp.asarray(hot), cold])
        want = ops.bloom_counts(idx, ncells, mode="xla")
        got = ops.bloom_counts(idx, ncells, mode="interpret", block_s=128)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_counts_drop_masked_sentinels(self):
        ncells = 1 << 10
        idx = jnp.concatenate(
            [
                self._idx(3, 500, ncells, nblocks=8).reshape(-1),
                jnp.full((64,), jnp.int32(2**31 - 1)),  # masked keys
            ]
        )
        got = ops.bloom_counts(idx, ncells, mode="interpret")
        want = ops.bloom_counts(idx, ncells, mode="xla")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert int(jnp.sum(got)) == 500 * 4  # sentinels landed nowhere

    @pytest.mark.parametrize("k", [2, 4, 7])
    def test_probe_matches_gather(self, k):
        ncells = 1 << 13
        ins = self._idx(4, 2000, ncells, k=k)
        cells = (
            ops.bloom_counts(ins.reshape(-1), ncells, mode="xla") > 0
        ).astype(jnp.uint8)
        queries = jnp.concatenate([ins[:700], self._idx(5, 1300, ncells, k=k)])
        want = ops.bloom_probe(cells, queries, mode="xla")
        got = ops.bloom_probe(cells, queries, mode="interpret")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_probe_overflow_window_fallback(self):
        """wblk smaller than a bin span: every tile overflows, the exact
        fallback must carry the whole batch."""
        ncells = 1 << 12
        ins = self._idx(6, 1500, ncells, nblocks=4)  # 1024-cell bins
        cells = (
            ops.bloom_counts(ins.reshape(-1), ncells, mode="xla") > 0
        ).astype(jnp.uint8)
        queries = self._idx(7, 1000, ncells, nblocks=4)
        want = ops.bloom_probe(cells, queries, mode="xla")
        got = ops.bloom_probe(cells, queries, mode="interpret", wblk=256)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("counting", [False, True])
    def test_blocked_bloom_filter_end_to_end(self, counting, monkeypatch):
        from repro import filters

        # pin the interpreter: with the platform default (xla on CPU)
        # insert/delete route to the reference scatter directly, which
        # would make this parity check compare identical code
        monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
        rng = np.random.default_rng(8)
        keys = jnp.asarray(rng.integers(0, 2**32, 4000, np.int64).astype(np.uint32))
        probes = jnp.asarray(rng.integers(0, 2**32, 2000, np.int64).astype(np.uint32))
        spec = dict(m_bits=1 << 16, k=4, block_bits=512, counting=counting)
        c_r, s_r = filters.make("blocked_bloom", **spec)
        c_p, s_p = filters.make("blocked_bloom", **spec, backend="pallas")
        s_r = filters.insert(c_r, s_r, keys)
        s_p = filters.insert(c_p, s_p, keys)
        np.testing.assert_array_equal(np.asarray(s_r.cells), np.asarray(s_p.cells))
        for batch in (probes, keys[:1000]):
            np.testing.assert_array_equal(
                np.asarray(filters.contains(c_r, s_r, batch)),
                np.asarray(filters.contains(c_p, s_p, batch)),
            )
        if counting:
            s_r = filters.delete(c_r, s_r, keys[:500])
            s_p = filters.delete(c_p, s_p, keys[:500])
            np.testing.assert_array_equal(
                np.asarray(s_r.cells), np.asarray(s_p.cells)
            )


# ---------------------------------------------------------------------------
# Kernel-vs-oracle parity (the spec checker's declared bindings)
# ---------------------------------------------------------------------------


class TestOracleParity:
    """Direct wrapper-vs-ref.py parity, one test per spec-check binding.

    ``repro.analysis.spec_check`` asserts every kernel wrapper has a
    bound pure-jnp oracle and a parity test; these are those tests for
    the kernels whose existing coverage went through ``ops`` only.
    """

    def test_bloom_probe_tiles_matches_bloom_probe_ref(self):
        from repro.kernels.bloom_block import bloom_probe_tiles

        ncells, k = 1 << 12, 4
        rng = np.random.default_rng(11)
        cells = jnp.asarray(rng.integers(0, 2, ncells).astype(np.int32))
        blk = rng.integers(0, 16, 512)
        span = ncells // 16
        idx = np.sort(
            (blk[:, None] * span + rng.integers(0, span, (512, k))).astype(np.int32),
            axis=1,
        )
        idx = jnp.asarray(idx[np.argsort(idx.min(axis=1), kind="stable")])
        hit, ovf = bloom_probe_tiles(cells, idx, tile_t=128, wblk=1024)
        want = ref.bloom_probe_ref(cells, idx)
        ok = np.asarray(ovf) == 0
        assert ok.any()
        np.testing.assert_array_equal(
            np.asarray(hit, bool)[ok], np.asarray(want)[ok]
        )

    def test_bloom_count_tiles_matches_bloom_count_ref(self):
        from repro.kernels.bloom_block import bloom_count_tiles

        ncells = 1 << 10
        rng = np.random.default_rng(12)
        idx = jnp.asarray(np.sort(rng.integers(0, ncells, 800)).astype(np.int32))
        counts, fits = bloom_count_tiles(idx, ncells, block_s=256)
        want = ref.bloom_count_ref(idx, ncells)
        got = np.asarray(counts)[:ncells]
        mask = np.repeat(np.asarray(fits), 256)[:ncells]
        assert mask.any()
        np.testing.assert_array_equal(got[mask], np.asarray(want)[mask])

    def test_cascade_probe_tiles_matches_cascade_probe_ref(self):
        from repro.kernels.cascade_probe import cascade_probe_tiles

        # coherent single-slot runs: items at pos == fq, no shifting
        def mkplanes(total, occupied_fq, fr_of):
            pos = jnp.asarray(occupied_fq, jnp.int32)
            fr = fr_of(pos)
            rem, meta, occ = ref.build_ref(
                total, pos, pos, fr,
                jnp.zeros_like(pos), jnp.zeros_like(pos),
            )
            con = meta & 1
            shf = meta >> 1
            return rem, occ, shf, con

        planes = [
            mkplanes(256, np.arange(0, 256, 2), lambda p: p + 1),
            mkplanes(512, np.arange(0, 512, 3), lambda p: p * 2 + 1),
        ]
        B = 128
        fq0 = jnp.arange(B, dtype=jnp.int32)
        fq_levels = [fq0, fq0 * 2]
        fr_levels = [fq0 + 1, (fq0 * 2) * 2 + 1]  # all stored fr match
        hit, ovf = cascade_probe_tiles(
            planes, fq_levels, fr_levels, tile_t=32, wblk=256
        )
        rhit, rovf = ref.cascade_probe_ref(planes, fq_levels, fr_levels, window=8)
        ok = (np.asarray(ovf) == 0) & (np.asarray(rovf) == 0)
        assert ok.any()
        np.testing.assert_array_equal(np.asarray(hit)[ok], np.asarray(rhit)[ok])

    def test_fuse_probe_tiles_matches_fuse_probe_ref(self):
        from repro.kernels.fuse_probe import fuse_probe_tiles

        total = 1 << 11
        rng = np.random.default_rng(13)
        table = jnp.asarray(rng.integers(0, 2**32, total, np.int64).astype(np.uint32))
        p0 = np.sort(rng.integers(0, total - 3, 256)).astype(np.int32)
        p1, p2 = p0 + 1, p0 + 2
        fp_hit = np.asarray(table)[p0] ^ np.asarray(table)[p1] ^ np.asarray(table)[p2]
        fp = fp_hit.copy()
        fp[::2] ^= np.uint32(0xDEAD)  # force misses on even rows
        args = tuple(map(jnp.asarray, (p0, p1, p2, fp)))
        hit, ovf = fuse_probe_tiles(
            table.view(jnp.int32), *args, tile_t=64, wblk=512
        )
        want = ref.fuse_probe_ref(table, *args)
        ok = np.asarray(ovf) == 0
        assert ok.any()
        np.testing.assert_array_equal(np.asarray(hit, bool)[ok], np.asarray(want)[ok])


def test_grids_split_into_launches_match_one_launch(monkeypatch):
    """A grid whose scalar prefetch would outgrow SMEM is split into
    several launches; the split outputs must equal the single launch."""
    cfg, st, keys, _ = _mkfilter(12, 10, 2000)
    fq, fr = qf.fingerprints(cfg, keys)
    fq_s, fr_s = qf._pad_sort(fq, fr, jnp.ones(fq.shape, bool))
    idx = jnp.arange(fq_s.shape[0], dtype=jnp.int32)
    spos = idx + jax.lax.cummax(fq_s - idx)
    planes = tuple(x.astype(jnp.int32) for x in (st.rem, st.occ, st.shf, st.con))
    order = jnp.argsort(fq)
    q, r = fq[order][:1920], fr[order][:1920].astype(jnp.int32)

    def run():
        probe = qf_probe_tiles(*planes, q, r, tile_t=128, wblk=1024)
        build = qf_build_planes(spos, fr_s, idx & 3, cfg.total_slots, block_s=128)
        return [np.asarray(x) for x in probe + build]

    whole = run()
    monkeypatch.setattr(dispatch, "MAX_PREFETCH_WORDS", 8)
    assert len(dispatch.launches(15, 2)) > 1
    for got, want in zip(run(), whole):
        np.testing.assert_array_equal(got, want)
