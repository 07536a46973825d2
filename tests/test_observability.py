"""Named scopes and lookup counters of the quotient filter.

``filters.contains(..., with_stats=True)`` answers as plain ``contains``
does and returns int32 counters that the same program computes; the
QF's bulk passes carry documented ``jax.named_scope`` names into the
compiled program's ``op_name`` metadata (README "Observability").
Kernel paths run in ``interpret`` mode at a small geometry.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import filters
from repro.core import quotient_filter as qf
from repro.kernels import ops

PALLAS_KEYS = {"queries", "tiles", "tiles_unfit", "queries_exact", "exact_passes"}
REFERENCE_KEYS = {"queries", "queries_retry", "queries_exact", "exact_passes"}
WINDOW, TILE = 1024, 128  # ops.lookup's defaults: wblk, tile_t


def _keys(n, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32))


def _qf(backend, q=12, n=1500, seed=0):
    cfg, st = filters.make("qf", q=q, r=20, backend=backend)
    keys = _keys(2 * n, seed)
    return cfg, filters.insert(cfg, st, keys[:n]), keys


def _as_ints(stats):
    for v in stats.values():
        assert v.dtype == jnp.int32 and v.shape == ()
    return {k: int(v) for k, v in stats.items()}


@pytest.mark.parametrize(
    "backend,mode,want",
    [
        ("reference", None, REFERENCE_KEYS),
        ("pallas", "interpret", PALLAS_KEYS),
        ("pallas", "xla", PALLAS_KEYS),
    ],
)
def test_with_stats_answers_match_contains(monkeypatch, backend, mode, want):
    if mode:
        monkeypatch.setenv("REPRO_KERNEL_MODE", mode)
    cfg, st, keys = _qf(backend)
    q = keys[1000:2024]  # half stored, half not
    hits, stats = filters.contains(cfg, st, q, with_stats=True)
    np.testing.assert_array_equal(
        np.asarray(hits), np.asarray(filters.contains(cfg, st, q))
    )
    assert bool(hits[:500].all())
    got = _as_ints(stats)
    assert set(got) == want
    assert got["queries"] == q.shape[0]
    assert 0 <= got["queries_exact"] <= got["queries"]
    assert got["exact_passes"] in (0, 1)
    if mode == "xla":  # no tiles: every query is answered by the exact decode
        assert got == dict(
            queries=1024, tiles=0, tiles_unfit=0, queries_exact=1024, exact_passes=1
        )


def _numpy_tile_spans(fq, total):
    """The schedule before window-aligned tiles: sorted quotients in
    consecutive ``TILE``-query tiles, each fitting when its span stays
    inside the two-block window less a quarter block of run tail.
    Returns ``fits`` per tile."""
    tiles = np.sort(np.asarray(fq)).reshape(-1, TILE)
    lo, hi = tiles[:, 0], tiles[:, -1]
    margin = WINDOW // 4
    n_blocks = -(-total // WINDOW) + 1
    base = np.clip((lo - margin) // WINDOW, 0, n_blocks - 2) * WINDOW
    return (hi - base) < 2 * WINDOW - margin


def _numpy_aligned_tiles(fq, total):
    """Window-aligned tiles of a batch padded, as the probe pads it, to
    whole ``TILE``s with copies of its largest quotient: each query takes
    the block its window would start at, and a block of ``c`` queries
    makes ``ceil(c / TILE)`` tiles."""
    fq = np.sort(np.asarray(fq))
    fq = np.concatenate([fq, np.full((-fq.size) % TILE, fq[-1])])
    margin = WINDOW // 4
    n_blocks = -(-total // WINDOW) + 1
    _, counts = np.unique(
        np.clip((fq - margin) // WINDOW, 0, n_blocks - 2), return_counts=True
    )
    return int((-(-counts // TILE)).sum())


def _probe_stats(cfg, st, fq, fr):
    """Answers of the interpreted kernel path, checked against the exact
    lookup, and its counters."""
    fq, fr = jnp.asarray(fq, jnp.int32), jnp.asarray(fr, jnp.uint32)
    hits, stats = ops.lookup(cfg, st, fq, fr, mode="interpret", with_stats=True)
    np.testing.assert_array_equal(
        np.asarray(hits), np.asarray(qf.lookup_exact(cfg, st, fq, fr))
    )
    return _as_ints(stats)


def test_stats_count_tiles_that_outrun_the_window():
    # load 1/16: no cluster overflows a window that holds its tile
    cfg = qf.QFConfig(q=14, r=16)
    st = qf.insert(cfg, qf.empty(cfg), _keys(1024, 1))
    rng = np.random.default_rng(2)
    dense = rng.integers(3000, 3400, 512)  # four tiles inside one window
    spread = rng.integers(0, cfg.m, 512)  # tiles spanning ~4 windows each
    fq = np.concatenate([dense, spread])
    fr = rng.integers(0, 2**16, 1024)
    # consecutive tiles of this batch would outrun their windows; tiles
    # aligned to the windows all fit
    fits = _numpy_tile_spans(fq, cfg.total_slots)
    assert 0 < (~fits).sum() < fits.size
    assert _probe_stats(cfg, st, fq, fr) == dict(
        queries=1024,
        tiles=_numpy_aligned_tiles(fq, cfg.total_slots),
        tiles_unfit=0,
        queries_exact=0,
        exact_passes=0,
    )


def _quotients(case, m, rng):
    if case == "sparse":  # every consecutive tile spans several windows
        return rng.integers(0, m, 1024)
    if case == "crowded_block":  # one block holds 300 queries: three tiles
        return np.concatenate([rng.integers(3328, 4352, 300), rng.integers(0, m, 200)])
    if case == "short_batch":  # fewer queries than a tile
        return rng.integers(0, m, 100)
    if case == "edge_blocks":  # the first block's clipped start, the last block
        return np.concatenate([rng.integers(0, 300, 200), rng.integers(m - 300, m, 200)])
    raise ValueError(case)


def _table(cfg, fq, fr):
    """A QF holding ``fq``/``fr`` fingerprints."""
    return qf.insert_batch(
        cfg,
        qf.empty(cfg),
        jnp.asarray(fq, jnp.int32),
        jnp.asarray(fr, jnp.uint32),
        jnp.ones((len(fq),), jnp.bool_),
    )


@pytest.mark.parametrize(
    "case", ["sparse", "crowded_block", "short_batch", "edge_blocks"]
)
def test_window_aligned_tiles_all_fit(case):
    cfg = qf.QFConfig(q=14, r=16)
    rng = np.random.default_rng(5)
    fq = _quotients(case, cfg.m, rng)
    fr = rng.integers(0, 2**16, fq.size)
    if case == "sparse":
        assert not _numpy_tile_spans(fq, cfg.total_slots).any()
    # load ~1/4, holding every other query
    bq, br = rng.integers(0, cfg.m, 4096), rng.integers(0, 2**16, 4096)
    st = _table(cfg, np.concatenate([bq, fq[::2]]), np.concatenate([br, fr[::2]]))
    assert _probe_stats(cfg, st, fq, fr) == dict(
        queries=fq.size,
        tiles=_numpy_aligned_tiles(fq, cfg.total_slots),
        tiles_unfit=0,
        queries_exact=0,
        exact_passes=0,
    )


def test_cluster_longer_than_the_margin_takes_the_exact_path():
    # one run of 1,200 fingerprints from quotient 5000 and a second,
    # shifted, from 5400: their cluster outruns the windows of the
    # queries on it, whose tiles still fit
    cfg = qf.QFConfig(q=14, r=16)
    fq = np.concatenate([np.full(1200, 5000), np.full(50, 5400)])
    fr = np.concatenate([np.arange(1200), np.arange(50)])
    st = _table(cfg, fq, fr)
    rng = np.random.default_rng(6)
    q = np.concatenate([fq[::25], rng.integers(0, cfg.m, 300)])
    absent = (np.arange(50) % 2) * 3000  # every other query on the run misses
    r = np.concatenate([fr[::25] + absent, rng.integers(0, 2**16, 300)])
    got = _probe_stats(cfg, st, q, r)
    assert got["tiles"] == _numpy_aligned_tiles(q, cfg.total_slots)
    assert got["tiles_unfit"] == 0
    assert got["queries_exact"] > 0 and got["exact_passes"] == 1


def test_reference_stats_count_retries_and_exact_answers():
    # a 4-slot window at load 0.7: many clusters outrun it, and some
    # outrun the 16-slot retry too
    cfg, st = filters.make("qf", q=12, r=20, window=4)
    keys = _keys(6000, 3)
    st = filters.insert(cfg, st, keys[:3000])
    q = keys[2000:3024]
    hits, stats = filters.contains(cfg, st, q, with_stats=True)
    np.testing.assert_array_equal(
        np.asarray(hits), np.asarray(filters.contains(cfg, st, q))
    )
    assert bool(hits[:1000].all())
    fq, fr = qf.fingerprints(cfg.core, q)
    _, ovf = qf._window_decode(cfg.core, st, fq, fr, 4)
    _, o2 = qf._window_decode(cfg.core, st, fq, fr, 16)
    got = _as_ints(stats)
    assert got == dict(
        queries=1024,
        queries_retry=int(ovf.sum()),
        queries_exact=int((ovf & o2).sum()),
        exact_passes=1,
    )
    assert got["queries_retry"] > got["queries_exact"] > 0


def test_stats_of_one_repeated_key(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    cfg, st, keys = _qf("pallas")
    q = jnp.full((1024,), keys[7], jnp.uint32)
    hits, stats = filters.contains(cfg, st, q, with_stats=True)
    assert bool(hits.all())
    assert _as_ints(stats) == dict(
        queries=1024, tiles=8, tiles_unfit=0, queries_exact=0, exact_passes=0
    )


def test_with_stats_is_qf_only():
    """The counters are the QF probe's: the flat ``qf`` and the
    ``cascade`` of QFs have them, other families refuse."""
    cfg, st = filters.make("bloom", m_bits=1 << 12, k=4)
    with pytest.raises(filters.UnsupportedOpError):
        filters.contains(cfg, st, _keys(8, 0), with_stats=True)
    assert sorted(
        n for n in filters.names() if filters.supports(n, "contains_stats")
    ) == ["cascade", "qf"]


def _op_names(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    # no host round trip on the hot path: no callback, outfeed or send
    assert not re.search(r'custom_call_target="[^"]*callback|outfeed\(|send\(', text)
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize(
    "op,scope",
    [
        ("contains", "qf.probe"),
        ("contains", "qf.exact"),
        ("contains", "qf.decode"),
        ("insert", "qf.decode"),
        ("insert", "qf.sort"),
        ("insert", "qf.build"),
    ],
)
def test_scopes_reach_the_compiled_program(op, scope):
    cfg, st, keys = _qf("pallas", q=10, n=300)
    fn = lambda s, k: getattr(filters, op)(cfg, s, k)  # noqa: E731
    names = _op_names(fn, st, keys[:256])
    assert any(scope in n.split("/") for n in names)


def test_with_stats_adds_no_callback():
    cfg, st, keys = _qf("reference", q=10, n=300)
    _op_names(lambda s, k: filters.contains(cfg, s, k, with_stats=True), st, keys)
