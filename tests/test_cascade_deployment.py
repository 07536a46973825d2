"""The paper's cascade deployment at 2^-12 of its size, against plain
references.

The benchmark's ``paper_cascade_1to24`` configuration runs
``cascade(ram_q=22, p=40, fanout=2, levels=5, backend="pallas")``: 2^25
keys loaded in batches of 2^20, then 40 batches of 2^20 that collapse
into every level and end at 24 x Q0's capacity.  Here the same shape
runs at ``ram_q=10`` (2^13 keys loaded in batches of 2^8, then 40
batches of 2^8) with the Pallas kernels in the interpreter, and is
checked against exact numpy membership, the plain collapse replay of
``bench/configs/paper_cascade_1to24.py`` and the ``reference``
backend.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import filters
from repro.core import quotient_filter as qf

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAKE = {"ram_q": 10, "p": 40, "fanout": 2, "levels": 5}
PRELOAD, BATCH, N_BATCHES = 2**13, 2**8, 40
QUERIES = 4096  # a lookup batch: 1024 loaded, 1024 inserted, 2048 absent
WINDOW, TILE = 1024, 128  # the probe's window block and query tile
SCOPES = (
    "cascade.q0",
    "cascade.collapse",
    "cascade.probe",
    "cascade.exact",
    "cascade.kernel",
)


def _replay_module():
    path = os.path.join(ROOT, "bench", "configs", "paper_cascade_1to24.py")
    spec = importlib.util.spec_from_file_location("cascade_replay", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REPLAY = _replay_module()


def keys_of(idx):
    """Distinct 32-bit keys: an odd multiplier is a bijection mod 2^32."""
    idx = np.asarray(idx, np.uint64)
    return ((idx * 0x9E3779B1 + 0x7F4A7C15) % 2**32).astype(np.uint32)


def _query_batch(rng, stored):
    """Record indices of one lookup batch and their exact answers, with
    records ``[0, stored)`` stored."""
    loaded = rng.choice(PRELOAD, 1024, replace=False)
    inserted = PRELOAD + rng.choice(BATCH * N_BATCHES, 1024, replace=False)
    absent = 2**31 + rng.choice(2**20, 2048, replace=False)
    idx = np.concatenate([loaded, inserted, absent])
    return idx, idx < stored


def _run(backend):
    """Load and one cycle; level counts after every batch, and the
    answers (with the counters, under pallas) after the load and after
    the cycle."""
    cfg, st = filters.make("cascade", backend=backend, **MAKE)
    k0 = jnp.asarray(keys_of(np.arange(BATCH)))
    insert = jax.jit(lambda s, k: filters.insert(cfg, s, k), donate_argnums=0)
    ins = insert.lower(st, k0).compile()
    counts = jax.jit(lambda s: filters.stats(cfg, s)["level_counts"])
    if backend == "pallas":
        look = jax.jit(lambda s, k: filters.contains(cfg, s, k, with_stats=True))
    else:
        look = jax.jit(lambda s, k: (filters.contains(cfg, s, k), {}))
    lookup = look.lower(st, jnp.asarray(keys_of(np.arange(QUERIES)))).compile()
    rng = np.random.default_rng(2**31 + 15)
    out = {"counts": [], "answers": [], "stats": [], "want": [], "queries": []}
    stored = 0
    for n_batches in (PRELOAD // BATCH, N_BATCHES):
        for _ in range(n_batches):
            st = ins(st, jnp.asarray(keys_of(np.arange(stored, stored + BATCH))))
            stored += BATCH
            out["counts"].append(np.asarray(counts(st)))
        idx, want = _query_batch(rng, stored)
        hits, stats = lookup(st, jnp.asarray(keys_of(idx)))
        out["answers"].append(np.asarray(hits))
        out["stats"].append(jax.device_get(stats))
        out["want"].append(want)
        out["queries"].append(keys_of(idx))
    out["compiled"] = ins.as_text() + lookup.as_text()
    return out


@pytest.fixture(scope="module")
def pallas_run():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_KERNEL_MODE", "interpret")
        yield _run("pallas")


@pytest.fixture(scope="module")
def reference_run():
    return _run("reference")


@pytest.mark.parametrize("phase", ["load", "cycle"])
def test_level_counts_follow_the_plain_replay(pallas_run, phase):
    make = dict(MAKE, backend="pallas")
    n_load = PRELOAD // BATCH
    got = pallas_run["counts"]
    steps = range(n_load) if phase == "load" else range(n_load, n_load + N_BATCHES)
    for j in steps:
        if j < n_load:
            want = REPLAY.expected_counts(make, (j + 1) * BATCH, 0, 0)
        else:
            want = REPLAY.expected_counts(make, PRELOAD, BATCH, j + 1 - n_load)
        assert list(got[j]) == want["level_counts"], (j, list(got[j]))
    if phase == "cycle":  # the cycle collapses into every level, then ends at 1:24
        assert list(got[-1]) == [0, 0, 0, 0, PRELOAD + BATCH * N_BATCHES]
        assert got[-1][-1] == 24 * int(0.75 * 2 ** MAKE["ram_q"])


@pytest.mark.parametrize("group", ["loaded", "inserted", "absent"])
@pytest.mark.parametrize("when", [0, 1], ids=["after_load", "after_cycle"])
def test_answers_are_exact_membership(pallas_run, when, group):
    part = {"loaded": slice(0, 1024), "inserted": slice(1024, 2048),
            "absent": slice(2048, QUERIES)}[group]
    got = pallas_run["answers"][when][part]
    want = pallas_run["want"][when][part]
    np.testing.assert_array_equal(got, want)


def test_backends_agree(pallas_run, reference_run):
    for a, b in zip(pallas_run["counts"], reference_run["counts"]):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(pallas_run["answers"], reference_run["answers"]):
        np.testing.assert_array_equal(a, b)


def _aligned_tiles(keys, cfg):
    """Window-aligned tiles of one structure: each sorted query takes the
    block its window would start at, and a block of ``c`` queries makes
    ``ceil(c / TILE)`` tiles."""
    fq = np.asarray(qf.fingerprints(cfg, jnp.asarray(keys))[0])
    n_blocks = -(-cfg.total_slots // WINDOW) + 1
    blk = np.clip((fq - WINDOW // 4) // WINDOW, 0, n_blocks - 2)
    return int((-(-np.unique(blk, return_counts=True)[1] // TILE)).sum())


@pytest.mark.parametrize("when", [0, 1], ids=["after_load", "after_cycle"])
def test_stats_are_consistent(pallas_run, when):
    s = pallas_run["stats"][when]
    structures = 1 + MAKE["levels"]
    assert int(s["queries"]) == QUERIES
    for key in ("tiles", "tiles_unfit", "queries_exact"):
        assert s[key].shape == (structures,), key
    # each structure tiles the batch by its own window blocks
    cfg, _ = filters.make("cascade", backend="pallas", **MAKE)
    cfgs = [cfg.q0_cfg] + [cfg.level_cfg(i) for i in range(cfg.levels)]
    keys = pallas_run["queries"][when]
    assert list(s["tiles"]) == [_aligned_tiles(keys, c) for c in cfgs]
    # so every tile fits, and no cluster outruns a 2,048-slot window at
    # load 0.75 or less: no structure takes an exact pass
    assert not s["tiles_unfit"].any()
    assert not s["queries_exact"].any()
    assert int(s["exact_passes"]) == 0


def test_stats_refused_for_the_reference_backend():
    cfg, st = filters.make("cascade", backend="reference", **MAKE)
    with pytest.raises(filters.UnsupportedOpError):
        filters.contains(cfg, st, jnp.asarray(keys_of(np.arange(8))), with_stats=True)


@pytest.mark.parametrize("scope", SCOPES)
def test_scopes_reach_the_compiled_programs(pallas_run, scope):
    """Each scope tags ops of the insert or lookup program (``op_name``
    metadata, which a trace carries)."""
    assert f"/{scope}/" in pallas_run["compiled"]
