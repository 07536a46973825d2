"""The ``paper_cascade_1to24`` cells rehearsed through the harness on
the CPU, the control that must fail them, and the readers of the
cascade's scopes.

``bench/rehearse.py`` knows tiny stand-ins of the flat QF only, so the
specs here are made as ``test_control.spec_at`` makes its own:

* at 2^-12 of the cell (Q0 q = 10, 2^13 keys loaded in batches of 2^8,
  the same five levels and p), kernels interpreted and traced, each
  cell must end correct and read its new per-layer metrics;
* at a size where a fingerprint one bit narrower than the key
  (``reference.CONTROL_P``) shows its false positives (2^17 keys
  stored, 2^20 absent keys looked up: ~32 expected), in the XLA
  lowering, the program must end correct and the control not.

    python3 -m pytest -q bench/tests/test_cascade_cells.py
"""

from __future__ import annotations

import copy
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import rehearse  # noqa: E402

rehearse.cpu_env()

import cascade_scopes  # noqa: E402
import harness  # noqa: E402
import scopes  # noqa: E402
from reference import CONTROL_P  # noqa: E402

LOAD, READ = "paper_cascade_1to24.load_b20", "paper_cascade_1to24.read_c"
CONFIG = "paper_cascade_1to24"
# (Q0 quotient bits, set-up load, traffic) of each size; the set-up
# batch is a third of Q0's capacity, as in the cell
SIZE = {
    "tiny": (
        10,
        {"keys": 2**13, "batch": 2**8},
        {
            LOAD: {"batch": 2**8, "check": {"batch": 1024, "inserted": 512, "preloaded": 256, "absent": 256}},
            READ: {"batch": 1024, "check": {"batch": 1024, "inserted": 0, "preloaded": 0, "absent": 1024}},
        },
    ),
    "control": (
        14,
        {"keys": 2**17, "batch": 2**12},
        {
            LOAD: {"batch": 2**12, "check": {"batch": 2**16, "inserted": 2**14, "preloaded": 2**14, "absent": 2**20}},
            READ: {"batch": 2**14, "check": {"batch": 2**16, "inserted": 0, "preloaded": 0, "absent": 2**20}},
        },
    ),
}
# the per-layer metrics that the CPU can read (the kernels.json kernel
# time of qf_build_roofline needs a TPU trace)
NEW_METRICS = {
    LOAD: ["cascade.collapse_ns_per_key", "cascade.q0_ns_per_key"],
    READ: ["cascade.probe_ns_per_key", "cascade.exact_ns_per_key", "cascade_probe_roofline"],
}


def spec_at(cell: str, size: str, control: bool = False) -> dict:
    spec = copy.deepcopy(harness.cell_spec(cell))
    ram_q, preload, traffic = SIZE[size]
    spec["config"]["make"]["ram_q"] = ram_q
    spec["config"]["preload"] = dict(preload)
    spec["traffic"].update(copy.deepcopy(traffic[cell]))
    if control:
        spec["config"]["make"]["p"] = CONTROL_P
    return spec


def test_reference_matches_the_configuration():
    spec = harness.cell_spec(LOAD)
    ref = harness.load_module(
        os.path.join(BENCH, "configs", CONFIG + ".py"), "conf_ref_" + CONFIG
    )
    make, preload = spec["config"]["make"], spec["config"]["preload"]
    assert ref.preload_batch(make) == preload["batch"]
    table = ref.table(make)
    assert sum(slots for slots, _ in table) == 268_369_920
    assert [r for _, r in table] == [18, 17, 16, 15, 14, 13]
    end = ref.expected_counts(make, preload["keys"], spec["traffic"]["batch"], 40)
    assert end["level_counts"] == [0, 0, 0, 0, 24 * 3 * 2**20]  # 1:24


@pytest.mark.parametrize("control", [False, True], ids=["program", "control"])
@pytest.mark.parametrize("cell", [LOAD, READ])
def test_control_fails_program_passes(cell, control, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "xla")
    spec = spec_at(cell, "control", control)
    result, _ = rehearse.rehearse(cell, 2**31 + 5, 2.0, False, spec=spec)
    checks = result["checks"]
    for name in ("preload_count_error", "overflow"):
        assert checks[name]["value"] == 0, checks
    if control:
        assert not result["correct"] and checks["wrong_answers"]["value"] > 0, checks
    else:
        assert result["correct"], checks


@pytest.fixture(scope="module", params=[LOAD, READ])
def traced(request):
    """A traced rehearsal of the cell at 2^-12, kernels interpreted,
    with the programs compiled afresh: a CPU program loaded from the
    persistent compile cache leaves its ``HloProto`` out of the trace,
    and with it the scopes."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    cell = request.param
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("REPRO_KERNEL_MODE", "interpret")
            result, record = rehearse.rehearse(
                cell, 2**31 + 12345, 1.0, True, spec=spec_at(cell, "tiny")
            )
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    trace_dir = os.path.join(BENCH, ".trace", cell)
    return cell, result, record, cascade_scopes.reduce_dir(trace_dir)


def test_tiny_cell_is_correct_and_reads_its_metrics(traced):
    cell, result, record, _ = traced
    assert result["correct"], result["checks"]
    assert record["batches"]
    for name in NEW_METRICS[cell]:
        assert result["metrics"][name]["value"] > 0, (name, result["metrics"])
    if cell == LOAD:
        # every cycle's level counts matched the plain replay
        assert record["cycles"] and all(c["count_error"] == 0 for c in record["cycles"])
    else:
        assert 0 < result["metrics"]["cascade_probe_roofline"]["value"] < 100


def test_recorded_scopes_nest(traced):
    cell, _, _, r = traced
    s = r["scope_s"]
    if cell == LOAD:
        assert {"cascade.q0", "cascade.collapse", "qf.decode", "qf.sort", "qf.build"} <= set(r["scopes"])
        assert s["cascade.q0"] > 0 and s["cascade.collapse"] > 0
        # the flat passes run inside Q0's absorb and the collapses
        for qf_scope in ("qf.decode", "qf.sort", "qf.build"):
            assert s[qf_scope] <= s["cascade.q0"] + s["cascade.collapse"] * (1 + 1e-9)
    else:
        assert {"cascade.probe", "cascade.exact", "cascade.kernel"} <= set(r["scopes"])
        assert s["cascade.kernel"] > 0
        assert s["cascade.exact"] + s["cascade.kernel"] <= s["cascade.probe"] * (1 + 1e-9)


def test_cascade_scope_path():
    assert cascade_scopes.scope_path(
        "jit(f)/jit(_insert_impl)/cascade.q0/qf.decode/jit(x)/qf.decode/add"
    ) == ("cascade.q0", "qf.decode")
    assert cascade_scopes.scope_path("jit(f)/cascade.q0s/add") == ()


@pytest.mark.parametrize("cell", ["ycsb_qf28.read_c", "ycsb_qf28.load"])
def test_program_without_cascade_scopes_reads_none(cell, monkeypatch):
    """The flat QF's recording holds none of the cascade's scopes, so
    their readers give nothing, as on a library without them; its
    ``qf.*`` times are those ``bench/scopes.py`` reads."""
    path = os.path.join(BENCH, "testdata", cell + ".scoped.xplane.pb")
    r = cascade_scopes._reduce_file(path, 0)
    flat = scopes._reduce_file(path, 0)
    assert not set(r["scopes"]) & set(cascade_scopes.CASCADE)
    assert r["scope_s"] == flat["scope_s"] and r["own_s"] == flat["own_s"]
    with open(os.path.join(BENCH, "testdata", cell + ".scoped.json")) as f:
        result = json.load(f)
    record = {"cell": cell, "batches": [{"keys": 1}] * result["attempted"]}
    monkeypatch.setattr(cascade_scopes, "reduce_dir", lambda trace_dir: r)
    reduced = {"window_s": r["window_s"]}
    for name in NEW_METRICS[LOAD] + NEW_METRICS[READ]:
        mod = harness.load_module(os.path.join(BENCH, "metrics", name + ".py"), name)
        assert mod.read(record, reduced, None) is None, name
