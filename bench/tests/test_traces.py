"""Check the trace reduction (``bench/traces.py``) on traces recorded
on the chip and on hand-made intervals.

``bench/testdata/<cell>.xplane.pb`` is the trace that one ``--trace 1``
run of the cell wrote on a TPU v5e (``bench/.trace/<cell>/``), with
that run's result line beside it as ``<cell>.json``.  Both were
recorded at r = 16, and the ``read_c`` sample with half of its reads
absent; the reduction depends on neither.  The source-file
paths in the traces' metadata were rewritten to a neutral prefix of the
same length; no event was changed.

    python3 -m pytest -q bench/tests/test_traces.py
"""

from __future__ import annotations

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import traces  # noqa: E402

DATA = os.path.join(BENCH, "testdata")
SAMPLES = {"ycsb_qf28.read_c": "qf_probe", "ycsb_qf28.load": "qf_build"}


def sweep_busy(intervals, lo, hi) -> int:
    """Covered length by a counting sweep (independent of ``union``)."""
    edges = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            edges += [(s, 1), (e, -1)]
    edges.sort()
    covered, depth, last = 0, 0, None
    for t, d in edges:
        if depth > 0:
            covered += t - last
        depth += d
        last = t
    return covered


def test_union_subtract_by_hand():
    a = traces.union([(5, 9), (0, 2), (1, 3), (8, 12), (20, 21)])
    assert a == [(0, 3), (5, 12), (20, 21)]
    assert traces.subtract([(0, 30)], a) == [(3, 5), (12, 20), (21, 30)]
    assert traces.subtract(a, [(1, 6), (10, 25)]) == [(0, 1), (6, 10)]
    spans = [("window", 0, 30), ("wait", 2, 4), ("generate", 11, 22)]
    assert traces.name_gap((3, 5), spans) == "wait"
    assert traces.name_gap((12, 20), spans) == "generate"
    assert traces.name_gap((25, 30), spans) == "window"


def test_reduce_by_hand():
    ops = [("d0", "%_lookup.1 = s32[8] custom-call()", 10, 20), ("d0", "%fusion.2 = s32[8] fusion()", 15, 30), ("d0", "%sort.1 = s32[8] sort()", 50, 60)]
    spans = [("window", 0, 100), ("wait", 30, 50), ("generate", 60, 100)]
    ops = [(d, n + ' custom_call_target="tpu_custom_call"' if "lookup" in n else n, s, e) for d, n, s, e in ops]
    r = traces.reduce_events(ops, spans, {"qf_probe": ["%_lookup."]})
    assert r["busy_s"] == 30e-9 and r["window_s"] == 100e-9
    assert r["kernel_s"]["qf_probe"] == 10e-9 and r["nonkernel_s"] == 20e-9
    assert r["idle_gaps"][0] == ["generate", 40e-9]
    assert sorted(n for n, _ in r["idle_gaps"]) == ["generate", "wait", "window"]
    assert r["top_ops"][0] == ["%fusion.2 = s32[8] fusion", 15e-9]


def test_self_times_nested():
    got = dict(traces.self_times([("cond", 0, 10), ("a", 1, 3), ("b", 4, 6), ("c", 12, 13)]))
    assert got == {"cond": 6, "a": 2, "b": 2, "c": 1}
    # an overlap that is not nesting takes nothing from either
    assert dict(traces.self_times([("x", 0, 10), ("y", 5, 15)])) == {"x": 10, "y": 10}


@pytest.fixture(scope="module", params=sorted(SAMPLES))
def sample(request):
    cell = request.param
    path = os.path.join(DATA, cell + ".xplane.pb")
    ops, spans = traces.read_events(path)
    with open(os.path.join(DATA, cell + ".json")) as f:
        result = json.load(f)
    traffic = harness.cell_spec(cell)["traffic"]
    return cell, ops, spans, {"result": result, "batches": result["attempted"] // traffic["batch"]}


def test_sample_is_a_chip_trace(sample):
    cell, ops, spans, recorded = sample
    assert {o[0] for o in ops} == {"/device:TPU:0"}
    assert recorded["result"]["device"]["platform"] == "tpu"
    names = [s[0] for s in spans]
    assert names.count("window") == 1
    assert names.count("dispatch") == names.count("wait") == recorded["batches"]


def test_busy_and_gaps_add_up(sample):
    cell, ops, spans, _ = sample
    r = traces.reduce_events(ops, spans, traces.kernel_names())
    (lo, hi) = [(s, e) for n, s, e in spans if n == "window"][0]
    busy = sweep_busy([(o[2], o[3]) for o in ops], lo, hi)
    assert r["busy_s"] == pytest.approx(busy / 1e9, abs=1e-12)
    assert 0 < r["busy_s"] < r["window_s"]
    # device ops run inside the host's window: the clocks agree
    inside = [o for o in ops if o[2] >= lo and o[3] <= hi]
    assert len(inside) >= 0.9 * len(ops)


def test_kernel_found_and_within_busy(sample):
    cell, ops, spans, recorded = sample
    r = traces.reduce_events(ops, spans, traces.kernel_names())
    k = SAMPLES[cell]
    assert r["kernel_calls"][k] >= recorded["batches"]
    assert 0 < r["kernel_s"][k] <= r["busy_s"]
    assert r["nonkernel_s"] + sum(r["kernel_s"].values()) == pytest.approx(r["busy_s"], rel=1e-9)


def test_reduction_matches_the_run(sample):
    cell, ops, spans, recorded = sample
    r = traces.reduce_events(ops, spans, traces.kernel_names())
    assert r["window_s"] == recorded["result"]["device"]["window_s"]
    # the run counted "XLA Ops" alone; program spans only add to that
    assert recorded["result"]["device"]["busy_s"] <= r["busy_s"] <= r["window_s"]


def test_programs_cover_their_ops(sample):
    cell, ops, spans, _ = sample
    modules = [(s, e) for _, n, s, e in ops if " = " not in n]
    inner = [(s, e) for _, n, s, e in ops if " = " in n]
    assert modules and inner
    assert traces.total(traces.subtract(traces.union(inner), traces.union(modules))) == 0
