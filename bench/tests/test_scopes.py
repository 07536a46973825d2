"""Check the reduction of a trace by named scope (``bench/scopes.py``)
on hand-made events and programs, and on traces recorded on the chip.

``bench/testdata/<cell>.scoped.xplane.pb`` is the trace of one
``--trace 1`` run of the cell on a TPU v5e with the library's scopes in
place, its result line beside it as ``<cell>.scoped.json``; the
source-file paths in its metadata were rewritten to a neutral prefix of
the same length, and no event was changed.  The older
``<cell>.xplane.pb`` recordings come from a library without scopes.

    python3 -m pytest -q bench/tests/test_scopes.py
"""

from __future__ import annotations

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import scopes  # noqa: E402
import traces  # noqa: E402

DATA = os.path.join(BENCH, "testdata")
# each cell's passes, and the scope its kernel runs in
CELLS = {
    "ycsb_qf28.read_c": (["qf.decode", "qf.exact", "qf.probe"], "qf.probe"),
    "ycsb_qf28.load": (["qf.build", "qf.decode", "qf.sort"], "qf.build"),
}


# -- a protobuf encoder for hand-made programs -------------------------------


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _instruction(name, op_name=None, calls=()):
    msg = _field(1, name)
    if op_name is not None:
        msg += _field(7, _field(2, op_name))  # metadata: OpMetadata.op_name
    if calls:
        msg += _field(38, b"".join(_varint(c) for c in calls))  # packed ids
    return msg


def _computation(cid, *instructions):
    return _field(1, f"comp.{cid}") + b"".join(
        _field(2, i) for i in instructions
    ) + _field(5, cid)


MODULE = b"".join(
    _field(3, c)
    for c in (
        _computation(
            1,
            _instruction("fusion.1", "jit(f)/qf.probe/jit(_lookup)/cond/qf.exact/"
                         "jit(extract)/qf.decode/qf.decode/gather"),
            _instruction("fusion.2", calls=[2]),  # no metadata: its callee's
            _instruction("copy.3"),  # no metadata, no callee
            _instruction("sort.4", "jit(f)/sort"),  # metadata, no scope
        ),
        _computation(
            2,
            _instruction("p.0", "jit(f)/qf.sort/select_n"),
            _instruction("p.1", "jit(f)/qf.sort/sort"),
            _instruction("p.2", "jit(f)/qf.build/add"),
        ),
    )
)


def test_scope_path_keeps_documented_scopes_in_order():
    assert scopes.scope_path("jit(a)/qf.probe/jit(b)/qf.exact/x/qf.exact/y") == (
        "qf.probe",
        "qf.exact",
    )
    assert scopes.scope_path("jit(a)/qf.probes/sort") == ()


def test_instruction_scopes_of_a_hand_made_program():
    got = scopes.instruction_scopes(MODULE)
    assert got["fusion.1"] == ("qf.probe", "qf.exact", "qf.decode")
    assert got["fusion.2"] == ("qf.sort",)  # two of its callee's three
    assert got["copy.3"] == () and got["sort.4"] == ()
    assert got["p.2"] == ("qf.build",)


def test_reduce_nested_scopes_by_hand():
    prog = "jit_f(7)"
    table = {
        (prog, "exact"): ("qf.probe", "qf.exact"),
        (prog, "decode"): ("qf.probe", "qf.exact", "qf.decode"),
        (prog, "kernel"): ("qf.probe",),
        (prog, "cond"): ("qf.probe",),
    }
    d = "/device:TPU:0"
    ops = [
        (d, prog, 0, 100, prog, None),  # the program: its gaps are unscoped
        (d, "%kernel = s32[8] custom-call()", 5, 15, prog, "kernel"),
        (d, "%cond = s32[8] conditional()", 20, 90, prog, "cond"),
        (d, "%exact = s32[8] fusion()", 25, 40, prog, "exact"),
        (d, "%decode = s32[8] fusion()", 40, 80, prog, "decode"),
        (d, "%copy = s32[8] copy()", 92, 96, prog, "copy"),  # no scope
        (d, "%late = s32[8] copy()", 150, 160, prog, "kernel"),  # after window
    ]
    r = scopes.reduce_scopes(ops, [("window", 0, 120)], table)
    ns = lambda d: {k: round(v * 1e9) for k, v in d.items()}  # noqa: E731
    assert ns(r["scope_s"]) == {"qf.probe": 80, "qf.exact": 55, "qf.decode": 40}
    assert ns(r["own_s"]) == {"qf.probe": 25, "qf.exact": 15, "qf.decode": 40}
    assert round(r["scoped_s"] * 1e9) == 80 and round(r["unscoped_s"] * 1e9) == 20
    assert r["busy_s"] == pytest.approx(100e-9) and r["window_s"] == pytest.approx(120e-9)
    assert r["scopes"] == ["qf.decode", "qf.exact", "qf.probe"]


def test_ns_per_key_reads_only_scopes_the_programs_hold(monkeypatch):
    record = {"cell": "c", "batches": [{"keys": 500}, {"keys": 500}]}
    reduced = {"window_s": 2.0}
    r = {"window_s": 2.0, "scope_s": {"qf.exact": 1e-3}, "own_s": {"qf.exact": 4e-4},
         "scopes": ["qf.decode", "qf.exact"]}
    monkeypatch.setattr(scopes, "reduce_dir", lambda trace_dir: r)
    assert scopes.ns_per_key(record, reduced, "qf.exact") == pytest.approx(1000.0)
    assert scopes.ns_per_key(record, reduced, "qf.exact", own=True) == pytest.approx(400.0)
    assert scopes.ns_per_key(record, reduced, "qf.decode") == 0.0  # held, never ran
    assert scopes.ns_per_key(record, reduced, "qf.sort") is None  # not held
    assert scopes.ns_per_key(record, None, "qf.exact") is None  # untraced
    # a trace left there by another run is not read
    assert scopes.ns_per_key(record, {"window_s": 3.0}, "qf.exact") is None


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_recording_without_scopes_reads_none(cell):
    path = os.path.join(DATA, cell + ".xplane.pb")
    ops, spans = scopes.read_ops(path)
    r = scopes.reduce_scopes(ops, spans, scopes.scope_table(scopes.hlo_protos(path)))
    assert r["scopes"] == [] and r["scoped_s"] == 0
    assert r["unscoped_s"] == pytest.approx(r["busy_s"], rel=1e-9)


@pytest.fixture(scope="module", params=sorted(CELLS))
def scoped(request):
    cell = request.param
    path = os.path.join(DATA, cell + ".scoped.xplane.pb")
    ops, spans = scopes.read_ops(path)
    table = scopes.scope_table(scopes.hlo_protos(path))
    with open(os.path.join(DATA, cell + ".scoped.json")) as f:
        result = json.load(f)
    return cell, path, ops, spans, table, result


def test_scoped_recording_names_every_pass(scoped):
    cell, path, ops, spans, table, result = scoped
    r = scopes.reduce_scopes(ops, spans, table)
    passes, _ = CELLS[cell]
    assert set(passes) <= set(r["scopes"])
    assert all(r["scope_s"][s] > 0 for s in passes)
    # the window's programs, and no op of them that the table misses
    ran = {o[4] for o in ops}
    assert ran <= {prog for prog, _ in table}
    assert all((o[4], o[5]) in table for o in ops if o[5] is not None)


def test_scoped_recording_agrees_with_the_trace_reduction(scoped):
    cell, path, ops, spans, table, result = scoped
    r = scopes.reduce_scopes(ops, spans, table)
    base = traces.reduce_events(
        [o[:4] for o in ops], spans, traces.kernel_names()
    )
    assert r["window_s"] == base["window_s"] == result["device"]["window_s"]
    assert r["busy_s"] == pytest.approx(base["busy_s"], rel=1e-12)
    # self times add up to busy time; the scopes cover nearly all of it
    assert r["scoped_s"] + r["unscoped_s"] == pytest.approx(r["busy_s"], rel=1e-6)
    assert r["scoped_s"] >= 0.95 * r["busy_s"]
    # a nested scope's time lies inside its parent's
    if "qf.exact" in r["scope_s"]:
        assert r["scope_s"]["qf.decode"] <= r["scope_s"]["qf.exact"]
        assert r["scope_s"]["qf.exact"] <= r["scope_s"]["qf.probe"]


def test_kernels_run_inside_their_scope(scoped):
    cell, path, ops, spans, table, result = scoped
    _, scope = CELLS[cell]
    kernels = [
        o for o in ops
        if traces.is_kernel(o[1], [p for ps in traces.kernel_names().values() for p in ps])
    ]
    assert kernels
    assert all(table[(o[4], o[5])][-1] == scope for o in kernels)


def test_scoped_recording_is_the_cell(scoped):
    cell, path, ops, spans, table, result = scoped
    assert {o[0] for o in ops} == {"/device:TPU:0"}
    assert result["device"]["platform"] == "tpu" and result["correct"]
    traffic = harness.cell_spec(cell)["traffic"]
    names = [s[0] for s in spans]
    assert names.count("wait") == result["attempted"] // traffic["batch"]
