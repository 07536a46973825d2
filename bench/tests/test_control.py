"""The control, a filter one fingerprint bit narrower than the key,
must come out not correct; the program at the configuration's width
must come out correct at the same size.

The configuration states p = 40 fingerprint bits for 32-bit keys, and
every answer is exact because p >= 32.  The nearest narrower width
that breaks that guarantee is p = 31, the program's own option (a
smaller remainder).  Its false positives show once the stored keys are
a sizeable share of 2^31 / absent queries, so the test runs each cell
at a size between the rehearsal's and the cell's, in the XLA lowering.

    python3 -m pytest -q bench/tests/test_control.py
"""

from __future__ import annotations

import copy
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import rehearse  # noqa: E402

rehearse.cpu_env()
os.environ["REPRO_KERNEL_MODE"] = "xla"

import harness  # noqa: E402
from reference import CONTROL_P  # noqa: E402
SIZE = {
    "ycsb_qf28.read_c": (
        {"make": {"q": 20, "r": 24}, "preload": {"keys": 2**19, "batch": 2**19}},
        {"batch": 2**14, "check": {"batch": 2**14, "inserted": 0, "preloaded": 0, "absent": 2**18}},
    ),
    "ycsb_qf28.load": (
        {"make": {"q": 20, "r": 24}, "preload": {"keys": 2**19, "batch": 2**19}},
        {"batch": 2**14, "check": {"batch": 2**16, "inserted": 2**14, "preloaded": 2**14, "absent": 2**15}},
    ),
}


def spec_at(cell: str, control: bool) -> dict:
    spec = copy.deepcopy(harness.cell_spec(cell))
    conf, traffic = SIZE[cell]
    spec["config"]["make"].update(conf["make"])
    spec["config"]["preload"] = dict(conf["preload"])
    spec["traffic"].update(copy.deepcopy(traffic))
    if control:
        make = spec["config"]["make"]
        make["r"] = CONTROL_P - make["q"]
    return spec


@pytest.mark.parametrize("control", [False, True], ids=["program", "control"])
@pytest.mark.parametrize("cell", sorted(SIZE))
def test_control_fails_program_passes(cell, control):
    result, _ = rehearse.rehearse(cell, 2**31 + 5, 2.0, False, spec=spec_at(cell, control))
    wrong = result["checks"]["wrong_answers"]["value"]
    if control:
        assert not result["correct"] and wrong > 0, result["checks"]
    else:
        assert result["correct"], result["checks"]
