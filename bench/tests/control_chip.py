#!/usr/bin/env python3
"""Run the control at each cell's own size on the chip.

The control is the program one fingerprint bit narrower than the key
(``reference.CONTROL_P``; see ``test_control.py``), driven through a short run of the
cell at its own load and compared with the reference at the width the
configuration states.  Prints each seed's compared numbers: the upper
readings that the limits in ``PERF.md`` were set from.  Needs a TPU.

    python3 bench/tests/control_chip.py --cells ycsb_qf28.read_c --seeds 1 2 3
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
from reference import CONTROL_P  # noqa: E402


def control_spec(cell: str) -> dict:
    spec = harness.cell_spec(cell)
    make = spec["config"]["make"]
    make["r"] = CONTROL_P - make["q"]
    return spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    for cell in args.cells.split(","):
        for seed in args.seeds:
            result, record = harness.execute(control_spec(cell), seed, args.seconds, False)
            line = {"cell": cell, "seed": seed, "control_p": CONTROL_P,
                    "correct": result["correct"], "checks": result["checks"],
                    "answers_checked": record["answers_checked"]}
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
