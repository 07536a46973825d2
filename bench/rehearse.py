#!/usr/bin/env python3
"""CPU rehearsal of every benchmark cell at a tiny geometry.

Runs the harness end to end (set-up, window, check, metrics, and with
``--trace 1`` the trace reduction) on the CPU, with the Pallas kernels
in interpret mode, without the chip checks.  It shows wrong paths,
arguments and control flow; its times are the CPU's and mean nothing
for the chip.  Not part of the measurement command.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--cells a,b] [--trace 1]
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

# tiny stand-ins that keep each cell's proportions: load 0.5 before the
# window, 16 load batches to 0.75, p >= 32 (exact answers) and r <= 31
# (Pallas)
TINY_CONFIG = {
    "qf": {"make": {"q": 12, "r": 20}, "preload": {"keys": 2048, "batch": 2048}},
}
TINY_TRAFFIC = {
    "read_c": {"batch": 1024, "check": {"batch": 1024, "inserted": 0, "preloaded": 0, "absent": 1024}},
    "load": {"batch": 64, "check": {"batch": 256, "inserted": 128, "preloaded": 64, "absent": 64}},
}


def tiny(spec: dict) -> dict:
    """The cell at its tiny geometry (same traffic shape, small sizes)."""
    spec = copy.deepcopy(spec)
    c = TINY_CONFIG[spec["config"]["family"]]
    spec["config"]["make"].update(c["make"])
    spec["config"]["preload"] = dict(c["preload"])
    for k, v in TINY_TRAFFIC[spec["traffic_name"]].items():
        spec["traffic"][k] = copy.deepcopy(v)
    return spec


def cpu_env() -> None:
    """CPU, kernels in the interpreter, no compile cache (CPU programs
    there would only be noise for the chip runs).  Call before JAX is
    imported."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("REPRO_KERNEL_MODE", "interpret")
    os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")


def rehearse(workload: str, seed: int, seconds: float, trace: bool, spec=None):
    spec = spec or tiny(harness.cell_spec(workload))
    return harness.execute(spec, seed, seconds, trace, require_tpu=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default="")
    ap.add_argument("--seed", type=int, default=2**31 + 12345)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cpu_env()
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cells = args.cells.split(",") if args.cells else [w["name"] for w in bench["workloads"]]
    ok = True
    for cell in cells:
        result, record = rehearse(cell, args.seed, args.seconds, bool(args.trace))
        ok &= result["correct"]
        print(cell, json.dumps(result), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
