#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics are read by name from
``BENCHMARK.json`` at the checkout root and from the files under
``bench/``.  The run needs a TPU: without one, with fewer chips than
the cell asks for, with a kernel mode other than ``mosaic``, with a
kernel-path program that holds no Mosaic kernel, or on a device kind
missing from ``bench/peaks.json``, it exits with code 2 and prints no
result.  The compared numbers and their limits are printed as the last
lines on standard error and under ``checks`` in the result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

# the TPU compiler would otherwise log to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = harness.cell_spec(args.workload)
    try:
        result, _ = harness.execute(
            spec, args.seed, args.seconds, bool(args.trace), t_start=T_START
        )
    except harness.SetupError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        harness.log(f"check {name}={c['value']} limit={c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
