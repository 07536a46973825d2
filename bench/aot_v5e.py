#!/usr/bin/env python3
"""Compile each cell's programs at cell size for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a device that is
described, not attached.  For every program a cell's run compiles it
prints whether the compiler took it, whether it holds a Mosaic kernel
(``tpu_custom_call``) and its ``memory_analysis``: argument, output and
temporary bytes on the chip.  Nothing runs, so this says nothing about
results or times.

    JAX_PLATFORMS=cpu python3 bench/aot_v5e.py [--cells a,b]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

GIB = 2**30


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default="")
    args = ap.parse_args(argv)
    # the CPU host compiles for the described chip; kernels lower to
    # Mosaic; nothing is cached, since nothing here can read it back
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["REPRO_KERNEL_MODE"] = "mosaic"
    os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    put = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cells = [w["name"] for w in bench["workloads"]]
    cells = args.cells.split(",") if args.cells else cells
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    from repro import filters

    ok = True
    for cell in cells:
        spec = harness.cell_spec(cell)
        counters = harness.load_module(
            os.path.join(harness.BENCH, "configs", spec["config_name"] + ".py"), "ref"
        ).COUNTERS
        for label, (fn, shapes, donate, kernel) in harness.programs(
            spec, filters, counters
        ).items():
            if not shapes:
                continue  # makes an empty state: nothing to place
            shapes = jax.tree.map(put, shapes)
            t0 = time.perf_counter()
            try:
                exe = jax.jit(fn, donate_argnums=donate).lower(*shapes).compile()
            except Exception as e:  # report and go on to the next program
                ok = False
                msg = f"{type(e).__name__}: {str(e)[:400]}"
                print(f"{cell} {label}: REFUSED {msg}", flush=True)
                continue
            dt = time.perf_counter() - t0
            m = exe.memory_analysis()
            mosaic = "tpu_custom_call" in exe.as_text()
            ok &= mosaic or not kernel
            print(
                f"{cell} {label}: compile {dt:.1f}s mosaic={mosaic} "
                f"args {m.argument_size_in_bytes / GIB:.3f} GiB "
                f"out {m.output_size_in_bytes / GIB:.3f} GiB "
                f"temp {m.temp_size_in_bytes / GIB:.3f} GiB "
                f"alias {m.alias_size_in_bytes / GIB:.3f} GiB",
                flush=True,
            )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
