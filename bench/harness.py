"""One run of one benchmark cell: set-up, measured window, check, result.

Everything specific to a cell is data found by name:

* ``BENCHMARK.json`` at the checkout root names the cell's
  configuration, traffic mix and metrics;
* ``configs/<config>.json`` holds the deployment (the filter family,
  its ``filters.make`` arguments, the records loaded in set-up, the
  guarantees), and ``configs/<config>.py`` the plain reference of its
  counts;
* ``traffic/<mix>.json`` holds the parameters that the one general
  generator and loop here read;
* ``metrics/<metric>.py`` computes one metric from the run's record
  (and, in a traced run, from the reduced profiler trace).

The window drives the filter library's façade (``repro.filters``)
through programs compiled ahead of time in set-up, one batch in flight
at a time.  Each batch is timed on the host clock from dispatch to
``block_until_ready`` of its result.  Answers are checked after the
window against ``reference`` by construction of the keys.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

import numpy as np

import keys as K
import reference

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class SetupError(RuntimeError):
    """The run cannot measure: no chip, wrong kernel mode, no kernel."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(workload: str, bench_file: str | None = None) -> dict:
    """The cell's entry of ``BENCHMARK.json`` with its configuration,
    traffic, config reference and the metrics it reports."""
    bench = load_json(bench_file or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    conf_entry = confs[cell["config"]]
    config = load_json(os.path.join(ROOT, conf_entry["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [
        m
        for m in bench["per_layer"]
        if (workload in m["workloads"] if "workloads" in m else m["moves"] in reported)
    ]
    return {
        "name": workload,
        "chips": cell["chips"],
        "config_name": cell["config"],
        "traffic_name": cell["traffic"],
        "config": config,
        "traffic": traffic,
        "end_to_end": e2e,
        "per_layer": layer,
        "run_seconds": bench["run_seconds"],
    }


# ---------------------------------------------------------------------------
# JAX set-up: compile cache, device checks
# ---------------------------------------------------------------------------


def setup_jax(require_tpu: bool, chips: int):
    """Import JAX with the persistent compile cache at a fixed path and
    check the device.  Returns ``(jax, devices, compile_events)``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        BENCH, ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    events = []

    def on_event(name, *args, **kw):
        if name.startswith("/jax/core/compile/") or name.endswith(
            "compile_requests_use_cache"
        ):
            events.append(name)

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_event)

    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise SetupError(f"no TPU: JAX sees platform {devices[0].platform!r}")
        if len(devices) < chips:
            raise SetupError(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return jax, devices, events


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise SetupError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def bytes_in_use(jax, devices) -> int:
    stats = devices[0].memory_stats()
    if stats is not None:
        return int(stats["bytes_in_use"])
    # a CPU rehearsal has no allocator statistics: count live arrays
    return int(sum(a.nbytes for a in jax.live_arrays()))


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def programs(spec: dict, filters, counters) -> dict:
    """Every program a run of the cell compiles, at the cell's sizes:
    ``label -> (fn, argument shapes, donated arguments, on the kernel
    path)``.  Labels: ``empty``, ``counts``, ``gen[n]``, ``insert[n]``,
    ``contains[n]`` for batches of ``n`` keys."""
    import jax
    import jax.numpy as jnp

    c, t = spec["config"], spec["traffic"]
    family, make = c["family"], c["make"]
    cfg = filters.by_name(family).cfg_cls(**make)
    state = jax.eval_shape(lambda: filters.make(family, **make)[1])
    u32 = lambda n: jax.ShapeDtypeStruct((n,), jnp.uint32)
    scalar = jax.ShapeDtypeStruct((), jnp.uint32)

    def empty():
        return filters.make(family, **make)[1]

    def read_counts(s):
        st = filters.stats(cfg, s)
        return {k: st[k] for k in counters}

    out = {
        "empty": (empty, (), (), False),
        "counts": (read_counts, (state,), (), False),
    }

    def add_gen(n):
        def make_keys(mult, off, start):
            return K.keys_device(mult, off, start, n)

        out[f"gen[{n}]"] = (make_keys, (scalar, scalar, scalar), (), False)

    def add_insert(n):
        def insert(s, k):
            return filters.insert(cfg, s, k)

        out[f"insert[{n}]"] = (insert, (state, u32(n)), (0,), True)

    def add_contains(n):
        def contains(s, k):
            return filters.contains(cfg, s, k)

        out[f"contains[{n}]"] = (contains, (state, u32(n)), (), True)

    batch = int(t["batch"])
    if t["op"] == "contains":
        add_contains(batch)
    else:
        add_gen(batch)
        add_insert(batch)
    if "check" in t:
        add_contains(int(t["check"]["batch"]))
    if int(c["preload"]["keys"]):
        add_gen(int(c["preload"]["batch"]))
        add_insert(int(c["preload"]["batch"]))
    return out


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


class Run:
    """Set-up, window and check of one cell; ``record`` is what the
    metric readers see."""

    def __init__(self, spec, seed, seconds, *, require_tpu=True, t_start=None):
        self.spec = spec
        self.seed = seed
        self.seconds = seconds
        self.require_tpu = require_tpu
        self.t_start = time.perf_counter() if t_start is None else t_start
        self.config = spec["config"]
        self.traffic = spec["traffic"]
        self.checks = {}  # name -> (number, limit)
        self.record = {"cell": spec["name"], "batches": [], "cycles": []}

    # -- programs -----------------------------------------------------------

    def compile(self, fn, *args, donate=(), kernel=False):
        """AOT-compile ``fn`` for ``args``; on the kernel path, where the
        kernel mode is ``mosaic``, the program must hold a Mosaic kernel."""
        jax = self.jax
        exe = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        if kernel and self.mode == "mosaic" and "tpu_custom_call" not in exe.as_text():
            raise SetupError(f"{fn.__name__}: compiled program holds no Mosaic kernel")
        return exe

    def setup(self):
        jax, self.devices, self.compile_events = setup_jax(
            self.require_tpu, self.spec["chips"]
        )
        self.jax = jax
        import jax.numpy as jnp

        self.jnp = jnp
        sys.path.insert(0, os.path.join(ROOT, "src"))
        from repro import filters
        from repro.kernels import dispatch

        self.mode = dispatch.default_mode()
        if self.require_tpu and self.mode != "mosaic":
            raise SetupError(f"kernel mode resolves to {self.mode!r}, not 'mosaic'")
        dev = self.devices[0]
        self.device = {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(self.devices),
        }
        if self.require_tpu:
            self.peaks = peaks_for(dev.device_kind)
        else:
            self.peaks = load_json(os.path.join(BENCH, "peaks.json"))["devices"][
                "TPU v5 lite"
            ]
        conf_ref = os.path.join(
            BENCH, "configs", self.spec["config_name"] + ".py"
        )
        self.conf_ref = load_module(conf_ref, "conf_ref_" + self.spec["config_name"])

        c, t = self.config, self.traffic
        self.p = self.conf_ref.fingerprint_bits(c["make"])
        # the reference answers exact membership, which the filter owes
        # only where its stated fingerprint is as wide as the key
        if int(c["guarantees"]["fingerprint_bits"]) < 32:
            raise ValueError("the reference needs fingerprint_bits >= 32")
        self.mult, self.off = K.walk(self.seed)
        self.preload = int(c["preload"]["keys"])
        exe = {
            label: self.compile(fn, *args, donate=donate, kernel=kernel)
            for label, (fn, args, donate, kernel) in programs(
                self.spec, filters, self.conf_ref.COUNTERS
            ).items()
        }
        batch = int(t["batch"])
        self.empty, self.counts = exe["empty"], exe["counts"]
        self.op = exe[f"{t['op']}[{batch}]"]
        self.gen = exe.get(f"gen[{batch}]")
        if "check" in t:
            self.check_op = exe[f"contains[{int(t['check']['batch'])}]"]
        state = self.empty()
        if self.preload:
            pb = int(c["preload"]["batch"])
            pgen, pins = exe[f"gen[{pb}]"], exe[f"insert[{pb}]"]
            for start in range(0, self.preload, pb):
                kb = pgen(np.uint32(self.mult), np.uint32(self.off), np.uint32(start))
                state = pins(state, kb)
                del kb
            del pins, pgen
        jax.block_until_ready(state)
        del exe
        counts = jax.device_get(self.counts(state))
        want = self.conf_ref.expected_counts(c["make"], self.preload, 0, 0)
        self.checks["preload_count_error"] = (count_error(counts, want), 0)
        self.checks["overflow"] = (int(bool(counts["overflow"])), 0)
        self.state = state
        self.record["fingerprint_bits"] = self.p
        if hasattr(self.conf_ref, "table"):
            self.record["table"] = self.conf_ref.table(c["make"])
        if self.preload:
            self.measure_resident(int(counts["n"]))
        self.backup = None
        if t.get("reset_every") and self.preload:
            self.backup = jax.tree.map(lambda x: jnp.array(x, copy=True), state)
            jax.block_until_ready(self.backup)

    # -- traffic ------------------------------------------------------------

    def read_batch(self, g):
        """Host-made lookup batch of record indices: loaded records and,
        where the mix has a ``present_share`` under 1, records never
        loaded, in a seeded order."""
        t = self.traffic
        n = int(t["batch"])
        n_in = int(round(n * float(t.get("present_share", 1.0))))
        idx = K.choose(g, t["present"], n_in, 0, self.preload)
        if n_in == n:
            return idx
        idx_out = K.choose(g, t["absent"], n - n_in, K.ABSENT_BASE, K.ABSENT_SPAN)
        return np.concatenate([idx, idx_out])[g.permutation(n)]

    def window(self):
        jax, jnp, t = self.jax, self.jnp, self.traffic
        from jax.profiler import TraceAnnotation

        g = K.rng(self.seed, 1)
        batch = int(t["batch"])
        reset_every = int(t.get("reset_every") or 0)
        inserted = 0  # records inserted by the traffic so far
        since_reset = 0  # batches since the last reset
        self.outputs = []
        state = self.state
        self.state = None
        n_events = len(self.compile_events)
        deadline = None
        prev_counts = jax.device_get(self.counts(state))
        cycle = {"keys": 0, "batches": 0}
        with TraceAnnotation("window"):
            t0w = time.perf_counter()
            self.record["window_start"] = t0w
            deadline = t0w + self.seconds
            while True:
                if time.perf_counter() >= deadline:
                    break
                if reset_every and since_reset == reset_every:
                    with TraceAnnotation("reset"):
                        self.close_cycle(state, since_reset, cycle)
                        del state
                        state = self.fresh_state()
                        jax.block_until_ready(state)
                        prev_counts = jax.device_get(self.counts(state))
                    since_reset = 0
                    cycle = {"keys": 0, "batches": 0}
                with TraceAnnotation("generate"):
                    if t["op"] == "contains":
                        idx = self.read_batch(g)
                        kb = jax.device_put(K.keys_np(self.seed, idx))
                    else:
                        start = self.preload + inserted
                        kb = self.gen(
                            np.uint32(self.mult), np.uint32(self.off), np.uint32(start)
                        )
                    jax.block_until_ready(kb)
                b0 = time.perf_counter()
                with TraceAnnotation("dispatch"):
                    out = self.op(state, kb)
                with TraceAnnotation("wait"):
                    jax.block_until_ready(out)
                b1 = time.perf_counter()
                entry = {"t0": b0, "dt": b1 - b0, "keys": batch}
                if t["op"] == "contains":
                    self.outputs.append((out, idx))
                else:
                    state = out
                    with TraceAnnotation("check"):
                        counts = jax.device_get(self.counts(state))
                    entry["events"] = self.conf_ref.batch_events(
                        self.config["make"], prev_counts, counts
                    )
                    prev_counts = counts
                    inserted += batch
                    since_reset += 1
                    cycle["keys"] += batch
                    cycle["batches"] += 1
                del kb
                self.record["batches"].append(entry)
            t1w = time.perf_counter()
        self.record["window_end"] = t1w
        self.record["compiles_in_window"] = len(self.compile_events) - n_events
        self.state = state
        self.inserted = inserted
        self.since_reset = since_reset
        self.last_cycle = cycle

    def measure_resident(self, stored: int):
        """Bytes in use with only the filter state live, once a run,
        beside the bytes of the arrays that are live then."""
        if "resident_bytes" not in self.record:
            self.record["resident_bytes"] = bytes_in_use(self.jax, self.devices)
            self.record["resident_keys"] = stored
            arrays = sum(a.nbytes for a in self.jax.live_arrays())
            self.record["resident_array_bytes"] = int(arrays)
            log(
                f"resident: bytes_in_use {self.record['resident_bytes']}, "
                f"live arrays {arrays}, keys {stored}"
            )

    def fresh_state(self):
        if self.backup is not None:
            copy = lambda x: self.jnp.array(x, copy=True)
            return self.jax.tree.map(copy, self.backup)
        return self.empty()

    def close_cycle(self, state, since_reset, cycle):
        """Record a completed cycle and check its counts (untimed)."""
        counts = self.jax.device_get(self.counts(state))
        want = self.conf_ref.expected_counts(
            self.config["make"], self.preload, int(self.traffic["batch"]), since_reset
        )
        self.measure_resident(int(counts["n"]))
        err = count_error(counts, want)
        self.record["cycles"].append({**cycle, "count_error": err})
        self.cycle_errors = getattr(self, "cycle_errors", 0) + err
        self.cycle_overflow = getattr(self, "cycle_overflow", 0) + int(
            bool(counts["overflow"])
        )

    # -- check --------------------------------------------------------------

    def check(self):
        """Compare what the window produced with the reference: every
        answer of a lookup window, then the check batches that the mix
        names, looked up in the state the window left."""
        jax = self.jax
        t = self.traffic
        state = self.state
        wrong = 0
        answers = 0
        for out, idx in self.outputs:
            got = np.asarray(out)
            wrong += int(np.sum(got != self.expected(idx, [(0, self.preload)])))
            answers += got.size
        self.outputs = []
        if t["op"] != "contains":
            if self.since_reset:
                self.close_cycle(state, self.since_reset, self.last_cycle)
            self.checks["count_error"] = (getattr(self, "cycle_errors", 0), 0)
            self.checks["overflow"] = (
                self.checks["overflow"][0] + getattr(self, "cycle_overflow", 0),
                0,
            )
        lo = self.preload + self.inserted - self.since_reset * int(t["batch"])
        ranges = [(0, self.preload), (lo, self.preload + self.inserted)]
        if "check" in t:
            for idx in self.check_batches(lo):
                kb = jax.device_put(K.keys_np(self.seed, idx))
                got = np.asarray(self.check_op(state, kb))
                wrong += int(np.sum(got != self.expected(idx, ranges)))
                answers += got.size
        self.checks["wrong_answers"] = (wrong, 0)
        self.record["answers_checked"] = answers
        self.state = None

    def expected(self, idx, ranges):
        """The reference's answers for record indices ``idx`` when the
        records in ``ranges`` are stored: exact membership."""
        return reference.in_ranges(idx, ranges)

    def check_batches(self, lo):
        """Record indices to look up after the window: keys inserted
        since the last reset (from ``lo``), preloaded and absent keys,
        drawn from the seed, in whole check batches."""
        c = self.traffic["check"]
        g = K.rng(self.seed, 2)
        n_new = self.inserted + self.preload - lo
        uniform = {"distribution": "uniform"}
        want = int(c["inserted"])
        if want >= n_new:
            parts = [np.arange(lo, lo + n_new, dtype=np.uint32)]
        else:
            parts = [K.choose(g, uniform, want, lo, n_new)]
        if self.preload and c.get("preloaded"):
            parts.append(K.choose(g, uniform, int(c["preloaded"]), 0, self.preload))
        n_absent = int(c["absent"])
        n_absent += (-(sum(p.size for p in parts) + n_absent)) % int(c["batch"])
        parts.append(K.choose(g, uniform, n_absent, K.ABSENT_BASE, K.ABSENT_SPAN))
        idx = np.concatenate(parts)[g.permutation(sum(p.size for p in parts))]
        cb = int(c["batch"])
        for s in range(0, idx.size, cb):
            yield idx[s : s + cb]


def count_error(counts: dict, want: dict) -> int:
    """Sum of absolute differences between reported and expected counts."""
    err = 0
    for k, v in want.items():
        diff = np.asarray(counts[k], np.int64) - np.asarray(v, np.int64)
        err += int(np.sum(np.abs(diff)))
    return err


# ---------------------------------------------------------------------------
# Metrics and the result line
# ---------------------------------------------------------------------------


def compute_metrics(run: Run, entries, reduced=None) -> dict:
    """Each metric from its reader ``metrics/<name>.py``; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in entries:
        path = os.path.join(BENCH, "metrics", m["name"] + ".py")
        mod = load_module(path, "metric_" + m["name"].replace(".", "_"))
        v = mod.read(run.record, reduced, run.peaks)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def execute(
    spec, seed, seconds, trace, *, require_tpu=True, t_start=None, trace_dir=None
):
    """Set-up, window, check and metrics of one run.  Returns the
    result dict (the last line a run prints)."""
    run = Run(spec, seed, seconds, require_tpu=require_tpu, t_start=t_start)
    run.setup()
    jax = run.jax
    record = run.record
    record["setup_s"] = time.perf_counter() - run.t_start
    if trace:
        import shutil

        trace_dir = trace_dir or os.path.join(BENCH, ".trace", spec["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    try:
        run.window()
    finally:
        if trace:
            jax.profiler.stop_trace()
    record["memory_peak_bytes"] = peak_bytes(run.devices)
    run.check()
    checks = dict(run.checks)
    if record["compiles_in_window"]:
        n = record["compiles_in_window"]
        raise RuntimeError(f"{n} compile events in the window")
    reduced = None
    if trace:
        import traces

        reduced = traces.reduce_dir(trace_dir)
    entries = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = compute_metrics(run, entries, reduced)
    correct = all(v <= lim for v, lim in checks.values())
    attempted = sum(b["keys"] for b in record["batches"])
    result = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(checks["wrong_answers"][0]),
        "metrics": metrics,
        "device": dict(run.device, memory_peak_bytes=record["memory_peak_bytes"]),
    }
    if trace:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": reduced["top_ops"][:10],
            "idle_gaps": reduced["idle_gaps"][:10],
        }
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return result, record
