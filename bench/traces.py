"""Reduce a JAX profiler trace to the numbers the metric readers use.

Input: the ``.xplane.pb`` that ``jax.profiler`` wrote for one run, with
the benchmark's host spans (``jax.profiler.TraceAnnotation``) in it:
``window`` around the measured window, and ``generate``, ``dispatch``,
``wait``, ``check`` and ``reset`` inside it.

* Device operations are the events of the ``XLA Ops`` and ``XLA
  Modules`` lines of each ``/device:TPU:<n>`` plane: a program keeps
  the device busy from its start to its end, also where none of its
  ops' events runs.  (A trace taken on the CPU has no device
  plane; there the events that carry an ``hlo_op`` statistic stand in,
  so the reduction can be rehearsed.)
* ``busy_s`` is the union of the device operations' intervals inside
  the window, averaged over devices; ``window_s`` the window span.
* A kernel's time is the summed duration of its device events: the
  ``tpu_custom_call`` ops named after the kernel's jitted wrapper, as
  listed in ``kernels.json``.
* ``nonkernel_s`` is the busy time that no kernel event covers.
* Every idle gap inside the window is named by the innermost host span
  that overlaps it most; the top device ops are ranked by self time
  (less the ops nested in them).
"""

from __future__ import annotations

import glob
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
SPANS = ("window", "generate", "dispatch", "wait", "check", "reset")
# a program runs on the device for its whole "XLA Modules" event, also
# where no op of its "XLA Ops" line runs (a big conditional's epilogue)
DEVICE_LINES = ("XLA Ops", "XLA Modules")


def kernel_names() -> dict:
    with open(os.path.join(HERE, "kernels.json")) as f:
        return json.load(f)["kernels"]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def read_events(path: str):
    """``(device_ops, spans)``: device ops as ``(plane, name, start_ns,
    end_ns)``, host spans as ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans, cpu_ops = [], [], []
    has_device = False
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") or plane.name.startswith("/device:GPU:"):
            has_device = True
            for line in plane.lines:
                if line.name not in DEVICE_LINES:
                    continue
                for ev in line.events:
                    s = ev.start_ns
                    ops.append((plane.name, ev.name, s, s + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns
                    if ev.name in SPANS:
                        spans.append((ev.name, s, s + ev.duration_ns))
                    elif "hlo_op" in _stats(ev):
                        cpu_ops.append(("/host:CPU", ev.name, s, s + ev.duration_ns))
    return (ops if has_device else cpu_ops), spans


def union(intervals):
    """Merged, sorted, disjoint ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of disjoint sorted intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def op_name(event_name: str) -> str:
    """``%fusion.3`` of an event named by its whole HLO instruction."""
    return event_name.split(" = ")[0]


def is_kernel(event_name: str, prefixes) -> bool:
    """A Mosaic kernel's event: a ``tpu_custom_call`` whose op is named
    after the jitted wrapper around the ``pallas_call``."""
    return "tpu_custom_call" in event_name and op_name(event_name).startswith(
        tuple(prefixes)
    )


def short_name(event_name: str) -> str:
    """Op name, result type and op kind: ``%fusion.3 = s32[268436480]
    fusion``, without operands or layouts."""
    head, _, rest = event_name.partition(" = ")
    m = re.match(r"(\(.*?\)|\S+)\s+([\w.\-]+)\(", re.sub(r"\{[^{}]*\}", "", rest))
    return f"{head} = {m.group(1)} {m.group(2)}"[:120] if m else head


def self_times(events):
    """Each event's duration less that of the events nested in it (a
    conditional or loop holds its body's ops on the same line)."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        # close what ended, and what this event outlasts (not nested)
        while stack and (stack[-1][2] <= s or stack[-1][2] < e):
            out.append(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0])
    out += stack
    return [(name, (e - s) - inner) for name, s, e, inner in out]


def name_gap(gap, spans):
    """The innermost host span that overlaps ``gap`` the most."""
    best, best_key = "none", None
    for name, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov <= 0:
            continue
        key = (name != "window", ov)
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def reduce_events(ops, spans, kernels: dict) -> dict:
    """The reduction proper (separate from file reading, so the check
    script can feed it recorded events)."""
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if not windows:
        raise ValueError("trace holds no 'window' span")
    lo, hi = windows[0]
    planes = sorted({o[0] for o in ops}) or ["none"]
    busy_ns, nonkernel_ns, gaps = 0, 0, []
    kernel_ns = {k: 0 for k in kernels}
    kernel_calls = {k: 0 for k in kernels}
    per_name = {}
    for plane in planes:
        mine = [o for o in ops if o[0] == plane and o[3] > lo and o[2] < hi]
        busy = union(clip([(o[2], o[3]) for o in mine], lo, hi))
        busy_ns += total(busy)
        kern = []
        for k, pats in kernels.items():
            ev = [(o[2], o[3]) for o in mine if is_kernel(o[1], pats)]
            kernel_ns[k] += total(clip(ev, lo, hi))
            kernel_calls[k] += len(ev)
            kern += ev
        nonkernel_ns += total(subtract(busy, union(clip(kern, lo, hi))))
        gaps += subtract([(lo, hi)], busy)
        clipped = [(short_name(o[1]), max(o[2], lo), min(o[3], hi)) for o in mine]
        for name, t in self_times(clipped):
            per_name[name] = per_name.get(name, 0) + t
    n_dev = len(planes)
    top = sorted(per_name.items(), key=lambda kv: -kv[1])
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n_dev / 1e9,
        "nonkernel_s": nonkernel_ns / n_dev / 1e9,
        "kernel_s": {k: v / n_dev / 1e9 for k, v in kernel_ns.items()},
        "kernel_calls": kernel_calls,
        "devices": n_dev,
        "ops": len(ops),
        "top_ops": [[n, v / 1e9] for n, v in top[:10]],
        "idle_gaps": [[name_gap(g, spans), (g[1] - g[0]) / 1e9] for g in gaps[:10]],
    }


def reduce_dir(trace_dir: str) -> dict:
    ops, spans = read_events(find_xplane(trace_dir))
    return reduce_events(ops, spans, kernel_names())
