#!/usr/bin/env python3
"""Device time by named scope, from a JAX profiler trace.

The filter library names the quotient filter's bulk passes with
``jax.named_scope`` (README "Observability"): ``qf.probe``,
``qf.exact``, ``qf.decode``, ``qf.sort`` and ``qf.build``.  A scope
reaches the compiled program as the ``op_name`` metadata of every op
traced under it, and a trace carries the programs that ran: its
``/host:metadata`` plane holds one ``HloProto`` per program.  This
module joins each device op of the measured window (the ``window``
host span) to its instruction in that program, by program and
instruction name, reads the instruction's scopes from its ``op_name``
and sums the ops' self times (``traces.self_times``):

* ``scope_s[s]``: device seconds of the ops under scope ``s``, scopes
  nested in it included (``qf.decode`` inside ``qf.exact`` counts for
  both; ``qf.exact`` runs inside ``qf.probe``);
* ``own_s[s]``: the ops whose innermost scope is ``s``;
* ``scoped_s`` / ``unscoped_s``: device seconds under some scope /
  under none (other programs, the programs' own epilogues, ops the
  compiler made without metadata);
* ``scopes``: the scopes that the window's programs hold at all.  A
  program built without them (an older library) holds none, and a
  metric of a scope it does not hold reads nothing.

An instruction without ``op_name`` (a multi-output fusion, say) takes
the scopes most of its called computations' instructions have.

    python3 bench/scopes.py <trace dir>    # prints the reduction
"""

from __future__ import annotations

import bisect
import collections
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import traces  # noqa: E402

SCOPES = ("qf.probe", "qf.exact", "qf.decode", "qf.sort", "qf.build")


# ---------------------------------------------------------------------------
# Protocol-buffer wire format: just enough to read the HloProtos that an
# XSpace (.xplane.pb) carries, with no generated classes
# ---------------------------------------------------------------------------


def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> dict:
    """``field number -> [values]`` of one message: ints for varints,
    bytes for length-delimited fields (fixed-width fields are skipped)."""
    out, i, n = {}, 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i : i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        out.setdefault(key >> 3, []).append(v)
    return out


def _packed(values) -> list:
    """Repeated int64 values, packed (bytes) or not (ints)."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
            continue
        i = 0
        while i < len(v):
            x, i = _varint(v, i)
            out.append(x)
    return out


def _text(values) -> str:
    return values[0].decode() if values else ""


def hlo_protos(path: str) -> dict:
    """``program -> HloModuleProto bytes`` from the ``/host:metadata``
    plane of an ``.xplane.pb``; a program is named as its device and
    host events name it, ``<module>(<program id>)``."""
    with open(path, "rb") as f:
        space = _fields(f.read())
    out = {}
    for plane in space.get(1, []):  # XSpace.planes
        p = _fields(plane)
        if _text(p.get(2)) != "/host:metadata":  # XPlane.name
            continue
        stat_names = {}
        for entry in p.get(5, []):  # XPlane.stat_metadata: id -> XStatMetadata
            sm = _fields(_fields(entry)[2][0])
            stat_names[sm.get(1, [0])[0]] = _text(sm.get(2))
        for entry in p.get(4, []):  # XPlane.event_metadata: id -> XEventMetadata
            em = _fields(_fields(entry)[2][0])
            for stat in em.get(5, []):  # XEventMetadata.stats
                s = _fields(stat)
                if stat_names.get(s.get(1, [0])[0]) == "Hlo Proto" and 6 in s:
                    out[_text(em.get(2))] = _fields(s[6][0])[1][0]  # HloProto.hlo_module
    return out


def scope_path(op_name: str) -> tuple:
    """The documented scopes in an ``op_name``, outermost first, each once."""
    path = []
    for part in op_name.split("/"):
        if part in SCOPES and part not in path:
            path.append(part)
    return tuple(path)


def instruction_scopes(module: bytes) -> dict:
    """``instruction name -> scope path`` of one HloModuleProto."""
    comps = {}  # computation id -> [(name, path or None, called ids)]
    for comp in _fields(module).get(3, []):  # HloModuleProto.computations
        c = _fields(comp)
        rows = []
        for ins in c.get(2, []):  # HloComputationProto.instructions
            i = _fields(ins)
            md = _fields(i[7][0]) if 7 in i else {}  # metadata: OpMetadata
            path = scope_path(_text(md.get(2))) if md.get(2) else None
            rows.append((_text(i.get(1)), path, _packed(i.get(38, []))))
        comps[c.get(5, [0])[0]] = rows  # HloComputationProto.id
    out = {}
    for rows in comps.values():
        for name, path, called in rows:
            if path is None:
                seen = collections.Counter(
                    p for cid in called for _, p, _ in comps.get(cid, []) if p
                )
                path = seen.most_common(1)[0][0] if seen else ()
            out[name] = path
    return out


def scope_table(protos: dict) -> dict:
    """``(program, instruction) -> scope path`` over every program."""
    return {
        (prog, name): path
        for prog, module in protos.items()
        for name, path in instruction_scopes(module).items()
    }


# ---------------------------------------------------------------------------
# Events and the reduction
# ---------------------------------------------------------------------------


def read_ops(path: str):
    """``(ops, spans)`` as ``traces.read_events`` gives them, each op
    with its program and instruction appended: ``(plane, name, start_ns,
    end_ns, program, instruction)``.  On a device plane an op's program
    is the ``XLA Modules`` event around it (whose own instruction is
    None); a CPU trace names both in each op's statistics."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans, cpu_ops = [], [], []
    has_device = False
    for plane in pd.planes:
        if plane.name.startswith(("/device:TPU:", "/device:GPU:")):
            has_device = True
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            mods = sorted(
                (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                for ev in lines.get("XLA Modules", [])
            )
            starts = [m[0] for m in mods]
            for name in traces.DEVICE_LINES:
                for ev in lines.get(name, []):
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if name == "XLA Modules":
                        ops.append((plane.name, ev.name, s, e, ev.name, None))
                        continue
                    k = bisect.bisect_right(starts, s) - 1
                    prog = mods[k][2] if k >= 0 and s < mods[k][1] else None
                    ins = traces.op_name(ev.name).lstrip("%")
                    ops.append((plane.name, ev.name, s, e, prog, ins))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if ev.name in traces.SPANS:
                        spans.append((ev.name, s, e))
                        continue
                    st = traces._stats(ev)
                    if "hlo_op" in st:
                        prog = f"{st.get('hlo_module')}({st.get('program_id')})"
                        cpu_ops.append(("/host:CPU", ev.name, s, e, prog, st["hlo_op"]))
    return (ops if has_device else cpu_ops), spans


def reduce_scopes(ops, spans, table: dict) -> dict:
    """The reduction proper, on ``read_ops`` events and a
    ``scope_table``."""
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if not windows:
        raise ValueError("trace holds no 'window' span")
    lo, hi = windows[0]
    planes = sorted({o[0] for o in ops}) or ["none"]
    scope_ns = collections.Counter()
    own_ns = collections.Counter()
    busy_ns = unscoped_ns = 0
    for plane in planes:
        mine = [o for o in ops if o[0] == plane and o[3] > lo and o[2] < hi]
        busy_ns += traces.total(traces.union(traces.clip([o[2:4] for o in mine], lo, hi)))
        clipped = [(k, max(o[2], lo), min(o[3], hi)) for k, o in enumerate(mine)]
        for k, t in traces.self_times(clipped):
            path = table.get((mine[k][4], mine[k][5]), ())
            if not path:
                unscoped_ns += t
                continue
            for s in path:
                scope_ns[s] += t
            own_ns[path[-1]] += t
    ran = {o[4] for o in ops if o[3] > lo and o[2] < hi}
    n_dev = len(planes)
    sec = lambda ns: ns / n_dev / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sec(busy_ns),
        "scope_s": {s: sec(v) for s, v in sorted(scope_ns.items())},
        "own_s": {s: sec(v) for s, v in sorted(own_ns.items())},
        "scoped_s": sec(sum(own_ns.values())),
        "unscoped_s": sec(unscoped_ns),
        "scopes": sorted({s for (prog, _), path in table.items() if prog in ran for s in path}),
    }


@functools.lru_cache(maxsize=4)
def _reduce_file(path: str, mtime_ns: int) -> dict:
    ops, spans = read_ops(path)
    return reduce_scopes(ops, spans, scope_table(hlo_protos(path)))


def reduce_dir(trace_dir: str) -> dict:
    path = traces.find_xplane(trace_dir)
    return _reduce_file(path, os.stat(path).st_mtime_ns)


def for_run(record: dict, reduced) -> dict | None:
    """The scope reduction of a traced run's own trace, in
    ``.trace/<cell>`` where ``harness.execute`` writes it; None in an
    untraced run, or where the trace there is not the one that
    ``reduced`` (the run's ``traces`` reduction) was made from."""
    if not reduced:
        return None
    try:
        r = reduce_dir(os.path.join(HERE, ".trace", record["cell"]))
    except FileNotFoundError:
        return None
    return r if r["window_s"] == reduced["window_s"] else None


def ns_per_key(record: dict, reduced, scope: str, own: bool = False):
    """Device time under ``scope`` (``own``: its own ops only) per key
    of the window's batches; None where the programs hold no such scope
    or the run was not traced."""
    r = for_run(record, reduced)
    keys = sum(x["keys"] for x in record["batches"])
    if r is None or scope not in r["scopes"] or not keys:
        return None
    return (r["own_s"] if own else r["scope_s"]).get(scope, 0.0) * 1e9 / keys


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.rstrip().splitlines()[-1].strip(), file=sys.stderr)
        return 2
    r = reduce_dir(argv[0])
    r["scoped_share"] = r["scoped_s"] / r["busy_s"] if r["busy_s"] else None
    print(json.dumps(r, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
