#!/usr/bin/env python3
"""Device time by the cascade filter's named scopes, from a JAX
profiler trace.

The cascade (``repro.filters.cascade``, README "Observability") names
its passes with ``jax.named_scope``: ``cascade.q0`` (Q0 absorbs a
batch), ``cascade.collapse`` (a full Q0 merges into a level),
``cascade.probe`` (the fused lookup over every structure),
``cascade.exact`` (a structure's exact fallback, inside
``cascade.probe``) and ``cascade.kernel`` (the ``cascade_probe``
launches, inside ``cascade.probe``).  The quotient filter's own
``qf.*`` scopes nest inside them.  ``bench/scopes.py`` reduces a trace
by the ``qf.*`` scopes alone; this module makes the same reduction
(``scopes.reduce_scopes``, from the same trace readers) with both
sets, so a cascade scope's time counts the ``qf.*`` passes nested in
it.

    python3 bench/cascade_scopes.py <trace dir>    # prints the reduction
"""

from __future__ import annotations

import collections
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import scopes  # noqa: E402
import traces  # noqa: E402

CASCADE = (
    "cascade.q0",
    "cascade.collapse",
    "cascade.probe",
    "cascade.exact",
    "cascade.kernel",
)
SCOPES = CASCADE + scopes.SCOPES


def scope_path(op_name: str) -> tuple:
    """The known scopes in an ``op_name``, outermost first, each once."""
    path = []
    for part in op_name.split("/"):
        if part in SCOPES and part not in path:
            path.append(part)
    return tuple(path)


def instruction_scopes(module: bytes) -> dict:
    """``instruction name -> scope path`` of one HloModuleProto, as
    ``scopes.instruction_scopes`` reads it, with ``SCOPES``."""
    comps = {}
    for comp in scopes._fields(module).get(3, []):
        c = scopes._fields(comp)
        rows = []
        for ins in c.get(2, []):
            i = scopes._fields(ins)
            md = scopes._fields(i[7][0]) if 7 in i else {}
            path = scope_path(scopes._text(md.get(2))) if md.get(2) else None
            rows.append((scopes._text(i.get(1)), path, scopes._packed(i.get(38, []))))
        comps[c.get(5, [0])[0]] = rows
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


@functools.lru_cache(maxsize=4)
def _reduce_file(path: str, mtime_ns: int) -> dict:
    ops, spans = scopes.read_ops(path)
    table = {
        (prog, name): p
        for prog, module in scopes.hlo_protos(path).items()
        for name, p in instruction_scopes(module).items()
    }
    return scopes.reduce_scopes(ops, spans, table)


def reduce_dir(trace_dir: str) -> dict:
    path = traces.find_xplane(trace_dir)
    return _reduce_file(path, os.stat(path).st_mtime_ns)


def for_run(record: dict, reduced) -> dict | None:
    """The reduction of a traced run's own trace (``.trace/<cell>``);
    None in an untraced run, or where the trace there is not the one
    that ``reduced`` was made from."""
    if not reduced:
        return None
    try:
        r = reduce_dir(os.path.join(HERE, ".trace", record["cell"]))
    except FileNotFoundError:
        return None
    return r if r["window_s"] == reduced["window_s"] else None


def scope_seconds(record: dict, reduced, scope: str) -> float | None:
    """Device seconds under ``scope``, nested scopes included; None
    where the window's programs hold no such scope or the run was not
    traced (a library without the cascade's scopes holds none)."""
    r = for_run(record, reduced)
    if r is None or scope not in r["scopes"]:
        return None
    return r["scope_s"].get(scope, 0.0)


def ns_per_key(record: dict, reduced, scope: str, less: str | None = None):
    """Device time under ``scope`` (less that under ``less``, nested in
    it) per key of the window's batches; None as ``scope_seconds``."""
    s = scope_seconds(record, reduced, scope)
    keys = sum(x["keys"] for x in record["batches"])
    if s is None or not keys:
        return None
    if less is not None:
        s -= scope_seconds(record, reduced, less) or 0.0
    return s * 1e9 / keys


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
