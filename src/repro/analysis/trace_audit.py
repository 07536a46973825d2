"""jaxpr trace audit: every registry family's ops stay pure device programs.

For each registered filter family (small geometry, per-family spec
below) the audit traces ``insert / contains / delete / merge / probe /
needs_resize / needs_shrink`` through ``jax.make_jaxpr`` and records,
per op:

- **status** — ``traced`` (pure jaxpr), ``host`` (raises a tracer
  concretization error: the op is host-composed by design, e.g. the
  frozen cascade's peeling merge-down), ``unbound`` (family does not
  register the op), or ``unsupported`` (config-level refusal).
- **eqns** — recursive equation count (through jit/cond/scan/switch
  sub-jaxprs), the audit's size fingerprint: a silent fallback from one
  fused program to an unrolled host loop shows up as a blow-up here.
- **prims** — recursive primitive histogram.  Callback and transfer
  primitives (``pure_callback``, ``io_callback``, ``debug_callback``,
  ``infeed``/``outfeed``, ``device_put``) are *forbidden* inside traced
  family ops and fail the audit outright — a new host round-trip cannot
  land silently.

The result diffs against the committed ``trace_manifest.json``:
status changes, new/removed ops, and eqn blow-ups (> ``BLOWUP`` x)
fail with a readable diff; primitive-set drift is informational (jax
versions move primitives around) unless ``--strict``.  Refresh with
``python -m repro.analysis trace --update`` after a reviewed change.
"""

from __future__ import annotations

import json
import os
from typing import Optional

import jax
import jax.numpy as jnp

MANIFEST_PATH = os.path.join(os.path.dirname(__file__), "trace_manifest.json")

OPS = (
    "insert",
    "contains",
    "delete",
    "merge",
    "probe",
    "needs_resize",
    "needs_shrink",
)

FORBIDDEN_PRIMITIVES = (
    "pure_callback",
    "io_callback",
    "debug_callback",
    "callback",
    "infeed",
    "outfeed",
    "device_put",
)

BLOWUP = 2.0  # traced-op eqn count may not exceed manifest * BLOWUP


def family_specs() -> dict[str, dict]:
    """Small, fast geometries — shapes only matter for tracing."""
    return {
        "qf": dict(q=8, r=8),
        "qf[pallas]": dict(q=8, r=8, backend="pallas"),
        "bloom": dict(m_bits=2048, k=4, counting=True),
        "blocked_bloom": dict(m_bits=65536, k=4, block_bits=32768, counting=True),
        "buffered_qf": dict(ram_q=6, disk_q=10, p=20),
        "cascade": dict(ram_q=6, p=20, levels=2),
        "cascade[pallas]": dict(ram_q=6, p=20, levels=2, backend="pallas"),
        "cascade[frozen]": dict(ram_q=6, p=24, levels=2, frozen_below=1),
        "sharded_qf": dict(q=8, r=8, n_shards=1),
        "xor_fuse": dict(capacity=128),
    }


def _keys(n: int = 64):
    # deterministic pseudo-random uint32 batch (Knuth multiplicative)
    mixed = jnp.arange(1, n + 1, dtype=jnp.uint32) * jnp.uint32(2654435761)
    return mixed ^ jnp.uint32(0x9E3779B9)


def _count_jaxpr(jaxpr) -> tuple[int, dict[str, int]]:
    """Recursive (eqn count, primitive histogram) through sub-jaxprs."""
    eqns = 0
    prims: dict[str, int] = {}

    def walk(jx):
        nonlocal eqns
        for eqn in jx.eqns:
            eqns += 1
            name = eqn.primitive.name
            prims[name] = prims.get(name, 0) + 1
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    walk(sub)

    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return eqns, prims


def _sub_jaxprs(value):
    from jax.extend import core as jex_core

    jaxpr_types = (jex_core.Jaxpr, jex_core.ClosedJaxpr)
    if isinstance(value, jaxpr_types):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            if isinstance(v, jaxpr_types):
                yield v


_HOST_ERRORS = (
    jax.errors.ConcretizationTypeError,
    jax.errors.TracerArrayConversionError,
    jax.errors.TracerBoolConversionError,
)


def trace_family(fam: str, spec: dict) -> dict[str, dict]:
    from repro import filters
    from repro.filters.registry import UnsupportedOpError, by_cfg

    name = fam.split("[")[0]
    cfg, state = filters.make(name, **spec)
    impl = by_cfg(cfg)
    keys = _keys()
    out: dict[str, dict] = {}
    for op in OPS:
        fn = getattr(impl, op, None)
        if fn is None:
            out[op] = {"status": "unbound"}
            continue
        if op == "delete" and not impl.deletable(cfg):
            out[op] = {"status": "unsupported"}
            continue
        if op in ("insert", "contains", "delete", "probe"):
            thunk, args = (lambda s, ks, fn=fn: fn(cfg, s, ks)), (state, keys)
        elif op == "merge":
            thunk, args = (lambda sa, sb, fn=fn: fn(cfg, sa, sb)), (state, state)
        else:  # needs_resize / needs_shrink
            thunk, args = (lambda s, fn=fn: fn(cfg, s)), (state,)
        try:
            jaxpr = jax.make_jaxpr(thunk)(*args)
        except _HOST_ERRORS:
            out[op] = {"status": "host"}
            continue
        except UnsupportedOpError:
            out[op] = {"status": "unsupported"}
            continue
        except Exception as e:  # noqa: BLE001 - audited + surfaced below
            out[op] = {"status": "error", "error": f"{type(e).__name__}: {e}"}
            continue
        eqns, prims = _count_jaxpr(jaxpr)
        out[op] = {"status": "traced", "eqns": eqns, "prims": prims}
    return out


def collect(families: Optional[list[str]] = None) -> dict:
    specs = family_specs()
    if families:
        specs = {
            k: v
            for k, v in specs.items()
            if k.split("[")[0] in families or k in families
        }
    return {"families": {fam: trace_family(fam, spec) for fam, spec in specs.items()}}


def forbidden_hits(current: dict) -> list[str]:
    hits = []
    for fam, ops in current["families"].items():
        for op, entry in ops.items():
            for prim, n in entry.get("prims", {}).items():
                if any(f in prim for f in FORBIDDEN_PRIMITIVES):
                    hits.append(
                        f"{fam}.{op}: forbidden primitive {prim!r} x{n} — a "
                        "traced family op performs a host callback/transfer"
                    )
    return hits


def errors(current: dict) -> list[str]:
    out = []
    for fam, ops in current["families"].items():
        for op, entry in ops.items():
            if entry["status"] == "error":
                out.append(f"{fam}.{op}: trace raised {entry['error']}")
    return out


def diff(current: dict, manifest: dict, strict: bool = False) -> tuple[list[str], bool]:
    """Readable diff lines + pass/fail against the committed manifest."""
    lines: list[str] = []
    failed = False
    cur, man = current["families"], manifest.get("families", {})
    for fam in sorted(set(cur) | set(man)):
        if fam not in man:
            lines.append(f"FAIL {fam}: new family not in manifest (run --update)")
            failed = True
            continue
        if fam not in cur:
            lines.append(f"FAIL {fam}: in manifest but no longer traced (run --update)")
            failed = True
            continue
        for op in sorted(set(cur[fam]) | set(man[fam])):
            c, m = cur[fam].get(op), man[fam].get(op)
            if m is None:
                lines.append(f"FAIL {fam}.{op}: new op not in manifest (run --update)")
                failed = True
                continue
            if c is None:
                lines.append(f"FAIL {fam}.{op}: op disappeared (run --update)")
                failed = True
                continue
            if c["status"] != m["status"]:
                lines.append(
                    f"FAIL {fam}.{op}: status {m['status']} -> {c['status']} — "
                    "a traced op degrading to host (or vice versa) must be a "
                    "reviewed change (run --update after review)"
                )
                failed = True
                continue
            if c["status"] != "traced":
                continue
            if c["eqns"] > m["eqns"] * BLOWUP:
                lines.append(
                    f"FAIL {fam}.{op}: eqn count {m['eqns']} -> {c['eqns']} "
                    f"(> {BLOWUP:.1f}x blow-up — fused program degraded?)"
                )
                failed = True
            added = set(c["prims"]) - set(m["prims"])
            removed = set(m["prims"]) - set(c["prims"])
            if added or removed:
                note = (
                    f"{'FAIL' if strict else 'note'} {fam}.{op}: primitive set "
                    f"drift (+{sorted(added)} -{sorted(removed)})"
                )
                lines.append(note)
                failed = failed or strict
    return lines, not failed


def load_manifest(path: str = MANIFEST_PATH) -> Optional[dict]:
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def write_manifest(current: dict, path: str = MANIFEST_PATH) -> None:
    payload = {
        "comment": (
            "Committed jaxpr trace manifest (see repro.analysis.trace_audit). "
            "Refresh with `python -m repro.analysis trace --update` after a "
            "reviewed change; bypass one CI run with [trace-skip]."
        ),
        "families": current["families"],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")


def render_summary(current: dict) -> str:
    lines = []
    for fam, ops in sorted(current["families"].items()):
        for op, entry in sorted(ops.items()):
            extra = ""
            if entry["status"] == "traced":
                jits = entry["prims"].get("jit", 0)
                extra = f" eqns={entry['eqns']} jit={jits}"
            lines.append(f"  {fam + '.' + op:40s} {entry['status']}{extra}")
    return "\n".join(lines)


def run_audit(
    update: bool = False,
    strict: bool = False,
    manifest_path: str = MANIFEST_PATH,
    verbose: bool = False,
) -> int:
    current = collect()
    problems = errors(current) + forbidden_hits(current)
    if verbose:
        print(render_summary(current))
    for p in problems:
        print(f"FAIL {p}")
    if update:
        if problems:
            print("trace-audit: refusing to --update a failing trace")
            return 1
        write_manifest(current, manifest_path)
        n_tr = sum(
            1
            for ops in current["families"].values()
            for e in ops.values()
            if e["status"] == "traced"
        )
        print(f"trace-audit: manifest refreshed ({n_tr} traced ops) -> {manifest_path}")
        return 0
    manifest = load_manifest(manifest_path)
    if manifest is None:
        print(f"trace-audit: no manifest at {manifest_path} (run --update)")
        return 1
    lines, ok = diff(current, manifest, strict=strict)
    for line in lines:
        print(line)
    n_ops = sum(len(ops) for ops in current["families"].values())
    verdict = "passed" if ok and not problems else "FAILED"
    print(
        f"trace-audit {verdict}: {len(current['families'])} families, "
        f"{n_ops} ops audited"
    )
    return 0 if ok and not problems else 1
