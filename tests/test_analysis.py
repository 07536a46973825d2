"""Tests for the static-analysis pass (repro.analysis).

Three layers:

- **rule fixtures** — minimal snippets that trip each repro-lint rule,
  next to near-misses that must NOT trip (the false-positive budget);
- **committed-artifact round-trips** — baseline allowlist and trace
  manifest load/apply/diff, including seeded violations of each class
  exiting non-zero;
- **spec-checker structure** — malformed BlockSpec / ref-count
  mismatches are rejected; the real kernels validate clean.

Plus the regression test for the bug the trace audit surfaced: the
xor_fuse reference lookup ran fully eager (no jit primitive) before it
was jitted.
"""

from __future__ import annotations

import subprocess
import sys

import pytest

from repro.analysis import trace_audit
from repro.analysis.lint import (
    BaselineEntry,
    analyze_sources,
    apply_baseline,
    load_baseline,
    load_toml,
)
from repro.analysis.spec_check import CapturedCall, validate_call


def rules_hit(code: str, path: str = "src/repro/fix.py") -> dict[str, int]:
    out: dict[str, int] = {}
    for f in analyze_sources({path: code}):
        out[f.rule] = out.get(f.rule, 0) + 1
    return out


JIT = "import jax\nimport jax.numpy as jnp\n"


# ---------------------------------------------------------------------------
# rule fixtures: one trip + one near-miss per rule
# ---------------------------------------------------------------------------


class TestRuleFixtures:
    def test_rl101_item_trips(self):
        code = JIT + "def f(x):\n    return x.item()\n"
        assert rules_hit(code).get("RL101") == 1

    def test_rl101_near_misses(self):
        code = JIT + (
            "def f(d, x):\n"
            "    a = d.items()\n"  # dict iteration, not a sync
            "    return x.item(0)\n"  # indexed .item is not the bare sync form
        )
        assert "RL101" not in rules_hit(code)

    def test_rl102_scalar_cast_trips(self):
        code = JIT + "def f(x):\n    return int(x) + float(x) + bool(x)\n"
        assert rules_hit(code).get("RL102") == 3

    def test_rl102_near_misses(self):
        code = JIT + (
            "LIMIT = 128\n"
            "def f(x, cfg):\n"
            "    a = int(x.shape[0])\n"  # static shape
            "    b = int(cfg.q)\n"  # config attribute (static root)
            "    c = int(LIMIT * 2)\n"  # module literal constant
            "    d = int('ff', 16)\n"  # two-arg form, host string parse
            "    return a + b + c + d\n"
        )
        assert "RL102" not in rules_hit(code)

    def test_rl103_numpy_roundtrip_trips(self):
        code = JIT + (
            "import numpy as np\n"
            "def f(x):\n"
            "    return np.asarray(x), jax.device_get(x)\n"
        )
        assert rules_hit(code).get("RL103") == 2

    def test_rl103_near_miss_jnp_asarray(self):
        code = JIT + "def f(x):\n    return jnp.asarray(x)\n"
        assert "RL103" not in rules_hit(code)

    def test_rl104_python_branch_in_jit_trips(self):
        code = JIT + (
            "@jax.jit\n"
            "def f(state):\n"
            "    if jnp.any(state.cells):\n"
            "        return state\n"
            "    return state\n"
        )
        assert rules_hit(code).get("RL104") == 1

    def test_rl104_not_reported_outside_jit(self):
        code = JIT + (
            "def f(state):\n"
            "    if jnp.any(state.cells):\n"
            "        return state\n"
            "    return state\n"
        )
        assert "RL104" not in rules_hit(code)

    def test_rl105_mode_resolve_in_jit_trips(self):
        code = JIT + (
            "from repro.kernels import dispatch\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    m = dispatch.resolve()\n"
            "    return x\n"
        )
        assert rules_hit(code).get("RL105") == 1

    def test_rl105_eager_wrapper_is_fine(self):
        code = JIT + (
            "from repro.kernels import dispatch\n"
            "def wrapper(x):\n"
            "    m = dispatch.resolve()\n"
            "    return x\n"
        )
        assert "RL105" not in rules_hit(code)

    def test_rl106_bare_sentinel_compare_trips(self):
        code = JIT + "def f(x):\n    return x == 2**31 - 1\n"
        assert rules_hit(code).get("RL106") == 1

    def test_rl106_dtype_wrapped_sentinel_is_fine(self):
        code = JIT + (
            "def f(x):\n"
            "    return (x == jnp.int32(2**31 - 1)) | (x == 5)\n"
        )
        assert "RL106" not in rules_hit(code)

    def test_rl107_state_thread_without_donate_trips(self):
        code = JIT + (
            "@jax.jit\n"
            "def step(state, keys):\n"
            "    return state._replace(n=state.n + 1)\n"
        )
        assert rules_hit(code).get("RL107") == 1

    def test_rl107_donated_state_is_fine(self):
        code = JIT + (
            "import functools\n"
            "@functools.partial(jax.jit, donate_argnums=0)\n"
            "def step(state, keys):\n"
            "    return state._replace(n=state.n + 1)\n"
        )
        assert "RL107" not in rules_hit(code)

    def test_jit_reachability_escalates_severity(self):
        # the same construct is a warning in host code, an error when a
        # jit-rooted function can reach it through the call graph
        host = JIT + "def helper(x):\n    return int(x)\n"
        sevs = [f.severity for f in analyze_sources({"src/repro/fix.py": host})]
        assert sevs == ["warning"]
        jit = host + "@jax.jit\ndef root(x):\n    return helper(x)\n"
        sevs = [f.severity for f in analyze_sources({"src/repro/fix.py": jit})]
        assert sevs == ["error"]


# ---------------------------------------------------------------------------
# baseline allowlist round-trip
# ---------------------------------------------------------------------------


class TestBaseline:
    CODE = JIT + "def f(x):\n    return int(x)\n"

    def test_covered_finding_passes(self):
        findings = analyze_sources({"src/repro/fix.py": self.CODE})
        res = apply_baseline(
            findings,
            [BaselineEntry("RL102", "src/repro/fix.py", "known host code", count=1)],
        )
        assert res.ok and res.covered == 1

    def test_count_overflow_fails(self):
        code = JIT + "def f(x):\n    return int(x) + int(x)\n"
        findings = analyze_sources({"src/repro/fix.py": code})
        res = apply_baseline(
            findings,
            [BaselineEntry("RL102", "src/repro/fix.py", "one known site", count=1)],
        )
        assert not res.ok and res.problems

    def test_stale_entry_noted_but_passes(self):
        res = apply_baseline(
            [], [BaselineEntry("RL102", "src/repro/gone.py", "was removed")]
        )
        assert res.ok and len(res.stale) == 1

    def test_uncovered_finding_fails(self):
        findings = analyze_sources({"src/repro/fix.py": self.CODE})
        assert not apply_baseline(findings, []).ok

    def test_load_rejects_missing_reason(self, tmp_path):
        p = tmp_path / "baseline.toml"
        p.write_text('[[allow]]\nrule = "RL102"\npath = "a.py"\n')
        with pytest.raises(ValueError):
            load_baseline(str(p))

    def test_load_roundtrip(self, tmp_path):
        p = tmp_path / "baseline.toml"
        p.write_text(
            "[[allow]]\n"
            'rule = "RL103"\n'
            'path = "src/repro/a.py"\n'
            'func = "F.g"\n'
            "count = 2\n"
            'reason = "because"\n'
        )
        (e,) = load_baseline(str(p))
        assert (e.rule, e.path, e.func, e.count) == (
            "RL103", "src/repro/a.py", "F.g", 2,
        )


# ---------------------------------------------------------------------------
# TOML loading (stdlib tomllib behind lint.load_toml)
# ---------------------------------------------------------------------------


class TestTomlLite:
    def test_sections_arrays_and_types(self, tmp_path):
        path = tmp_path / "demo.toml"
        path.write_text(
            "[tool.demo]\n"
            'name = "x"  # comment\n'
            "n = 3\n"
            "ratio = 1.5\n"
            "on = true\n"
            'paths = [\n  "a",\n  "b",\n]\n'
            "[[tool.demo.allow]]\n"
            'rule = "R1"\n'
            "[[tool.demo.allow]]\n"
            'rule = "R2"\n'
        )
        sec = load_toml(str(path))["tool"]["demo"]
        assert sec["name"] == "x" and sec["n"] == 3 and sec["ratio"] == 1.5
        assert sec["on"] is True and sec["paths"] == ["a", "b"]
        assert [e["rule"] for e in sec["allow"]] == ["R1", "R2"]

    def test_malformed_raises(self, tmp_path):
        path = tmp_path / "bad.toml"
        path.write_text("this is not toml\n")
        with pytest.raises(ValueError):
            load_toml(str(path))


# ---------------------------------------------------------------------------
# trace audit: manifest round-trip + seeded violations
# ---------------------------------------------------------------------------


def _fam(status="traced", eqns=100, prims=None):
    e = {"status": status}
    if status == "traced":
        e["eqns"] = eqns
        e["prims"] = prims or {"add": 3, "jit": 1}
    return e


class TestTraceAudit:
    def test_manifest_roundtrip(self, tmp_path):
        cur = {"families": {"qf": {"contains": _fam()}}}
        path = str(tmp_path / "m.json")
        trace_audit.write_manifest(cur, path)
        man = trace_audit.load_manifest(path)
        assert man["families"] == cur["families"]
        lines, ok = trace_audit.diff(cur, man)
        assert ok and not any(line.startswith("FAIL") for line in lines)

    def test_status_change_fails(self):
        cur = {"families": {"qf": {"contains": _fam(status="host")}}}
        man = {"families": {"qf": {"contains": _fam()}}}
        lines, ok = trace_audit.diff(cur, man)
        assert not ok and any("status" in line for line in lines)

    def test_eqn_blowup_fails(self):
        cur = {"families": {"qf": {"contains": _fam(eqns=500)}}}
        man = {"families": {"qf": {"contains": _fam(eqns=100)}}}
        lines, ok = trace_audit.diff(cur, man)
        assert not ok and any("blow-up" in line for line in lines)

    def test_new_op_fails_until_update(self):
        cur = {"families": {"qf": {"contains": _fam(), "probe": _fam()}}}
        man = {"families": {"qf": {"contains": _fam()}}}
        _, ok = trace_audit.diff(cur, man)
        assert not ok

    def test_prim_drift_notes_unless_strict(self):
        cur = {"families": {"qf": {"contains": _fam(prims={"add": 3, "mul": 1})}}}
        man = {"families": {"qf": {"contains": _fam()}}}
        lines, ok = trace_audit.diff(cur, man, strict=False)
        assert ok and any(line.startswith("note") for line in lines)
        _, ok = trace_audit.diff(cur, man, strict=True)
        assert not ok

    def test_forbidden_primitive_detected(self):
        cur = {
            "families": {
                "qf": {"insert": _fam(prims={"add": 1, "pure_callback": 1})}
            }
        }
        hits = trace_audit.forbidden_hits(cur)
        assert len(hits) == 1 and "pure_callback" in hits[0]

    def test_live_trace_matches_committed_manifest_for_qf(self):
        cur = trace_audit.collect(families=["qf"])
        man = trace_audit.load_manifest()
        assert man is not None, "committed trace_manifest.json missing"
        sub = {
            "families": {
                k: v for k, v in man["families"].items() if k in cur["families"]
            }
        }
        lines, ok = trace_audit.diff(cur, sub)
        assert ok, "\n".join(lines)
        assert not trace_audit.forbidden_hits(cur)


class TestFuseLookupCompiled:
    def test_xor_fuse_contains_traces_compiled(self):
        """Regression: the reference binary-fuse lookup silently ran
        fully eager (no jit primitive in its jaxpr) until it was jitted with the
        config static — the exact bug class the trace audit exists to
        catch."""
        import jax

        from repro import filters

        cfg, state = filters.make("xor_fuse", capacity=128, keys=trace_audit._keys(32))
        jaxpr = jax.make_jaxpr(lambda s, k: filters.contains(cfg, s, k))(
            state, trace_audit._keys(16)
        )
        _, prims = trace_audit._count_jaxpr(jaxpr)
        assert prims.get("jit", 0) >= 1


# ---------------------------------------------------------------------------
# spec checker: malformed launches rejected, real kernels clean
# ---------------------------------------------------------------------------


class _Spec:
    def __init__(self, block_shape, index_map):
        self.block_shape = block_shape
        self.index_map = index_map


def _call(**kw):
    base = dict(
        kernel_name="k",
        kernel_params=None,
        grid=(4,),
        num_scalar_prefetch=0,
        in_specs=[_Spec((1, 8), lambda t: (t, 0))],
        out_specs=[_Spec((1, 8), lambda t: (t, 0))],
        operand_shapes=[(4, 8)],
        scalar_values=[],
        out_shapes=[((4, 8), "int32")],
    )
    base.update(kw)
    return CapturedCall(**base)


class TestSpecChecker:
    def test_wellformed_launch_clean(self):
        assert validate_call(_call()) == []

    def test_tile_not_dividing_plane_rejected(self):
        bad = _call(in_specs=[_Spec((1, 7), lambda t: (t, 0))])
        assert any("does not divide" in p for p in validate_call(bad))

    def test_index_map_out_of_bounds_rejected(self):
        bad = _call(in_specs=[_Spec((1, 8), lambda t: (t + 1, 0))])
        assert any("out of bounds" in p for p in validate_call(bad))

    def test_operand_vs_spec_count_mismatch_rejected(self):
        bad = _call(operand_shapes=[(4, 8), (4, 8)])
        assert any("scalar-prefetch" in p for p in validate_call(bad))

    def test_kernel_arity_mismatch_rejected(self):
        bad = _call(kernel_params=5)  # needs 0 scalar + 1 in + 1 out = 2
        assert any("kernel body takes" in p for p in validate_call(bad))

    def test_index_map_uses_scalar_prefetch_values(self):
        import numpy as np

        # blk[t] style map: in-bounds only because of the clip the
        # wrapper applied to the prefetched block indices
        blk = np.asarray([0, 1, 2, 2], np.int32)
        out = [_Spec((1, 8), lambda t, b: (t, 0))]
        call = _call(
            num_scalar_prefetch=1,
            scalar_values=[blk],
            in_specs=[_Spec((1, 8), lambda t, b: (b[t], 0))],
            out_specs=out,
        )
        assert validate_call(call) == []
        unclipped = np.asarray([0, 1, 2, 3], np.int32)  # 3 -> off the plane
        call = _call(
            num_scalar_prefetch=1,
            scalar_values=[unclipped],
            in_specs=[_Spec((1, 8), lambda t, b: (b[t] + 1, 0))],
            out_specs=out,
        )
        assert any("out of bounds" in p for p in validate_call(call))

    def test_real_kernels_validate_clean(self):
        from repro.analysis.spec_check import (
            KERNELS,
            capture_kernel_calls,
        )

        for spec in KERNELS:
            calls = capture_kernel_calls(spec.driver)
            assert calls, f"{spec.entry}: no launch captured"
            for call in calls:
                assert validate_call(call) == [], spec.entry


# ---------------------------------------------------------------------------
# CLI: committed artifacts keep `python -m repro.analysis` green
# ---------------------------------------------------------------------------


class TestCli:
    @pytest.mark.parametrize("sub", ["lint", "spec"])
    def test_subcommand_exits_zero(self, sub):
        from repro.analysis.__main__ import main

        assert main([sub]) == 0

    def test_module_invocation(self):
        proc = subprocess.run(
            [sys.executable, "-m", "repro.analysis", "lint"],
            capture_output=True,
            text=True,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
