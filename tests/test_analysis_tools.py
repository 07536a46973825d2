"""Unit tests for the roofline/HLO analysis tooling and sharding rules."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_analysis as H
from repro.launch import roofline as rf


class TestHloAnalysis:
    def test_scan_trip_count_multiplied(self):
        def f(x, w):
            def body(c, wi):
                return jnp.tanh(c @ wi), None

            y, _ = jax.lax.scan(body, x, w)
            return y

        x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        w = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)
        c = jax.jit(f).lower(x, w).compile()
        got = H.analyze(c.as_text())["flops"]
        want = 2 * 10 * 64 * 128 * 128
        assert abs(got - want) / want < 0.01
        # raw xla under-counts by ~the trip count (regression canary)
        raw = c.cost_analysis()
        assert raw["flops"] < want / 5

    def test_grad_remat_flops(self):
        def f(x, w):
            body = jax.checkpoint(
                lambda c, wi: (jnp.tanh(c @ wi), None),
                policy=jax.checkpoint_policies.nothing_saveable,
            )

            def loss(x, w):
                y, _ = jax.lax.scan(body, x, w)
                return jnp.sum(y)

            return jax.grad(loss, argnums=1)(x, w)

        x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        w = jax.ShapeDtypeStruct((12, 128, 128), jnp.float32)
        c = jax.jit(f).lower(x, w).compile()
        got = H.analyze(c.as_text())["flops"]
        # fwd + remat-fwd + 2x bwd = 4 matmul-equivalents
        want = 4 * 2 * 12 * 64 * 128 * 128
        assert abs(got - want) / want < 0.05

    def test_collectives_parsed(self):
        mesh = jax.make_mesh((1,), ("d",))
        from jax.sharding import NamedSharding, PartitionSpec as P

        def f(x):
            return jax.lax.with_sharding_constraint(
                x.sum(), NamedSharding(mesh, P())
            )

        # single-device: no collectives expected; parser returns zeros
        c = jax.jit(f).lower(jax.ShapeDtypeStruct((128,), jnp.float32)).compile()
        coll = H.analyze(c.as_text())["collectives"]
        assert coll["total"] == 0

    def test_shape_parsing_with_index_comments(self):
        text = """
ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %w = (s32[], f32[8,8]{1,0}, /*index=5*/f32[16,16]{1,0}) while(%t), condition=%c, body=%b, backend_config={"known_trip_count":{"n":"3"}}
  ROOT %r = f32[4]{0} add(%p, %p)
}
%b (a: s32[]) -> s32[] {
  %a = s32[] parameter(0)
  %d = f32[8,8]{1,0} dot(%x, %y), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
        mod = H.HloModule(text)
        whiles = [
            i
            for comp in mod.computations.values()
            for i in comp.instrs
            if i.op == "while"
        ]
        assert len(whiles) == 1 and mod._trip(whiles[0]) == 3


class TestRoofline:
    def test_terms_and_bound(self):
        r = rf.Roofline(
            flops=197e12, bytes_accessed=819e9 * 2, coll_bytes=50e9 / 2, chips=4,
            model_flops=4 * 197e12 * 0.5,
        )
        assert r.t_compute == pytest.approx(1.0)
        assert r.t_memory == pytest.approx(2.0)
        assert r.t_collective == pytest.approx(0.5)
        assert r.bound == "memory"
        assert r.mfu == pytest.approx(0.25)

    def test_model_flops_train_vs_decode(self):
        from repro.configs import get_config

        cfg = get_config("qwen3-8b")
        tr = rf.model_flops_estimate(cfg, "train", 256, 4096)
        de = rf.model_flops_estimate(cfg, "decode", 128, 32768)
        assert tr > 6 * cfg.param_count() * 256 * 4096 * 0.99
        assert de < tr / 1000


class TestShardingRules:
    def test_divisibility_fallbacks(self):
        from repro import sharding as shd
        from repro.configs import get_config

        mesh = jax.make_mesh((1, 1), ("data", "model"))
        # single-device mesh: everything trivially divides
        rules = shd.ShardingRules.for_config(mesh, get_config("qwen3-8b"))
        assert rules.spec(("batch", None)) is not None

    def test_spec_dedups_reused_axes(self):
        from repro import sharding as shd

        mesh = jax.make_mesh((1, 1), ("data", "model"))
        rules = shd.ShardingRules(
            mesh=mesh, mapping={"batch": ("data",), "embed": ("data",)}
        )
        spec = rules.spec(("batch", "embed"))
        # embed must NOT reuse the data axis already taken by batch
        assert spec[0] == ("data",) or spec[0] == "data"
        assert spec[1] is None

    def test_shape_aware_fallback(self):
        from repro import sharding as shd
        from jax.sharding import PartitionSpec

        mesh = jax.make_mesh((1, 1), ("data", "model"))
        rules = shd.ShardingRules(mesh=mesh, mapping={"ffn": ("model",)})
        # dim not divisible by axis size 1? always divisible; simulate via
        # explicit check that shape-aware path returns a PartitionSpec
        assert isinstance(rules.spec(("ffn",), (7,)), PartitionSpec)
