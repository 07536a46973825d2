"""Multi-device functional tests (8 fake CPU devices via subprocess).

XLA locks the host device count at first init, so each test spawns a
subprocess with XLA_FLAGS set — keeping the main pytest session at one
device as required (smoke tests must see 1 device).
"""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
        cwd=REPO,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return out.stdout


def test_sharded_filter_insert_lookup():
    out = run_with_devices(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import sharded_filter as sf
        mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        cfg = sf.ShardedQFConfig(q=14, r=12, n_shards=8)
        state = sf.empty(cfg)
        B = 4096
        insert = jax.jit(sf.make_insert(cfg, mesh, B))
        lookup = jax.jit(sf.make_lookup(cfg, mesh, B))
        rng = np.random.default_rng(0)
        keys = jnp.asarray(rng.integers(0, 2**32, B, dtype=np.int64).astype(np.uint32))
        state = insert(state, keys)
        hit = lookup(state, keys)
        print("present:", bool(hit.all()))
        absent = jnp.asarray(
            rng.integers(0, 2**32, 4096, dtype=np.int64).astype(np.uint32)
        )
        fp = float(lookup(state, absent).mean())
        print("fp_ok:", fp < 0.01)
        """
    )
    assert "present: True" in out
    assert "fp_ok: True" in out


def test_train_step_multidevice_matches_single():
    """2x4 mesh train step: loss on the mesh == single-device loss."""
    out = run_with_devices(
        """
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import sharding as shd
        from repro.configs import get_config, make_smoke
        from repro.models import model
        from repro.train import optimizer as optim, train_step as ts

        cfg = make_smoke(get_config("qwen3-8b")).replace(
            d_model=128, n_layers=2)
        ocfg = optim.OptConfig()
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 64)), jnp.int32),
            "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 64)), jnp.int32),
        }
        state = ts.init_state(cfg, ocfg, 0)
        # single-device reference
        step0 = ts.make_train_step(cfg, ocfg)
        _, m0 = jax.jit(step0)(state, batch)

        AUTO = jax.sharding.AxisType.Auto
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AUTO, AUTO))
        step_j, rules = ts.jit_train_step(cfg, ocfg, mesh, donate=False)
        with mesh:
            _, m1 = step_j(state, batch)
        d = abs(float(m0["loss"]) - float(m1["loss"]))
        print("loss match:", d < 1e-3, float(m0["loss"]), float(m1["loss"]))
        """
    )
    assert "loss match: True" in out


def test_decode_multidevice_matches_single():
    out = run_with_devices(
        """
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import sharding as shd
        from repro.configs import get_config, make_smoke
        from repro.models import model
        from repro.serve.serve_step import cache_pspecs

        cfg = make_smoke(get_config("deepseek-7b"))
        rng = np.random.default_rng(1)
        params = model.init(cfg, 0)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32)
        }
        _, cache = model.prefill(params, cfg, batch, remat=False)
        tok = batch["tokens"][:, -1:]
        ref, _ = model.decode_step(params, cfg, cache, tok)

        AUTO = jax.sharding.AxisType.Auto
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AUTO, AUTO))
        rules = shd.ShardingRules.for_config(mesh, cfg, decode=True)
        def serve(params, cache, tokens):
            with shd.use_rules(rules):
                return model.decode_step(params, cfg, cache, tokens)
        with mesh:
            got, _ = jax.jit(serve)(params, cache, tok)
        d = float(jnp.max(jnp.abs(ref - got))) / float(jnp.max(jnp.abs(ref)))
        print("decode match:", d < 2e-3, d)
        """
    )
    assert "decode match: True" in out


def test_gradient_compression_collective_shrinks():
    """With int8 EF compression the logical all-reduce payload is int8;
    verify numerics stay sane on a real 8-way data-parallel step."""
    out = run_with_devices(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_config, make_smoke
        from repro.train import optimizer as optim, train_step as ts

        cfg = make_smoke(get_config("mamba2-130m"))
        ocfg = optim.OptConfig(compress_grads=True, lr=1e-3)
        rng = np.random.default_rng(0)
        state = ts.init_state(cfg, ocfg, 0)
        step = jax.jit(ts.make_train_step(cfg, ocfg))
        for i in range(3):
            batch = {
                "tokens": jnp.asarray(
                    rng.integers(0, cfg.vocab_size, (8, 64)), jnp.int32
                ),
                "targets": jnp.asarray(
                    rng.integers(0, cfg.vocab_size, (8, 64)), jnp.int32
                ),
            }
            state, m = step(state, batch)
            assert np.isfinite(float(m["loss"]))
        print("compressed training ok:", float(m["loss"]) > 0)
        """
    )
    assert "compressed training ok: True" in out


def test_elastic_restore_to_smaller_mesh():
    """Save on an 8-device mesh, restore onto a 4-device mesh (elastic)."""
    out = run_with_devices(
        """
        import tempfile, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, make_smoke
        from repro.models import model
        from repro.train import optimizer as optim, train_step as ts
        from repro.train.checkpoint import CheckpointManager
        from repro import sharding as shd

        cfg = make_smoke(get_config("gemma-7b"))
        ocfg = optim.OptConfig()
        state = ts.init_state(cfg, ocfg, 0)
        d = tempfile.mkdtemp()
        mgr = CheckpointManager(d)
        mgr.save(7, state)

        AUTO = jax.sharding.AxisType.Auto
        mesh2 = jax.make_mesh((1, 4), ("data", "model"), axis_types=(AUTO, AUTO))
        rules2 = shd.ShardingRules.for_config(mesh2, cfg)
        sspec = ts.state_pspecs(cfg, ocfg, rules2)
        sh = jax.tree.map(lambda s: NamedSharding(mesh2, s), sspec,
                          is_leaf=lambda x: isinstance(x, P))
        restored = mgr.restore(7, jax.eval_shape(lambda: state), shardings=sh)
        ok = all(
            np.allclose(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored))
        )
        print("elastic restore ok:", ok)
        """,
        n_devices=8,
    )
    assert "elastic restore ok: True" in out
