"""Cascade probe (hash once, sort once) vs the per-level reference walk.

A probe against a cascade must consult Q0 plus every non-empty level.
The reference backend re-fingerprints the batch per level and walks the
structures one by one; the pallas backend's ``ops.cascade_lookup``
hashes once, sorts once (the canonical fingerprint order is
simultaneously sorted for every level's quotient — requotienting is
monotone), and probes each unfrozen structure in its own window-aligned
tiles, folding frozen (binary-fuse) levels in via their 3-gather pass.

The gated ``kernelratio_cascade_probe`` row is the deployed time over
the reference walk on a 4-level mixed-frozen stack at 16k probes —
capped absolutely at ``perf_gate.RATIO_MAX`` so the single-sort pass
can never silently regress behind the per-level path it replaces.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro import filters
from repro.kernels import dispatch

from .common import Row, keys_u32, time_fn

RAM_Q = 9
P = 28
LEVELS = 4
FROZEN_BELOW = 2  # levels 2..3 demoted to binary-fuse form
N_KEYS = 20_000
N_PROBES = 1 << 14


def _grown(rng, backend):
    cfg, st = filters.make(
        "cascade",
        ram_q=RAM_Q,
        p=P,
        fanout=4,
        levels=LEVELS,
        backend=backend,
        frozen_below=FROZEN_BELOW,
    )
    keys = keys_u32(rng, N_KEYS)
    for i in range(0, N_KEYS, 512):
        st = filters.insert(cfg, st, keys[i : i + 512])
    return cfg, jax.block_until_ready(st), keys


def run() -> list[Row]:
    rng = np.random.default_rng(23)
    mode = dispatch.default_mode()
    cfg_p, st, keys = _grown(rng, "pallas")
    cfg_r = cfg_p._replace(backend="reference")
    probes = jnp.concatenate(
        [keys[: N_PROBES // 2], keys_u32(rng, N_PROBES // 2)]
    )

    # jit both sides: the ratio should compare the single-sort probe
    # against the per-level *algorithm*, not against the eager
    # dispatch overhead of walking five structures op by op
    f_ref = jax.jit(lambda s, p: filters.contains(cfg_r, s, p))
    f_fused = jax.jit(lambda s, p: filters.contains(cfg_p, s, p))
    t_ref = time_fn(lambda: f_ref(st, probes), iters=7, agg=np.min)
    t_fused = time_fn(lambda: f_fused(st, probes), iters=7, agg=np.min)
    got = filters.contains(cfg_p, st, probes)
    want = filters.contains(cfg_r, st, probes)
    assert bool(jnp.all(got == want)), "fused cascade probe mismatch"

    ns = [int(s.n) for s in st.levels]
    nonempty = sum(1 for n in ns if n > 0)
    rows = [
        Row(
            "cascade_probe_fused",
            t_fused * 1e6,
            f"mode={mode};per_level_ref_us={t_ref*1e6:.0f};"
            f"levels={LEVELS};frozen_below={FROZEN_BELOW};"
            f"nonempty={nonempty};queries={N_PROBES}",
        ),
        Row(
            "kernelratio_cascade_probe",
            t_fused / t_ref,
            f"fused_over_per_level;levels={LEVELS};queries={N_PROBES}",
        ),
    ]
    return rows
