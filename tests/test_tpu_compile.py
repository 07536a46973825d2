"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Interpret-mode parity tests cannot see what the TPU compiler refuses —
block shapes off the (8, 128) tiling, unsupported primitives, scoped
VMEM overruns, oversized scalar prefetch.  These tests compile the
kernels at deployment widths for a *described* v5e (no chip needed)
and check that each program really holds a Mosaic kernel.  They pass
``mode="mosaic"`` per call, so nothing here depends on the platform
the tests run on.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import filters
from repro.core import quotient_filter as qf
from repro.kernels import dispatch, ops
from repro.kernels.cascade_probe import cascade_probe_tiles
from repro.kernels.qf_probe import qf_probe_tiles

MOSAIC = "mosaic"
Q = 26  # a 2^26-slot table
BATCH = 1 << 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # the TPU compiler would otherwise log to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """One described v5e chip; the persistent compile cache is off while
    these compiles run (its entries could not be read back without a
    chip)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _planes(chip, total):
    """(rem, occ, shf, con) plane shapes of a ``total``-slot QF."""
    return (
        _spec(chip, (total,), jnp.uint32),
        _spec(chip, (total,), jnp.bool_),
        _spec(chip, (total,), jnp.bool_),
        _spec(chip, (total,), jnp.bool_),
    )


def _state(chip, cfg):
    return qf.QFState(
        *_planes(chip, cfg.total_slots),
        _spec(chip, (), jnp.int32),
        _spec(chip, (), jnp.bool_),
    )


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_qf_probe_lookup_compiles(chip):
    cfg = qf.QFConfig(q=Q, r=16)
    interpret = dispatch.pallas_interpret(dispatch.resolve(MOSAIC))

    def probe(rem, occ, shf, con, fq, fr):
        return qf_probe_tiles(rem, occ, shf, con, fq, fr, interpret=interpret)

    _assert_kernel(
        probe,
        *_planes(chip, cfg.total_slots),
        _spec(chip, (BATCH,), jnp.int32),
        _spec(chip, (BATCH,), jnp.uint32),
    )


def test_qf_build_sorted_compiles(chip):
    cfg = qf.QFConfig(q=Q, r=16)
    t = cfg.total_slots
    _assert_kernel(
        lambda fq, fr, n: ops.build_sorted(cfg, fq, fr, n, mode=MOSAIC),
        _spec(chip, (t,), jnp.int32),
        _spec(chip, (t,), jnp.uint32),
        _spec(chip, (), jnp.int32),
    )


def test_build_span_compiles(chip):
    cfg = qf.QFConfig(q=Q, r=16)
    scalar = _spec(chip, (), jnp.int32)
    _assert_kernel(
        lambda st, fq, fr, k, lp, lf: ops.build_span(
            cfg, st, fq, fr, k, lp, lf, mode=MOSAIC
        ),
        _state(chip, cfg),
        _spec(chip, (BATCH,), jnp.int32),
        _spec(chip, (BATCH,), jnp.uint32),
        scalar,
        scalar,
        scalar,
    )


def test_cascade_probe_compiles_for_smoke_geometry(chip):
    """The fused probe over Q0 plus four levels (2^18 .. 2^26 slots) of
    ``cascade(ram_q=18, p=44, fanout=4, levels=4)``."""
    cfg, _ = filters.make("cascade", ram_q=18, p=44, fanout=4, levels=4)
    totals = [cfg.q0_cfg.total_slots] + [
        cfg.level_cfg(i).total_slots for i in range(cfg.levels)
    ]
    interpret = dispatch.pallas_interpret(dispatch.resolve(MOSAIC))

    def probe(planes, fqs, frs):
        return cascade_probe_tiles(planes, fqs, frs, interpret=interpret)

    q = _spec(chip, (BATCH,), jnp.int32)
    _assert_kernel(
        probe,
        [_planes(chip, t) for t in totals],
        [q] * len(totals),
        [q] * len(totals),
    )
