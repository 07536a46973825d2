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
import re

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


def test_sparse_lookup_runs_as_several_launches(chip):
    """``ops.contains`` of a sparse batch: 2^16 queries over a 2^26-slot
    table may touch one window block each, so the probe's static grid
    bound (a tile per block besides the full tiles) outgrows one
    launch's scalar prefetch and the kernel runs as several launches."""
    cfg = qf.QFConfig(q=Q, r=16)
    text = (
        jax.jit(lambda st, k: ops.contains(cfg, st, k, mode=MOSAIC))
        .lower(_state(chip, cfg), _spec(chip, (BATCH,), jnp.uint32))
        .compile()
        .as_text()
    )
    blocks = -(-cfg.total_slots // 1024)  # ops.lookup's default window block
    n_tiles = BATCH // 128 + min(BATCH, blocks)
    launches = len(dispatch.launches(n_tiles, 2))
    assert launches > 1
    assert text.count('custom_call_target="tpu_custom_call"') == launches


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


def _kernel_ops(fn, *args):
    """``(instruction, op_name)`` of each Mosaic kernel op in the
    compiled program."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return [
        (line.split(" = ")[0].strip(), re.search(r'op_name="([^"]*)"', line).group(1))
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]


@pytest.mark.parametrize("with_stats", [False, True])
def test_probe_kernel_keeps_its_name_inside_its_scope(chip, with_stats):
    """A trace names the kernel's op after the jitted wrapper
    (``bench/kernels.json``); the ``qf.probe`` scope only tags it."""
    cfg = qf.QFConfig(q=16, r=16)
    ops_ = _kernel_ops(
        lambda st, k: ops.contains(cfg, st, k, mode=MOSAIC, with_stats=with_stats),
        _state(chip, cfg),
        _spec(chip, (4096,), jnp.uint32),
    )
    assert ops_
    for name, op_name in ops_:
        assert name.startswith("%_lookup."), name
        assert "qf.probe" in op_name.split("/"), op_name


def test_build_kernel_keeps_its_name_inside_its_scope(chip):
    cfg = qf.QFConfig(q=16, r=16)
    t = cfg.total_slots
    ops_ = _kernel_ops(
        lambda fq, fr, n: ops.build_sorted(cfg, fq, fr, n, mode=MOSAIC),
        _spec(chip, (t,), jnp.int32),
        _spec(chip, (t,), jnp.uint32),
        _spec(chip, (), jnp.int32),
    )
    assert ops_
    for name, op_name in ops_:
        assert name.startswith("%_build_sorted."), name
        assert "qf.build" in op_name.split("/"), op_name


def test_cascade_probe_compiles_for_the_paper_cell(chip):
    """The fused probe of ``paper_cascade_1to24.read_c``: 2^20 queries
    over Q0 (2^22 slots) and five levels (2^23 .. 2^27 slots) of
    ``cascade(ram_q=22, p=40, fanout=2, levels=5)``."""
    cfg, _ = filters.make("cascade", ram_q=22, p=40, fanout=2, levels=5)
    totals = [cfg.q0_cfg.total_slots] + [
        cfg.level_cfg(i).total_slots for i in range(cfg.levels)
    ]
    interpret = dispatch.pallas_interpret(dispatch.resolve(MOSAIC))

    def probe(planes, fqs, frs):
        return cascade_probe_tiles(planes, fqs, frs, interpret=interpret)

    q = _spec(chip, (1 << 20,), jnp.int32)
    _assert_kernel(
        probe,
        [_planes(chip, t) for t in totals],
        [q] * len(totals),
        [q] * len(totals),
    )


def _cascade_ops(chip, monkeypatch, op):
    """Kernel ops of a small pallas cascade's insert or lookup program
    (the cascade's kernels take the process's kernel mode)."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", MOSAIC)
    make = dict(ram_q=12, p=40, fanout=2, levels=2, backend="pallas")
    cfg, _ = filters.make("cascade", **make)
    state = jax.eval_shape(lambda: filters.make("cascade", **make)[1])
    state = jax.tree.map(lambda s: _spec(chip, s.shape, s.dtype), state)
    fn = getattr(filters, op)
    return _kernel_ops(
        lambda s, k: fn(cfg, s, k), state, _spec(chip, (1024,), jnp.uint32)
    )


def test_cascade_build_kernels_keep_their_name_inside_the_cascade_scopes(
    chip, monkeypatch
):
    """Q0's rebuild and every collapse run the flat ``qf_build`` kernel:
    its op keeps the ``%_build_sorted.`` name that ``bench/kernels.json``
    finds, under ``cascade.q0`` or ``cascade.collapse``."""
    ops_ = _cascade_ops(chip, monkeypatch, "insert")
    scopes = set()
    for name, op_name in ops_:
        assert name.startswith("%_build_sorted."), name
        path = op_name.split("/")
        assert "qf.build" in path, op_name
        scopes |= {"cascade.q0", "cascade.collapse"} & set(path)
    assert scopes == {"cascade.q0", "cascade.collapse"}


def test_cascade_probe_kernel_runs_inside_its_scopes(chip, monkeypatch):
    ops_ = _cascade_ops(chip, monkeypatch, "contains")
    assert ops_
    for name, op_name in ops_:
        assert not name.startswith(("%_lookup.", "%_build_sorted.")), name
        path = op_name.split("/")
        assert path.index("cascade.probe") < path.index("cascade.kernel"), op_name
