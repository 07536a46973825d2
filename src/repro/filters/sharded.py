"""Mesh-sharded quotient filter under the functional protocol (paper §6).

Adapter over :mod:`repro.core.sharded_filter`: the state is the stacked
per-shard QF pytree, and insert/contains route keys to their owner
shard with the MoE-dispatch all_to_all schedule.  The shard_map'd step
functions are built lazily per (cfg, batch) and cached — the mesh is
derived from the visible devices (``n_shards`` must divide the device
count; ``n_shards=1`` works on a single host).

``delete`` is not registered: a deletion would need the same routed
dispatch plus per-shard multiset diffs, which the core module does not
expose yet.  ``merge`` is the per-shard pairwise QF merge (shard s owns
the same quotient range in both inputs).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import quotient_filter as qf
from repro.core import sharded_filter as sf

from .registry import FilterImpl, register


class ShardedQFilterConfig(NamedTuple):
    q: int  # global log2 buckets
    r: int
    n_shards: int = 1
    axis: str = "data"
    seed: int = 0
    capacity_factor: float = 2.0
    shrink_load: float = 0.4  # low watermark for shard consolidation

    @property
    def core(self) -> sf.ShardedQFConfig:
        return sf.ShardedQFConfig(
            q=self.q,
            r=self.r,
            n_shards=self.n_shards,
            axis=self.axis,
            seed=self.seed,
            capacity_factor=self.capacity_factor,
        )


@functools.lru_cache(maxsize=None)
def _mesh(n_shards: int, axis: str):
    # Auto axes: the per-shard vmap'd structural ops (merge, grow,
    # shrink) run outside shard_map and must see plain arrays
    return jax.make_mesh(
        (n_shards,), (axis,), axis_types=(jax.sharding.AxisType.Auto,)
    )


def _on_mesh(cfg) -> NamedSharding:
    """Shard the stacked per-shard state's leading dim over the mesh."""
    return NamedSharding(_mesh(cfg.n_shards, cfg.axis), PartitionSpec(cfg.axis))


@functools.lru_cache(maxsize=None)
def _insert_fn(cfg: ShardedQFilterConfig, batch: int):
    core = cfg.core
    return jax.jit(sf.make_insert(core, _mesh(cfg.n_shards, cfg.axis), batch))


@functools.lru_cache(maxsize=None)
def _lookup_fn(cfg: ShardedQFilterConfig, batch: int):
    core = cfg.core
    return jax.jit(sf.make_lookup(core, _mesh(cfg.n_shards, cfg.axis), batch))


def _pad_batch(cfg, keys):
    """Pad to a multiple of n_shards (all_to_all needs equal splits)."""
    pad = (-keys.shape[0]) % cfg.n_shards
    if pad:
        keys = jnp.concatenate([keys, keys[:1].repeat(pad)])
    return keys, pad


def make(**spec):
    cfg = ShardedQFilterConfig(**spec)
    if cfg.n_shards & (cfg.n_shards - 1):
        raise ValueError("n_shards must be a power of two")
    if len(jax.devices()) % cfg.n_shards:
        raise ValueError(
            f"n_shards={cfg.n_shards} does not divide {len(jax.devices())} devices"
        )
    # each device materializes only its own shard
    state = jax.jit(lambda: sf.empty(cfg.core), out_shardings=_on_mesh(cfg))()
    return cfg, state


def insert(cfg: ShardedQFilterConfig, state, keys, k=None):
    if k is not None:
        raise NotImplementedError("sharded_qf insert does not take a valid count")
    if keys.shape[0] % cfg.n_shards:
        # padding would insert duplicate fingerprints (QF is a multiset)
        raise ValueError(
            f"insert batch ({keys.shape[0]}) must be a multiple of n_shards"
        )
    return _insert_fn(cfg, keys.shape[0])(state, keys)


def contains(cfg: ShardedQFilterConfig, state, keys):
    keys, pad = _pad_batch(cfg, keys)
    hit = _lookup_fn(cfg, keys.shape[0])(state, keys)
    return hit[: hit.shape[0] - pad] if pad else hit


def merge(cfg: ShardedQFilterConfig, sa, sb):
    local = cfg.core.local_cfg
    return jax.vmap(lambda a, b: qf.merge(local, local, local, a, b))(sa, sb)


def needs_resize(cfg: ShardedQFilterConfig, state):
    """Device predicate: global count at the paper's max-load point."""
    return jnp.sum(state.n) >= jnp.int32(cfg.core.local_cfg.capacity * cfg.n_shards)


def grow(cfg: ShardedQFilterConfig, state):
    """Per-shard growth: every shard steals one remainder bit, doubling
    the global bucket count while the quotient-prefix shard map is
    untouched (the owner bits are the *top* bits of the quotient).

    The stored local remainders are the global ``r`` real bits (the
    local config only declares the wider ``r + shard_bits`` slot so the
    shard id stays reconstructable), so the requotient must move the
    top bit of the *r-bit* remainder — the width-true split below, not
    ``local_cfg.r``.
    """
    if cfg.r <= 1:
        raise ValueError(
            f"cannot grow: fingerprint bits exhausted (q={cfg.q}, r={cfg.r})"
        )
    new_cfg = cfg._replace(q=cfg.q + 1, r=cfg.r - 1)
    lold, lnew = cfg.core.local_cfg, new_cfg.core.local_cfg
    win = lold._replace(r=cfg.r)
    wout = lnew._replace(r=cfg.r - 1)
    pad = lnew.total_slots - lold.total_slots

    def one(s):
        qs, rs, n = qf.extract(lold, s)
        qs, rs = qf._requotient(qs, rs, win, wout)
        qs = jnp.concatenate([qs, jnp.full((pad,), qf.INT32_MAX, jnp.int32)])
        rs = jnp.concatenate([rs, jnp.full((pad,), qf.UINT32_MAX, jnp.uint32)])
        new = qf.build_sorted(lnew, qs, rs, n)
        return new._replace(overflow=new.overflow | s.overflow)

    return new_cfg, jax.vmap(one)(state)


def resize(cfg: ShardedQFilterConfig, state, new_q: int):
    """Grow to ``new_q`` global quotient bits (shrinking the *table*
    would need per-slot re-merging across every shard; capacity comes
    back down by consolidating shards instead — see :func:`shrink`)."""
    if new_q < cfg.q:
        raise NotImplementedError(
            "sharded_qf tables only grow (new_q >= q); use shrink() to "
            "consolidate shards when load is low"
        )
    while cfg.q < new_q:
        cfg, state = grow(cfg, state)
    return cfg, state


def _can_halve(cfg: ShardedQFilterConfig) -> bool:
    # halving merges shard pairs AND re-merges one quotient bit into the
    # remainder (the inverse of grow): it needs an even pair count, a
    # surviving local table, and remainder headroom for the returned bit
    return (
        cfg.n_shards >= 2
        and cfg.n_shards % 2 == 0
        and cfg.q - cfg.core.shard_bits >= 2
        and cfg.r + cfg.core.shard_bits <= 32  # declared local width holds
    )


def needs_shrink(cfg: ShardedQFilterConfig, state):
    """Device predicate: the population fits the halved filter (half
    the shards AND half the global buckets) at the low watermark.

    Each shrink halves global capacity, so the threshold halves with
    it — real hysteresis: one quiet period consolidates one step, not
    the whole fleet, and the count must double again before the high
    watermark can trip."""
    if not _can_halve(cfg):
        return jnp.zeros((), jnp.bool_)
    halved = cfg._replace(q=cfg.q - 1, r=cfg.r + 1, n_shards=cfg.n_shards // 2)
    cap = halved.core.local_cfg.capacity * halved.n_shards
    return jnp.sum(state.n) <= jnp.int32(cfg.shrink_load * cap)


def shrink(cfg: ShardedQFilterConfig, state):
    """Halve the filter: shard pairs redistribute and a quotient bit
    re-merges into the remainder — the exact inverse of ``grow``.

    Dropping the global quotient's low bit sends it to the remainder
    top (paper §3 resizing, run downward), and dropping one owner bit
    hands shards ``2s`` and ``2s + 1`` to the new shard ``s``: after a
    per-shard width-true requotient the owner parity becomes the local
    top bit, so every entry of shard ``2s + 1`` lands exactly one
    half-table above shard ``2s``'s entries.  Both inputs are sorted
    streams with all of ``2s``'s quotients preceding ``2s + 1``'s
    offset quotients, so the redistribution is one sort-free two-stream
    merge + rebuild per pair — the same streaming pass schedule as
    every other structural op in this repo.  The local table geometry
    is unchanged; only the stacked leading dim halves.
    """
    if not _can_halve(cfg):
        raise ValueError(
            f"cannot halve q={cfg.q}, r={cfg.r}, n_shards={cfg.n_shards}"
        )
    new_cfg = cfg._replace(q=cfg.q - 1, r=cfg.r + 1, n_shards=cfg.n_shards // 2)
    lold, lnew = cfg.core.local_cfg, new_cfg.core.local_cfg
    # same local geometry before and after: one quotient bit moves from
    # the local table to the remainder while one owner bit moves back in
    assert (lnew.q, lnew.r) == (lold.q, lold.r)
    # width-true split: stored remainders carry the global r bits only
    win = lold._replace(r=cfg.r)
    wout = win._replace(q=lold.q - 1, r=cfg.r + 1)
    half = 1 << wout.q  # odd shards' entries take the upper half

    def one(pair):
        even = jax.tree.map(lambda x: x[0], pair)
        odd = jax.tree.map(lambda x: x[1], pair)
        qe, re_, ne = qf.extract(lold, even)
        qo, ro, no = qf.extract(lold, odd)
        qe, re_ = qf._requotient(qe, re_, win, wout)
        qo, ro = qf._requotient(qo, ro, win, wout)
        qo = jnp.where(qo == qf.INT32_MAX, qf.INT32_MAX, qo + half)
        allq, allr = qf.merge_streams(qe, re_, ne, qo, ro, no)
        new = qf.build_sorted(lnew, allq, allr, ne + no)
        return new._replace(overflow=new.overflow | even.overflow | odd.overflow)

    paired = jax.tree.map(
        lambda x: x.reshape(new_cfg.n_shards, 2, *x.shape[1:]), state
    )
    merged = jax.vmap(one)(paired)
    # the result leaves inherit the old (wider) device placement; commit
    # them onto the halved mesh so the shard_map'd step functions see a
    # consistent layout
    sharding = _on_mesh(new_cfg)
    return new_cfg, jax.tree.map(lambda x: jax.device_put(x, sharding), merged)


def stats(cfg: ShardedQFilterConfig, state):
    return {
        "n": jnp.sum(state.n),
        "shard_counts": state.n,
        "load": jnp.sum(state.n).astype(jnp.float32) / (1 << cfg.q),
        "overflow": jnp.any(state.overflow),
        "size_bytes": cfg.n_shards * cfg.core.local_cfg.size_bytes,
    }


IMPL = register(
    FilterImpl(
        name="sharded_qf",
        paper_section="§6 (future work: multi-device AMQ, quotient-prefix sharded)",
        cfg_cls=ShardedQFilterConfig,
        make=make,
        insert=insert,
        contains=contains,
        stats=stats,
        merge=merge,
        needs_resize=needs_resize,
        grow=grow,
        resize=resize,
        needs_shrink=needs_shrink,
        shrink=shrink,
    )
)
