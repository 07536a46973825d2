"""Mesh-sharded quotient filter (the paper's §6 multi-disk future work,
realised as a multi-chip distributed AMQ).

The fingerprint space is partitioned by quotient prefix: shard
``s = f_q >> (q - log2(n_shards))`` owns bucket range
``[s·m/n, (s+1)·m/n)``.  Inserts and lookups route keys to their owner
via a fixed-capacity all_to_all (the MoE-dispatch pattern), then run
the *local* bulk QF ops from quotient_filter.py unchanged — locality is
preserved because a shard's keys form one contiguous quotient range.

Built on shard_map so the collective schedule is explicit (one
all_to_all each way); lowers/compiles on the production mesh in the
dry-run (see tests/test_distributed.py for the 8-device functional run).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import quotient_filter as qf


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def _dispatch_capacity(cfg: "ShardedQFConfig", per_shard: int) -> int:
    """Per-(src, dst) bucket capacity for the fixed-size all_to_all.

    A source shard holding ``per_shard`` keys routes ~per_shard/n_shards
    to each owner; sizing is mean + capacity_factor standard deviations
    of the Binomial(per_shard, 1/n) tail (ceil, min 8, multiple of 8) so
    skewed routing does not silently drop keys.
    """
    mean = per_shard / cfg.n_shards
    std = math.sqrt(per_shard * (1 / cfg.n_shards) * (1 - 1 / cfg.n_shards))
    capacity = int(math.ceil(mean + max(6.0, cfg.capacity_factor) * std))
    capacity = max(8, capacity)
    return capacity + (-capacity) % 8


class ShardedQFConfig(NamedTuple):
    q: int  # global log2 buckets
    r: int
    n_shards: int
    axis: str = "data"
    seed: int = 0
    capacity_factor: float = 2.0

    @property
    def shard_bits(self) -> int:
        return int(math.log2(self.n_shards))

    @property
    def local_cfg(self) -> qf.QFConfig:
        return qf.QFConfig(
            q=self.q - self.shard_bits, r=self.r + self.shard_bits, seed=self.seed
        )
        # note: local remainder keeps full fingerprint width so the
        # shard id + local (q, r) reconstruct the global fingerprint


def empty(cfg: ShardedQFConfig) -> qf.QFState:
    """Stacked per-shard states, leading dim = n_shards."""
    local = qf.empty(cfg.local_cfg)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.n_shards,) + x.shape), local
    )


def _route(cfg: ShardedQFConfig, keys: jnp.ndarray, valid: jnp.ndarray):
    """Owner shard + local fingerprint for each key."""
    fq, fr = qf.fingerprint(keys, cfg.q, cfg.r, cfg.seed)
    owner = (fq >> (cfg.q - cfg.shard_bits)).astype(jnp.int32)
    # local quotient drops the shard prefix; remainder keeps width
    local_q = fq & ((1 << (cfg.q - cfg.shard_bits)) - 1)
    return jnp.where(valid, owner, -1), local_q, fr


def _dispatch(owner, payload, n_shards: int, capacity: int):
    """Bucket payload rows by owner with per-shard capacity (drop excess).

    Returns (buckets (n_shards, capacity, ...), valid (n_shards, capacity)).
    """
    B = owner.shape[0]
    order = jnp.argsort(owner)  # invalid (-1) sort first
    so = owner[order]
    start = jnp.searchsorted(so, jnp.arange(n_shards, dtype=jnp.int32))
    rank = jnp.arange(B, dtype=jnp.int32) - start[jnp.clip(so, 0, n_shards - 1)]
    keep = (so >= 0) & (rank < capacity)
    slot = jnp.where(keep, so * capacity + rank, jnp.int32(2**31 - 1))

    def scat(x):
        return (
            jnp.zeros((n_shards * capacity,) + x.shape[1:], x.dtype)
            .at[slot]
            .set(x[order], mode="drop")
            .reshape(n_shards, capacity, *x.shape[1:])
        )

    bucket_valid = (
        jnp.zeros((n_shards * capacity,), jnp.bool_)
        .at[slot]
        .set(keep, mode="drop")
        .reshape(n_shards, capacity)
    )
    return jax.tree.map(scat, payload), bucket_valid, order, slot


def make_insert(cfg: ShardedQFConfig, mesh: Mesh, batch: int):
    """Builds a jittable sharded bulk-insert: (state, keys) -> state.

    keys arrive sharded over the axis (batch/n_shards per shard); each
    shard buckets ITS OWN keys by owner (local sort), one all_to_all
    delivers every bucket to its owner, and the local bulk QF insert
    runs unchanged.  Exactly the MoE-dispatch collective schedule.
    """
    per_shard = batch // cfg.n_shards
    capacity = _dispatch_capacity(cfg, per_shard)
    local = cfg.local_cfg
    axis = cfg.axis

    def mapped(st, keys_local):
        keys_local = keys_local.reshape(-1)  # (per_shard,)
        valid = jnp.ones(keys_local.shape, jnp.bool_)
        owner, lq, fr = _route(cfg, keys_local, valid)
        (bq, bfr), bvalid, _, _ = _dispatch(
            owner, (lq, fr), cfg.n_shards, capacity
        )
        # (n_dst, cap) -> exchange -> (n_src, cap) rows owned by me
        bq = jax.lax.all_to_all(bq, axis, 0, 0, tiled=True)
        bfr = jax.lax.all_to_all(bfr, axis, 0, 0, tiled=True)
        bvalid = jax.lax.all_to_all(bvalid, axis, 0, 0, tiled=True)
        q_flat, r_flat, v_flat = bq.reshape(-1), bfr.reshape(-1), bvalid.reshape(-1)
        st0 = jax.tree.map(lambda x: x[0], st)
        new = qf.insert_batch(local, st0, q_flat, r_flat, v_flat)
        return jax.tree.map(lambda x: x[None], new)

    def insert(state, keys):
        return _shard_map(
            mapped, mesh, in_specs=(P(axis), P(axis)), out_specs=P(axis)
        )(state, keys)

    return insert


def make_lookup(cfg: ShardedQFConfig, mesh: Mesh, batch: int):
    """Builds a jittable sharded lookup: (state, keys) -> present (B,)."""
    per_shard = batch // cfg.n_shards
    capacity = _dispatch_capacity(cfg, per_shard)
    local = cfg.local_cfg
    axis = cfg.axis

    def mapped(st, keys_local):
        keys_local = keys_local.reshape(-1)
        valid = jnp.ones(keys_local.shape, jnp.bool_)
        owner, lq, fr = _route(cfg, keys_local, valid)
        (bq, bfr), bvalid, order, slot = _dispatch(
            owner, (lq, fr), cfg.n_shards, capacity
        )
        bq = jax.lax.all_to_all(bq, axis, 0, 0, tiled=True)
        bfr = jax.lax.all_to_all(bfr, axis, 0, 0, tiled=True)
        st0 = jax.tree.map(lambda x: x[0], st)
        hit = qf.lookup(local, st0, bq.reshape(-1), bfr.reshape(-1))
        # answers travel back to the requesting shard
        hit = jax.lax.all_to_all(
            hit.reshape(cfg.n_shards, capacity), axis, 0, 0, tiled=True
        )
        flat = hit.reshape(-1)
        out_sorted = jnp.where(
            slot < flat.shape[0], flat[jnp.clip(slot, 0, flat.shape[0] - 1)], False
        )
        out = jnp.zeros((per_shard,), jnp.bool_).at[order].set(out_sorted)
        return out

    def lookup(state, keys):
        return _shard_map(
            mapped, mesh, in_specs=(P(axis), P(axis)), out_specs=P(axis)
        )(state, keys)

    return lookup
