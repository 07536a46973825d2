"""Quotient filter under the functional protocol (paper §3).

Thin functional adapter over :mod:`repro.core.quotient_filter` with a
``backend`` spec field: ``"reference"`` uses the pure-jnp bulk ops,
``"pallas"`` routes the bandwidth-bound build/probe passes through the
mode-dispatched kernel layer in :mod:`repro.kernels.ops` (Mosaic on
real TPUs, a bit-exact kernel-equivalent XLA lowering on CPU/GPU — see
``kernels.dispatch``).  Deletes always use the reference build — they
are off the hot path and the kernel wrapper only accelerates
build/probe.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import quotient_filter as qf
from repro.kernels import ops as kops

from .registry import FilterImpl, register

BACKENDS = ("reference", "pallas")


class QFilterConfig(NamedTuple):
    q: int
    r: int
    slack: int = 1024
    seed: int = 0
    max_load: float = 0.75
    backend: str = "reference"
    window: int = 256  # reference lookup window (see qf.lookup)
    # low watermark: shrink only once the count fits the HALVED table at
    # this fraction of its design capacity (hysteresis vs needs_resize)
    shrink_load: float = 0.4

    @property
    def core(self) -> qf.QFConfig:
        return qf.QFConfig(
            q=self.q,
            r=self.r,
            slack=self.slack,
            seed=self.seed,
            max_load=self.max_load,
        )


def _check_backend(cfg) -> None:
    if cfg.backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {cfg.backend!r}")
    # widest remainder across levels: flat QF carries r, the layered
    # configs (buffered/cascade) derive it from p and the smallest q
    max_r = cfg.r if hasattr(cfg, "r") else cfg.p - cfg.ram_q
    if cfg.backend == "pallas" and max_r > 31:
        raise ValueError("pallas backend packs remainders in int32 lanes (r <= 31)")


def valid_mask(keys, k) -> jnp.ndarray:
    """bool[B] marking the first ``k`` rows valid (all rows if k is None)."""
    if k is None:
        return jnp.ones(keys.shape[0], jnp.bool_)
    return jnp.arange(keys.shape[0]) < jnp.asarray(k, jnp.int32)


def insert_fingerprints(
    core: qf.QFConfig, backend: str, state: qf.QFState, fq, fr, valid
) -> qf.QFState:
    """Merge a validity-masked fingerprint batch into ``state``."""
    k = jnp.sum(valid, dtype=jnp.int32)
    build = kops.build_sorted if backend == "pallas" else qf.build_sorted
    return qf.merge_sorted_with(core, state, fq, fr, k, build, batch_valid=valid)


def insert_keys(
    core: qf.QFConfig, backend: str, state: qf.QFState, keys, k=None
) -> qf.QFState:
    fq, fr = qf.fingerprints(core, keys)
    return insert_fingerprints(core, backend, state, fq, fr, valid_mask(keys, k))


def contains_keys(core: qf.QFConfig, backend: str, state, keys, window=256):
    if backend == "pallas":
        return kops.contains(core, state, keys)
    return qf.contains(core, state, keys, window)


def delete_masked(core: qf.QFConfig, state: qf.QFState, fq, fr, mask) -> qf.QFState:
    """Delete one copy of each fingerprint where ``mask`` is set."""
    fq, fr = qf._pad_sort(fq, fr, mask)
    return qf.delete_sorted(core, state, fq, fr, jnp.sum(mask, dtype=jnp.int32))


def batch_occurrence_rank(fq, fr, valid) -> jnp.ndarray:
    """0-based rank of each batch row among equal valid fingerprints.

    Used by the layered deletes (buffered/cascade) to route the j-th
    duplicate of a key to the j-th structure that still holds a copy.
    Equality of (fq, fr) is equality of the full p-bit fingerprint, so
    ranks computed under any (q, r) split agree.
    """
    B = fq.shape[0]
    idx = jnp.arange(B, dtype=jnp.int32)
    sq = jnp.where(valid, fq, qf.INT32_MAX)
    sr = jnp.where(valid, fr, qf.UINT32_MAX)
    sq_s, sr_s, idx_s = jax.lax.sort((sq, sr, idx), num_keys=2)
    first = qf.lex_searchsorted(sq_s, sr_s, sq_s, sr_s, "left")
    rank_s = idx - first  # position within the run of equal fingerprints
    return jnp.zeros((B,), jnp.int32).at[idx_s].set(rank_s)


def multiplicity(core: qf.QFConfig, state: qf.QFState, fq, fr) -> jnp.ndarray:
    """How many copies of each queried fingerprint the filter holds."""
    qs, rs, _ = qf.extract(core, state)
    lo = qf.lex_searchsorted(qs, rs, fq, fr, "left")
    hi = qf.lex_searchsorted(qs, rs, fq, fr, "right")
    return (hi - lo).astype(jnp.int32)


# -- protocol bindings -------------------------------------------------------


def make(**spec):
    cfg = QFilterConfig(**spec)
    _check_backend(cfg)
    return cfg, qf.empty(cfg.core)


def insert(cfg: QFilterConfig, state, keys, k=None):
    return insert_keys(cfg.core, cfg.backend, state, keys, k)


def contains(cfg: QFilterConfig, state, keys):
    return contains_keys(cfg.core, cfg.backend, state, keys, cfg.window)


def delete(cfg: QFilterConfig, state, keys, k=None):
    core = cfg.core
    fq, fr = qf.fingerprints(core, keys)
    return delete_masked(core, state, fq, fr, valid_mask(keys, k))


def merge(cfg: QFilterConfig, sa, sb):
    core = cfg.core
    return qf.merge(core, core, core, sa, sb)


def build_fn(cfg):
    """The bulk rebuild pass for this config's backend (reference jnp
    scatter vs the Pallas ``qf_build_planes`` kernel)."""
    return kops.build_sorted if cfg.backend == "pallas" else qf.build_sorted


def needs_resize(cfg: QFilterConfig, state):
    """Device predicate: at/over the paper's max-load operating point."""
    return state.n >= jnp.int32(cfg.core.capacity)


def resize(cfg: QFilterConfig, state, new_q: int):
    """Re-split the p-bit fingerprints at ``new_q`` (paper §3 'Resizing').

    Host-level structural op: the slot planes change shape.  The
    requotient+rebuild pass is one streaming device pass, routed through
    the Pallas build kernel when ``backend="pallas"``.
    """
    new_r = cfg.q + cfg.r - new_q
    if not (1 <= new_q <= 30 and 1 <= new_r):
        raise ValueError(
            f"cannot re-split p={cfg.q + cfg.r} fingerprint bits at q={new_q}"
        )
    core_new, st = qf.resize(cfg.core, state, new_q, build=build_fn(cfg))
    del core_new  # same fields as cfg.core with the new (q, r) split
    return cfg._replace(q=new_q, r=new_r), st


def grow(cfg: QFilterConfig, state):
    """One doubling step: steal one remainder bit for the quotient."""
    return resize(cfg, state, cfg.q + 1)


def _can_halve(cfg: QFilterConfig) -> bool:
    # shrinking re-merges a remainder bit: r widens by one, which must
    # stay inside the uint32 remainder plane (31 bits under pallas)
    max_r = 31 if cfg.backend == "pallas" else 32
    return cfg.q > 1 and cfg.r + 1 <= max_r


def needs_shrink(cfg: QFilterConfig, state):
    """Device predicate: the population fits the halved table at the
    low watermark (``shrink_load`` of its capacity) — the hysteresis
    band keeping grow/shrink from thrashing."""
    if not _can_halve(cfg):
        return jnp.zeros((), jnp.bool_)
    halved = cfg.core._replace(q=cfg.q - 1, r=cfg.r + 1)
    return state.n <= jnp.int32(cfg.shrink_load * halved.capacity)


def shrink(cfg: QFilterConfig, state):
    """One halving step: re-merge a quotient bit into the remainder
    (paper §3 resizing, run downward — the fp rate *improves*)."""
    if not _can_halve(cfg):
        raise ValueError(f"cannot shrink q={cfg.q}, r={cfg.r} further")
    return resize(cfg, state, cfg.q - 1)


def stats(cfg: QFilterConfig, state):
    return {
        "n": state.n,
        "load": qf.load(cfg.core, state),
        "overflow": state.overflow,
        "size_bytes": cfg.core.size_bytes,
    }


IMPL = register(
    FilterImpl(
        name="qf",
        paper_section="§3 (quotient filter: insert/may-contain/delete/merge/resize)",
        cfg_cls=QFilterConfig,
        make=make,
        insert=insert,
        contains=contains,
        stats=stats,
        delete=delete,
        merge=merge,
        needs_resize=needs_resize,
        grow=grow,
        resize=resize,
        needs_shrink=needs_shrink,
        shrink=shrink,
    )
)
