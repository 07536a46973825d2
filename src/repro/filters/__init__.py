"""``repro.filters`` — one functional AMQ API for the whole paper.

The paper's pitch is that a single family of structures covers the
RAM-to-flash spectrum with the same operations.  This package is that
pitch as an API: every filter is an opaque ``(cfg, state)`` pair where
``cfg`` is a hashable NamedTuple (jit-static) and ``state`` is a pure
pytree, and every operation is jittable with donated state — flush and
merge triggers are ``lax.cond``/``lax.switch`` on device scalars, so a
full ingest loop runs under one ``jax.jit``/``jax.lax.scan`` with zero
per-batch host syncs.

Registry name -> implementation -> paper section:

========================  =======================================================
``"qf"``                  Quotient filter (§3): the in-RAM structure; insert,
                          may-contain, delete, merge, all bulk-parallel.
``"bloom"``               Bloom filter baseline (§2); ``counting=True`` gives the
                          counting variant [3] with delete + additive merge.
``"blocked_bloom"``       Hash-localized Bloom filter (§2, buffered BF of Canim
                          et al.): all k probes in one block/page.
``"buffered_qf"``         Buffered quotient filter (§4): RAM QF buffer flushed
                          into a large flash QF by one streaming merge.
``"cascade"``             Cascade filter (§4): COLA-style geometric hierarchy of
                          QFs, insert-optimized; fixed-depth level stack.
``"sharded_qf"``          Multi-device QF (§6 future work): quotient-prefix
                          sharding + all_to_all dispatch on a device mesh.
``"steady_qf"``           Steady-state QF (§4 RAM buffer, always-on): O(buffer)
                          inserts + background settle ticks — LSM-style.
========================  =======================================================

Quickstart::

    from repro import filters

    cfg, state = filters.make("qf", q=16, r=12)
    state = filters.insert(cfg, state, keys)        # jittable, donatable
    hits  = filters.contains(cfg, state, keys)      # bool[B], no false negatives
    state = filters.delete(cfg, state, keys[:100])

    # the same four verbs for every registered structure:
    cfg, state = filters.make("cascade", ram_q=12, p=28, fanout=4, levels=4)
    step = jax.jit(lambda s, ks: (filters.insert(cfg, s, ks), None))
    state, _ = jax.lax.scan(step, state, key_batches)   # zero host syncs

    # dynamic resizing (the paper's headline QF advantage): a jittable
    # device predicate plus host-level structural growth, composed by
    # the ``auto_grow`` ingest driver — start small, never overflow:
    cfg, state = filters.make("qf", q=10, r=18)
    for batch in stream:                            # unbounded stream
        cfg, state = filters.auto_grow(cfg, state, batch)

    # ...or, for long-running consumers, ``auto_scale``: growth happens
    # *incrementally* (each batch moves one bounded chunk of quotient
    # runs into the wider table — no stop-the-world re-stream; see
    # ``filters.incremental_resize``) and a low-watermark ``shrink``
    # reclaims capacity when the population falls, with hysteresis so
    # the structure never thrashes between the two:
    for batch in stream:
        cfg, state = filters.auto_scale(cfg, state, batch)

A ``backend="pallas"`` spec field on the QF-family filters routes the
bandwidth-bound build/probe passes through the Pallas TPU kernels in
``repro.kernels`` (interpret mode on CPU).  ``probe`` is ``contains``
plus the paper's modeled I/O schedule accounted into device counters
inside the state; convert with ``repro.filters.iostats.to_iolog``.
"""

from __future__ import annotations

import jax.numpy as jnp

from . import (  # noqa: F401 (registration side effects)
    bloom_filter,
    buffered,
    cascade,
    incremental_resize,
    iostats,
    qf_filter,
    sharded,
    steady,
    xor_fuse,
)
from .auto_scale import auto_scale, settle
from .iostats import IOCounters, to_iolog
from .registry import (
    FilterImpl,
    UnsupportedOpError,
    by_cfg,
    by_name,
    names,
    register,
)

# every op name ``supports`` answers for; "insert" is optional since the
# frozen (xor_fuse) family is construct-only
_OPS = frozenset(
    {
        "insert",
        "contains",
        "contains_stats",
        "delete",
        "merge",
        "probe",
        "stats",
        "needs_resize",
        "grow",
        "resize",
        "needs_shrink",
        "shrink",
    }
)


def make(name: str, **spec):
    """Construct a filter by registry name: ``make(name, **spec) -> (cfg, state)``."""
    return by_name(name).make(**spec)


def insert(cfg, state, keys, k=None):
    """Insert a key batch; ``k`` = optional valid-prefix count for padded batches.

    Frozen (construct-only) families raise :class:`UnsupportedOpError`.
    """
    return by_cfg(cfg).require("insert")(cfg, state, keys, k)


def contains(cfg, state, keys, *, with_stats=False):
    """MAY-CONTAIN for a key batch (no false negatives).

    ``with_stats=True`` returns ``(hits, stats)``: ``stats`` is a dict
    of int32 device scalars that the same program computes, counting
    how the batch was answered (``"qf"``, and ``"cascade"`` under pallas
    with a count per structure: ``queries``, ``tiles``, ``tiles_unfit``,
    ``queries_exact``, ``exact_passes``; README "Observability").  Other
    families raise :class:`UnsupportedOpError`.
    """
    impl = by_cfg(cfg)
    if with_stats:
        return impl.require("contains_stats")(cfg, state, keys)
    return impl.contains(cfg, state, keys)


def delete(cfg, state, keys, k=None):
    """Remove one copy of each key (check ``supports(cfg, "delete")``)."""
    return by_cfg(cfg).require("delete", cfg)(cfg, state, keys, k)


def merge(cfg, state_a, state_b):
    """Union two same-config filters into one state."""
    return by_cfg(cfg).require("merge")(cfg, state_a, state_b)


def probe(cfg, state, keys):
    """``contains`` + modeled I/O accounting: returns ``(state, hits)``.

    Falls back to pure ``contains`` (state unchanged) for filters whose
    state carries no I/O counters.
    """
    impl = by_cfg(cfg)
    if impl.probe is None:
        return state, impl.contains(cfg, state, keys)
    return impl.probe(cfg, state, keys)


def stats(cfg, state) -> dict:
    """Device-scalar diagnostics (count, load, overflow, I/O counters...)."""
    return by_cfg(cfg).stats(cfg, state)


def needs_resize(cfg, state):
    """Device predicate: is the filter at/over its design capacity?

    Jittable (a ``bool[]`` scalar on device) — the cheap half of the
    resize protocol, safe to evaluate every batch inside a compiled
    ingest loop.  Filters without a resize binding report a constant
    False.  The structural ``grow``/``resize`` steps themselves change
    array shapes and must run on the host (see :func:`auto_grow`).
    """
    impl = by_cfg(cfg)
    if impl.needs_resize is None:
        return jnp.zeros((), jnp.bool_)
    return impl.needs_resize(cfg, state)


def grow(cfg, state):
    """One canonical growth step: ``(cfg, state) -> (cfg, state)``.

    Doubles the structure's capacity (QF: steal one remainder bit for
    the quotient; buffered: disk QF +1 quotient bit, one re-stream;
    cascade: one deeper level; sharded: +1 bit per shard; bloom: cell
    doubling).  Host-level — array shapes change — but the data
    movement is a single streaming device pass.
    """
    return by_cfg(cfg).require("grow")(cfg, state)


def resize(cfg, state, **kw):
    """Structural resize with per-family keyword targets:
    ``resize(cfg, state, new_q=18)`` (qf / sharded_qf),
    ``resize(cfg, state, disk_q=22)`` (buffered_qf),
    ``resize(cfg, state, levels=6, fanout=4)`` (cascade),
    ``resize(cfg, state, factor=4)`` (bloom / blocked_bloom).
    Returns the new ``(cfg, state)`` pair."""
    return by_cfg(cfg).require("resize")(cfg, state, **kw)


def needs_shrink(cfg, state):
    """Device predicate: is the filter far enough under its low
    watermark that one structural halving step is safe?

    The mirror image of :func:`needs_resize` — jittable, cheap, and
    deliberately conservative: each family's predicate only fires when
    the population fits the *shrunk* structure at a comfortable margin
    (``shrink_load`` on the config), which is the hysteresis band that
    keeps ``auto_scale`` from thrashing between grow and shrink.
    Filters without a shrink binding report a constant False.
    """
    impl = by_cfg(cfg)
    if impl.needs_shrink is None:
        return jnp.zeros((), jnp.bool_)
    return impl.needs_shrink(cfg, state)


def shrink(cfg, state):
    """One canonical halving step: ``(cfg, state) -> (cfg, state)``.

    Per family: qf re-merges a quotient bit into the remainder (the fp
    rate improves), buffered_qf re-streams its disk QF one bit
    narrower, cascade pops an empty deepest level, sharded_qf
    redistributes shard pairs and halves the shard count, bloom folds
    its doubled cell tiling back together.  Host-level — shapes change.
    """
    return by_cfg(cfg).require("shrink")(cfg, state)


def auto_grow(cfg, state, keys, k=None, max_steps: int = 32):
    """Insert with automatic growth: the dynamic-resizing ingest driver.

    Checks the device predicate before and after the insert and applies
    host-level ``grow`` steps until the structure is back under its
    design load, so an unbounded stream can be ingested through a
    filter that started at any size — the paper's "a quotient filter
    can be dynamically resized" property, end-to-end.  Returns the new
    ``(cfg, state)`` pair; callers must carry both.

    Each ``needs_resize`` evaluation is one device->host sync, so this
    driver is for host-driven ingest loops (pipelines, serving); fully
    on-device ``lax.scan`` ingest keeps a static size by construction.
    Batches should stay comfortably under the structure's slack so a
    single batch cannot overshoot capacity before the post-insert check
    runs (the QF-family default slack of 1024 covers typical batches).
    """
    impl = by_cfg(cfg)
    can = impl.needs_resize is not None and impl.grow is not None

    def settle(cfg, state):
        for _ in range(max_steps):
            if not bool(impl.needs_resize(cfg, state)):
                return cfg, state
            cfg, state = impl.grow(cfg, state)
        raise RuntimeError(
            f"{impl.name}: still over capacity after {max_steps} grow steps"
        )

    if can:
        cfg, state = settle(cfg, state)
    state = impl.require("insert")(cfg, state, keys, k)
    if can:
        cfg, state = settle(cfg, state)
    return cfg, state


def supports(name_or_cfg, op: str) -> bool:
    """Does filter ``name_or_cfg`` implement optional op ``"delete"`` /
    ``"merge"`` / ``"resize"`` / ``"grow"`` / ``"needs_resize"`` /
    ``"needs_shrink"`` / ``"shrink"``?

    Passing a cfg instance gives the config-exact answer (e.g. delete on
    a plain non-counting Bloom is False); a name answers for the family.
    Unknown op names raise ``ValueError`` (they used to fall through to
    ``getattr`` and leak an ``AttributeError`` — or worse, silently
    answer False for a typo'd op).
    """
    if op not in _OPS:
        raise ValueError(
            f"unknown filter op {op!r}; known ops: {', '.join(sorted(_OPS))}"
        )
    if isinstance(name_or_cfg, str):
        return getattr(by_name(name_or_cfg), op) is not None
    impl = by_cfg(name_or_cfg)
    if op == "delete":
        return impl.deletable(name_or_cfg)
    return getattr(impl, op) is not None


__all__ = [
    "FilterImpl",
    "IOCounters",
    "UnsupportedOpError",
    "auto_grow",
    "auto_scale",
    "by_cfg",
    "by_name",
    "contains",
    "delete",
    "grow",
    "incremental_resize",
    "insert",
    "iostats",
    "make",
    "merge",
    "names",
    "needs_resize",
    "needs_shrink",
    "probe",
    "register",
    "resize",
    "settle",
    "shrink",
    "stats",
    "supports",
    "to_iolog",
]
