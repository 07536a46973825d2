"""Filter registry: one functional protocol, many AMQ implementations.

Every implementation registers a :class:`FilterImpl` record binding its
static config class (a hashable NamedTuple — jit-static) to the
protocol's operations.  The façade functions in ``repro.filters``
dispatch on ``type(cfg)``, so call sites hold an opaque ``(cfg, state)``
pair and never name a concrete filter class.

Protocol (all ops pure; states are pytrees; every op is jittable):

    make(**spec)                  -> (cfg, state)
    insert(cfg, state, keys, k)   -> state
    contains(cfg, state, keys)    -> bool[B]
    contains_stats(cfg, state, keys) -> (bool[B], dict)  (optional)
    delete(cfg, state, keys, k)   -> state          (optional)
    merge(cfg, state_a, state_b)  -> state          (optional)
    probe(cfg, state, keys)       -> (state, bool[B])  # contains + I/O accounting
    stats(cfg, state)             -> dict[str, scalar]
    needs_resize(cfg, state)      -> bool[]         (optional, jittable)
    grow(cfg, state)              -> (cfg, state)   (optional, host-level)
    resize(cfg, state, **kw)      -> (cfg, state)   (optional, host-level)
    needs_shrink(cfg, state)      -> bool[]         (optional, jittable)
    shrink(cfg, state)            -> (cfg, state)   (optional, host-level)

``k`` is an optional valid-prefix count so fixed-shape (padded) batches
can carry a dynamic number of real keys through ``lax.scan``.

Resize changes array shapes, so it cannot live under ``jit`` — the
protocol splits it into a jit-friendly device predicate
(``needs_resize``) and host-level structural steps: ``grow`` is the
canonical one-step doubling (guaranteed to clear ``needs_resize``
eventually), ``resize`` takes per-family keyword targets (``new_q`` for
the QF families, ``levels``/``fanout`` for the cascade, ``factor`` for
the Bloom family).  ``needs_shrink``/``shrink`` are the mirror image:
a low-watermark device predicate plus the host-level halving step (qf
re-merges a fingerprint bit, buffered re-streams the disk QF one bit
narrower, cascade pops empty levels, sharded redistributes into half
the shards, bloom folds its cell tiling).  The façade's ``auto_grow``
and ``auto_scale`` drivers compose them into ingest loops.

Implementations registered with ``public=False`` dispatch through the
façade by config type but do not appear in ``names()`` — used for
transient wrapper structures (e.g. the in-flight incremental-resize
migration) that callers never construct by name.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional


class UnsupportedOpError(NotImplementedError):
    """A filter family (or this particular config of it) rejects an op.

    Structured — carries ``family``/``op``/``hint`` — so callers and
    drivers (``auto_grow``, ``auto_scale``, pipelines) can branch on
    capability rather than string-match a message or, worse, catch an
    ``AttributeError`` escaping from a half-bound registry record.
    Subclasses ``NotImplementedError`` so existing capability checks
    keep working.
    """

    def __init__(self, family: str, op: str, hint: str = ""):
        self.family = family
        self.op = op
        self.hint = hint
        msg = f"filter family {family!r} does not support {op!r}"
        if hint:
            msg = f"{msg} ({hint})"
        super().__init__(msg)


class FilterImpl(NamedTuple):
    name: str
    paper_section: str
    cfg_cls: type
    make: Callable  # (**spec) -> (cfg, state)
    insert: Optional[Callable]  # (cfg, state, keys, k=None) -> state; None = frozen
    contains: Callable  # (cfg, state, keys) -> bool[B]
    stats: Callable  # (cfg, state) -> dict
    delete: Optional[Callable] = None
    merge: Optional[Callable] = None
    # contains plus the lookup path's counters (int32 device scalars)
    contains_stats: Optional[Callable] = None  # (cfg, state, keys) -> (bool[B], dict)
    probe: Optional[Callable] = None  # (cfg, state, keys) -> (state, bool[B])
    needs_resize: Optional[Callable] = None  # (cfg, state) -> bool[] (device)
    grow: Optional[Callable] = None  # (cfg, state) -> (cfg, state)
    resize: Optional[Callable] = None  # (cfg, state, **kw) -> (cfg, state)
    needs_shrink: Optional[Callable] = None  # (cfg, state) -> bool[] (device)
    shrink: Optional[Callable] = None  # (cfg, state) -> (cfg, state)
    # config-dependent capability (e.g. bloom deletes only when counting);
    # None means "delete works for every cfg of this type"
    can_delete: Optional[Callable] = None  # (cfg) -> bool
    # hint strings surfaced in UnsupportedOpError, keyed by op name
    op_hints: dict = {}

    def deletable(self, cfg=None) -> bool:
        if self.delete is None:
            return False
        if cfg is None or self.can_delete is None:
            return True
        return bool(self.can_delete(cfg))

    @property
    def supports_merge(self) -> bool:
        return self.merge is not None

    def require(self, op: str, cfg=None) -> Callable:
        """The bound op, or a structured :class:`UnsupportedOpError`.

        The façade's single dispatch point for optional ops: family-level
        absence (unbound op) and config-level refusal (``can_delete``)
        both surface as the same typed error.
        """
        fn = getattr(self, op, None)
        if fn is None or (op == "delete" and not self.deletable(cfg)):
            raise UnsupportedOpError(self.name, op, self.op_hints.get(op, ""))
        return fn


_BY_NAME: dict[str, FilterImpl] = {}
_BY_CFG: dict[type, FilterImpl] = {}
_INTERNAL: set[str] = set()


def register(impl: FilterImpl, public: bool = True) -> FilterImpl:
    if impl.name in _BY_NAME:
        raise ValueError(f"filter {impl.name!r} already registered")
    _BY_NAME[impl.name] = impl
    _BY_CFG[impl.cfg_cls] = impl
    if not public:
        _INTERNAL.add(impl.name)
    return impl


def names() -> tuple[str, ...]:
    return tuple(sorted(set(_BY_NAME) - _INTERNAL))


def by_name(name: str) -> FilterImpl:
    try:
        return _BY_NAME[name]
    except KeyError:
        raise KeyError(
            f"unknown filter {name!r}; registered: {', '.join(names())}"
        ) from None


def by_cfg(cfg) -> FilterImpl:
    try:
        return _BY_CFG[type(cfg)]
    except KeyError:
        raise TypeError(
            f"{type(cfg).__name__} is not a registered filter config"
        ) from None
