"""Kernel backend/mode resolution + shared window-prefetch helpers.

Every op in :mod:`repro.kernels.ops` has up to three lowerings:

* ``"mosaic"``    — the Pallas kernel compiled for TPU (``interpret=False``).
* ``"interpret"`` — the same Pallas kernel run by the Pallas interpreter.
  This is a *validation* mode: it executes the exact kernel body
  (one-hot gathers, windowed tiles) but pays a sequential grid loop and
  block-copy overhead, so it is never a production path and benchmarks
  must not present it as one (pre-PR-7 they did, which is where the
  committed "pallas loses to reference by 8x" rows came from).
* ``"xla"``       — a kernel-equivalent jnp lowering: the same algorithm
  (shared decode, window math, exact-fallback semantics) expressed as
  plain XLA ops, minus the hardware tiling that only a real TPU
  rewards.  Bit-identical results to the kernel path.

``resolve`` picks the deployed mode: Mosaic on TPU, the XLA lowering
everywhere else (the perf gate's ``kernelratio_*`` rows hold its CPU
cost at or under 1.10x the reference).  The ``REPRO_KERNEL_MODE``
environment variable forces a mode globally (tests use it to pin the
interpreter); per-call ``mode=``/legacy ``interpret=`` arguments
override everything.

Kernel layout: planes are lane-dense ``(rows, 128)`` int32 arrays and
query tiles ``(1, T)`` lane rows, the shapes the TPU compiler's
(8, 128) tiling accepts (``plane_blocks``, ``window_spec``,
``query_tiles``, ``query_spec``).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import lanes

MODES = ("mosaic", "interpret", "xla")

_ENV_VAR = "REPRO_KERNEL_MODE"


def default_mode() -> str:
    """Deployed mode for this process: env override, else by platform."""
    env = os.environ.get(_ENV_VAR)
    if env:
        if env not in MODES:
            raise ValueError(f"{_ENV_VAR} must be one of {MODES}, got {env!r}")
        return env
    return "mosaic" if jax.default_backend() == "tpu" else "xla"


def resolve(mode: str | None = None, interpret: bool | None = None) -> str:
    """Resolve a per-call mode override (``interpret`` is the legacy bool)."""
    if mode is not None:
        if mode not in MODES:
            raise ValueError(f"kernel mode must be one of {MODES}, got {mode!r}")
        return mode
    if interpret is not None:
        return "interpret" if interpret else "mosaic"
    return default_mode()


def is_pallas(mode: str) -> bool:
    """Does this mode execute the Pallas kernel body (vs the jnp lowering)?"""
    return mode in ("mosaic", "interpret")


def pallas_interpret(mode: str) -> bool:
    """The ``interpret=`` kwarg for ``pl.pallas_call`` under this mode."""
    return mode == "interpret"


# ---------------------------------------------------------------------------
# Shared window-prefetch geometry (used by qf_probe / fuse_probe /
# bloom_block wrappers)
# ---------------------------------------------------------------------------


def sorted_tile_order(sort_key: jnp.ndarray, tile_t: int) -> jnp.ndarray:
    """Permutation gathering queries into ascending ``tile_t``-tiles.

    Pads by duplicating the last (largest) element so sortedness — the
    invariant every window kernel relies on — survives the padding.
    """
    iota = jnp.arange(sort_key.shape[0], dtype=jnp.int32)
    # (key, index) pairs are distinct: the stable argsort order, from an
    # unstable sort that compiles far faster on TPU
    _, order = jax.lax.sort((sort_key, iota), num_keys=2, is_stable=False)
    pad = (-sort_key.shape[0]) % tile_t
    if pad:
        order = jnp.concatenate([order, jnp.full((pad,), order[-1])])
    return order


def unpermute(order: jnp.ndarray, n: int, *tiled):
    """Scatter per-query kernel outputs from tile order back to query
    order, all in one scatter.  The padding copies of the last query sit
    in the same (last) tile as the query itself, so every copy carries
    the same values and it does not matter which one lands."""
    rows = jnp.stack([x.astype(jnp.int32) for x in tiled], axis=1)
    out = jnp.zeros((n, len(tiled)), jnp.int32).at[order].set(rows, mode="drop")
    return tuple(out[:, i] for i in range(len(tiled)))


def plane_blocks(plane: jnp.ndarray, wblk: int) -> jnp.ndarray:
    """Pad a 1-D plane to whole ``wblk``-slot blocks plus one zero block,
    laid out lane-dense as ``(nbw * wblk // 128, 128)`` int32.

    A window block is then ``(wblk // 128, 128)`` rows — a tile the TPU
    compiler accepts when ``wblk`` is a multiple of 1024.  The extra
    block lets clipped window bases (``blk + 1``) stay in range without
    wrapping into live data.
    """
    _rows(wblk)
    total = plane.shape[0]
    nbw = -(-total // wblk) + 1
    pad = nbw * wblk - total
    return jnp.concatenate(
        [plane.astype(jnp.int32), jnp.zeros((pad,), jnp.int32)]
    ).reshape(-1, lanes.LANES)


def _rows(wblk: int) -> int:
    if wblk <= 0 or wblk % lanes.LANES:
        raise ValueError(f"block of {wblk} slots is not a multiple of {lanes.LANES}")
    return wblk // lanes.LANES


def meta_plane(occ, shf, con) -> jnp.ndarray:
    """Pack the three QF metadata planes into one int32 plane:
    bit 0 is_occupied, bit 1 is_shifted, bit 2 is_continuation."""
    i32 = lambda x: x.astype(jnp.int32)
    return (i32(occ) & 1) | ((i32(shf) & 1) << 1) | ((i32(con) & 1) << 2)


def window_spec(wblk: int, off: int, blk_arg: int = 0):
    """BlockSpec of window block ``blk + off`` over a ``plane_blocks``
    plane; ``blk`` is scalar-prefetch argument ``blk_arg``."""
    return pl.BlockSpec(
        (_rows(wblk), lanes.LANES), lambda t, *s: (s[blk_arg][t] + off, 0)
    )


def query_tiles(x: jnp.ndarray, tile_t: int) -> jnp.ndarray:
    """``(B,)`` -> ``(B // tile_t, 1, tile_t)`` int32 query tiles: each
    grid step reads one ``(1, tile_t)`` lane row, whose block equals the
    array's last two dims (a tile shape the TPU compiler accepts)."""
    return x.astype(jnp.int32).reshape(-1, 1, tile_t)


def query_spec(tile_t: int):
    """BlockSpec of query tile ``t`` over a ``query_tiles`` array."""
    return pl.BlockSpec((1, 1, tile_t), lambda t, *s: (t, 0, 0))


# Scalar-prefetch operands live in SMEM (1 MiB on a v5e); a grid whose
# per-tile scalars would outgrow this many words is split into launches.
MAX_PREFETCH_WORDS = 1 << 16


def launches(n_tiles: int, words_per_tile: int):
    """``(start, size)`` tile ranges, one per ``pallas_call`` launch, so
    that no launch prefetches more than ``MAX_PREFETCH_WORDS`` scalars."""
    step = max(1, MAX_PREFETCH_WORDS // words_per_tile)
    return [(s, min(step, n_tiles - s)) for s in range(0, n_tiles, step)]


def concat_launches(parts):
    """Join per-launch output lists along the tile axis."""
    return [jnp.concatenate(p, axis=0) if len(p) > 1 else p[0] for p in zip(*parts)]


def tile_base(first_ref, out_ref):
    """Absolute first slot of this program's output tile: the launch's
    first tile (scalar ``first_ref[0]``) plus the grid position."""
    return (first_ref[0] + pl.program_id(0)) * out_ref.shape[0] * out_ref.shape[1]


def launch_tiles(kernel, blk, items, S, n_out, interpret, s, n):
    """One launch of a scatter grid over output tiles ``[s, s + n)``.

    ``items`` are ``(rows, 128)`` item planes, each passed as the two
    consecutive ``S``-item blocks from ``blk[t]``; the kernel writes
    ``n_out`` lane-dense ``(S // 128, 128)`` int32 output tiles.  Its
    scalar refs are ``blk`` and the launch's first tile ``s``.
    """
    win = lambda off: window_spec(S, off)
    out = pl.BlockSpec((S // lanes.LANES, lanes.LANES), lambda t, *_: (t, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[win(off) for _ in items for off in (0, 1)],
        out_specs=[out] * n_out,
    )
    plane = jax.ShapeDtypeStruct((n * S // lanes.LANES, lanes.LANES), jnp.int32)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=[plane] * n_out, interpret=interpret
    )(
        blk[s : s + n],
        jnp.full((1,), s, jnp.int32),
        *(x for x in items for _ in (0, 1)),
    )


def window_base(
    min_pos: jnp.ndarray,
    max_pos: jnp.ndarray,
    total: int,
    wblk: int,
    margin: int = 0,
):
    """Per-tile aligned window start + residency check.

    Returns ``(blk, wbase, fits)``: the tile reads blocks ``blk`` and
    ``blk + 1`` (a ``2 * wblk`` window at ``wbase``); ``fits`` is False
    when ``[min_pos - margin, max_pos + margin]`` outruns the window
    (the caller resolves those tiles on its exact path).
    """
    nbw = -(-total // wblk) + 1
    blk = jnp.clip((min_pos - margin) // wblk, 0, nbw - 2).astype(jnp.int32)
    wbase = blk * wblk
    fits = (max_pos - wbase) < (2 * wblk - margin)
    return blk, wbase, fits
