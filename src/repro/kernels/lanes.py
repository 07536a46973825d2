"""In-kernel vector helpers for lane-dense ``(rows, 128)`` tiles.

The TPU compiler tiles the last two dimensions of every block by
(8, 128) for 32-bit data, so the kernels keep slot planes and item
streams as ``(rows, 128)`` lane-dense arrays and query tiles as
``(1, T)`` lane rows.  The decode math needs two things Mosaic has no
primitive for — a per-query gather from a window and a prefix count
over a window — and a way to move a query row into a column so that
query values broadcast against window lanes.  Everything here is
built from iota compares, selects, lane/sublane reductions and
``pltpu.roll``: operations the Pallas TPU lowering supports, and the
interpreter runs bit-identically.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def row_to_col(row: jnp.ndarray) -> jnp.ndarray:
    """``(1, T)`` lane row -> ``(T, 1)`` column (identity-mask reduce)."""
    T = row.shape[1]
    r = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    full = jnp.broadcast_to(row, (T, T))
    return jnp.sum(jnp.where(r == c, full, 0), axis=1, keepdims=True)


def col_to_row(col: jnp.ndarray) -> jnp.ndarray:
    """``(T, 1)`` column -> ``(1, T)`` lane row (identity-mask reduce)."""
    T = col.shape[0]
    r = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    full = jnp.broadcast_to(col, (T, T))
    return jnp.sum(jnp.where(r == c, full, 0), axis=0, keepdims=True)


def flat_index(shape) -> jnp.ndarray:
    """Row-major slot index of every element of a ``(rows, 128)`` tile."""
    r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return r * shape[1] + c


def prefix_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum of an int32 ``(rows, 128)`` tile in row-major
    slot order: log-step shifted adds along the lanes, then the same
    over the per-row totals along the sublanes."""
    rows, lanes = x.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    s = 1
    while s < lanes:
        x = x + jnp.where(lane >= s, pltpu.roll(x, s, 1), 0)
        s *= 2
    if rows == 1:
        return x
    # every lane of row i holds row i's total after the broadcast
    tot = jnp.broadcast_to(x[:, lanes - 1 : lanes], x.shape)
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    inc = tot
    s = 1
    while s < rows:
        inc = inc + jnp.where(row >= s, pltpu.roll(inc, s, 0), 0)
        s *= 2
    return x + (inc - tot)


def gather(win: jnp.ndarray, rel: jnp.ndarray) -> jnp.ndarray:
    """``win[rel]`` for a ``(T, 1)`` column of row-major window slots.

    One-hot selects accumulate over the window rows into one
    ``(T, 128)`` tile (at most one lane of it is set per query), which
    is lane-reduced once; a slot outside the window gathers 0.
    """
    rows, lanes = win.shape
    T = rel.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (T, lanes), 1)
    acc = jnp.zeros((T, lanes), win.dtype)
    for i in range(rows):
        acc = acc + jnp.where(lane == (rel - i * lanes), win[i : i + 1, :], 0)
    return jnp.sum(acc, axis=1, keepdims=True)


def scatter_rows(pos, vals, out_refs, base):
    """Reduce items onto lane-dense output rows: ``out[p - base] = v``.

    ``pos`` is an ``(rows, 128)`` int32 item block, ``vals`` a list of
    item blocks of the same shape, ``out_refs`` one ``(sr, 128)`` output
    ref per value.  Items whose position falls outside the ``sr * 128``
    output slots contribute nothing; several items on one slot add up
    (callers with unique positions get a plain scatter).
    """
    sr, width = out_refs[0].shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (width, width), 1)
    acc = [[jnp.zeros((width, width), jnp.int32)] * sr for _ in out_refs]
    for k in range(pos.shape[0]):
        p = row_to_col(pos[k : k + 1, :]) - base
        cols = [row_to_col(v[k : k + 1, :]) for v in vals]
        for c in range(sr):
            hit = (p - c * width) == lane
            for a, col in zip(acc, cols):
                a[c] = a[c] + jnp.where(hit, col, 0)
    for ref, a in zip(out_refs, acc):
        for c in range(sr):
            ref[c : c + 1, :] = jnp.sum(a[c], axis=0, keepdims=True)
