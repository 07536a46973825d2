"""Inclusive prefix scans of long 1-D arrays, blocked into rows.

The filters' bulk passes are prefix scans over whole tables: rank
counts (``cumsum``) and linear-probe positions (``cummax``).  The TPU
compiler spends 10-20 s on a single 1-D scan over millions of elements,
but about a second on the same scan laid out as ``(rows, 1024)``: each
row is scanned along its lanes, the row totals are scanned the same way
(recursively), and every row adds the carry of the rows before it.
The results are identical to ``jnp.cumsum`` / ``lax.cummax``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_WIDTH = 1024


def _blocked(x: jnp.ndarray, op, row_scan, identity) -> jnp.ndarray:
    n = x.shape[0]
    if n <= _WIDTH:
        return row_scan(x.reshape(1, n), axis=1).reshape(n)
    pad = (-n) % _WIDTH
    fill = jnp.full((pad,), identity, x.dtype)
    rows = row_scan(jnp.concatenate([x, fill]).reshape(-1, _WIDTH), axis=1)
    totals = _blocked(rows[:, -1], op, row_scan, identity)
    carry = jnp.concatenate([jnp.full((1,), identity, x.dtype), totals[:-1]])
    return op(rows, carry[:, None]).reshape(-1)[:n]


def cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """``jnp.cumsum`` of a 1-D integer array."""
    return _blocked(x, jnp.add, jnp.cumsum, 0)


def cummax(x: jnp.ndarray) -> jnp.ndarray:
    """``jax.lax.cummax`` of a 1-D integer array."""
    return _blocked(x, jnp.maximum, jax.lax.cummax, jnp.iinfo(x.dtype).min)
