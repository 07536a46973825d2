"""Seeded key generation: record numbers -> distinct 32-bit keys.

Every run draws one bijection of the 32-bit index space from its seed:
``key(i) = fmix32(i * mult + off  mod 2^32)`` with an odd ``mult``, so
distinct indices give distinct keys.  Index ranges then decide
membership by construction:

* ``[0, preload)``: the records loaded in set-up;
* ``[preload, ABSENT_BASE)``: records inserted by the traffic, in order;
* ``[ABSENT_BASE, 2^32)``: keys that are never inserted.

The same bijection is written twice, in numpy (for the reference) and
in ``jax.numpy`` (to make large batches on the device).  YCSB's
scrambled Zipfian record chooser is in numpy.
"""

from __future__ import annotations

import numpy as np

MASK32 = np.uint64(0xFFFFFFFF)
ABSENT_BASE = 1 << 31
ABSENT_SPAN = 1 << 31


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator ``stream`` of a run's seed (any integer)."""
    return np.random.default_rng(np.random.SeedSequence([seed % 2**64, stream]))


def walk(seed: int) -> tuple[int, int]:
    """The run's bijection parameters ``(mult, off)``, ``mult`` odd."""
    mult, off = (int(v) for v in rng(seed, 0).integers(0, 2**32, 2, dtype=np.uint64))
    return mult | 1, off


def fmix32(x: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finalizer on uint32 (a bijection)."""
    x = np.asarray(x).astype(np.uint32)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


def keys_np(seed: int, idx: np.ndarray) -> np.ndarray:
    """Keys of record indices ``idx`` (uint32), on the host."""
    mult, off = walk(seed)
    idx = np.asarray(idx).astype(np.uint64)
    return fmix32(((idx * np.uint64(mult) + np.uint64(off)) & MASK32).astype(np.uint32))


def keys_device(mult, off, start, n: int):
    """Keys of the ``n`` consecutive indices from ``start``, in jnp
    (traceable: ``mult``, ``off``, ``start`` may be device scalars)."""
    import jax.numpy as jnp

    idx = jnp.arange(n, dtype=jnp.uint32) + jnp.asarray(start, jnp.uint32)
    x = idx * jnp.asarray(mult, jnp.uint32) + jnp.asarray(off, jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


# ---------------------------------------------------------------------------
# YCSB record choosers
# ---------------------------------------------------------------------------

FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)


def fnvhash64(v: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64``: FNV-1 over the value's 8 bytes, low
    byte first, then Java's ``Math.abs`` of the signed result."""
    v = np.asarray(v).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= FNV_PRIME_64
        v = v >> np.uint64(8)
    s = h.view(np.int64)
    return np.where(s < 0, -s, s).astype(np.uint64)


def scrambled_zipfian(
    g: np.random.Generator, n: int, itemcount: int, theta: float, zetan: float,
    items: int,
) -> np.ndarray:
    """YCSB ``ScrambledZipfianGenerator``: a Zipfian draw over ``items``
    (YCSB fixes 10^10, with ``zetan`` precomputed for it) hashed by
    ``fnvhash64`` and folded onto ``[0, itemcount)``."""
    alpha = 1.0 / (1.0 - theta)
    zeta2 = 1.0 + 0.5**theta
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = g.random(n)
    uz = u * zetan
    ret = np.floor(items * np.power(eta * u - eta + 1.0, alpha)).astype(np.uint64)
    ret = np.where(uz < 1.0 + 0.5**theta, np.uint64(1), ret)
    ret = np.where(uz < 1.0, np.uint64(0), ret)
    return fnvhash64(ret) % np.uint64(itemcount)


def choose(g: np.random.Generator, spec: dict, n: int, lo: int, count: int):
    """``n`` record indices in ``[lo, lo + count)`` by a traffic file's
    chooser ``spec`` (``{"distribution": "scrambled_zipfian" | "uniform"}``)."""
    dist = spec["distribution"]
    if dist == "uniform":
        off = g.integers(0, count, n, dtype=np.uint64)
    elif dist == "scrambled_zipfian":
        off = scrambled_zipfian(
            g, n, count, spec["theta"], spec["zetan"], spec["items"]
        )
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    return (np.uint64(lo) + off).astype(np.uint32)
