#!/usr/bin/env python3
"""Bring-up smoke test: the filter main path end to end on a TPU.

One process drives the paper's two deployment structures through
``repro.filters`` with ``backend="pallas"`` at deployment size, and
checks every answer against plain numpy over the same keys:

* flat quotient filter (§3): ``qf`` with q=26, r=16 (2^26 slots),
  2^25 distinct keys inserted in batches of 2^22, 2^20 inserted and
  2^20 absent keys queried, then 2^16 keys deleted.  A 2^28-slot
  table passes the same checks on a v5e but takes about 890 s there,
  since every insert batch sorts and rebuilds the whole table;
* cascade filter (§4): ``cascade`` with ram_q=18, p=44, fanout=4,
  levels=4 (levels of 2^20 to 2^26 slots), 2^25 keys ingested as one
  jitted ``lax.scan`` over batches of 2^16, then ``contains`` and
  ``probe``.

``--chips 4`` runs only the multi-chip path: ``sharded_qf`` with q=30,
r=14 over four shards, 2^28 keys in batches of 2^22.

The reference is independent of the repository: key membership is a
sorted-array lookup, and the p-bit fingerprints (needed to tell which
deleted keys still share a fingerprint with a stored key) are the
murmur3 fmix32 pair the filters hash with, recomputed in numpy.  All
keys come from ``--seed``.  A false negative, a false-positive count
above twice the analytic ``n / 2^p`` rate plus 10, a count that does
not match, or a program without a Mosaic kernel fails the run.

The script needs a TPU: it exits non-zero, printing no result, when
JAX finds none.  The compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``.jax_cache/`` next to this file.  Each phase prints its
compile and run seconds, the device's peak bytes and its checks; the
last line is the JSON result.

Usage::

    python chip_smoke.py [--seed 0]
    python chip_smoke.py --chips 4
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# the TPU compiler would otherwise log to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = os.path.dirname(os.path.abspath(__file__))
MASK32 = np.uint64(0xFFFFFFFF)


# ---------------------------------------------------------------------------
# numpy reference: keys, fingerprints, membership
# ---------------------------------------------------------------------------


def fmix32(x: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finalizer on uint32 (a bijection)."""
    x = x.astype(np.uint32)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


def distinct_keys(seed: int, n: int) -> np.ndarray:
    """``n`` distinct uint32 keys: a seeded odd-multiplier walk pushed
    through a bijection, so no two indices share a key."""
    rng = np.random.default_rng(seed)
    mult, off = (int(v) for v in rng.integers(0, 2**32, 2, dtype=np.uint64))
    idx = np.arange(n, dtype=np.uint64)
    walk = ((idx * np.uint64(mult | 1) + np.uint64(off)) & MASK32).astype(np.uint32)
    return fmix32(walk)


def fingerprints(keys: np.ndarray, p: int, seed: int = 0) -> np.ndarray:
    """Top ``p`` bits of the 64-bit (hi:lo) hash the filters store."""
    s = np.uint32(seed)
    with np.errstate(over="ignore"):
        c_hi = fmix32(np.array([s * np.uint32(2) + np.uint32(1)]))[0]
        c_lo = fmix32(np.array([s * np.uint32(2) + np.uint32(2)]))[0]
        hi = fmix32(keys ^ c_hi).astype(np.uint64)
        lo = fmix32((keys + np.uint32(0x9E3779B9)) ^ c_lo).astype(np.uint64)
    return ((hi << np.uint64(32)) | lo) >> np.uint64(64 - p)


def member(stored: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """Sorted-array membership of each query among ``stored``.

    The queries are searched in sorted order, which keeps the binary
    searches of a large stored array cache-friendly."""
    s = np.sort(stored)
    order = np.argsort(queries)
    sq = queries[order]
    pos = np.searchsorted(s, sq)
    found = np.zeros(sq.size, bool)
    inside = pos < s.size
    found[inside] = s[pos[inside]] == sq[inside]
    out = np.empty(sq.size, bool)
    out[order] = found
    return out


# ---------------------------------------------------------------------------
# device helpers
# ---------------------------------------------------------------------------


def log(msg: str) -> None:
    print(msg, flush=True)


class Phase:
    """Times one phase's compiles and runs, checks, and its memory."""

    def __init__(self, name: str):
        self.name = name
        self.compile_s = {}
        self.run_s = {}
        log(f"[{name}] start")

    def compile(self, label, fn, *args, donate=(), kernel=True):
        """AOT-compile ``fn`` for ``args``.  With ``kernel``, wherever the
        kernel mode resolves to ``mosaic`` the program must contain a
        Mosaic kernel (no xla or interpret fallback)."""
        import jax

        from repro.kernels import dispatch

        t0 = time.perf_counter()
        exe = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        self.compile_s[label] = time.perf_counter() - t0
        mosaic = dispatch.default_mode() == "mosaic"
        if kernel and mosaic and "tpu_custom_call" not in exe.as_text():
            raise AssertionError(f"{self.name}: {label} holds no Mosaic kernel")
        log(f"[{self.name}] compiled {label} in {self.compile_s[label]:.3f}s")
        return exe, label

    def run(self, prog, *args):
        import jax

        exe, label = prog
        t0 = time.perf_counter()
        out = jax.block_until_ready(exe(*args))
        self.run_s[label] = self.run_s.get(label, 0.0) + time.perf_counter() - t0
        return out

    def check(self, ok: bool, what: str) -> None:
        log(f"[{self.name}] {'ok  ' if ok else 'FAIL'} {what}")
        if not ok:
            raise AssertionError(f"{self.name}: {what}")

    def done(self, devices) -> None:
        runs = " ".join(f"{k}={v:.3f}" for k, v in self.run_s.items())
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
        log(
            f"[{self.name}] compile_s={sum(self.compile_s.values()):.3f} "
            f"run_s={sum(self.run_s.values()):.3f} ({runs}) peak_bytes_in_use={peaks}"
        )


def fp_limit(n_stored: int, n_queries: int, p: int) -> int:
    return int(2 * n_queries * n_stored / 2**p) + 10


def check_answers(ph, hits, present_mask, absent_mask, n_stored, p):
    fn = int(np.sum(present_mask & ~hits))
    fp = int(np.sum(absent_mask & hits))
    lim = fp_limit(n_stored, int(absent_mask.sum()), p)
    present = int(present_mask.sum())
    ph.check(fn == 0, f"queries={hits.size} present={present} false_negatives={fn}")
    absent = int(absent_mask.sum())
    ph.check(fp <= lim, f"absent={absent} false_positives={fp} limit={lim}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def qf_phase(
    seed,
    devices,
    q=26,
    r=16,
    n_ins=2**25,
    batch=2**22,
    nq=2**20,
    ndel=2**16,
):
    import jax
    import jax.numpy as jnp

    from repro import filters

    ph = Phase(f"qf q={q} r={r}")
    keys = distinct_keys(seed, n_ins + nq)
    ins, absent = keys[:n_ins], keys[n_ins:]
    cfg, st = filters.make("qf", q=q, r=r, backend="pallas")
    p = q + r

    kb = jax.ShapeDtypeStruct((batch,), jnp.uint32)
    insert = ph.compile(
        "insert", lambda s, k: filters.insert(cfg, s, k), st, kb, donate=(0,)
    )
    for i in range(n_ins // batch):
        st = ph.run(insert, st, jnp.asarray(ins[i * batch : (i + 1) * batch]))
        if i % 8 == 7:
            done = (i + 1) * batch
            log(f"[{ph.name}] inserted {done} keys, {ph.run_s['insert']:.3f}s")
    stats = jax.device_get(filters.stats(cfg, st))
    ph.check(
        int(stats["n"]) == n_ins and not stats["overflow"],
        f"keys={n_ins} stored={int(stats['n'])} overflow={bool(stats['overflow'])}",
    )

    # queries: every (n_ins // nq)-th inserted key, then nq absent keys
    queries = np.concatenate([ins[:: n_ins // nq][:nq], absent])
    contains = ph.compile(
        "contains", lambda s, k: filters.contains(cfg, s, k), st, jnp.asarray(queries)
    )
    hits = np.asarray(ph.run(contains, st, jnp.asarray(queries)))
    is_in = member(ins, queries)
    check_answers(ph, hits, is_in, ~is_in, n_ins, p)

    # delete the first ndel inserted keys; they must answer absent
    # unless a key still stored shares their p-bit fingerprint
    gone = ins[:ndel]
    delete = ph.compile(
        "delete",
        lambda s, k: filters.delete(cfg, s, k),
        st,
        jnp.asarray(gone),
        donate=(0,),
        kernel=False,
    )
    st = ph.run(delete, st, jnp.asarray(gone))
    kept = ins[ndel:]
    stats = jax.device_get(filters.stats(cfg, st))
    stored = int(stats["n"])
    ph.check(stored == n_ins - ndel, f"deleted={ndel} stored={stored}")
    queries2 = np.concatenate([gone, queries[ndel:]])
    hits2 = np.asarray(ph.run(contains, st, jnp.asarray(queries2)))
    shared = member(fingerprints(kept, p), fingerprints(gone, p))
    wrong = int(np.sum(hits2[:ndel] != shared))
    ph.check(
        wrong == 0,
        f"deleted keys answering present={int(hits2[:ndel].sum())} "
        f"sharing a stored fingerprint={int(shared.sum())} mismatches={wrong}",
    )
    is_in2 = member(kept, queries2)
    is_in2[:ndel] = False
    rest = np.arange(queries2.size) >= ndel
    check_answers(ph, hits2, rest & is_in2, rest & ~is_in2, n_ins - ndel, p)
    del st
    ph.done(devices)


def cascade_phase(
    seed,
    devices,
    ram_q=18,
    p=44,
    n_ins=2**25,
    batch=2**16,
    nq=2**20,
):
    import jax
    import jax.numpy as jnp

    from repro import filters

    ph = Phase(f"cascade ram_q={ram_q} p={p} fanout=4 levels=4")
    keys = distinct_keys(seed + 1, n_ins + nq)
    ins, absent = keys[:n_ins], keys[n_ins:]
    cfg, st = filters.make(
        "cascade", ram_q=ram_q, p=p, fanout=4, levels=4, backend="pallas"
    )

    def ingest(s, kb):
        step = lambda s, k: (filters.insert(cfg, s, k), None)
        return jax.lax.scan(step, s, kb)[0]

    kb = jnp.asarray(ins.reshape(-1, batch))
    scan = ph.compile("insert_scan", ingest, st, kb, donate=(0,))
    st = ph.run(scan, st, kb)
    del kb
    stats = jax.device_get(filters.stats(cfg, st))
    levels = [int(v) for v in stats["level_counts"]]
    ph.check(
        int(stats["n"]) == n_ins and not stats["overflow"] and levels[-1] > 0,
        f"keys={n_ins} stored={int(stats['n'])} level_counts={levels} "
        f"merges={int(stats['merges'])} overflow={bool(stats['overflow'])}",
    )

    queries = jnp.asarray(np.concatenate([ins[:: n_ins // nq][:nq], absent]))
    is_in = member(ins, np.asarray(queries))
    # contains and probe share one program (and most of its compile)
    both = ph.compile(
        "contains+probe",
        lambda s, k: (filters.contains(cfg, s, k), filters.probe(cfg, s, k)[1]),
        st,
        queries,
    )
    hits, phits = (np.asarray(h) for h in ph.run(both, st, queries))
    check_answers(ph, hits, is_in, ~is_in, n_ins, p)
    check_answers(ph, phits, is_in, ~is_in, n_ins, p)
    ph.check(bool(np.all(phits == hits)), "probe answers == contains answers")
    del st
    ph.done(devices)


def sharded_phase(
    seed, devices, q=30, r=14, n_ins=2**28, batch=2**22, nq=2**20, qbatch=2**19
):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from repro import filters

    ph = Phase(f"sharded_qf q={q} r={r} n_shards=4")
    keys = distinct_keys(seed + 2, n_ins + nq)
    ins, absent = keys[:n_ins], keys[n_ins:]
    cfg, st = filters.make("sharded_qf", q=q, r=r, n_shards=4)
    spread = {d.id for d in st.rem.sharding.device_set}
    ph.check(len(spread) == 4, f"state sharded over devices {sorted(spread)}")
    mesh = st.rem.sharding.mesh
    on_mesh = NamedSharding(mesh, PartitionSpec(cfg.axis))

    def put(x):
        return jax.device_put(x, on_mesh)

    kb = jax.ShapeDtypeStruct((batch,), jnp.uint32, sharding=on_mesh)
    insert = ph.compile(
        "insert",
        lambda s, k: filters.insert(cfg, s, k),
        st,
        kb,
        donate=(0,),
        kernel=False,
    )
    for i in range(n_ins // batch):
        st = ph.run(insert, st, put(ins[i * batch : (i + 1) * batch]))
        if i % 8 == 7:
            done = (i + 1) * batch
            log(f"[{ph.name}] inserted {done} keys, {ph.run_s['insert']:.3f}s")
    stats = jax.device_get(filters.stats(cfg, st))
    shard_n = [int(v) for v in stats["shard_counts"]]
    ph.check(
        int(stats["n"]) == n_ins and not stats["overflow"],
        f"keys={n_ins} stored={int(stats['n'])} shard_counts={shard_n} "
        f"overflow={bool(stats['overflow'])}",
    )
    per_dev = {s.device.id: s.data.nbytes for s in st.rem.addressable_shards}
    ph.check(
        len(per_dev) == 4 and min(per_dev.values()) > 0,
        f"remainder-plane bytes per device={per_dev}",
    )

    # queried in chunks: the lookup's exact-fallback decode of a local
    # table already takes most of a chip's memory
    queries = np.concatenate([ins[:: n_ins // nq][:nq], absent])
    chunks = [put(queries[i : i + qbatch]) for i in range(0, queries.size, qbatch)]
    contains = ph.compile(
        "contains",
        lambda s, k: filters.contains(cfg, s, k),
        st,
        chunks[0],
        kernel=False,
    )
    hits = np.concatenate([np.asarray(ph.run(contains, st, c)) for c in chunks])
    is_in = member(ins, queries)
    check_answers(ph, hits, is_in, ~is_in, n_ins, q + r)
    del st
    ph.done(devices)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def setup_cache(jax):
    """Persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` if set,
    else the fixed ``.jax_cache/`` beside this file.  Returns a counter
    of cache hits and misses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", path)
    counts = {"hits": 0, "misses": 0}

    def on_event(name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    return path, counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--chips",
        type=int,
        choices=(1, 4),
        default=1,
        help="4 runs only the sharded four-chip path",
    )
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU found (JAX sees platform {devices[0].platform!r}); "
            "this smoke test runs only on a TPU",
            file=sys.stderr,
        )
        return 1
    if len(devices) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
            f"found {len(devices)}",
            file=sys.stderr,
        )
        return 1
    cache_dir, cache = setup_cache(jax)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.kernels import dispatch

    mode = dispatch.default_mode()
    if mode != "mosaic":
        print(
            f"chip_smoke: kernel mode resolves to {mode!r}, not 'mosaic'",
            file=sys.stderr,
        )
        return 1
    log(
        f"device_kind={devices[0].device_kind!r} count={len(devices)} "
        f"jax={jax.__version__} kernel_mode={mode} compile_cache={cache_dir}"
    )

    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(args.seed, devices[:4])
    else:
        qf_phase(args.seed, devices[:1])
        cascade_phase(args.seed, devices[:1])
    log(
        f"total_s={time.perf_counter() - t0:.3f} compile_cache_hits={cache['hits']} "
        f"compile_cache_misses={cache['misses']}"
    )
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": devices[0].platform,
                    "kind": devices[0].device_kind,
                    "count": len(devices),
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
