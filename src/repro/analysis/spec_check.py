"""Pallas kernel-contract spec checker.

Every Pallas kernel module in ``repro.kernels`` publishes a wrapper
that assembles a grid spec (scalar-prefetch refs, windowed
``BlockSpec``s, query tiles) and launches ``pl.pallas_call``.  The
contract between the wrapper and the kernel body is entirely
structural — ref counts, block shapes, index-map ranges — and a
mismatch surfaces at trace time at best, or as silent garbage reads
(a window index map stepping off the padded plane) at worst.

This checker validates the contract *statically*, without executing a
single kernel program: it monkey-patches ``pl.pallas_call`` to capture
``(kernel_fn, grid_spec, out_shape, operands)``, drives each wrapper
with a tiny synthetic geometry, and checks each captured launch:

- operand count == ``num_scalar_prefetch`` + ``len(in_specs)``, and the
  kernel body's positional arity covers scalars + inputs + outputs;
- every ``BlockSpec`` tile shape divides its operand's plane shape
  (no partial edge blocks — the kernels assume whole windows);
- every index map, evaluated over the FULL grid in block units with
  the concrete scalar-prefetch values, stays in bounds for its operand
  (this is exactly the clipping invariant the wrappers' ``jnp.clip`` /
  ``minimum`` guards exist to uphold);
- the same for ``out_specs`` against ``out_shape``.

Separately it checks each kernel's *bindings*: the declared pure-jnp
oracle exists in ``repro.kernels.ref`` and both the wrapper and the
oracle appear in at least one test under ``tests/`` (a parity test the
kernel cannot silently lose).
"""

from __future__ import annotations

import dataclasses
import inspect
import itertools
import os
from typing import Callable, Optional

import numpy as np
import jax.numpy as jnp


# --------------------------------------------------------------------------
# capture


@dataclasses.dataclass
class CapturedCall:
    """One intercepted ``pl.pallas_call`` launch, reduced to structure."""

    kernel_name: str
    kernel_params: Optional[int]  # None when the body takes *refs
    grid: tuple
    num_scalar_prefetch: int
    in_specs: list
    out_specs: list
    operand_shapes: list  # tensor operands (scalar-prefetch args excluded)
    scalar_values: list  # concrete scalar-prefetch arrays (numpy)
    out_shapes: list  # (shape, dtype) per output


def _positional_arity(fn) -> Optional[int]:
    params = list(inspect.signature(fn).parameters.values())
    if any(p.kind == inspect.Parameter.VAR_POSITIONAL for p in params):
        return None
    return len(params)


def capture_kernel_calls(driver: Callable[[], None]) -> list[CapturedCall]:
    """Run ``driver`` with ``pl.pallas_call`` replaced by a recorder.

    The recorder returns zero arrays of the declared ``out_shape`` so
    wrapper post-processing (reshapes, overflow ORs) still runs; no
    kernel program is traced or executed.
    """
    from jax.experimental import pallas as pl

    captured: list[CapturedCall] = []
    real = pl.pallas_call

    def fake_pallas_call(kernel, *, grid_spec=None, out_shape=None, **kw):
        shapes = out_shape if isinstance(out_shape, (list, tuple)) else [out_shape]

        def launch(*operands):
            nsp = getattr(grid_spec, "num_scalar_prefetch", 0)
            captured.append(
                CapturedCall(
                    kernel_name=getattr(kernel, "__name__", repr(kernel)),
                    kernel_params=_positional_arity(kernel),
                    grid=tuple(getattr(grid_spec, "grid", ())),
                    num_scalar_prefetch=nsp,
                    in_specs=list(getattr(grid_spec, "in_specs", [])),
                    out_specs=list(getattr(grid_spec, "out_specs", [])),
                    operand_shapes=[tuple(o.shape) for o in operands[nsp:]],
                    scalar_values=[np.asarray(o) for o in operands[:nsp]],
                    out_shapes=[(tuple(s.shape), s.dtype) for s in shapes],
                )
            )
            outs = [jnp.zeros(s.shape, s.dtype) for s in shapes]
            return outs if isinstance(out_shape, (list, tuple)) else outs[0]

        return launch

    pl.pallas_call = fake_pallas_call
    try:
        driver()
    finally:
        pl.pallas_call = real
    return captured


# --------------------------------------------------------------------------
# validation


def _block_shape(spec):
    bs = getattr(spec, "block_shape", None)
    return tuple(bs) if bs is not None else None


def _index_map(spec):
    return getattr(spec, "index_map", None)


def validate_call(call: CapturedCall) -> list[str]:
    """Structural problems with one captured launch (empty = clean)."""
    problems: list[str] = []
    k = call.kernel_name

    if not call.grid or any(g <= 0 for g in call.grid):
        problems.append(f"{k}: empty/degenerate grid {call.grid}")
        return problems

    n_in = len(call.in_specs)
    n_out = len(call.out_specs)
    if len(call.operand_shapes) != n_in:
        problems.append(
            f"{k}: {len(call.operand_shapes)} tensor operands for {n_in} "
            f"in_specs (num_scalar_prefetch={call.num_scalar_prefetch} — "
            "scalar-prefetch ref count out of step with the call site?)"
        )
        return problems
    if call.kernel_params is not None:
        want = call.num_scalar_prefetch + n_in + n_out
        if call.kernel_params != want:
            problems.append(
                f"{k}: kernel body takes {call.kernel_params} refs but the "
                f"grid spec binds {want} "
                f"({call.num_scalar_prefetch} scalar + {n_in} in + {n_out} out)"
            )

    grid_points = list(itertools.product(*(range(g) for g in call.grid)))

    def check_spec(spec, shape, role, idx):
        bs = _block_shape(spec)
        if bs is None:
            problems.append(f"{k}: {role}[{idx}] has no block_shape")
            return
        if len(bs) != len(shape):
            problems.append(
                f"{k}: {role}[{idx}] block rank {len(bs)} != operand rank "
                f"{len(shape)} (shape {shape})"
            )
            return
        for d, (b, s) in enumerate(zip(bs, shape)):
            if b <= 0 or s % b != 0:
                problems.append(
                    f"{k}: {role}[{idx}] tile dim {d} ({b}) does not divide "
                    f"plane dim ({s}) — partial edge block"
                )
                return
        imap = _index_map(spec)
        if imap is None:
            problems.append(f"{k}: {role}[{idx}] has no index_map")
            return
        nblocks = tuple(s // b for s, b in zip(shape, bs))
        for point in grid_points:
            try:
                out = imap(*point, *call.scalar_values)
            except Exception as e:  # noqa: BLE001 - reported, not raised
                problems.append(
                    f"{k}: {role}[{idx}] index_map raised at grid {point}: "
                    f"{type(e).__name__}: {e}"
                )
                return
            out = tuple(int(v) for v in (out if isinstance(out, tuple) else (out,)))
            if len(out) != len(nblocks):
                problems.append(
                    f"{k}: {role}[{idx}] index_map returns rank {len(out)} "
                    f"for rank-{len(nblocks)} operand"
                )
                return
            for d, (v, n) in enumerate(zip(out, nblocks)):
                if not (0 <= v < n):
                    problems.append(
                        f"{k}: {role}[{idx}] index_map out of bounds at grid "
                        f"{point}: block index {v} on dim {d} (valid 0..{n - 1})"
                    )
                    return

    for i, (spec, shape) in enumerate(zip(call.in_specs, call.operand_shapes)):
        check_spec(spec, shape, "in_specs", i)
    if len(call.out_specs) != len(call.out_shapes):
        problems.append(
            f"{k}: {len(call.out_specs)} out_specs for "
            f"{len(call.out_shapes)} out_shapes"
        )
    else:
        for i, (spec, (shape, _)) in enumerate(zip(call.out_specs, call.out_shapes)):
            check_spec(spec, shape, "out_specs", i)
    return problems


# --------------------------------------------------------------------------
# kernel registry: tiny synthetic drivers + oracle/test bindings


def _planes(total):
    z = jnp.zeros((total,), jnp.int32)
    return z, z, z, z


# window and item blocks are lane-dense rows of 128 slots
_W = 128


def _drive_qf_probe():
    from repro.kernels.qf_probe import qf_probe_tiles

    rem, occ, shf, con = _planes(8 * _W)
    fq = jnp.arange(8, dtype=jnp.int32) * 64
    fr = jnp.zeros((8,), jnp.int32)
    qf_probe_tiles(rem, occ, shf, con, fq, fr, tile_t=4, wblk=_W, interpret=True)


def _drive_qf_build():
    from repro.kernels.qf_build import qf_build_planes

    pos = jnp.arange(6, dtype=jnp.int32) * 50
    fr = jnp.ones((6,), jnp.int32)
    mb = jnp.zeros((6,), jnp.int32)
    qf_build_planes(pos, fr, mb, total_slots=4 * _W, block_s=_W, interpret=True)


def _drive_bloom_probe():
    from repro.kernels.bloom_block import bloom_probe_tiles

    cells = jnp.zeros((8 * _W,), jnp.int32)
    idx = jnp.sort(
        (jnp.arange(24, dtype=jnp.int32).reshape(8, 3) * 40) % (8 * _W), axis=1
    )
    idx = idx[jnp.argsort(jnp.min(idx, axis=1))]
    bloom_probe_tiles(cells, idx, tile_t=4, wblk=_W, interpret=True)


def _drive_bloom_count():
    from repro.kernels.bloom_block import bloom_count_tiles

    idx = jnp.sort(jnp.arange(10, dtype=jnp.int32) * 45)
    bloom_count_tiles(idx, ncells=4 * _W, block_s=_W, interpret=True)


def _drive_cascade_probe():
    from repro.kernels.cascade_probe import cascade_probe_tiles

    planes = [_planes(4 * _W), _planes(8 * _W)]
    fq0 = jnp.arange(8, dtype=jnp.int32) * 60
    cascade_probe_tiles(
        planes,
        [fq0, fq0 * 2],
        [jnp.zeros((8,), jnp.int32)] * 2,
        tile_t=4,
        wblk=_W,
        interpret=True,
    )


def _drive_fuse_probe():
    from repro.kernels.fuse_probe import fuse_probe_tiles

    table = jnp.zeros((8 * _W,), jnp.int32)
    p0 = jnp.arange(8, dtype=jnp.int32) * 60
    fuse_probe_tiles(
        table, p0, p0 + 1, p0 + 2, jnp.zeros((8,), jnp.uint32),
        tile_t=4, wblk=_W, interpret=True,
    )


@dataclasses.dataclass
class KernelSpec:
    name: str  # kernel module (under repro.kernels)
    entry: str  # public wrapper function
    oracle: str  # bound pure-jnp oracle in repro.kernels.ref
    driver: Callable[[], None]


KERNELS = (
    KernelSpec("qf_probe", "qf_probe_tiles", "probe_ref", _drive_qf_probe),
    KernelSpec("qf_build", "qf_build_planes", "build_ref", _drive_qf_build),
    KernelSpec(
        "bloom_block", "bloom_probe_tiles", "bloom_probe_ref", _drive_bloom_probe
    ),
    KernelSpec(
        "bloom_block", "bloom_count_tiles", "bloom_count_ref", _drive_bloom_count
    ),
    KernelSpec(
        "cascade_probe",
        "cascade_probe_tiles",
        "cascade_probe_ref",
        _drive_cascade_probe,
    ),
    KernelSpec("fuse_probe", "fuse_probe_tiles", "fuse_probe_ref", _drive_fuse_probe),
)


def check_bindings(spec: KernelSpec, tests_dir: str) -> list[str]:
    """The kernel's oracle exists and a parity test references both."""
    from repro.kernels import ref

    problems = []
    if not callable(getattr(ref, spec.oracle, None)):
        problems.append(
            f"{spec.entry}: declared oracle repro.kernels.ref.{spec.oracle} "
            "does not exist"
        )
    seen_entry = seen_oracle = False
    if os.path.isdir(tests_dir):
        for fn in sorted(os.listdir(tests_dir)):
            if not (fn.startswith("test_") and fn.endswith(".py")):
                continue
            with open(os.path.join(tests_dir, fn)) as f:
                text = f.read()
            seen_entry = seen_entry or spec.entry in text
            seen_oracle = seen_oracle or spec.oracle in text
    if not seen_entry:
        problems.append(f"{spec.entry}: no test under tests/ exercises the wrapper")
    if not seen_oracle:
        problems.append(
            f"{spec.entry}: no test under tests/ references oracle {spec.oracle} "
            "(parity test missing)"
        )
    return problems


def run_spec_check(tests_dir: Optional[str] = None, verbose: bool = False) -> int:
    """Drive every registered kernel, validate every captured launch."""
    if tests_dir is None:
        # src/repro/analysis/spec_check.py -> repo root / tests
        here = os.path.dirname(os.path.abspath(__file__))
        root = os.path.dirname(os.path.dirname(os.path.dirname(here)))
        tests_dir = os.path.join(root, "tests")
    problems: list[str] = []
    n_calls = 0
    for spec in KERNELS:
        try:
            calls = capture_kernel_calls(spec.driver)
        except Exception as e:  # noqa: BLE001 - audited + surfaced
            problems.append(
                f"{spec.entry}: driver failed before launch: "
                f"{type(e).__name__}: {e}"
            )
            continue
        if not calls:
            problems.append(f"{spec.entry}: driver captured no pallas_call launch")
        for call in calls:
            n_calls += 1
            ps = validate_call(call)
            problems.extend(ps)
            if verbose:
                status = "FAIL" if ps else "ok"
                problems_note = f" ({len(ps)} problems)" if ps else ""
                print(
                    f"  {spec.entry:24s} {call.kernel_name:20s} grid={call.grid} "
                    f"prefetch={call.num_scalar_prefetch} "
                    f"in={len(call.in_specs)} out={len(call.out_specs)} "
                    f"{status}{problems_note}"
                )
        problems.extend(check_bindings(spec, tests_dir))
    for p in problems:
        print(f"FAIL {p}")
    verdict = "FAILED" if problems else "passed"
    print(
        f"spec-check {verdict}: {len(KERNELS)} kernels, {n_calls} launches "
        f"validated, {len(problems)} problems"
    )
    return 1 if problems else 0
