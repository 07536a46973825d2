"""Roofline-term extraction from compiled (dry-run) artifacts.

Three terms per (arch x shape x mesh), in seconds:

  compute    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
  memory     = HLO_bytes_per_device / HBM_bw_per_chip
  collective = collective_bytes_per_device / ICI_link_bw

cost_analysis() reports the per-device (SPMD-partitioned) module, so
per-device numbers over per-chip rates equal the assignment's
"total / (chips x rate)" formulation.  Collective bytes are not in
cost_analysis — we parse the optimized HLO and sum the output-shape
bytes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute (output-shape convention recorded in EXPERIMENTS.md).
"""

from __future__ import annotations

import re
from dataclasses import dataclass

# TPU v5e per-chip constants (assignment-provided)
PEAK_FLOPS = 197e12  # bf16
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s/link

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "pred": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_LINE_RE = re.compile(
    r"=\s+(?P<shapes>\([^=]*?\)|\S+)\s+(?P<op>"
    + "|".join(_COLLECTIVES)
    + r")(?:-start|-done)?[\.(]"
)


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes by collective kind (output-shape convention)."""
    out = {k: 0 for k in _COLLECTIVES}
    seen_done = set()
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if not m:
            continue
        if "-done" in line.split("=")[1][:160]:
            continue  # async pair: count the -start only
        out[m.group("op")] += _shape_bytes(m.group("shapes"))
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclass
class Roofline:
    flops: float
    bytes_accessed: float
    coll_bytes: float
    chips: int
    model_flops: float = 0.0  # analytic 6·N·D (or serve equivalent)

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def bound(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Perfect-overlap lower bound: max of the three engines."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flop_fraction(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs (global) — remat/redundancy waste."""
        total_hlo = self.flops * self.chips
        return self.model_flops / total_hlo if total_hlo else 0.0

    @property
    def mfu(self) -> float:
        """Roofline MFU: useful model FLOPs over peak at the step-time
        lower bound."""
        t = self.step_time
        if t <= 0:
            return 0.0
        return self.model_flops / (self.chips * PEAK_FLOPS * t)

    def as_dict(self) -> dict:
        return {
            "flops_per_device": self.flops,
            "bytes_per_device": self.bytes_accessed,
            "coll_bytes_per_device": self.coll_bytes,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bound": self.bound,
            "step_time_lb_s": self.step_time,
            "model_flops": self.model_flops,
            "useful_flop_fraction": self.useful_flop_fraction,
            "roofline_mfu": self.mfu,
            "chips": self.chips,
        }


def kernel_roofline(fn, *args, **kw) -> "Roofline":
    """Compiled cost-analysis of one kernel-layer op as a Roofline.

    Single chip, no collectives: the filter kernels are per-device
    streaming passes, so the roofline reduces to the compute-vs-HBM
    pair and ``t_memory`` is the TPU projection for a bandwidth-bound
    op.  Works on any backend — the CPU-compiled module's FLOP/byte
    counts are the same structural quantities the TPU module streams.
    """
    import jax

    compiled = jax.jit(fn).lower(*args, **kw).compile()
    cost = compiled.cost_analysis() or {}
    return Roofline(
        flops=float(cost.get("flops", 0.0) or 0.0),
        bytes_accessed=float(cost.get("bytes accessed", 0.0) or 0.0),
        coll_bytes=0.0,
        chips=1,
    )


def model_flops_estimate(cfg, shape_kind: str, batch: int, seq: int) -> float:
    """Analytic useful FLOPs: 6·N_active·D for training, 2·N_active·D
    (+ attention KV term) for serving."""
    n_active = cfg.active_param_count()
    if shape_kind == "train":
        base = 6.0 * n_active * batch * seq
        # attention score/value FLOPs (causal ~ S^2/2), fwd+bwd (x3)
        if cfg.attn_kind != "none":
            attn = (
                cfg.n_layers
                * batch
                * (seq * seq / 2)
                * cfg.n_heads
                * cfg.head_dim
                * 2
                * 2
                * 3
            )
            base += attn
        return base
    if shape_kind == "prefill":
        base = 2.0 * n_active * batch * seq
        if cfg.attn_kind != "none":
            base += (
                cfg.n_layers * batch * (seq * seq / 2) * cfg.n_heads * cfg.head_dim * 4
            )
        return base
    # decode: one token; attention reads the whole cache
    base = 2.0 * n_active * batch
    if cfg.attn_kind != "none":
        kv_len = seq if not cfg.attn_window else min(seq, cfg.attn_window)
        base += cfg.n_layers * batch * kv_len * cfg.n_heads * cfg.head_dim * 4
    return base
