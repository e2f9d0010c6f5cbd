"""Roofline terms from a compiled dry-run artifact.

    compute term    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory term     = HLO_bytes / (chips * HBM_bw)
    collective term = collective_bytes / (chips * link_bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``.  Collective
bytes are NOT in cost_analysis: we parse the post-SPMD HLO text and sum the
operand sizes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op.
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one accelerator kind."""

    flops_bf16: float   # FLOP/s
    hbm_bw: float       # B/s
    ici_bw: float       # B/s per chip-to-chip link
    source: str


V5E = "TPU v5 lite"   # jax's device_kind for a TPU v5e chip

# keyed by ``jax.devices()[0].device_kind``
PEAKS = {
    V5E: DevicePeaks(
        flops_bf16=197e12, hbm_bw=819e9,
        # 1,600 Gbit/s of interconnect over 4 links
        ici_bw=50e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI'),
}


def device_peaks(kind: str | None = None) -> DevicePeaks:
    """Peaks of device kind ``kind`` (default: the live device's).  A kind
    not in ``PEAKS`` raises: there is no default chip."""
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  "bf16[16,512,128]{2,1,0} all-gather(" — shape of the RESULT
_OP_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\][^=]*?\s("
    + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\(")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes_from_hlo(hlo_text: str) -> dict[str, int]:
    """Sum result-operand sizes per collective kind (bytes, per-program =
    per-device in SPMD)."""
    out: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        # '-start' ops are counted; their '-done' twins produce no new bytes
        if m.group(0).find("-done(") >= 0:
            continue
        out[kind] += _shape_bytes(dtype, dims)
    return out


def model_flops(param_count: int, active_param_count: int, tokens: int,
                kind: str) -> float:
    """MODEL_FLOPS = 6*N*D (dense) with N = active params for MoE; decode
    steps use 2*N_active per token (forward only)."""
    n = active_param_count or param_count
    if kind == "train":
        return 6.0 * n * tokens
    return 2.0 * n * tokens


@dataclasses.dataclass(frozen=True)
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float          # per-device FLOPs from cost_analysis
    hlo_bytes: float          # per-device bytes accessed
    collective_bytes: float   # per-device collective bytes (sum over kinds)
    collective_detail: dict
    model_flops_total: float  # analytic 6ND (global)
    peak_memory_per_device: float
    peaks: DevicePeaks

    @property
    def compute_s(self) -> float:
        return self.hlo_flops / self.peaks.flops_bf16

    @property
    def memory_s(self) -> float:
        return self.hlo_bytes / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / self.peaks.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs * chips) — remat/redundancy waste."""
        total_hlo = self.hlo_flops * self.chips
        return self.model_flops_total / total_hlo if total_hlo else 0.0

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "hlo_flops_per_device": self.hlo_flops,
            "hlo_bytes_per_device": self.hlo_bytes,
            "collective_bytes_per_device": self.collective_bytes,
            "collective_detail": self.collective_detail,
            "model_flops_total": self.model_flops_total,
            "peak_memory_per_device": self.peak_memory_per_device,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
        }


def roofline_terms(*, arch: str, shape: str, mesh_name: str, chips: int,
                   cost: dict, hlo_text: str, model_flops_total: float,
                   peak_memory: float, peaks: DevicePeaks
                   ) -> RooflineReport:
    coll = collective_bytes_from_hlo(hlo_text)
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops=float(cost.get("flops", 0.0)),
        hlo_bytes=float(cost.get("bytes accessed", 0.0)),
        collective_bytes=float(sum(coll.values())),
        collective_detail=coll,
        model_flops_total=model_flops_total,
        peak_memory_per_device=peak_memory,
        peaks=peaks,
    )
