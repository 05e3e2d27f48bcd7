"""Roofline terms from a compiled dry-run artifact.

  compute term    = HLO_FLOPs / (chips * peak_FLOP/s)
  memory term     = HLO_bytes / (chips * HBM_bw)
  collective term = per-chip collective link-bytes / link_bw

All three inputs come from the scan-aware HLO analyzer (``roofline.hlo``),
because XLA's ``cost_analysis()`` counts while-loop bodies once (verified —
see hlo.py docstring).  The analyzer returns PER-DEVICE numbers (the module
is the SPMD-partitioned program), so the compute/memory terms divide by the
per-chip peaks only; "chips" is retained in the report for context.  The
dry run models a TPU v5e pod, so the peaks are v5e's, asked for by name.
"""
from __future__ import annotations

import dataclasses

from repro.roofline import hw
from repro.roofline.hlo import HloCosts, analyze  # noqa: F401 (re-export)

_PEAKS = hw.peaks(hw.V5E_KIND)


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float               # per device
    hlo_bytes: float               # per device
    collective_bytes_per_chip: float
    model_flops: float             # whole model, all chips
    compute_s: float
    memory_s: float
    collective_s: float
    collective_detail: dict
    per_device_hbm_bytes: float | None = None
    xla_cost_flops: float | None = None  # raw cost_analysis value (body-once)

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step time = max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_fraction(self) -> float:
        total = self.hlo_flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """MODEL_FLOPs/s at roofline step time vs aggregate peak — the MFU
        upper bound of this compiled program."""
        t = self.step_time_s
        if t <= 0:
            return 0.0
        return self.model_flops / t / (self.chips * _PEAKS.flops_bf16)

    def row(self) -> dict:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "chips": self.chips,
            "hlo_flops_per_dev": self.hlo_flops,
            "hlo_bytes_per_dev": self.hlo_bytes,
            "collective_bytes_per_chip": self.collective_bytes_per_chip,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "step_time_s": self.step_time_s,
            "useful_flops_fraction": self.useful_flops_fraction,
            "roofline_fraction": self.roofline_fraction,
            "collective_detail": self.collective_detail,
            "per_device_hbm_bytes": self.per_device_hbm_bytes,
            "xla_cost_flops": self.xla_cost_flops,
        }


def build(
    *,
    arch: str,
    shape: str,
    mesh_name: str,
    chips: int,
    costs: HloCosts,
    model_flops: float,
    per_device_hbm_bytes: float | None = None,
    xla_cost_flops: float | None = None,
) -> Roofline:
    return Roofline(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        chips=chips,
        hlo_flops=costs.flops,
        hlo_bytes=costs.bytes,
        collective_bytes_per_chip=costs.collective_bytes,
        model_flops=model_flops,
        compute_s=costs.flops / _PEAKS.flops_bf16,
        memory_s=costs.bytes / _PEAKS.hbm_bw,
        collective_s=costs.collective_bytes / _PEAKS.ici_link_bw,
        collective_detail=dict(costs.collective_detail),
        per_device_hbm_bytes=per_device_hbm_bytes,
        xla_cost_flops=xla_cost_flops,
    )


def model_flops_estimate(n_active_params: float, tokens: float, kind: str) -> float:
    """6·N·D for training, 2·N·D forward-only (decode: D = batch tokens)."""
    per_tok = 6.0 if kind == "train" else 2.0
    return per_tok * n_active_params * tokens
