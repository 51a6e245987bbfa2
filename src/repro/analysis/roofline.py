"""Roofline terms, against one table of published per-chip peaks.

    compute term    = HLO_FLOPs / peak_FLOP/s          (per chip)
    memory term     = HLO_bytes / HBM_bw               (per chip)
    collective term = collective_bytes / link_bw       (per chip)

The analyzer inputs are already per-device (post-SPMD module), so no
further division by chip count is needed.  ``PEAKS`` is keyed by the
``device_kind`` JAX reports; a TPU kind that is not in it is an error,
and a device that is not a TPU has no peaks (no roofline is reported).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class HwSpec:
    name: str
    peak_flops_bf16: float
    hbm_bw: float
    ici_bw: float


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s of
# HBM bandwidth, 1,600 Gbit/s of interchip interconnect (4 links of
# 50 GB/s each).
PEAKS = {
    "TPU v5 lite": HwSpec("TPU v5 lite", 197e12, 819e9, 50e9),
}
V5E = PEAKS["TPU v5 lite"]      # the target of the dry-run projections


def peaks_for(platform: str, device_kind: str) -> Optional[HwSpec]:
    """Peaks of a device as JAX names it: the ``PEAKS`` entry of a TPU
    kind, ``None`` off the TPU.  An unlisted TPU kind raises."""
    if platform != "tpu":
        return None
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for TPU kind {device_kind!r};"
                         f" add it to analysis.roofline.PEAKS (known: "
                         f"{sorted(PEAKS)})")
    return PEAKS[device_kind]


def device_peaks() -> Optional[HwSpec]:
    """``peaks_for`` the first device of the default backend."""
    import jax
    d = jax.devices()[0]
    return peaks_for(d.platform, d.device_kind)


def roofline_terms(cost: dict, hw: HwSpec, *, model_flops_per_device:
                   float | None = None) -> dict:
    t_compute = cost["flops"] / hw.peak_flops_bf16
    t_memory = cost["bytes"] / hw.hbm_bw
    t_collective = cost["collective_bytes"] / hw.ici_bw
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_collective}
    dominant = max(terms, key=terms.get)
    out = dict(t_compute=t_compute, t_memory=t_memory,
               t_collective=t_collective, dominant=dominant,
               bound_seconds=max(terms.values()))
    if model_flops_per_device is not None and cost["flops"] > 0:
        out["model_flops"] = model_flops_per_device
        out["useful_flop_frac"] = model_flops_per_device / cost["flops"]
        # roofline fraction: useful work at peak / achievable step time
        out["roofline_frac"] = (model_flops_per_device / hw.peak_flops_bf16
                                ) / max(terms.values())
    return out


def route_efficiency(est_seconds: float, cost: dict, hw: HwSpec, *,
                     flag_headroom: float = 2.0) -> dict:
    """How close a route's (estimated or measured) time sits to its
    roofline bound for the work in ``cost`` (an analyzer-style dict:
    flops / bytes / collective_bytes).

    ``efficiency`` is bound/achieved in (0, 1]; ``headroom`` its
    reciprocal.  ``flagged`` marks routes leaving more than
    ``flag_headroom``x on the table -- the kernel-work signal the
    sparsity-roofline paper argues for (a route at 4x headroom is a
    kernel to fix, not a shape to avoid)."""
    bound = roofline_terms(cost, hw)
    achieved = max(float(est_seconds), 1e-12)
    eff = min(1.0, bound["bound_seconds"] / achieved)
    headroom = achieved / max(bound["bound_seconds"], 1e-12)
    return {
        "achieved_seconds": achieved,
        "bound_seconds": bound["bound_seconds"],
        "dominant": bound["dominant"],
        "efficiency": eff,
        "headroom": headroom,
        "flagged": headroom > flag_headroom,
    }


def model_flops_train(n_active_params: int, tokens: int) -> float:
    """6·N·D for a train step (fwd 2ND + bwd 4ND)."""
    return 6.0 * n_active_params * tokens


def model_flops_forward(n_active_params: int, tokens: int) -> float:
    """2·N·D for inference (prefill/decode)."""
    return 2.0 * n_active_params * tokens
