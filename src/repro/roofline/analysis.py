"""Three-term roofline model over dry-run artifacts (deliverable g).

Hardware constants come from ``CHIP_PEAKS``, keyed by the device kind JAX
reports (``jax.devices()[0].device_kind``); a device that is not in the
table is an error, never a default.  The collective term charges the single
busiest link, bidirectional (2× per-link bytes/s) — conservative.

``compiled.cost_analysis()`` counts a while-loop body **once**; scanned
transformers execute theirs L (layers) × M (microbatches) times.  The
collective side is fixed by roofline/hlo.py's trip-count-aware parser.  For
FLOPs/bytes we use the two-point method: lower the same cell at two layer
counts and extrapolate

    per_layer = (cost(L₂) − cost(L₁)) / (L₂ − L₁)
    total     = cost(L₁) + (L − L₁) · per_layer

which is exact for layer-homogeneous stacks (all ours are, per group).
benchmarks/roofline_table.py drives this.

``roofline_terms`` is also the cost kernel of the solver-scheduling planner
(``repro.core.solvers.planner``, DESIGN.md §9): per-FW-iteration FLOP/byte
counts are fed through the same three-term bound — with the planner's
conservative CPU constants substituted via the ``peak_flops``/``hbm_bw``
keywords on host platforms — to choose between Alg-1/Alg-2 engines and
between vmapped and sequential sweep execution.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks, in the keywords ``roofline_terms`` takes."""

    peak_flops: float    # bf16 FLOP/s
    hbm_bw: float        # HBM bytes/s
    ici_bw: float        # interconnect bytes/s per link per direction


# Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB
# of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (over four
# links: 50 GB/s each).
CHIP_PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; raises for any other chip."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to CHIP_PEAKS (known: {', '.join(sorted(CHIP_PEAKS))})") from None


def roofline_terms(*, flops: float, bytes_accessed: float,
                   collective_bytes: float, chips: int, peak_flops: float,
                   hbm_bw: float, ici_bw: float) -> Dict[str, float]:
    """The three roofline times (seconds) + dominant bottleneck.

    ``flops``/``bytes_accessed`` are per-device (that's what
    cost_analysis() of an SPMD module reports), so the per-chip rates apply
    directly; ``collective_bytes`` is per-device bytes crossing its busiest
    link (2× for bidirectional links).
    """
    t_comp = flops / peak_flops
    t_mem = bytes_accessed / hbm_bw
    t_coll = collective_bytes / (2.0 * ici_bw)
    terms = {"t_compute_s": t_comp, "t_memory_s": t_mem,
             "t_collective_s": t_coll}
    dominant = max(terms, key=terms.get)
    terms["bottleneck"] = {"t_compute_s": "compute", "t_memory_s": "memory",
                           "t_collective_s": "collective"}[dominant]
    terms["t_bound_s"] = max(t_comp, t_mem, t_coll)
    terms["roofline_fraction"] = (t_comp / terms["t_bound_s"]
                                  if terms["t_bound_s"] > 0 else 0.0)
    return terms


def model_flops(n_params: float, tokens: float, *, active_params: Optional[float] = None,
                training: bool = True) -> float:
    """MODEL_FLOPS = 6·N·D (train) or 2·N·D (inference); MoE uses N_active."""
    n = active_params if active_params is not None else n_params
    return (6.0 if training else 2.0) * n * tokens


def two_point_total(cost_l1: float, cost_l2: float, l1: int, l2: int,
                    l_target: int) -> float:
    """Extrapolate a per-layer-homogeneous cost to the full layer count."""
    per_layer = (cost_l2 - cost_l1) / max(l2 - l1, 1)
    return cost_l1 + (l_target - l1) * per_layer
