"""§Roofline table (deliverable g) — consumes dryrun_results.json.

Per (arch × shape) on the single-pod 16×16 mesh:
  * three roofline terms from the compiled dry-run (per-device flops/bytes
    from cost_analysis, trip-count-corrected collective bytes from the HLO
    parser),
  * FLOPs/bytes corrected by the two-point layer extrapolation when present
    (cost_analysis counts scan bodies once — see roofline/analysis.py),
  * MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE) and the useful-compute
    ratio vs compiled HLO FLOPs,
  * dominant bottleneck + one-line what-would-move-it-down note.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, Optional

from repro.configs import ARCH_IDS, get_config
from repro.models.config import SHAPES
from repro.roofline.analysis import (chip_peaks, model_flops, roofline_terms,
                                     two_point_total)

CHIPS = 256
# the dry run lowers for a v5e pod
PEAKS = chip_peaks("TPU v5 lite")

# N_active for MoE archs (routed top-k + shared + attention/embed), computed
# from the configs' analytic param counts.
def _active_params(arch: str) -> float:
    cfg = get_config(arch)
    total = cfg.param_count()
    if not cfg.n_experts:
        return total
    # replace the full expert stack with top_k + shared experts
    gated = 3 if cfg.act in ("swiglu", "geglu") else 2
    per_expert = gated * cfg.d_model * cfg.moe_d_ff
    moe_layers = cfg.n_layers - cfg.first_dense_layers
    total -= moe_layers * cfg.n_experts * per_expert
    total += moe_layers * cfg.top_k * per_expert
    return total


def _tokens(shape_name: str) -> float:
    s = SHAPES[shape_name]
    if s.kind in ("train", "prefill"):
        return s.global_batch * s.seq_len
    return s.global_batch * 1.0          # decode: one token per sequence


def _fix_note(bottleneck: str, arch: str, shape: str) -> str:
    if bottleneck == "compute":
        return "at compute roofline — gains need lower-precision matmuls or fewer FLOPs (e.g. less remat)"
    if bottleneck == "memory":
        return "HBM-bound — increase arithmetic intensity: larger fused blocks, bf16 state, fewer activations re-reads"
    return "ICI-bound — reshard to cut collective volume (reduce-scatter instead of all-reduce, or move the axis)"


# bytes of HBM traffic a step cannot avoid (structural lower bound):
# cost_analysis bytes assume ZERO fusion (every elementwise op round-trips
# HBM) and count VMEM-resident flash/scan tiles as HBM — a gross upper bound.
# Real TPU traffic lies between; matmul-heavy cells sit near this lower one.
_ACT_IO = 12  # per-layer activation r/w factor: residual save w+r, block io,
              # qkv/ffn intermediates across fwd + remat-recompute + bwd


def _struct_bytes(arch: str, shape_name: str) -> float:
    cfg = get_config(arch)
    s = SHAPES[shape_name]
    n_params = cfg.param_count()
    if s.kind == "train":
        # weights bf16 ×3 passes + adam m/v f32 r/w (adafactor ≈ 4B)
        opt_io = 16.0 if cfg.optimizer == "adamw" else 4.0
        params_io = n_params * (2 * 3 + opt_io) / CHIPS
        tok_loc = s.global_batch * s.seq_len / CHIPS * 16  # per-device tokens ×16 model-replication of batch shards
        act_io = cfg.n_layers * tok_loc * cfg.d_model * 2 * _ACT_IO
        return params_io + act_io
    if s.kind == "prefill":
        params_io = n_params * 2 / CHIPS
        tok_loc = s.global_batch * s.seq_len / CHIPS * 16
        act_io = cfg.n_layers * tok_loc * cfg.d_model * 2 * (_ACT_IO / 3)
        return params_io + act_io
    # decode: every live weight read once + cache read/write
    active = _active_params(arch)
    cache = (s.global_batch * s.seq_len * cfg.n_layers *
             2 * cfg.n_kv_heads * cfg.hd * 2) if cfg.n_kv_heads else 0
    return (active * 2 + cache * 1.5) / CHIPS


def build_table(dryrun_json: str, mesh: str = "16x16") -> Dict:
    data = json.load(open(dryrun_json))
    rows = []
    for r in data["results"]:
        if r["mesh"] != mesh or r["arch"] == "paper-lasso":
            continue
        arch, shape = r["arch"], r["shape"]
        cfg = get_config(arch)
        flops = r["flops"]
        bytes_ = r["bytes_accessed"]
        tp = r.get("two_point")
        if tp:
            flops = two_point_total(tp["l1"]["flops"], tp["l2"]["flops"],
                                    tp["l1"]["layers"], tp["l2"]["layers"],
                                    tp["l_full"])
            bytes_ = two_point_total(tp["l1"]["bytes"], tp["l2"]["bytes"],
                                     tp["l1"]["layers"], tp["l2"]["layers"],
                                     tp["l_full"])
        coll = sum(r["collective_bytes"].values())
        terms = roofline_terms(flops=flops, bytes_accessed=bytes_,
                               collective_bytes=coll, chips=CHIPS,
                               **dataclasses.asdict(PEAKS))
        kind = SHAPES[shape].kind
        mf = model_flops(cfg.param_count(), _tokens(shape),
                         active_params=_active_params(arch),
                         training=(kind == "train")) / CHIPS  # per-device
        # structural (fusion-aware) memory floor; the cost_analysis bytes are
        # the zero-fusion ceiling.  Bottleneck ranking uses the floor — real
        # TPU HBM traffic sits close to it for matmul-dominated cells.
        t_mem_floor = _struct_bytes(arch, shape) / PEAKS.hbm_bw
        eff = {"t_compute_s": terms["t_compute_s"],
               "t_mem_floor_s": t_mem_floor,
               "t_collective_s": terms["t_collective_s"]}
        bottleneck = max(eff, key=eff.get)
        bname = {"t_compute_s": "compute", "t_mem_floor_s": "memory",
                 "t_collective_s": "collective"}[bottleneck]
        t_bound = max(eff.values())
        rows.append({
            "arch": arch, "shape": shape,
            "flops_per_dev": flops, "bytes_per_dev": bytes_,
            "collective_bytes_per_dev": coll,
            **{k: v for k, v in terms.items()},
            "t_mem_floor_s": t_mem_floor,
            "bottleneck": bname,
            "t_bound_s": t_bound,
            "roofline_fraction": (terms["t_compute_s"] / t_bound
                                  if t_bound > 0 else 0.0),
            "model_flops_per_dev": mf,
            "useful_compute_ratio": mf / flops if flops else 0.0,
            "note": _fix_note(bname, arch, shape),
        })
    return {"mesh": mesh, "chips": CHIPS, "rows": rows}


def format_markdown(table: Dict) -> str:
    hdr = ("| arch | shape | t_comp (ms) | t_mem (ms) | t_coll (ms) | bound |"
           " roofline frac | useful/HLO |\n"
           "|---|---|---|---|---|---|---|---|\n")
    lines = []
    for r in table["rows"]:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']*1e3:.2f} "
            f"| {r['t_memory_s']*1e3:.2f} | {r['t_collective_s']*1e3:.2f} "
            f"| {r['bottleneck']} | {r['roofline_fraction']:.2f} "
            f"| {r['useful_compute_ratio']:.2f} |")
    return hdr + "\n".join(lines)


def run(dryrun_json: str = "dryrun_results.json") -> Dict:
    try:
        table = build_table(dryrun_json)
    except FileNotFoundError:
        return {"table": "roofline", "skipped": f"{dryrun_json} not found — "
                "run `python -m repro.launch.dryrun --both-meshes` first"}
    return {"table": "roofline", **table, "markdown": format_markdown(table)}
