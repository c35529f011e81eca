"""Roofline analysis (deliverable g): three terms per (arch x shape x mesh)
from the dry-run's compiled artifacts.

    compute_term    = HLO_FLOPs_per_chip / peak_FLOPs        [s]
    memory_term     = HLO_bytes_per_chip / HBM_bw            [s]
    collective_term = collective_bytes_per_chip / link_bw    [s]

The dry-run records per-chip numbers (verified against a controlled probe:
XLA reports cost_analysis/memory_analysis for one partition), with the
while-body x trip-count correction applied (see launch/dryrun._body_cost).
MODEL_FLOPS = 6*N*D for training (2*N*D for inference), N_active for MoE —
the useful-fraction ratio MODEL_FLOPS / HLO_FLOPs exposes remat recompute,
replicated-compute waste, and quadratic-attention overhead.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

from repro.configs import registry
from repro.configs.shapes import SHAPES
from repro.core.hbmplan import param_count
from repro.launch.cache import enable_compile_cache

PEAK_FLOPS = 197e12      # TPU v5e bf16 per chip
HBM_BW = 819e9           # bytes/s per chip
LINK_BW = 50e9           # bytes/s per ICI link

DRYRUN_JSON = os.path.join("artifacts", "dryrun", "dryrun.json")


def model_flops_per_chip(arch: str, shape_name: str, n_chips: int) -> float:
    cfg = registry.get_config(arch)
    shape = SHAPES[shape_name]
    n = param_count(cfg)
    if cfg.family == "moe":
        # active params: shared attention + top_k of the expert stack
        total_exp = cfg.n_layers * cfg.n_experts * 3 * cfg.d_model * cfg.d_ff
        active_exp = total_exp * cfg.top_k / cfg.n_experts
        n = n - total_exp + active_exp
    tokens = shape.global_batch * (shape.seq_len
                                   if shape.kind != "decode" else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n * tokens / n_chips


def analyze(records: Optional[List[Dict]] = None) -> List[Dict]:
    if records is None:
        with open(DRYRUN_JSON) as f:
            records = json.load(f)
    # single-pod rows indexed for the multi-pod per-chip derivation
    single = {(r["arch"], r["shape"]): r for r in records
              if r.get("status") == "ok" and not r["mesh"].startswith("2x")}
    rows: List[Dict] = []
    for r in records:
        if r.get("status") != "ok":
            continue
        n_chips = 512 if r["mesh"].startswith("2x") else 256
        if r["mesh"].startswith("2x") and (r["arch"], r["shape"]) in single:
            # multi-pod per-chip work: the model axis is unchanged (16) and
            # data parallelism doubles, so every per-chip term of the
            # single-pod cell halves.  (The dry-run's cost probes run on
            # the single-pod mesh; deriving here avoids re-probing and is
            # exact for per-chip quantities under pure-DP scaling.)
            s = single[(r["arch"], r["shape"])]
            r = dict(r)
            r["flops"] = s["flops"] / 2
            r["hlo_bytes"] = s["hlo_bytes"] / 2
            r["collectives"] = {k: v / 2
                                for k, v in s["collectives"].items()}
        compute = r["flops"] / PEAK_FLOPS
        memory = r["hlo_bytes"] / HBM_BW
        coll_bytes = sum(r.get("collectives", {}).values())
        collective = coll_bytes / LINK_BW
        terms = {"compute": compute, "memory": memory,
                 "collective": collective}
        bottleneck = max(terms, key=terms.get)
        mf = model_flops_per_chip(r["arch"], r["shape"], n_chips)
        useful = mf / r["flops"] if r["flops"] else 0.0
        step_time = max(terms.values())
        mfu = (mf / step_time) / PEAK_FLOPS if step_time else 0.0
        rows.append({
            "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
            "compute_s": compute, "memory_s": memory,
            "collective_s": collective, "bottleneck": bottleneck,
            "model_flops": mf, "hlo_flops": r["flops"],
            "useful_ratio": useful,
            "roofline_fraction": mfu,
            "strategy": r.get("strategy", {}),
            "what_would_help": _advice(bottleneck, useful, r),
        })
    return rows


def _advice(bottleneck: str, useful: float, r: Dict) -> str:
    strat = r.get("strategy", {})
    if bottleneck == "compute" and useful < 0.5:
        if strat.get("attention") == "dp_replicated":
            return ("attention compute is replicated across the model "
                    "axis: switch to head-TP (or widen data parallelism)")
        return ("recompute dominates: relax the remat policy or move the "
                "flash backward to the fused-kernel custom VJP")
    if bottleneck == "compute":
        return "near compute roofline: larger per-chip batch or quantization"
    if bottleneck == "memory":
        return ("HBM-bound: fuse elementwise chains (Pallas), keep "
                "activations bf16, raise arithmetic intensity via larger "
                "tiles")
    return ("collective-bound: overlap collectives under compute (async "
            "ring schedules), gradient compression on the DP axis, or "
            "rebalance the CP toward less TP")


def table(rows: List[Dict]) -> str:
    hdr = ("| arch | shape | mesh | compute s | memory s | collective s | "
           "bottleneck | useful | roofline frac |")
    sep = "|" + "---|" * 9
    out = [hdr, sep]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['compute_s']:.3f} | {r['memory_s']:.3f} "
            f"| {r['collective_s']:.3f} | {r['bottleneck']} "
            f"| {r['useful_ratio']:.2f} | {r['roofline_fraction']:.2%} |")
    return "\n".join(out)


def main() -> None:
    enable_compile_cache()
    rows = analyze()
    print(table(rows))
    # summary picks for the §Perf hillclimb
    single = [r for r in rows if r["mesh"] == "16x16"
              and r["shape"] == "train_4k"]
    if single:
        worst = min(single, key=lambda r: r["roofline_fraction"])
        coll = max(rows, key=lambda r: (r["collective_s"]
                                        / max(max(r["compute_s"],
                                                  r["memory_s"]), 1e-12)))
        print(f"\nworst roofline fraction: {worst['arch']} x "
              f"{worst['shape']} ({worst['roofline_fraction']:.2%})")
        print(f"most collective-bound:   {coll['arch']} x {coll['shape']} "
              f"({coll['collective_s']:.3f}s vs compute "
              f"{coll['compute_s']:.3f}s)")


if __name__ == "__main__":
    main()
