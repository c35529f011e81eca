"""Fig. 7 reproduction: DNN building blocks x toolchains (FLOPS comparison).

Paper headlines (relative to MATCH, best-device-per-layer sequential):
  * ResNet-50 block:   async-only -18.22 %, tile-centric -35.02 %
  * ResNeXt-50 block:  async-only  -9.47 %, tile-centric -17.55 %
  * Transformer block: async-only  -7.21 %, tile-centric -23.65 %
TVM host-only baseline: MATCHA speedups between 11.04x and 40.34x.
"""

from __future__ import annotations

from typing import Dict, List

from repro.core.api import compile_model
from repro.core.runtime import plan_matches_oracle
from repro.launch.cache import enable_compile_cache
from repro.models import edge
from repro.soc.carfield import carfield_patterns, carfield_soc

MODES = ("tvm", "match", "matcha_nt", "matcha")

PAPER_REDUCTION = {   # % latency reduction vs MATCH
    "resnet50_block": {"matcha_nt": 18.22, "matcha": 35.02},
    "resnext50_block": {"matcha_nt": 9.47, "matcha": 17.55},
    "transformer_block": {"matcha_nt": 7.21, "matcha": 23.65},
}


def run(check_numerics: bool = True, verbose: bool = True) -> List[Dict]:
    soc = carfield_soc()
    pats = carfield_patterns()
    rows: List[Dict] = []
    for name, fn in edge.BLOCKS.items():
        g = fn()
        per_mode: Dict[str, float] = {}
        for mode in MODES:
            cm = compile_model(g, soc, pats, mode=mode, time_budget_s=3.0)
            if check_numerics:
                assert plan_matches_oracle(cm.plan), (name, mode)
            per_mode[mode] = cm.makespan_cycles
            rows.append({
                "block": name, "mode": mode, "cycles": cm.makespan_cycles,
                "flops": cm.flops_per_s(),
                "util": cm.plan.utilization(),
            })
        if verbose:
            m, a, nt, tv = (per_mode["match"], per_mode["matcha"],
                            per_mode["matcha_nt"], per_mode["tvm"])
            pr = PAPER_REDUCTION[name]
            print(f"{name:18s} red={100*(1-a/m):6.2f}% (paper {pr['matcha']})"
                  f"  nt_red={100*(1-nt/m):6.2f}% (paper {pr['matcha_nt']})"
                  f"  tvm_speedup={tv/a:6.2f}x")
    return rows


def main() -> None:
    enable_compile_cache()
    print("block,mode,cycles,flops")
    for r in run(verbose=False):
        print(f"{r['block']},{r['mode']},{r['cycles']:.0f},{r['flops']:.3e}")


if __name__ == "__main__":
    main()
