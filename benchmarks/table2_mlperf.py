"""Table 2 reproduction: MLPerf-Tiny x {TVM, MATCH, MATCHA-no-tiling, MATCHA}.

Reports cycles, runtime (ms at 50 MHz) and FLOPS per toolchain, plus the
relative reductions the paper headlines:
  * ResNet:       MATCHA -28.8 % vs MATCH (no-tiling -13.3 %)
  * AutoEncoder:  MATCHA -33.3 % vs MATCH
  * DS-CNN / MobileNet: ~0 % (tiling rejected: slice/concat overheads)
  * TVM host-only 4.61x - 12.28x slower than MATCHA
"""

from __future__ import annotations

import time
from typing import Dict, List

from repro.core.api import compile_model
from repro.core.runtime import plan_matches_oracle
from repro.launch.cache import enable_compile_cache
from repro.models import edge
from repro.soc.carfield import carfield_patterns, carfield_soc

MODES = ("tvm", "match", "matcha_nt", "matcha")

PAPER_MS = {   # Table 2 runtimes (ms) for reference
    "autoencoder": {"tvm": 100.2, "match": 20.1, "matcha_nt": 20.1,
                    "matcha": 13.4},
    "ds_cnn": {"tvm": 604.6, "match": 131.1, "matcha_nt": 131.1,
               "matcha": 131.1},
    "mobilenet": {"tvm": 3137.8, "match": 486.7, "matcha_nt": 486.7,
                  "matcha": 486.7},
    "resnet": {"tvm": 3991.7, "match": 456.6, "matcha_nt": 395.9,
               "matcha": 325.1},
}


def run(check_numerics: bool = True, verbose: bool = True) -> List[Dict]:
    soc = carfield_soc()
    pats = carfield_patterns()
    rows: List[Dict] = []
    for name, fn in edge.MLPERF_TINY.items():
        g = fn()
        per_mode: Dict[str, float] = {}
        for mode in MODES:
            t0 = time.perf_counter()
            cm = compile_model(g, soc, pats, mode=mode, time_budget_s=3.0)
            if check_numerics:
                assert plan_matches_oracle(cm.plan), (name, mode)
            per_mode[mode] = cm.makespan_cycles
            rows.append({
                "model": name, "mode": mode,
                "macs": g.total_macs(), "params": g.total_params(),
                "cycles": cm.makespan_cycles,
                "runtime_ms": cm.runtime_ms,
                "flops": cm.flops_per_s(),
                "paper_ms": PAPER_MS[name][mode],
                "compile_s": time.perf_counter() - t0,
            })
        if verbose:
            m, a, nt, tv = (per_mode["match"], per_mode["matcha"],
                            per_mode["matcha_nt"], per_mode["tvm"])
            print(f"{name:12s} match={m/1e6:7.3f}M  matcha={a/1e6:7.3f}M  "
                  f"red={100*(1-a/m):5.1f}%  nt_red={100*(1-nt/m):5.1f}%  "
                  f"tvm_speedup={tv/a:5.2f}x")
    return rows


def main() -> None:
    enable_compile_cache()
    print("model,mode,macs,params,cycles,runtime_ms,flops,paper_ms")
    for r in run(verbose=False):
        print(f"{r['model']},{r['mode']},{r['macs']},{r['params']},"
              f"{r['cycles']:.0f},{r['runtime_ms']:.2f},{r['flops']:.3e},"
              f"{r['paper_ms']}")


if __name__ == "__main__":
    main()
