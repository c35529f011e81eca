"""Ablation: beyond-paper scheduler features on the Fig. 7 blocks.

Quantifies the contribution of each stage-2 scheduler extension over the
paper's baseline pipeline (greedy list scheduling only):

  greedy       — HEFT-ranked greedy list scheduling (paper-equivalent)
  +strict      — strict-sequencing mode (devices may wait for their
                 highest-priority pending task)
  +anneal      — simulated-annealing polish over strict priorities (full)

All variants run on the same MATCHA-no-tiling assignment so the deltas
isolate the *scheduler*, not the tiling optimizer.
"""

from __future__ import annotations

from typing import Dict

from repro.core import schedule as S
from repro.core.heft import heft_solution
from repro.core.rewrite import rewrite
from repro.launch.cache import enable_compile_cache
from repro.models import edge
from repro.soc.carfield import carfield_patterns, carfield_soc


def run(verbose: bool = True) -> Dict[str, Dict[str, float]]:
    soc = carfield_soc()
    pats = carfield_patterns()
    out: Dict[str, Dict[str, float]] = {}
    for name in ("resnet50_block", "resnext50_block", "transformer_block",
                 "resnet"):
        g = edge.ALL_MODELS[name]()
        sol = heft_solution(g, soc, pats, fuse_joins=False)
        tg = rewrite(g, soc, sol)
        dag = S.build_dag(tg, soc)
        rank = S._upward_rank(dag)

        greedy = S.simulate(tg, soc, False, rank, nodes=dag,
                            strict=False).makespan
        strict = S.simulate(tg, soc, False, rank, nodes=dag,
                            strict=True).makespan
        full = S.schedule(tg, soc, "matcha_nt").makespan
        out[name] = {"greedy": greedy, "strict": strict, "anneal": full}
        if verbose:
            print(f"{name:18s} greedy={greedy / 1e6:8.2f}M  "
                  f"strict={strict / 1e6:8.2f}M "
                  f"({100 * (1 - strict / greedy):+5.1f}%)  "
                  f"anneal={full / 1e6:8.2f}M "
                  f"({100 * (1 - full / greedy):+5.1f}%)")
    return out


def main() -> None:
    enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
