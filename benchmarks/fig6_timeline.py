"""Fig. 6 reproduction: ResNet inference profiling timeline + per-device
execution-time breakdown (busy vs idle) under MATCHA."""

from __future__ import annotations

from typing import Dict, List

from repro.core.api import compile_model
from repro.launch.cache import enable_compile_cache
from repro.models import edge
from repro.soc.carfield import carfield_patterns, carfield_soc


def run(verbose: bool = True) -> Dict:
    soc = carfield_soc()
    cm = compile_model(edge.resnet(), soc, carfield_patterns(),
                       mode="matcha", time_budget_s=3.0)
    plan = cm.plan
    util = plan.utilization()
    breakdown = {r: {"busy_cycles": b, "busy_frac": util[r]}
                 for r, b in plan.busy.items()}
    timeline: List[Dict] = []
    for name in plan.order:
        n = plan.nodes[name]
        timeline.append({"name": n.name, "kind": n.kind,
                         "resource": n.resource,
                         "start": n.start, "end": n.end})
    if verbose:
        print(f"makespan: {plan.makespan / 1e6:.2f} M cycles "
              f"({soc.cycles_to_ms(plan.makespan):.1f} ms)")
        for r, d in breakdown.items():
            print(f"  {r:6s} busy {d['busy_cycles'] / 1e6:7.2f}M "
                  f"({d['busy_frac']:6.1%})")
        # ASCII timeline (compressed)
        span = plan.makespan
        width = 72
        for r in ("host", "pulp", "spatz", "dma"):
            row = [" "] * width
            for t in timeline:
                if t["resource"] != r or t["start"] < 0:
                    continue
                a = int(t["start"] / span * (width - 1))
                b = max(a + 1, int(t["end"] / span * (width - 1)))
                ch = {"kernel": "#", "slice": "s", "concat": "c",
                      "load": ".", "store": "."}.get(t["kind"], "?")
                for i in range(a, min(b, width)):
                    row[i] = ch
            print(f"  {r:6s}|{''.join(row)}|")
    return {"makespan": plan.makespan, "breakdown": breakdown,
            "timeline": timeline}


def main() -> None:
    enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
