#!/usr/bin/env python3
"""Find an open-loop cell's knee on the chip: the highest total rate the
engine sustains without a growing backlog.

    python3 bench/sweep.py --workload <cell> --rates 8,12,16 --seconds <s> --seed <n>

In this one process, for each rate: the cell's traffic at that rate, a
fresh engine (``run.prepare``) and one window (``run.run_window``).  A
line per rate gives the median and 95th percentile latency, the requests
still unfinished when the window closed, and the median latency of the
last quarter of arrivals over that of the first: a backlog that grows
makes the ratio climb.  The knee is then written into the cell's traffic
file by hand.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    spec = run.load_cell(args.workload)
    if spec["traffic"]["kind"] != "poisson":
        raise SystemExit("a knee is an open-loop rate")
    models = list(spec["config"]["models"])
    run.require_chips(int(spec["cell"]["chips"]))
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    compiled, _, occupancies = run.load_deployment(spec)
    for rate in (float(r) for r in args.rates.split(",")):
        s = dict(spec, traffic=dict(spec["traffic"], rate_per_s=rate))
        driver, _, _, _ = run.prepare(s, compiled, occupancies, args.seed)
        win = run.run_window(driver, s["traffic"], len(models),
                             args.seconds, args.seed, None)
        fin = sorted(driver.finished)
        lat = np.array([1e3 * (done - due) for due, done, _ in fin])
        q = max(1, len(fin) // 4)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(fin),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "unfinished_at_close": sum(1 for _, done, _ in fin
                                       if done > args.seconds),
            "last_over_first_quarter": float(np.median(lat[-q:])
                                             / np.median(lat[:q])),
            "rounds": driver.engine.rounds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
