#!/usr/bin/env python3
"""Readings that the limit of ``correct`` is set from, on the chip.

    python3 bench/readings.py --workload <cell> --seeds 1,2,... --seconds <s> [--out <file>]

For each seed, in this one process: that seed's weights and inputs, a
fresh engine on the cell's compiled deployment (``run.prepare``), a
window of ``--seconds`` at the cell's own load (``run.run_window``) and
the check of every answer served (``run.check``): the program's widest
gap.  Then the control on the same seed: the reference computed at
``high`` (three bfloat16 passes, ``reference.NN``) in the program's
place, read as its widest gap against the reference over the same served
inputs.  The control has to read above the limit and the program below.

The benchmark's own runs never run this.  Prints one line per seed and,
with ``--out``, writes the readings as JSON.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import run

import numpy as np

import reference


def control_gaps(models, params, pools, served,
                 precision: str = "high") -> dict:
    """Widest gap of the control against the reference, per model, over
    the pooled inputs in ``served`` (per tenant, a set of pool indices)."""
    want = reference.reference_outputs(models, params, pools)
    ctl = reference.reference_outputs(models, params, pools, precision)
    out = {}
    for t, m in enumerate(models):
        idx = sorted(served[t])
        out[m] = max((reference.gap(ctl[t][i], want[t][i]) for i in idx),
                     default=float("inf"))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    spec = run.load_cell(args.workload)
    models = list(spec["config"]["models"])
    run.require_chips(int(spec["cell"]["chips"]))
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    limit = float(spec["config"]["limits"]["gap"])
    compiled, _, occupancies = run.load_deployment(spec)
    rows = []
    for seed in seeds:
        driver, plans, params, pools = run.prepare(spec, compiled,
                                                   occupancies, seed)
        win = run.run_window(driver, spec["traffic"], len(models),
                             args.seconds, seed, None)
        answers = driver.answers
        served = [set() for _ in models]
        for t, i, _ in answers:
            served[t].add(i)
        del driver, plans
        gc.collect()
        correct, _, failed, checks, worst = run.check(
            models, answers, win["unanswered"], params, pools, limit)
        ctl = control_gaps(models, params, pools, served)
        native = control_gaps(models, params, pools, served, "high_native")
        row = {"seed": seed, "answers": len(answers),
               "program": checks["gap"]["value"], "program_by_model": worst,
               "control": max(ctl.values()), "control_by_model": ctl,
               "high_native": max(native.values()),
               "correct": correct, "failed": failed}
        rows.append(row)
        print(json.dumps(row), flush=True)
    prog = [r["program"] for r in rows]
    ctl = [r["control"] for r in rows]
    summary = {"workload": args.workload, "limit": limit,
               "program_max": max(prog), "program_median": float(
                   np.median(prog)), "control_min": min(ctl),
               "control_median": float(np.median(ctl)), "rows": rows}
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}),
          flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
