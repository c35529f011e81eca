"""Shape-bucketed serving benchmark: LM + vision co-scheduling.

Three claims of the shape-bucket rework, measured on the analytic
schedule model (deterministic seeds — same numbers on any machine):

  * **Decode co-rounds beat the sequential floor.**  A decode-bucket
    round co-scheduled with the vision tenant
    (``plan_for([vision, lm], shapes={lm: 1})``) must cost strictly less
    than running the two members' compile-alone schedules back to back —
    the concat floor the engine would otherwise serve.
  * **Lattice prefetch removes bucket-transition misses.**  The same
    prefill-then-decode trace is replayed twice: with the
    shape/occupancy-lattice prefetcher (plus the engine's arrival-time
    transition announcements) every bucket transition lands on a warm
    plan — zero floor rounds; with prefetching off the transitions pay
    request-visible floor rounds (the trace must actually exercise the
    miss path, or the zero on the other arm is vacuous).
  * **No starvation under heterogeneous round costs.**  With mixed
    prefill/decode/vision traffic and deadlines in play, the composer's
    hard no-starvation bound must hold even though per-request service
    times now differ by orders of magnitude within one tenant.

Every plan the sessions emit is checked by the static plan analyzer;
the report carries its tallies (the gate is zero ERROR diagnostics).

    PYTHONPATH=src python -m benchmarks.shapes --json artifacts/shapes.json
    PYTHONPATH=src python -m benchmarks.check_regression \\
        benchmarks/baseline.json --shapes artifacts/shapes.json
"""

from __future__ import annotations

import argparse
import json
import random

from repro.core.deploy import CompileRequest, DeploymentSession
from repro.launch.cache import enable_compile_cache
from repro.models.lm_graphs import lm_tenant
from repro.serve.admission import (AdmissionController, ClassPolicy,
                                   Priority, RoundComposer)
from repro.serve.compiler_thread import BackgroundCompiler
from repro.serve.engine import MultiModelEngine
from repro.soc.testbed import dense_chain, two_acc_soc

MAX_SEQ = 32


def _session() -> DeploymentSession:
    soc, pats = two_acc_soc(512, 8.0)
    lm_graph, lm_spec = lm_tenant("rwkv6", max_seq=MAX_SEQ, d=64, ffn=128)
    vision = dense_chain("vision", [64, 64, 64])
    return DeploymentSession(CompileRequest(
        graphs=[vision, lm_graph], soc=soc, patterns=pats,
        requested_tiles=4, time_budget_s=0.5,
        joint_time_budget_s=1.0, lazy_joint_time_budget_s=0.5,
        incremental_time_budget_s=0.5,
        shape_buckets={1: lm_spec}))


def decode_coround(session: DeploymentSession) -> dict:
    """Decode-bucket co-round vs the sequential (compile-alone concat)
    floor, in analytic milliseconds."""
    mc = session.compile()
    plan = session.plan_for([0, 1], shapes={1: 1})
    co_ms = mc.soc.cycles_to_ms(plan.makespan)
    floor_cycles = (mc.singles[0].plan.makespan
                    + session.bucket_single(1, 1).plan.makespan)
    floor_ms = mc.soc.cycles_to_ms(floor_cycles)
    return {"co_ms": co_ms, "seq_floor_ms": floor_ms,
            "speedup": floor_ms / co_ms if co_ms else 1.0}


def _trace(engine: MultiModelEngine, compiler: BackgroundCompiler,
           n_prompts: int, decode_steps: int, pump: bool,
           seed: int = 0) -> dict:
    """One prefill-then-decode trace: per prompt, a prefill request at a
    random bucket plus ``decode_steps`` decode requests, the vision
    tenant riding along every step, a sprinkling of deadlines so the
    composer's EDF path engages.  ``pump`` drains the background compile
    queue between steps (the deterministic stand-in for idle worker
    time)."""
    rng = random.Random(seed)
    base_s = engine._floor_s(0)

    def step():
        if pump:
            compiler.run_pending()
        engine.step()

    for _ in range(n_prompts):
        engine.submit(1, seq_len=rng.randint(2, MAX_SEQ),
                      deadline_s=rng.choice([None, 50.0 * base_s]))
        engine.submit(0, priority=rng.choice(list(Priority)))
        step()
        for _ in range(decode_steps):
            engine.submit(1, seq_len=1,
                          deadline_s=rng.choice([None, 20.0 * base_s]))
            engine.submit(0)
            step()
    while engine.pending:
        step()
    rep = engine.report()
    return {"served": rep["served"], "rounds": rep["rounds"],
            "co_rounds": rep["co_rounds"],
            "floor_rounds": rep["floor_rounds"],
            "starvation_events": rep["starvation_events"],
            "clock_s": rep["clock_s"],
            "prefetch_compiled":
                rep["async_compiler"]["prefetch_compiled"]}


def transition_misses(n_prompts: int = 3, decode_steps: int = 6) -> dict:
    """The same trace with and without lattice prefetching.  A floor
    round in this trace IS a request-visible bucket-transition miss:
    both tenants submit every step, so the occupancy never changes —
    only the bucket vector does — and the bare full house is always
    cached."""
    arms = {}
    for label, prefetch in (("with_prefetch", True),
                            ("without_prefetch", False)):
        session = _session()
        mc = session.compile()
        compiler = BackgroundCompiler(session, start=False,
                                      prefetch=prefetch)
        adm = AdmissionController(
            {Priority.LOW: ClassPolicy(max_queued=16)})
        eng = MultiModelEngine(mc, execute=False, async_compile=compiler,
                               admission=adm, composer=RoundComposer())
        # both arms pump the compile queue between steps — demand-miss
        # compiles land either way, so the arms differ only in whether
        # the prefetcher warmed the plan BEFORE it was demanded
        arms[label] = _trace(eng, compiler, n_prompts, decode_steps,
                             pump=True)
        arms[label]["analysis"] = session.analysis_stats()
    return arms


def run(n_prompts: int = 3, decode_steps: int = 6) -> dict:
    session = _session()
    co = decode_coround(session)
    arms = transition_misses(n_prompts, decode_steps)
    report = {
        "decode_coround": co,
        "prefetch": arms,
        "starvation_events": sum(a["starvation_events"]
                                 for a in arms.values()),
        "analysis": session.analysis_stats(),
    }
    print(f"decode co-round {co['co_ms']:.3f} ms vs sequential floor "
          f"{co['seq_floor_ms']:.3f} ms ({co['speedup']:.2f}x)")
    for label, a in arms.items():
        print(f"{label}: {a['floor_rounds']} transition-miss floor "
              f"rounds over {a['rounds']} rounds "
              f"({a['served']} served, "
              f"{a['starvation_events']} starvation)")
    return report


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", default=None,
                    help="write the report to this path")
    ap.add_argument("--prompts", type=int, default=3)
    ap.add_argument("--decode-steps", type=int, default=6)
    args = ap.parse_args(argv)
    report = run(args.prompts, args.decode_steps)
    if args.json:
        import os
        d = os.path.dirname(args.json)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
