"""Multi-tenant serving through the deployment-session API: two DNNs
co-compiled onto ONE Carfield SoC and served concurrently at varying
occupancy.

A :class:`~repro.core.deploy.DeploymentSession` wraps the whole pipeline:
a typed ``CompileRequest`` (graphs, SoC, patterns, tile budgets) and a
typed ``Objective`` (makespan-primary, eviction-count tie-break) drive one
unified candidate search; the session then owns an occupancy-indexed
``PlanStore``, so ``plan_for(active)`` answers any subset of tenants with
a real co-schedule — the serving engine never falls back to compile-alone
plans when only some tenants have queued work.

Incremental re-solve
--------------------

Occupancy churn is usually *small*: tenants arrive and leave one at a
time, so the occupancy a miss lands on differs from some already-cached
occupancy by one member.  The session exploits that: every landed plan's
per-tenant tiling solutions go into a non-evicting sidecar of the
``PlanStore`` (a few integers per tenant — it survives LRU eviction of
the plan itself), and a ``plan_for`` miss warm-starts from the
Hamming-nearest cached occupancy (superset preferred: it co-tiled every
member under at least this much contention).  The warm start becomes
both a candidate tiling set *and* the joint CP's incumbent seed, so the
re-solve runs under the small ``incremental_time_budget_s`` instead of
the full from-scratch budget — on churny traces the miss compile-latency
p99 drops >= 2x (see ``benchmarks/multi_tenant.py``), while the
compile-alone concat floor still guarantees zero negative-gain rounds.
The shared L2 is re-split among the active tenants *proportionally to
their linearized working sets* (``l2_split="proportional"``), arbitrated
against the old equal split so the shipped plan is never worse.  The
demo below replays a churny trace and prints each miss's warm-start
source and compile wall time (``session.miss_events``).

Compile pipeline
----------------

Past ~10 tenants the monolithic joint CP stops converging inside its
time budget, and at fleet scale the occupancy lattice makes solve
*count* the bottleneck.  Three opt-in layers keep the compile pipeline
ahead of the request stream:

* **Decomposed joint solve** (``CompileRequest(decompose="auto")``,
  :mod:`repro.core.decompose`): tenants are clustered by dominant-device
  affinity (each fused region credited to the cheapest device offering
  it), oversized clusters split to ``decompose_max_cluster`` members,
  and the clusters solved concurrently under split L2/DMA budgets —
  then reconciled with Benders-style cuts from the exact stage-2
  ``schedule_multi`` evaluation (a cluster whose realized makespan
  exceeds its CP relaxation gets a bigger L2 slice and an overflow cut,
  iterated to a bounded fixpoint with an any-time incumbent).  The
  decomposed solutions enter candidate arbitration *alongside* the
  monolithic joint solve, so at equal total budget the session can
  never ship a worse plan — and wins outright once the monolithic
  solve stops converging (gated by ``check_regression --solve``).

* **Worker pool + occupancy-lattice prefetcher**
  (:class:`~repro.serve.compiler_thread.BackgroundCompiler` with
  ``max_workers``/``prefetch``): background miss compiles drain through
  a bounded priority pool (reactive misses always outrank speculation),
  while the prefetcher predicts likely next occupancies — Hamming-1
  neighbors of recently served occupancies plus external hints such as
  a fleet placement's per-SoC tenant sets — ranked by predicted request
  probability x staleness, so the next churn step's plan is usually
  compiled before it is requested.

* **Fleet-wide dedup**: every SoC hosting a class mix shares ONE
  ``BackgroundCompiler`` through the fleet's ``PlanCache``
  (``FleetConfig(async_compile=True)``), so an identical compile key
  queued or in flight anywhere in the rack bounces every other SoC's
  submit of the same key.

``MultiModelEngine.report()["solver"]`` exposes the per-session solver
telemetry (nodes, wall, budget exhaustion, incumbent sources, per-
context and decomposed tallies), and ``compile_latency_stats()`` splits
the latency percentiles by source (foreground/background/prefetch) so
speculative compiles cannot mask a foreground regression.

Serving & SLOs
--------------

Requests carry a priority class and an optional deadline, and the engine
grows three opt-in layers (all default-off; the bare engine stays FIFO):

    from repro.serve.admission import (AdmissionController, ClassPolicy,
                                       Priority, RoundComposer)

    eng = MultiModelEngine(
        mc,
        # bound best-effort queue depth; over-bound submits are rejected
        admission=AdmissionController({Priority.LOW:
                                       ClassPolicy(max_queued=8)}),
        # deadline-driven round composition: the occupancy dispatched
        # each round maximizes the predicted priority-weighted deadline
        # attainment (FIFO's all-active round wins all ties, starved
        # heads are force-included, feasible deadlines of deferred
        # tenants are protected)
        composer=RoundComposer(),
        # plan_for misses compile in the background (smaller
        # lazy_joint_time_budget_s); the round serves the compile-alone
        # concat floor instead of stalling on the joint CP solve
        async_compile=True,
        # drain up to 4 queued requests per tenant per round; repeated
        # waves of the same plan skip the parameter-load DMA traffic
        max_batch=4)

    eng.submit("kws", priority=Priority.HIGH, deadline_s=0.050)
    eng.submit("vision")                  # NORMAL, no deadline
    eng.run()
    eng.report()["per_class"]["HIGH"]     # attainment, p50/p99 e2e

``submit`` returns ``None`` for an admission-rejected request;
``report()`` adds per-class attainment/percentiles, round decomposition
(co / solo / floor rounds), starvation events (structurally 0)
and the admission/composer/background-compiler counters.

The legacy one-shot wrapper (``compile_multi``) is demoed at the end for
compat; it builds the same session internally.

Static plan analysis
--------------------

Every plan the session emits — the full house, each ``plan_for``
occupancy, the compile-alone references — passes through the static
plan analyzer (:mod:`repro.analysis`) before it lands in the
``PlanStore``.  The analyzer replays the schedule symbolically and
reports severity-graded diagnostics with stable rule ids:

    ===== ==================================================
    PA001 precedence: a node starts before a predecessor ends
    PA002 resource overlap: two kernels (or DMAs) share a
          device/DMA-engine window
    PA003 data hazard: a DMA moves a tensor while a kernel
          reads/writes it (RAW/WAR/WAW)
    PA004 use-after-evict: an access window not covered by an
          L2 residency rectangle
    PA005 aliasing: concurrently-live L2 allocations overlap
          in address space (or fall outside L2)
    PA006 tenant isolation: foreign owner in a namespace, or a
          tenant's static footprint over its budget slice
          (soft-budget peaks are WARNINGs)
    PA007 malformed DAG: cycles, unknown preds, unscheduled
          nodes
    PA008 double-buffer discipline: a DMA transfer with no
          backing L2 rectangle
    ===== ==================================================

``CompileRequest(analysis=...)`` picks the policy: ``"strict"`` (the
default) raises on any ERROR diagnostic so a hazardous plan can never
be cached or served, ``"warn"`` records diagnostics in
``session.analysis_stats()`` (surfaced under ``report()["analysis"]``
by the serving engine) but ships the plan, ``"off"`` skips analysis.
``repro.analysis.analyze_errors(plan)`` runs the same rules on any plan.

    PYTHONPATH=src python examples/multi_tenant.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.api import compile_multi
from repro.core.deploy import (CompileRequest, DeploymentSession, Objective)
from repro.core.runtime import multi_plan_matches_oracle
from repro.models import edge
from repro.serve.admission import Priority, RoundComposer
from repro.serve.engine import MultiModelEngine
from repro.soc.carfield import carfield_patterns, carfield_soc


def main() -> None:
    soc = carfield_soc()
    patterns = carfield_patterns()
    graphs = [edge.autoencoder(), edge.ds_cnn()]

    # -- the session API ----------------------------------------------------
    request = CompileRequest(graphs=graphs, soc=soc, patterns=patterns,
                             mode="matcha", time_budget_s=3.0)
    objective = Objective()            # makespan, evictions as tie-break
    session = DeploymentSession(request, objective)

    print("co-compiling", " + ".join(g.name for g in graphs),
          "onto", soc.name, "...")
    # pre-compile the useful partial occupancies alongside the full house
    mc = session.compile(precompile=[[0], [1]])
    assert multi_plan_matches_oracle(mc.plan)   # co-exec == each alone

    print(f"\n{'model':14s} {'alone (ms)':>11s} {'co-scheduled (ms)':>18s}")
    for i, g in enumerate(graphs):
        alone = soc.cycles_to_ms(mc.singles[i].plan.makespan)
        print(f"{g.name:14s} {alone:11.2f} {mc.tenant_latency_ms(i):18.2f}")
    seq_ms = soc.cycles_to_ms(mc.sequential_makespan_cycles)
    pr1_ms = soc.cycles_to_ms(mc.baseline_makespan_cycles)
    br_ms = soc.cycles_to_ms(mc.best_response_makespan_cycles)
    print(f"\nround makespan: {seq_ms:.2f} ms sequential -> "
          f"{pr1_ms:.2f} ms co-scheduled -> "
          f"{br_ms:.2f} ms best-response re-tiled -> "
          f"{mc.runtime_ms:.2f} ms joint "
          f"({mc.speedup:.2f}x, origin={mc.plan.origin}, "
          f"{session.hint_rounds} hint round(s), "
          f"joint={mc.joint_stats()}, L2 budgets = "
          f"{[b // 1024 for b in mc.plan.budgets]} KiB)")
    util = mc.plan.utilization()
    print("utilization: " + "  ".join(f"{d}={u:.0%}"
                                      for d, u in sorted(util.items())))

    # any occupancy gets a validated co-schedule from the plan store;
    # replaying a churny trace (tenants leaving/returning one at a time)
    # only compiles each occupancy once
    for active in ([0, 1], [0], [1], [0, 1], [0], [1]):
        plan = session.plan_for(active)
        names = " + ".join(graphs[i].name for i in active)
        print(f"plan_for({active}): {names:28s} "
              f"{soc.cycles_to_ms(plan.makespan):8.2f} ms")

    # incremental re-solve: each subset miss above warm-started from the
    # Hamming-nearest cached occupancy's tiling solutions (here the full
    # house — recorded in the plan store's non-evicting sidecar) instead
    # of re-tiling from scratch
    for ev in session.miss_events:
        print(f"miss {ev['occupancy']}: warm={ev['warm']} "
              f"neighbor={ev['neighbor']} origin={ev['origin']} "
              f"compiled in {ev['wall_s'] * 1e3:.0f} ms")
    lat = session.compile_latency_stats()
    print(f"miss compile latency: p50 {lat['p50_ms']:.0f} ms  "
          f"p99 {lat['p99_ms']:.0f} ms  "
          f"({lat['warm']['count']} warm / {lat['cold']['count']} cold; "
          f"L2 split wins: proportional {lat['prop_split_wins']}, "
          f"equal {lat['equal_split_wins']})")

    # serve a mixed-tenant workload; the uneven tail is a real (cached)
    # occupancy-1 dispatch, not a compile-alone fallback
    eng = MultiModelEngine(mc)
    for _ in range(3):
        eng.submit("autoencoder")
        eng.submit("ds_cnn")
    eng.submit("autoencoder")           # one tenant deeper than the other
    eng.run()
    rep = eng.report()
    print(f"\nserved {rep['served']} requests: "
          f"{rep['co_rounds']} co-scheduled rounds "
          f"({rep['subset_co_rounds']} at partial occupancy) + "
          f"{rep['solo_dispatches']} solo dispatches, "
          f"{rep['throughput_inf_per_s']:.1f} inf/s aggregate")
    for t in rep["per_tenant"]:
        print(f"  {t['model']:14s} served={t['served']}  "
              f"mean latency {t['mean_latency_ms']:.2f} ms")
    print(f"plan store: {rep['plan_store']}")
    ana = rep["analysis"]
    print(f"plan analysis ({ana['mode']}): {ana['plans_analyzed']} plans "
          f"analyzed, {ana['errors']} errors, "
          f"{ana['warnings']} warnings ({ana['by_rule'] or 'clean'})")

    # -- SLO-aware serving: priorities, deadlines, async compiles ----------
    # the autoencoder is latency-critical (HIGH, deadline between its
    # compile-alone latency and its co-scheduled completion); ds_cnn
    # submits a deadline-less backlog.  The deadline-driven composer
    # fast-paths the HIGH requests where FIFO would co-schedule them
    # behind the backlog.
    alone_s = soc.cycles_to_ms(mc.singles[0].plan.makespan) / 1e3
    co_s = soc.cycles_to_ms(mc.plan.tenant_makespans[0]) / 1e3
    deadline_s = 0.5 * (alone_s + co_s)
    slo = MultiModelEngine(mc, composer=RoundComposer(), execute=False)
    for _ in range(4):
        slo.submit("ds_cnn")
    for _ in range(3):
        slo.submit("autoencoder", priority=Priority.HIGH,
                   deadline_s=deadline_s)
    slo.run()
    srep = slo.report()
    high = srep["per_class"]["HIGH"]
    print(f"\nSLO serving: HIGH deadline {deadline_s * 1e3:.2f} ms -> "
          f"attainment {high['slo_attainment']:.0%} "
          f"(p99 e2e {high['p99_e2e_ms']:.2f} ms), "
          f"{srep['starvation_events']} starvation events, "
          f"composer {srep['composer']}")

    # -- legacy wrapper, still working ------------------------------------
    mc2 = compile_multi(graphs, soc, patterns, time_budget_s=3.0)
    print(f"\ncompile_multi wrapper: same winning makespan = "
          f"{mc2.runtime_ms:.2f} ms "
          f"(session-backed: {mc2.session is not None})")


if __name__ == "__main__":
    main()
