"""Multi-tenant co-scheduling benchmark (the paper's Fig. 4 utilization
story generalized from intra-model to inter-model concurrency).

For each model mix, N MLPerf-Tiny models are compiled onto the Carfield
SoC four ways:

  * sequential — each model compiled alone, run back-to-back
    (sum of single-model makespans),
  * PR-1 co-scheduled — ``compile_multi`` without re-tiling: merged
    execution DAGs of the compile-alone tilings under per-device mutual
    exclusion, shared budgeted L2, double-buffered DMA,
  * best-response re-tiled — stage 1 re-run per tenant under
    contention-adjusted budgets (shrunk L2 slice, co-resident device
    load, congested DMA) plus complementary candidate selection, with the
    exact shared-resource model arbitrating (the PR 2/3 pipeline; phase A
    of the deployment session's fixpoint), and
  * joint-CP — ONE constraint program over every tenant's tile variables
    (shared device loads, one shared-L2 capacity constraint, coupled DMA)
    solved per occupancy; by construction
    joint <= best-response <= PR-1 <= sequential on every mix.

Reported per mix: per-tenant latency, aggregate throughput, per-device
utilization, the co-scheduling speedups, the winning candidate's origin,
and the shared-L2 eviction counts.  A forced-contention section shrinks
the shared L2 until the compile-alone tilings thrash, showing re-tiling
reducing ``SharedL2Allocator`` evictions while winning the makespan.  A
partial-occupancy section replays a tenants-arriving/leaving trace
against the session's occupancy-indexed plan store — tiling is re-decided
per occupancy (compile-alone warm starts, L2 re-split among the active
tenants), so every round's subset co-schedule beats (or ties) the old
compile-alone back-to-back fallback: no negative-gain rounds.

An incremental-re-solve section replays a *churny* trace (adjacent
occupancies differ by one tenant) through two fresh sessions — warm
starts on vs off — and reports per-miss compile-latency p50/p99 both
ways: warm misses re-seed the joint CP from the Hamming-nearest cached
occupancy's tiling solutions and run under the small incremental budget,
cutting the miss p99 >= 2x (gated by ``check_regression``) with zero
negative-gain rounds, while the shared-L2 re-split is arbitrated
proportional-vs-equal per plan so the working-set-weighted split never
ships a worse co-schedule.

Two serving-layer sections close the report.  An async-compile probe
dispatches one round at an *unseen* occupancy with the background
compiler attached: the round costs the compile-alone concat floor (gated
at <= 1.1x) instead of stalling on the subset compile's joint CP solve.
An SLO section replays one deterministic open-loop arrival trace per mix
through a FIFO engine and a deadline-driven engine
(``serve.admission.RoundComposer``): the contention-hurt tenant carries
HIGH priority and a deadline halfway between its compile-alone latency
and its co-scheduled completion, the rest submit saturating bulk traffic
— reported per class as SLO attainment and p99 e2e latency, gated on the
HIGH class beating FIFO and on zero starvation events.

    PYTHONPATH=src python -m benchmarks.multi_tenant [--fast] [--json OUT]

``--json OUT`` writes every reported number to ``OUT`` (uploaded as a CI
artifact; ``benchmarks.check_regression`` diffs it against the committed
``benchmarks/baseline.json`` to gate >5% makespan regressions — refresh
the baseline with ``--json benchmarks/baseline.json`` after intentional
perf changes).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

import time

from repro.core.api import compile_multi
from repro.core.runtime import multi_plan_matches_oracle
from repro.core.schedule import _search_coschedule, default_budgets
from repro.launch.cache import enable_compile_cache
from repro.models import edge
from repro.serve.admission import Priority, RoundComposer
from repro.serve.compiler_thread import BackgroundCompiler
from repro.serve.engine import MultiModelEngine
from repro.soc.carfield import carfield_patterns, carfield_soc
from repro.soc.testbed import (FORCED_L2_KIB, forced_contention_setup,
                               hetero_setup)

MIXES = [
    ("autoencoder", "ds_cnn"),
    ("autoencoder", "resnet"),
    ("ds_cnn", "mobilenet"),
    ("autoencoder", "ds_cnn", "resnet"),
]

def run(mixes=MIXES, check_numerics: bool = True, verbose: bool = True,
        time_budget_s: float = 2.0):
    soc = carfield_soc()
    pats = carfield_patterns()
    rows = []
    for mix in mixes:
        graphs = [edge.ALL_MODELS[m]() for m in mix]
        mc = compile_multi(graphs, soc, pats, time_budget_s=time_budget_s)
        if check_numerics:
            assert multi_plan_matches_oracle(mc.plan)
        co_ms = mc.runtime_ms
        br_ms = soc.cycles_to_ms(mc.best_response_makespan_cycles)
        pr1_ms = soc.cycles_to_ms(mc.baseline_makespan_cycles)
        seq_ms = soc.cycles_to_ms(mc.sequential_makespan_cycles)
        assert co_ms <= br_ms + 1e-6 <= pr1_ms + 2e-6 <= seq_ms + 3e-6, \
            (mix, co_ms, br_ms, pr1_ms, seq_ms)
        rows.append((mix, mc, co_ms, pr1_ms, seq_ms))
        if verbose:
            print(f"\nmix: {' + '.join(mix)}")
            print(f"  {'model':18s} {'alone (ms)':>11s} "
                  f"{'co-sched (ms)':>14s}")
            for i, m in enumerate(mix):
                alone = soc.cycles_to_ms(mc.singles[i].plan.makespan)
                print(f"  {m:18s} {alone:11.2f} "
                      f"{mc.tenant_latency_ms(i):14.2f}")
            thr_co = len(mix) / (co_ms / 1e3)
            thr_seq = len(mix) / (seq_ms / 1e3)
            gain = (1.0 - co_ms / br_ms) * 100.0 if br_ms else 0.0
            print(f"  round makespan: sequential {seq_ms:.2f} ms  "
                  f"PR-1 co-scheduled {pr1_ms:.2f} ms  "
                  f"best-response {br_ms:.2f} ms  "
                  f"joint {co_ms:.2f} ms "
                  f"({'+' if gain >= 0 else ''}{gain:.1f}% vs best-response, "
                  f"{mc.speedup:.2f}x vs sequential, "
                  f"origin={mc.plan.origin}, "
                  f"joint={mc.joint_stats()})")
            print(f"  L2 evictions: PR-1 plan "
                  f"{mc.baseline_plan.memory.evictions}  final plan "
                  f"{mc.plan.memory.evictions}")
            print(f"  aggregate throughput: {thr_seq:.1f} -> {thr_co:.1f} "
                  f"inf/s")
            util = mc.plan.utilization()
            seq_busy = {}
            for cm in mc.singles:
                for r, b in cm.plan.busy.items():
                    seq_busy[r] = seq_busy.get(r, 0.0) + b
            seq_util = {r: b / mc.sequential_makespan_cycles
                        for r, b in seq_busy.items()}
            print("  utilization (sequential):   " + "  ".join(
                f"{d}={u:.0%}" for d, u in sorted(seq_util.items())))
            print("  utilization (co-scheduled): " + "  ".join(
                f"{d}={u:.0%}" for d, u in sorted(util.items())))
    if verbose:
        improved = sum(1 for _, mc, co, pr1, _ in rows
                       if mc.plan.makespan < mc.baseline_makespan_cycles)
        joint_won = sum(1 for _, mc, *_ in rows
                        if mc.plan.makespan
                        < mc.best_response_makespan_cycles)
        print(f"\njoint <= best-response <= PR-1 <= sequential on "
              f"{len(rows)}/{len(rows)} mixes; strictly beat PR-1 on "
              f"{improved}, strictly beat best-response on {joint_won}")
    return rows


def rows_to_json(rows):
    out = []
    for mix, mc, co_ms, pr1_ms, seq_ms in rows:
        soc = mc.soc
        split = (mc.session.fullhouse_split
                 if mc.session is not None else None)
        if split is not None:
            split = {
                "winner": split["winner"],
                "budgets": split["budgets"],
                "equal_makespan_ms":
                    soc.cycles_to_ms(split["equal_makespan"]),
                "proportional_makespan_ms":
                    soc.cycles_to_ms(split["proportional_makespan"]),
            }
        out.append({
            "l2_split": split,
            "mix": list(mix),
            "sequential_ms": seq_ms,
            "pr1_coscheduled_ms": pr1_ms,
            "best_response_ms":
                soc.cycles_to_ms(mc.best_response_makespan_cycles),
            "retiled_coscheduled_ms": co_ms,
            "plan_origin": mc.plan.origin,
            "speedup_vs_sequential": mc.speedup,
            "retiled": mc.retiled,
            "hint_rounds": (mc.session.hint_rounds
                            if mc.session is not None else None),
            "joint_cp": mc.joint_stats(),
            "l2_evictions_pr1": mc.baseline_plan.memory.evictions,
            "l2_evictions_retiled": mc.plan.memory.evictions,
            "tenant_latency_ms": [mc.tenant_latency_ms(i)
                                  for i in range(len(mix))],
            "utilization": mc.plan.utilization(),
        })
    return out


def analysis_summary(rows, forced_mc=None):
    """Static plan-analyzer tallies aggregated across every deployment
    session the benchmark ran (the co-scheduling mixes plus the
    forced-contention compile): plans analyzed, ERROR/WARNING diagnostic
    counts, and per-rule counts.  The sessions run in ``"strict"``
    analysis mode, so a hazardous plan aborts the benchmark outright;
    ``check_regression`` additionally gates the report on zero ERROR
    diagnostics so the analyzer demonstrably ran over every plan."""
    sessions = [mc.session for _, mc, *_ in rows if mc.session is not None]
    if forced_mc is not None and forced_mc.session is not None:
        sessions.append(forced_mc.session)
    total = {"plans_analyzed": 0, "errors": 0, "warnings": 0,
             "by_rule": {}}
    for s in sessions:
        st = s.analysis_stats()
        total["plans_analyzed"] += st["plans_analyzed"]
        total["errors"] += st["errors"]
        total["warnings"] += st["warnings"]
        for rule, n in st["by_rule"].items():
            total["by_rule"][rule] = total["by_rule"].get(rule, 0) + n
    return total


# ---------------------------------------------------------------------------
# Forced contention: shrunk shared L2, sole-occupancy tiles thrash
# ---------------------------------------------------------------------------


def run_forced_contention(verbose: bool = True):
    """Two deep dense chains on a 2-accelerator SoC whose shared L2 holds
    only ~3 of the weight tensors (``repro.soc.testbed``, shared with
    tests/test_retile_contention.py): the compile-alone tilings split
    every layer across both accelerators, stretching weight residency
    across the co-tenant's interleaved kernels, and the co-schedule pays
    contention evictions.  Re-tiling under the shrunk per-tenant budgets
    wins the makespan with fewer SharedL2Allocator evictions."""
    soc, pats, graphs = forced_contention_setup()
    mc = compile_multi(graphs, soc, pats, requested_tiles=8,
                       time_budget_s=0.5)
    forced, err = _search_coschedule([cm.tiled for cm in mc.singles], soc,
                                     default_budgets(soc, 2), 3, 0)
    if verbose:
        print(f"\nforced contention (shared L2 = {FORCED_L2_KIB} KiB, "
              f"2 tenants x 7 dense layers of 18 KiB weights):")
        print(f"  sequential concat:                    "
              f"{mc.sequential_makespan_cycles:10.0f} cycles")
        if forced is None:
            print(f"  co-schedule of compile-alone tilings: infeasible "
                  f"({err})")
        else:
            print(f"  co-schedule of compile-alone tilings: "
                  f"{forced.makespan:10.0f} cycles, "
                  f"{forced.memory.evictions} L2 evictions")
        print(f"  contention-re-tiled co-schedule:      "
              f"{mc.plan.makespan:10.0f} cycles, "
              f"{mc.plan.memory.evictions} L2 evictions "
              f"(retiled={mc.retiled})")
    return mc, forced


# ---------------------------------------------------------------------------
# Partial occupancy: tenants arriving/leaving, served from the plan store
# ---------------------------------------------------------------------------


# a tenants-arriving/leaving trace over a 3-tenant deployment: indices are
# the tenants with queued work that round; repeats exercise the cache
OCCUPANCY_TRACE = [(0, 1, 2), (0, 1), (1, 2), (0, 2), (1,), (0, 1),
                   (0, 1, 2), (1, 2)]

PARTIAL_MIX = ("autoencoder", "ds_cnn", "resnet")


def run_partial_occupancy(verbose: bool = True, time_budget_s: float = 2.0,
                          trace=OCCUPANCY_TRACE, mc=None):
    """The occupancy win: before the deployment-session API, any round
    where only some tenants had queued work fell back to compile-alone
    plans run back-to-back; now ``plan_for(active)`` answers every subset
    from the occupancy-indexed plan store (lazily compiled, then cached),
    so partial rounds stay concurrent.

    ``mc`` reuses an already-compiled artifact for ``PARTIAL_MIX`` (the
    mix also appears in ``MIXES``, so ``main`` passes ``run``'s result
    instead of paying the 3-tenant compile twice)."""
    if mc is None:
        soc = carfield_soc()
        pats = carfield_patterns()
        graphs = [edge.ALL_MODELS[m]() for m in PARTIAL_MIX]
        mc = compile_multi(graphs, soc, pats, time_budget_s=time_budget_s)
    soc = mc.soc
    rows = []
    if verbose:
        print(f"\npartial occupancy ({' + '.join(PARTIAL_MIX)}): subset "
              f"co-schedule vs compile-alone back-to-back fallback")
        print(f"  {'active tenants':22s} {'subset (ms)':>12s} "
              f"{'fallback (ms)':>14s} {'gain':>7s}  origin")
    subset_total = fallback_total = 0.0
    negative_rounds = 0
    per_occupancy = {}
    for rnd, occ in enumerate(trace):
        ids = sorted(occ)
        before = mc.store_stats()
        plan = mc.plan_for(ids)
        after = mc.store_stats()
        subset_ms = soc.cycles_to_ms(plan.makespan)
        # the pre-session engine behaviour at partial occupancy: each
        # active tenant's COMPILE-ALONE schedule, back-to-back (not the
        # tenant_plan reference, which for a re-tiled tenant is a
        # different schedule — the gain must be honest vs the old engine)
        fallback_ms = soc.cycles_to_ms(
            sum(mc.singles[i].plan.makespan for i in ids))
        subset_total += subset_ms
        fallback_total += fallback_ms
        gain = (1.0 - subset_ms / fallback_ms) * 100.0 if fallback_ms else 0.0
        if gain < -1e-6:
            negative_rounds += 1
        row = {"round": rnd, "active": ids,
               "subset_coschedule_ms": subset_ms,
               "compile_alone_fallback_ms": fallback_ms,
               "gain_pct": gain,
               "plan_origin": plan.origin,
               # served without compiling anything new (the shared hit
               # counter also counts tenant-reference hits, so the compile
               # delta is the honest cache signal)
               "store_hit": after["compiles"] == before["compiles"]}
        rows.append(row)
        agg = per_occupancy.setdefault(
            "+".join(str(i) for i in ids),
            {"active": ids, "rounds": 0, "subset_coschedule_ms": subset_ms,
             "compile_alone_fallback_ms": fallback_ms, "gain_pct": gain,
             "plan_origin": plan.origin})
        agg["rounds"] += 1
        if verbose:
            names = " + ".join(PARTIAL_MIX[i] for i in ids)
            print(f"  {names:22s} {subset_ms:12.2f} {fallback_ms:14.2f} "
                  f"{gain:6.1f}%  {plan.origin}")
    stats = mc.store_stats()
    if verbose:
        gain = (1.0 - subset_total / fallback_total) * 100.0 \
            if fallback_total else 0.0
        print(f"  {'TOTAL over trace':22s} {subset_total:12.2f} "
              f"{fallback_total:14.2f} {gain:6.1f}%")
        print(f"  negative-gain rounds: {negative_rounds} "
              f"(per-occupancy re-tiling makes the compile-alone "
              f"back-to-back a hard floor)")
        print(f"  plan store: {stats['co_plans']} cached co-schedules, "
              f"{stats['compiles']} compiles, {stats['hits']} hits, "
              f"{stats['evictions']} LRU evictions ({len(trace)} rounds)")
    return {"mix": list(PARTIAL_MIX), "rounds": rows,
            "per_occupancy": per_occupancy,
            "negative_gain_rounds": negative_rounds,
            "subset_total_ms": subset_total,
            "fallback_total_ms": fallback_total,
            "plan_store": stats}


# ---------------------------------------------------------------------------
# Incremental re-solve: churny occupancy trace, warm vs from-scratch misses
# ---------------------------------------------------------------------------


# a churny trace: adjacent occupancies differ by (mostly) one tenant, so
# every miss has a Hamming-distance-1 neighbor already cached to warm-start
# from; repeats at the end exercise the cache (no re-compiles)
CHURN_TRACE = [(0, 1, 2), (1, 2), (2,), (0, 2), (0, 1, 2), (0, 1), (1,),
               (1, 2), (0, 1, 2), (0, 2)]


def _pct(vals, q):
    if not vals:
        return None
    vs = sorted(vals)
    k = max(min(math.ceil(q * len(vs)) - 1, len(vs) - 1), 0)
    return vs[k]


def run_incremental_resolve(verbose: bool = True,
                            time_budget_s: float = 1.0,
                            trace=CHURN_TRACE):
    """Per-miss compile latency under a churny partial-occupancy trace,
    incremental warm starts ON vs OFF (same mix, same trace, two fresh
    sessions).  With ``incremental=True`` each subset miss re-seeds the
    joint CP from the Hamming-nearest cached occupancy's tiling solutions
    and solves under the small ``incremental_time_budget_s``; from
    scratch it pays the full ``joint_time_budget_s``.  Reported: per-miss
    compile-latency p50/p99 both ways, the p99 speedup (gated >= 2x by
    ``check_regression``), the proportional-vs-equal L2 split winners,
    and the zero-negative-gain check (warm starts must never push a
    subset plan above the compile-alone concat floor)."""
    soc = carfield_soc()
    pats = carfield_patterns()
    sessions = {}
    for label, inc in (("incremental", True), ("scratch", False)):
        graphs = [edge.ALL_MODELS[m]() for m in PARTIAL_MIX]
        sessions[label] = compile_multi(graphs, soc, pats,
                                        time_budget_s=time_budget_s,
                                        incremental=inc).session
    out = {"mix": list(PARTIAL_MIX),
           "trace": [list(occ) for occ in trace]}
    negative_rounds = 0
    for label, session in sessions.items():
        subset_total = 0.0
        for occ in trace:
            ids = sorted(occ)
            plan = session.plan_for(ids)
            subset_total += session.request.soc.cycles_to_ms(plan.makespan)
            floor = sum(session.singles[i].plan.makespan for i in ids)
            if plan.makespan > floor + 1e-6:
                negative_rounds += 1
        lat = session.compile_latency_stats()
        walls = [e["wall_s"] for e in session.miss_events]
        out[label] = {
            "misses": len(walls),
            "p50_ms": _pct(walls, 0.50) * 1e3 if walls else None,
            "p99_ms": _pct(walls, 0.99) * 1e3 if walls else None,
            "subset_total_ms": subset_total,
            "warm_misses": sum(1 for e in session.miss_events if e["warm"]),
            "incremental_hits": lat["incremental_hits"],
            "prop_split_wins": lat["prop_split_wins"],
            "equal_split_wins": lat["equal_split_wins"],
            "store": session.store.stats(),
        }
    out["negative_gain_rounds"] = negative_rounds
    warm_p99 = out["incremental"]["p99_ms"]
    cold_p99 = out["scratch"]["p99_ms"]
    warm_p50 = out["incremental"]["p50_ms"]
    cold_p50 = out["scratch"]["p50_ms"]
    out["p99_speedup"] = (cold_p99 / warm_p99
                          if warm_p99 and cold_p99 else None)
    out["p50_speedup"] = (cold_p50 / warm_p50
                          if warm_p50 and cold_p50 else None)
    if verbose:
        print(f"\nincremental re-solve ({' + '.join(PARTIAL_MIX)}, "
              f"{len(trace)}-round churny trace, "
              f"{out['incremental']['misses']} misses each way):")
        print(f"  {'':14s} {'p50 (ms)':>10s} {'p99 (ms)':>10s} "
              f"{'warm':>5s} {'subset total (ms)':>18s}")
        for label in ("scratch", "incremental"):
            r = out[label]
            print(f"  {label:14s} {r['p50_ms']:10.0f} {r['p99_ms']:10.0f} "
                  f"{r['warm_misses']:5d} {r['subset_total_ms']:18.2f}")
        print(f"  p99 miss-compile speedup: {out['p99_speedup']:.2f}x "
              f"(p50 {out['p50_speedup']:.2f}x); "
              f"negative-gain rounds: {negative_rounds}")
        inc = out["incremental"]
        print(f"  L2 split arbitration: proportional won "
              f"{inc['prop_split_wins']}, equal won "
              f"{inc['equal_split_wins']}; "
              f"sidecar seeds: {inc['store']['solution_seeds']}, "
              f"re-misses: {inc['store']['re_misses']}")
    return out


# ---------------------------------------------------------------------------
# SLO-aware serving: open-loop arrival trace, FIFO vs deadline-driven rounds
# ---------------------------------------------------------------------------


def _open_loop(engine: MultiModelEngine, arrivals) -> MultiModelEngine:
    """Replay an open-loop trace: arrivals land at fixed wall times
    (``arrival_s``) regardless of service progress; the engine's idle
    clock jumps to the next arrival when its queues drain."""
    i = 0
    while i < len(arrivals) or engine.pending:
        while i < len(arrivals) and arrivals[i][0] <= engine.clock_s + 1e-12:
            t, tenant, prio, dl = arrivals[i]
            i += 1
            engine.submit(tenant, priority=prio, deadline_s=dl, arrival_s=t)
        if not engine.pending:
            if i >= len(arrivals):
                break
            engine.advance_clock(arrivals[i][0])
            continue
        engine.step()
    return engine


def build_slo_trace(mc, n_high: int = 24):
    """A deterministic open-loop trace for one compiled mix.

    The tenant most hurt by co-residency (largest co-scheduled vs alone
    completion ratio) becomes the HIGH class, with the deadline "one
    in-flight round plus my solo latency" (full-house makespan + the
    tenant's compile-alone latency): a request that arrives mid-round
    can always make it *if* the next round fast-paths it, so the
    deadline-driven composer attains it structurally, while FIFO — whose
    rounds under load co-schedule everyone — pays the tenant's
    co-scheduled completion on top of the alignment wait and misses in
    proportion to the co-vs-alone gap.  The remaining tenants submit
    deadline-less NORMAL/LOW bulk traffic slightly above their service
    rate, so their queues are (almost) never empty — the contention that
    forces the composer to actually choose."""
    soc = mc.soc
    n = len(mc.graphs)
    alone_s = [soc.cycles_to_ms(mc.singles[i].plan.makespan) / 1e3
               for i in range(n)]
    co_s = [soc.cycles_to_ms(mc.plan.tenant_makespans[i]) / 1e3
            for i in range(n)]
    full_s = soc.cycles_to_ms(mc.plan.makespan) / 1e3
    high = max(range(n), key=lambda i: co_s[i] / alone_s[i])
    bulk = [i for i in range(n) if i != high]
    # the longest round a HIGH arrival can land behind: the bulk-only
    # co-round (both engines run it while no HIGH request is queued)
    bulk_round_s = soc.cycles_to_ms(mc.plan_for(bulk).makespan) / 1e3
    deadline_s = bulk_round_s + alone_s[high]
    high_period = 3.0 * full_s
    arrivals = []
    for k in range(n_high):
        arrivals.append((k * high_period, high, Priority.HIGH, deadline_s))
    for i in range(n):
        if i == high:
            continue
        period = 0.8 * alone_s[i]          # saturating: queues stay busy
        prio = Priority.NORMAL if i % 2 == 0 else Priority.LOW
        t = 0.33 * period
        while t < n_high * high_period:
            arrivals.append((t, i, prio, None))
            t += period
    arrivals.sort(key=lambda a: (a[0], a[1]))
    return arrivals, high, deadline_s


def run_slo_trace(rows, verbose: bool = True):
    """FIFO vs SLO-aware serving on the same open-loop trace, per mix:
    SLO attainment and per-class p99 e2e latency.  The acceptance story:
    the HIGH class's attainment under the deadline-driven composer
    strictly exceeds the FIFO baseline on most mixes, with zero
    starvation events (bulk traffic still drains inside the composer's
    hard bound)."""
    out = []
    if verbose:
        print("\nSLO-aware serving (open-loop arrival trace): "
              "FIFO vs deadline-driven rounds")
        print(f"  {'mix':34s} {'class':7s} {'attain FIFO':>12s} "
              f"{'attain SLO':>11s} {'p99 FIFO':>10s} {'p99 SLO':>9s}")
    for mix, mc, *_ in rows:
        arrivals, high, deadline_s = build_slo_trace(mc)
        fifo = _open_loop(MultiModelEngine(mc, execute=False), arrivals)
        slo = _open_loop(MultiModelEngine(mc, composer=RoundComposer(),
                                          execute=False), arrivals)
        rep_f, rep_s = fifo.report(), slo.report()
        high_name = mc.graphs[high].name
        row = {
            "mix": list(mix),
            "high_tenant": high_name,
            "deadline_ms": deadline_s * 1e3,
            "requests": rep_f["served"],
            "fifo": {"slo_attainment": rep_f["slo_attainment"],
                     "per_class": rep_f["per_class"]},
            "slo": {"slo_attainment": rep_s["slo_attainment"],
                    "per_class": rep_s["per_class"]},
            "high_attainment_fifo":
                rep_f["per_class"]["HIGH"]["slo_attainment"],
            "high_attainment_slo":
                rep_s["per_class"]["HIGH"]["slo_attainment"],
            "starvation_events": rep_s["starvation_events"],
            "composer": rep_s["composer"],
        }
        row["high_win"] = (row["high_attainment_slo"] or 0.0) > \
            (row["high_attainment_fifo"] or 0.0) + 1e-12
        out.append(row)
        if verbose:
            for cls in ("HIGH", "NORMAL", "LOW"):
                cf, cs = rep_f["per_class"][cls], rep_s["per_class"][cls]
                if cf["served"] == 0:
                    continue
                af = cf["slo_attainment"]
                asl = cs["slo_attainment"]
                print(f"  {' + '.join(mix):34s} {cls:7s} "
                      f"{('-' if af is None else f'{af:.0%}'):>12s} "
                      f"{('-' if asl is None else f'{asl:.0%}'):>11s} "
                      f"{cf['p99_e2e_ms']:9.2f}m {cs['p99_e2e_ms']:8.2f}m")
    wins = sum(1 for r in out if r["high_win"])
    starved = sum(r["starvation_events"] for r in out)
    if verbose:
        print(f"  HIGH-class attainment strictly beats FIFO on "
              f"{wins}/{len(out)} mixes; {starved} starvation events")
    return {"mixes": out, "high_wins": wins, "total_mixes": len(out),
            "starvation_events": starved}


def run_async_first_round(rows, verbose: bool = True):
    """First-round latency at an *unseen* occupancy with the background
    compiler attached: the analytic round cost must stay within 1.1x the
    compile-alone concat floor (it equals the floor by construction — no
    joint solve runs on the dispatch path), and the wall-clock dispatch
    time is reported next to the background compile's wall time for
    scale."""
    mix, mc, *_ = rows[0]              # 2-tenant mix: singletons unseen
    session = mc.session
    occupancy = [0]
    floor_ms = mc.soc.cycles_to_ms(
        sum(mc.singles[i].plan.makespan for i in occupancy))
    bg = BackgroundCompiler(session, start=False)
    eng = MultiModelEngine(mc, async_compile=bg, execute=False)
    unseen = session.try_plan_for(occupancy) is None
    eng.submit(occupancy[0])
    t0 = time.perf_counter()
    eng.step()
    dispatch_wall_s = time.perf_counter() - t0
    first_round_ms = eng.clock_s * 1e3
    t0 = time.perf_counter()
    bg.run_pending()
    compile_wall_s = time.perf_counter() - t0
    ratio = first_round_ms / floor_ms if floor_ms else 1.0
    if verbose:
        print(f"\nasync compile at unseen occupancy "
              f"({mc.graphs[0].name} of {' + '.join(mix)}):")
        print(f"  first round: {first_round_ms:.2f} ms analytic "
              f"({ratio:.3f}x the compile-alone floor, unseen={unseen}); "
              f"dispatch wall {dispatch_wall_s * 1e3:.1f} ms vs "
              f"background compile wall {compile_wall_s:.2f} s")
    return {"mix": list(mix), "occupancy": occupancy,
            "floor_ms": floor_ms, "first_round_ms": first_round_ms,
            "floor_ratio": ratio, "unseen": unseen,
            "dispatch_wall_s": dispatch_wall_s,
            "compile_wall_s": compile_wall_s,
            "floor_rounds": eng.floor_rounds}


# ---------------------------------------------------------------------------
# Decomposed joint solve at scale: 10/16 tenants, equal budget both ways
# ---------------------------------------------------------------------------


DECOMPOSED_TENANT_COUNTS = (10, 16)


def run_decomposed_scaling(verbose: bool = True,
                           counts=DECOMPOSED_TENANT_COUNTS,
                           joint_budget_s: float = 1.5):
    """The joint CP's time budget stops scaling past ~10 tenants: one
    monolithic solve over every tenant's tile variables burns the whole
    budget exploring a space whose useful structure is per-device.  The
    decomposed solve clusters tenants by dominant-device affinity (with
    oversized clusters split to ``decompose_max_cluster`` members so
    subproblem size stays bounded), solves the clusters concurrently
    under split L2/DMA budgets, and
    reconciles with stage-2 cuts — then both candidates are arbitrated,
    so at EQUAL total budget the decomposed session can never ship a
    worse plan (gated by ``check_regression --solve``) and wins outright
    once the monolithic solve stops converging."""
    mixes = []
    for n in counts:
        soc, pats, graphs = hetero_setup(n, widths=(48, 48, 48, 48),
                                         l2_kib=64)
        arms = {}
        for label, dec in (("monolithic", "off"), ("decomposed", "on")):
            t0 = time.perf_counter()
            mc = compile_multi(
                graphs, soc, pats, requested_tiles=8,
                time_budget_s=0.3, max_hint_rounds=1,
                joint_time_budget_s=joint_budget_s,
                lazy_joint_time_budget_s=min(1.0, joint_budget_s),
                decompose=dec, max_workers=4)
            sess = mc.session
            solver = sess.solver_stats()
            arms[label] = {
                "makespan_ms": soc.cycles_to_ms(mc.plan.makespan),
                "plan_origin": mc.plan.origin,
                "compile_wall_s": time.perf_counter() - t0,
                "solver_solves": solver["solves"],
                "solver_nodes": solver["nodes"],
                "budget_exhausted": solver["budget_exhausted"],
                "decomposed_solves": solver["decomposed_solves"],
                "decomposed_fallbacks": solver["decomposed_fallbacks"],
                "decomposed_cuts": solver["decomposed_cuts"],
                "decomposed": solver["decomposed"],
                "analyzer_errors": sess.analysis_stats()["errors"],
            }
        mono = arms["monolithic"]["makespan_ms"]
        deco = arms["decomposed"]["makespan_ms"]
        row = {"tenants": n, "joint_budget_s": joint_budget_s,
               "monolithic": arms["monolithic"],
               "decomposed": arms["decomposed"],
               "win": bool(deco < mono - 1e-9)}
        mixes.append(row)
        if verbose:
            if n == counts[0]:
                print(f"\ndecomposed joint solve at scale (hetero SoC, "
                      f"{joint_budget_s:.1f} s joint budget both ways):")
                print(f"  {'tenants':>7s} {'monolithic (ms)':>16s} "
                      f"{'decomposed (ms)':>16s} {'gain':>7s}  "
                      f"clusters/cuts  origin")
            st = arms["decomposed"]["decomposed"] or {}
            gain = (1.0 - deco / mono) * 100.0 if mono else 0.0
            print(f"  {n:7d} {mono:16.2f} {deco:16.2f} {gain:6.1f}%  "
                  f"{st.get('clusters', '-')}/{st.get('cuts', '-'):>4}  "
                  f"{arms['decomposed']['plan_origin']}")
    wins = sum(1 for r in mixes if r["win"])
    if verbose:
        print(f"  decomposed <= monolithic at equal budget on "
              f"{len(mixes)}/{len(mixes)} mixes; strictly better on "
              f"{wins}")
    return {"mixes": mixes, "wins": wins}


# ---------------------------------------------------------------------------
# Compile pipeline: churny trace, reactive-only vs prefetching worker pool
# ---------------------------------------------------------------------------


def run_compile_pipeline(verbose: bool = True, time_budget_s: float = 1.0,
                         trace=CHURN_TRACE):
    """Request-visible cold-miss compile latency on the churny trace,
    reactive-only (the PR-6 behaviour: a miss enqueues its own compile,
    which lands *after* the degraded floor round) vs the worker pool
    with the occupancy-lattice prefetcher (every resolve also enqueues
    the Hamming-adjacent neighbors at lower priority, so the next churn
    step's plan is usually compiled before it is requested).

    The per-round *visible stall* is the background compile wall the
    round's occupancy itself paid (0 when the plan was already cached —
    i.e. prefetched in an earlier round).  Reported per arm: visible
    misses, stall p50/p99 over all rounds, and the prefetcher counters;
    ``check_regression --solve`` gates the prefetch arm's p99 at <= half
    the reactive arm's."""
    soc = carfield_soc()
    pats = carfield_patterns()
    out = {"mix": list(PARTIAL_MIX),
           "trace": [list(occ) for occ in trace]}
    for label, prefetch in (("reactive", False), ("prefetch", True)):
        graphs = [edge.ALL_MODELS[m]() for m in PARTIAL_MIX]
        session = compile_multi(graphs, soc, pats,
                                time_budget_s=time_budget_s).session
        bg = BackgroundCompiler(session, start=False, max_workers=2,
                                prefetch=prefetch)
        stalls, visible = [], 0
        for occ in trace:
            ids = sorted(occ)
            missed = session.try_plan_for(ids) is None
            if missed:                 # the engine's reactive miss path
                visible += 1
                bg.submit(ids)
            bg.observe(ids)            # every resolve feeds the lattice
            bg.run_pending()           # pool drains between rounds
            if missed:
                ev = next((e for e in reversed(session.miss_events)
                           if e["occupancy"] == tuple(ids)), None)
                stalls.append(ev["wall_s"] * 1e3 if ev else 0.0)
            else:
                stalls.append(0.0)
        out[label] = {
            "visible_misses": visible,
            "stall_p50_ms": _pct(stalls, 0.50),
            "stall_p99_ms": _pct(stalls, 0.99),
            "compiler": bg.stats(),
            "latency": {k: session.compile_latency_stats()[k]
                        for k in ("foreground", "background", "prefetch")},
        }
    react = out["reactive"]["stall_p99_ms"]
    pre = out["prefetch"]["stall_p99_ms"]
    out["p99_speedup"] = (react / pre) if pre else None
    if verbose:
        print(f"\ncompile pipeline ({' + '.join(PARTIAL_MIX)}, "
              f"{len(trace)}-round churny trace): reactive vs "
              f"prefetching pool")
        print(f"  {'':10s} {'visible misses':>14s} {'stall p50':>10s} "
              f"{'stall p99':>10s} {'prefetched':>11s}")
        for label in ("reactive", "prefetch"):
            r = out[label]
            print(f"  {label:10s} {r['visible_misses']:14d} "
                  f"{r['stall_p50_ms']:10.1f} {r['stall_p99_ms']:10.1f} "
                  f"{r['compiler']['prefetch_compiled']:11d}")
        sp = out["p99_speedup"]
        print(f"  visible cold-miss p99: "
              f"{react:.1f} ms -> {pre:.1f} ms "
              f"({'inf' if sp is None else f'{sp:.1f}'}x; gate >= 2x)")
    return out


def main(argv=None) -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the numeric allclose re-validation")
    ap.add_argument("--json", metavar="OUT", default=None,
                    help="write all reported numbers to OUT as JSON")
    args = ap.parse_args(argv)
    print("=" * 72)
    print("Multi-tenant co-scheduling — re-tiled vs. PR-1 vs. sequential")
    print("=" * 72)
    rows = run(check_numerics=not args.fast, verbose=True)
    mc, forced = run_forced_contention(verbose=True)
    async_first = run_async_first_round(rows, verbose=True)
    partial_mc = next((m for mix, m, *_ in rows if tuple(mix) == PARTIAL_MIX),
                      None)
    partial = run_partial_occupancy(verbose=True, mc=partial_mc)
    incremental = run_incremental_resolve(verbose=True)
    decomposed = run_decomposed_scaling(verbose=True)
    pipeline = run_compile_pipeline(verbose=True)
    slo = run_slo_trace(rows, verbose=True)
    if args.json:
        report = {
            "mixes": rows_to_json(rows),
            "forced_contention": {
                "l2_kib": FORCED_L2_KIB,
                "sequential_cycles": mc.sequential_makespan_cycles,
                "compile_alone_coschedule_cycles":
                    forced.makespan if forced is not None else None,
                "compile_alone_evictions":
                    forced.memory.evictions if forced is not None else None,
                "retiled_cycles": mc.plan.makespan,
                "retiled_evictions": mc.plan.memory.evictions,
                "retiled": mc.retiled,
            },
            "partial_occupancy": partial,
            "incremental_resolve": incremental,
            "decomposed_scaling": decomposed,
            "compile_pipeline": pipeline,
            "slo_serving": slo,
            "async_first_round": async_first,
            "analysis": analysis_summary(rows, mc),
        }
        out_dir = os.path.dirname(args.json)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"\nwrote JSON report to {args.json}")


if __name__ == "__main__":
    sys.exit(main())
