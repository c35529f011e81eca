"""Fleet-scale serving benchmark: contention-aware placement vs the
round-robin and random baselines over a simulated many-SoC rack.

A fleet of N identical Carfield SoCs (default 16, ``--socs`` up to 64)
serves four MLPerf-Tiny model classes, each replicated several times.
One deterministic open-loop arrival trace is replayed against THREE
fleets that differ only in tenant placement:

  * ``contention`` — the CP/greedy hybrid of
    :func:`repro.fleet.placement.place_contention_aware`, whose edge
    weights are predicted pairwise co-residency contention from the
    joint-CP cost model (``excess = pair - max(alone)``),
  * ``round_robin`` — deal tenants across SoCs in submission order,
  * ``random`` — uniform feasible assignment, median of several seeds.

All fleets share one :class:`~repro.fleet.placement.PlanCache` (the
rack is homogeneous, so the same class mix compiles once) — the
comparison isolates *placement*, not compile luck.  The most
contention-sensitive class carries HIGH priority and a deadline; the
rest submit saturating bulk traffic.  Reported per placement: trace
makespan, HIGH-class SLO attainment, round counts, and router
warm/cold routes.  The acceptance gate
(``benchmarks.check_regression --fleet``): contention-aware strictly
beats BOTH baselines on trace makespan and is no worse on HIGH
attainment.

A failure scenario then replays the same trace against the
contention-aware fleet with one mid-trace SoC death: queued requests
evacuate, orphaned classes re-host on survivors (compiles warm-started
from the dead SoC's solutions sidecar), and the router audit must show
ZERO dropped requests with every migrated plan analyzer-clean — also
gated.

    PYTHONPATH=src python -m benchmarks.fleet [--fast] [--socs N]
        [--json OUT]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

from repro.fleet import (ContentionModel, FailureEvent, Fleet, FleetConfig,
                         FleetRebalancer, FleetRouter, PlanCache,
                         balanced_utilization, default_demand,
                         place_contention_aware, place_random,
                         place_round_robin, replay_open_loop)
from repro.launch.cache import enable_compile_cache
from repro.models import edge
from repro.serve.admission import Priority
from repro.soc.carfield import carfield_patterns, carfield_soc

CLASSES = ("autoencoder", "ds_cnn", "mobilenet", "resnet")
# Skewed tenant census (relative replica counts per class).  Real
# fleets do not onboard one tenant of each architecture in lockstep:
# here the heavy classes dominate, so bad heavy+heavy co-residency
# (mobilenet+resnet) cannot be fully avoided — the placements differ
# in HOW MANY such pairs they create and which light classes absorb
# the rest, which is exactly the decision contention-awareness informs.
TENANT_WEIGHTS = {"autoencoder": 4, "ds_cnn": 6,
                  "mobilenet": 12, "resnet": 8}
RANDOM_SEEDS = (1, 2, 3)
# The trace's demand shape is the rate-free default (every replica
# equally busy) with the HIGH class throttled to leave deadline
# headroom; absolute rates are then scaled so the CONTENTION-AWARE
# placement's bottleneck utilization (balanced_utilization) sits at
# RHO_TARGET.  Above 1.0 the fleet is open-loop overloaded, so trace
# makespan measures realized capacity directly: every placement ends
# with makespan ~ horizon x (its true bottleneck rho), and a placement
# that wastes slots on needless heavy+heavy rounds finishes late.
RHO_TARGET = 1.10
HIGH_SHAPE = 0.6


def build_config(n_socs: int, capacity: int = 2) -> FleetConfig:
    return FleetConfig(
        soc_factory=lambda: (carfield_soc(), carfield_patterns()),
        n_socs=n_socs, capacity=capacity, requested_tiles=8,
        time_budget_s=0.5, joint_time_budget_s=1.0,
        lazy_joint_time_budget_s=0.5, incremental_time_budget_s=0.5,
        execute=False, prefetch=True, max_workers=2)


def build_tenants(n_socs: int, capacity: int) -> list:
    """Apportion ``TENANT_WEIGHTS`` over all but two of the rack's
    slots (largest-remainder), then interleave by largest remaining
    count.  A nearly-full rack is where placement matters: almost
    every SoC hosts a co-residency set, so the router cannot hide a
    bad placement behind contention-free single-tenant SoCs.  The two
    free slots are the failure scenario's migration headroom.  Replica
    counts are capped at ``n_socs`` (same-class tenants never share a
    SoC) with the overflow re-apportioned."""
    slots = n_socs * capacity - 2
    total = sum(TENANT_WEIGHTS.values())
    counts = {c: (w * slots) // total
              for c, w in TENANT_WEIGHTS.items()}
    rema = sorted(CLASSES, key=lambda c: -(
        TENANT_WEIGHTS[c] * slots % total))
    for c in rema:
        if sum(counts.values()) == slots:
            break
        counts[c] += 1
    for c in CLASSES:                 # feasibility: <= one replica/SoC
        counts[c] = min(counts[c], n_socs)
    while sum(counts.values()) < slots:
        c = max(CLASSES, key=lambda c: (n_socs - counts[c],
                                        TENANT_WEIGHTS[c]))
        counts[c] += 1
    left = dict(counts)
    tenants = []
    while any(left.values()):
        for c in sorted(CLASSES, key=lambda c: -left[c]):
            if left[c]:
                tenants.append(c)
                left[c] -= 1
    return tenants


def build_demand_shape(contention: ContentionModel, tenants) -> tuple:
    """The trace's per-class relative arrival rates plus the HIGH
    class: the rate-free default (each replica equally busy), with the
    most contention-sensitive class — largest worst-pair makespan
    excess relative to its alone time — throttled to ``HIGH_SHAPE`` of
    its share so its deadline stays attainable under load."""
    alone = {c: contention.alone_s(c) for c in CLASSES}
    high = max(CLASSES, key=lambda c: max(
        contention.excess_s(c, o) for o in CLASSES if o != c) / alone[c])
    shape = default_demand(tenants, contention)
    shape[high] *= HIGH_SHAPE
    return shape, high


def build_trace(contention: ContentionModel, rates: dict, high: str,
                duration_rounds: int) -> tuple:
    """One deterministic open-loop trace shared by every placement:
    per-class periodic arrivals at absolute ``rates`` (req/s) with
    deterministic phase offsets.  The HIGH class carries priority and a
    ``2.5x alone`` deadline; the rest submit deadline-less bulk."""
    alone = {c: contention.alone_s(c) for c in CLASSES}
    deadline_s = 2.5 * alone[high]
    horizon = duration_rounds * max(alone.values())
    arrivals = []
    for c in CLASSES:
        period = 1.0 / rates[c]
        t = 0.37 * period            # deterministic phase offset
        while t < horizon:
            if c == high:
                arrivals.append((t, c, Priority.HIGH, deadline_s))
            else:
                arrivals.append((t, c, Priority.NORMAL, None))
            t += period
    arrivals.sort(key=lambda a: (a[0], a[1]))
    return arrivals, deadline_s


def replay_placement(config: FleetConfig, graphs, cache: PlanCache,
                     contention: ContentionModel, placement, trace,
                     failures=(), with_rebalancer: bool = False) -> dict:
    fleet = Fleet(config, graphs, cache=cache, contention=contention)
    fleet.apply_placement(placement)
    router = FleetRouter(fleet, split=placement.demand_split)
    reb = (FleetRebalancer(fleet, router)
           if (with_rebalancer or failures) else None)
    summary = replay_open_loop(fleet, router, trace, failures=failures,
                               rebalancer=reb)
    summary["placement"] = {
        "method": placement.method,
        "assignment": ["+".join(names) for names in placement.assignment],
        "predicted_round_s": placement.objective_s,
        "max_rho": placement.max_rho,
        "capacity_ratio": placement.capacity_ratio,
        "stats": placement.stats,
    }
    return summary


def _row(summary: dict) -> dict:
    high = summary["per_class"]["HIGH"]
    return {
        "makespan_s": summary["makespan_s"],
        "high_attainment": high["slo_attainment"],
        "high_served": high["served"],
        "served": summary["served"],
        "dropped": summary["router"]["dropped"],
        "starvation_events": summary["starvation_events"],
        "warm_routes": summary["router"]["warm_routes"],
        "cold_routes": summary["router"]["cold_routes"],
        "max_rho": summary["placement"]["max_rho"],
        "capacity_ratio": summary["placement"]["capacity_ratio"],
        "predicted_round_s": summary["placement"]["predicted_round_s"],
    }


def run_failover_pod(config: FleetConfig, graphs, cache: PlanCache,
                     contention: ContentionModel, rates: dict, tenants,
                     high: str, duration_rounds: int,
                     verbose: bool = True) -> dict:
    """Forced-migration proof: a 4-SoC pod hosting ONE replica of each
    class, so a mid-trace SoC death orphans its classes — unlike the
    replicated main fleet, serving can only continue by re-hosting them
    on survivors (cache-hit rebind or sidecar-warm-started compile),
    and every migrated-tenant plan must come out analyzer-clean."""
    pod_socs = 4
    pod_config = dataclasses.replace(config, n_socs=pod_socs)
    pod_tenants = list(CLASSES)
    placement = place_contention_aware(pod_tenants, pod_socs,
                                       config.capacity, contention)
    counts: dict = {}
    for t in tenants:
        counts[t] = counts.get(t, 0) + 1
    # one replica per class here vs counts[c] in the main fleet, run
    # at ~70% of the per-replica rate so the pod serves, not drowns
    pod_rates = {c: 0.7 * rates[c] / counts[c] for c in CLASSES}
    trace, _ = build_trace(contention, pod_rates, high,
                           duration_rounds // 2)
    fleet = Fleet(pod_config, graphs, cache=cache, contention=contention)
    fleet.apply_placement(placement)
    victim = fleet.hosts_of(high)[0].soc_id
    t_fail = trace[len(trace) // 2][0]
    del fleet
    summary = replay_placement(
        pod_config, graphs, cache, contention, placement, trace,
        failures=[FailureEvent(at_s=t_fail, soc_id=victim, kind="fail")],
        with_rebalancer=True)
    reb = summary["rebalance"]
    row = _row(summary)
    row.update(
        socs=pod_socs, requests=len(trace), victim_soc=victim,
        at_s=t_fail, migrations=reb["migrations"],
        migration_cache_hits=reb["cache_hits"],
        seeded_occupancies=reb["seeded_occupancies"],
        analyzer_errors=reb["analyzer_errors"],
        recovery_s=reb["recovery_s"],
        requeued=summary["router"]["requeued"])
    if verbose:
        print(f"\n  failover pod: {pod_socs} SoCs, 1 replica/class; "
              f"SoC {victim} (hosting {high}) dies at "
              f"t={t_fail * 1e3:.2f} ms")
        print(f"    served {row['served']}/{len(trace)}, dropped "
              f"{row['dropped']}, requeued {row['requeued']}, "
              f"{row['migrations']} forced migration(s) "
              f"({row['migration_cache_hits']} cache hit(s), "
              f"{row['seeded_occupancies']} sidecar occupancies seeded), "
              f"analyzer errors {row['analyzer_errors']}, recovery "
              f"{[f'{r * 1e3:.1f}ms' for r in row['recovery_s']]}")
    return row


def run(n_socs: int = 16, capacity: int = 2, duration_rounds: int = 60,
        verbose: bool = True) -> dict:
    config = build_config(n_socs, capacity)
    graphs = [edge.ALL_MODELS[m]() for m in CLASSES]
    cache = PlanCache(config, graphs)
    contention = ContentionModel(cache)
    tenants = build_tenants(n_socs, capacity)

    shape, high = build_demand_shape(contention, tenants)
    placements = {
        "contention": place_contention_aware(tenants, n_socs, capacity,
                                             contention, demand=shape),
        "round_robin": place_round_robin(tenants, n_socs, capacity,
                                         contention, demand=shape),
    }
    randoms = {seed: place_random(tenants, n_socs, capacity, contention,
                                  seed=seed, demand=shape)
               for seed in RANDOM_SEEDS}
    # absolute rates: the contention-aware placement's bottleneck sits
    # at RHO_TARGET (balanced_utilization is linear in demand, so the
    # placements and their relative max_rho are scale-invariant)
    scale = RHO_TARGET / placements["contention"].max_rho
    rates = {c: shape[c] * scale for c in CLASSES}
    for p in list(placements.values()) + list(randoms.values()):
        p.max_rho *= scale
    trace, deadline_s = build_trace(contention, rates, high,
                                    duration_rounds)
    if verbose:
        print(f"fleet: {n_socs} SoCs x capacity {capacity}, "
              f"{len(tenants)} tenants over {len(CLASSES)} classes, "
              f"{len(trace)} requests")
        print(f"  HIGH class: {high} (deadline {deadline_s * 1e3:.2f} ms); "
              f"pair contention edges:")
        for pair, edge_stats in contention.edges().items():
            print(f"    {pair:24s} excess {edge_stats['excess_s']*1e3:7.3f} "
                  f"ms  slowdown {edge_stats['slowdown']:.2f}x")
    results = {name: _row(replay_placement(config, graphs, cache,
                                           contention, p, trace))
               for name, p in placements.items()}

    rand_rows = [_row(replay_placement(config, graphs, cache, contention,
                                       p, trace))
                 for p in randoms.values()]
    rand_rows.sort(key=lambda r: r["makespan_s"])
    results["random"] = rand_rows[len(rand_rows) // 2]   # median makespan
    results["random"]["seeds"] = len(RANDOM_SEEDS)
    results["random"]["seed_makespans"] = [r["makespan_s"]
                                           for r in rand_rows]

    if verbose:
        print(f"\n  {'placement':14s} {'makespan (s)':>13s} "
              f"{'HIGH attain':>12s} {'served':>7s} {'dropped':>8s} "
              f"{'max rho':>8s}")
        for name in ("round_robin", "random", "contention"):
            r = results[name]
            att = r["high_attainment"]
            print(f"  {name:14s} {r['makespan_s']:13.4f} "
                  f"{('-' if att is None else f'{att:.1%}'):>12s} "
                  f"{r['served']:7d} {r['dropped']:8d} "
                  f"{r['max_rho']:8.3f}")
        ca, rr = results["contention"], results["round_robin"]
        rd = results["random"]
        print(f"  contention vs round_robin makespan: "
              f"{(1 - ca['makespan_s'] / rr['makespan_s']) * 100:+.1f}%  "
              f"vs random: "
              f"{(1 - ca['makespan_s'] / rd['makespan_s']) * 100:+.1f}%")

    # -- failure scenario: same trace, one mid-trace SoC death ------------
    fail_placement = placements["contention"]
    fleet = Fleet(config, graphs, cache=cache, contention=contention)
    fleet.apply_placement(fail_placement)
    # kill a SoC hosting the HIGH class, mid-trace
    victim = fleet.hosts_of(high)[0].soc_id
    t_fail = trace[len(trace) // 2][0]
    del fleet
    failure_summary = replay_placement(
        config, graphs, cache, contention, fail_placement, trace,
        failures=[FailureEvent(at_s=t_fail, soc_id=victim, kind="fail")],
        with_rebalancer=True)
    reb = failure_summary["rebalance"]
    fail_row = _row(failure_summary)
    fail_row.update(
        victim_soc=victim, at_s=t_fail,
        migrations=reb["migrations"],
        migration_cache_hits=reb["cache_hits"],
        seeded_occupancies=reb["seeded_occupancies"],
        analyzer_errors=reb["analyzer_errors"],
        recovery_s=reb["recovery_s"],
        requeued=failure_summary["router"]["requeued"])
    if verbose:
        att = fail_row["high_attainment"]
        print(f"\n  failure scenario: SoC {victim} (hosting {high}) dies "
              f"at t={t_fail * 1e3:.2f} ms")
        print(f"    served {fail_row['served']}, dropped "
              f"{fail_row['dropped']}, requeued {fail_row['requeued']}, "
              f"{fail_row['migrations']} migration(s) "
              f"({fail_row['migration_cache_hits']} cache hit(s), "
              f"{fail_row['seeded_occupancies']} sidecar occupancies "
              f"seeded), analyzer errors {fail_row['analyzer_errors']}, "
              f"HIGH attainment "
              f"{('-' if att is None else f'{att:.1%}')}, recovery "
              f"{[f'{r * 1e3:.1f}ms' for r in fail_row['recovery_s']]}")

    pod_row = run_failover_pod(config, graphs, cache, contention, rates,
                               tenants, high, duration_rounds,
                               verbose=verbose)

    # -- async serving arm: the same contention placement replayed with
    # the background compile pipeline on — every SoC hosting a mix
    # shares ONE BackgroundCompiler through the PlanCache (fleet-wide
    # compile dedup) and each host seeds the occupancy-lattice
    # prefetcher with its tenant set.  With the cache warm this must
    # serve identically to the synchronous arm (gated by
    # ``check_regression --fleet``); the compiler counters prove the
    # pool ran clean (no failed keys).
    async_config = dataclasses.replace(config, async_compile=True)
    async_summary = replay_placement(async_config, graphs, cache,
                                     contention, placements["contention"],
                                     trace)
    async_row = _row(async_summary)
    async_row["compilers"] = cache.stats()["compilers"]
    cache.stop_compilers()
    if verbose:
        n_comp = len(async_row["compilers"])
        submitted = sum(c.get("submitted", 0)
                        for c in async_row["compilers"].values())
        dup = sum(c.get("duplicates", 0)
                  for c in async_row["compilers"].values())
        print(f"\n  async serving arm (shared compile pools): makespan "
              f"{async_row['makespan_s']:.4f} s, served "
              f"{async_row['served']}, dropped {async_row['dropped']}; "
              f"{n_comp} shared pool(s), {submitted} submit(s), "
              f"{dup} fleet-wide dedup bounce(s)")

    return {
        "socs": n_socs, "capacity": capacity, "tenants": len(tenants),
        "classes": list(CLASSES), "requests": len(trace),
        "high_class": high, "deadline_ms": deadline_s * 1e3,
        "rho_target": RHO_TARGET,
        "rates_per_s": {c: round(v, 3) for c, v in rates.items()},
        "contention_edges": contention.edges(),
        "placements": results,
        "failure": fail_row,
        "failover_pod": pod_row,
        "async_serving": async_row,
        "plan_cache": cache.stats(),
    }


def main(argv=None) -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--socs", type=int, default=16,
                    help="fleet size (default 16; the paper-scale sweep "
                         "uses 64)")
    ap.add_argument("--capacity", type=int, default=2,
                    help="tenant slots per SoC (default 2)")
    ap.add_argument("--fast", action="store_true",
                    help="shorter trace (CI lane)")
    ap.add_argument("--json", metavar="OUT", default=None,
                    help="write the report to OUT as JSON")
    args = ap.parse_args(argv)
    print("=" * 72)
    print("Fleet-scale serving — contention-aware placement vs baselines")
    print("=" * 72)
    report = run(n_socs=args.socs, capacity=args.capacity,
                 duration_rounds=30 if args.fast else 60, verbose=True)
    if args.json:
        out_dir = os.path.dirname(args.json)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"\nwrote JSON report to {args.json}")


if __name__ == "__main__":
    sys.exit(main())
