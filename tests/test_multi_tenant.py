"""Multi-tenant co-scheduler: golden makespan, numerics regression vs. the
single-model oracle, and the serving engine on top of the co-schedule."""

import numpy as np
import pytest

from repro.core.api import compile_multi
from repro.core.memplan import validate_plan
from repro.core.runtime import (execute_multi_plan, execute_plan,
                                init_inputs, init_params,
                                multi_plan_matches_oracle)
from repro.core.schedule import validate_multi_schedule
from repro.models import edge
from repro.serve.engine import MultiModelEngine
from repro.soc.carfield import carfield_patterns, carfield_soc

SOC = carfield_soc()
PATS = carfield_patterns()

# fixed MLPerf-Tiny-style pair for the makespan golden test
GOLDEN_PAIR = ("autoencoder", "ds_cnn")


@pytest.fixture(scope="module")
def golden_mc():
    graphs = [edge.ALL_MODELS[m]() for m in GOLDEN_PAIR]
    return compile_multi(graphs, SOC, PATS, time_budget_s=1.0)


def test_coscheduled_makespan_beats_sequential(golden_mc):
    """Concurrency guard: re-tiled co-scheduled makespan <= PR-1
    co-scheduled makespan (compile-alone tilings) <= running each model
    alone back-to-back (the compile-each-model baseline)."""
    assert golden_mc.plan.makespan <= \
        golden_mc.baseline_makespan_cycles + 1e-6
    assert golden_mc.baseline_makespan_cycles <= \
        golden_mc.sequential_makespan_cycles + 1e-6
    assert golden_mc.speedup >= 1.0


def test_coschedule_is_feasible(golden_mc):
    assert validate_multi_schedule(golden_mc.plan) == []
    assert validate_plan(golden_mc.plan.memory) == []
    assert golden_mc.plan.memory.peak <= SOC.l2.size


def test_tenant_makespans_bounded(golden_mc):
    plan = golden_mc.plan
    for i in range(len(GOLDEN_PAIR)):
        assert 0.0 < plan.tenant_makespans[i] <= plan.makespan + 1e-6


def test_multi_numerics_matches_oracle(golden_mc):
    """Co-scheduled interleaved execution == per-model whole-graph oracle."""
    assert multi_plan_matches_oracle(golden_mc.plan)


def test_multi_numerics_bitmatch_single_plan(golden_mc):
    """Interleaving tenants must not perturb numerics at all: each tenant's
    outputs are bit-identical to executing a single-model plan over the
    same tiled graph alone (``tenant_plan`` — the compile-alone plan
    unless the tenant was contention-re-tiled)."""
    graphs = golden_mc.graphs
    params = [init_params(g, 2 * i) for i, g in enumerate(graphs)]
    inputs = [init_inputs(g, 2 * i + 1) for i, g in enumerate(graphs)]
    multi_out = execute_multi_plan(golden_mc.plan, inputs, params)
    for i, g in enumerate(graphs):
        single_out = execute_plan(golden_mc.tenant_plan(i), inputs[i],
                                  params[i])
        for t in g.outputs:
            assert np.array_equal(np.asarray(single_out[t]),
                                  np.asarray(multi_out[i][t])), (g.name, t)


def test_plan_for_partial_occupancy_no_fallback(golden_mc):
    """The session-backed artifact answers partial occupancy with a real
    validated co-schedule (the pre-PR-3 behaviour returned None and the
    engine fell back to compile-alone plans)."""
    for active in ([0], [1]):
        plan = golden_mc.plan_for(active)
        assert plan is not None
        assert validate_multi_schedule(plan) == []
        assert plan.makespan <= \
            golden_mc.tenant_plan(active[0]).makespan + 1e-6
    assert golden_mc.plan_for([0, 1]) is golden_mc.plan
    assert golden_mc.store_stats()["co_plans"] >= 1


def test_multi_engine_mixed_traffic(golden_mc):
    eng = MultiModelEngine(golden_mc)
    rids = [eng.submit("autoencoder"), eng.submit("ds_cnn"),
            eng.submit("autoencoder")]
    results = eng.run()
    assert set(results) == set(rids)
    rep = eng.report()
    assert rep["served"] == 3
    # 2 requests paired into one co-scheduled round, 1 solo leftover
    assert rep["co_rounds"] == 1
    assert rep["solo_dispatches"] == 1
    assert rep["throughput_inf_per_s"] > 0
    # co-scheduled requests report the tenant's co-schedule latency
    co = [r for r in eng.done.values() if r.co_scheduled]
    assert len(co) == 2
    for r in co:
        assert r.latency_ms == pytest.approx(
            golden_mc.tenant_latency_ms(r.tenant))


def test_multi_engine_output_correctness(golden_mc):
    """Engine-served outputs equal the direct execution of the plan the
    engine dispatched, for the same inputs and the engine's own
    parameters.  A lone tenant runs the session's subset co-schedule for
    its occupancy (``plan_for([0])``), whose tiling may differ from the
    reference schedule ``tenant_plan(0)``: a plan runs as one jitted
    program, and XLA may fuse a tile's weight slice into its dot, so two
    tilings agree to the oracle tolerance rather than bit for bit."""
    eng = MultiModelEngine(golden_mc, seed=7)
    g0 = golden_mc.graphs[0]
    x = init_inputs(g0, 99)
    rid = eng.submit(0, inputs=x)
    eng.run()
    want = execute_multi_plan(golden_mc.plan_for([0]), [x],
                              [eng.params[0]])[0]
    ref = execute_plan(golden_mc.tenant_plan(0), x, eng.params[0])
    for t in g0.outputs:
        assert np.array_equal(np.asarray(want[t]),
                              np.asarray(eng.results[rid][t]))
        np.testing.assert_allclose(np.asarray(ref[t]),
                                   np.asarray(eng.results[rid][t]),
                                   atol=1e-4, rtol=1e-4)
