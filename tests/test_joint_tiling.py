"""Joint cross-tenant tiling CP (the PR-4 tentpole): one constraint
program over all tenants' tile variables — coordinated solutions, the
``joint <= best-response <= sequential`` property, per-occupancy re-tiling
with bitwise numerics against per-tiling reference schedules, the solver
time budget + best-response fallback, the ``PlanStore`` LRU bound, and the
configurable ``Objective`` tie-break chains."""

import dataclasses

import numpy as np
import pytest

from _hypo import given, settings, st

from repro.analysis import analyze_errors
from repro.core import cpsolver
from repro.core.api import compile_multi
from repro.core.deploy import (CompileRequest, DeploymentSession, Objective,
                               PlanStore, default_strategy_names,
                               get_strategy)
from repro.core.rewrite import rewrite
from repro.core.runtime import (execute_multi_plan, execute_plan,
                                init_inputs, init_params)
from repro.core.tiling import (JointTilingProblem, conservation_ok,
                               optimize_tiling)
from repro.soc.testbed import dense_chain, two_acc_soc

REQUESTED_TILES = 4
TIME_BUDGET_S = 0.5
JOINT_BUDGET_S = 2.0


def make_session(graphs, soc, pats, **kw) -> DeploymentSession:
    kw.setdefault("requested_tiles", REQUESTED_TILES)
    kw.setdefault("time_budget_s", TIME_BUDGET_S)
    kw.setdefault("joint_time_budget_s", JOINT_BUDGET_S)
    return DeploymentSession(CompileRequest(
        graphs=graphs, soc=soc, patterns=pats, **kw))


def three_tenant_session(**kw) -> DeploymentSession:
    soc, pats = two_acc_soc(64, 8.0)
    graphs = [dense_chain("a", [64, 64, 64]),
              dense_chain("b", [48, 48, 48]),
              dense_chain("c", [32, 32, 32])]
    return make_session(graphs, soc, pats, **kw)


@pytest.fixture(scope="module")
def session():
    return three_tenant_session()


@pytest.fixture(scope="module")
def mc(session):
    return session.compile()


# ---------------------------------------------------------------------------
# JointCpModel: the multi-tenant composition layer
# ---------------------------------------------------------------------------


def test_joint_cp_model_merges_keyed_loads():
    """Loads with the same key accumulate across tenants (the shared-device
    coupling); the objective is the max over merged keys."""
    jm = cpsolver.JointCpModel()
    x0 = jm.new_int(0, 0, 4, "x0")
    x1 = jm.new_int(1, 0, 4, "x1")
    jm.add_eq({x0: 1.0}, -2.0)           # x0 == 2
    jm.add_eq({x1: 1.0}, -3.0)           # x1 == 3
    jm.add_load("dev", {x0: 1.0})
    jm.add_load("dev", {x1: 1.0})        # same key: summed
    jm.add_load("other", {x0: 1.0})
    sol = jm.solve(time_budget_s=1.0)
    assert sol.objective == pytest.approx(5.0)   # 2 + 3 on "dev"
    assert jm.tenant_values(sol.values, 0) == {x0: 2}
    assert jm.tenant_values(sol.values, 1) == {x1: 3}


def test_joint_cp_model_shared_capacity():
    """One capacity constraint spanning both tenants' variables forces the
    joint optimum to trade them off (neither tenant can max out alone)."""
    jm = cpsolver.JointCpModel()
    x0 = jm.new_int(0, 0, 10, "x0")
    x1 = jm.new_int(1, 0, 10, "x1")
    # maximize-ish: makespan term rewards balance; capacity caps the sum
    jm.add_capacity({x0: 1.0, x1: 1.0}, 10.0)
    jm.add_load("d0", {x0: -1.0}, const=10.0)    # 10 - x0
    jm.add_load("d1", {x1: -1.0}, const=10.0)    # 10 - x1
    sol = jm.solve(time_budget_s=1.0)
    assert sol.values[x0] + sol.values[x1] <= 10
    assert sol.objective == pytest.approx(5.0)   # balanced split 5/5


def test_joint_cp_model_zero_budget_raises():
    jm = cpsolver.JointCpModel()
    jm.new_int(0, 0, 1, "x")
    with pytest.raises(cpsolver.Infeasible):
        jm.solve(time_budget_s=0.0)


# ---------------------------------------------------------------------------
# JointTilingProblem: coordinated per-tenant solutions from one solve
# ---------------------------------------------------------------------------


def joint_setup():
    soc, pats = two_acc_soc(64, 8.0)
    graphs = [dense_chain("a", [64, 64, 64]),
              dense_chain("b", [48, 48, 48])]
    return soc, pats, graphs


def test_joint_problem_solutions_conserve_tiles():
    soc, pats, graphs = joint_setup()
    prob = JointTilingProblem(graphs, soc, pats,
                              requested_tiles=REQUESTED_TILES)
    sols = prob.solve(time_budget_s=JOINT_BUDGET_S)
    assert len(sols) == len(graphs)
    for g, s in zip(graphs, sols):
        assert conservation_ok(g, s)
        assert rewrite(g, soc, s).repairs == 0


def test_joint_warm_start_is_feasible():
    """Per-tenant compile-alone solutions always map to a feasible joint
    start (the overflow variable absorbs their combined footprint)."""
    soc, pats, graphs = joint_setup()
    alone = [optimize_tiling(g, soc, pats,
                             requested_tiles=REQUESTED_TILES,
                             time_budget_s=TIME_BUDGET_S) for g in graphs]
    prob = JointTilingProblem(graphs, soc, pats,
                              requested_tiles=REQUESTED_TILES)
    hint = prob.warm_start(alone)
    assert hint is not None
    prob.joint._finalize()
    assert prob.joint.model._feasible(hint)


def test_joint_objective_not_worse_than_warm_start():
    """The joint solve only moves away from the warm start when the joint
    (shared-resource) objective improves."""
    soc, pats, graphs = joint_setup()
    alone = [optimize_tiling(g, soc, pats,
                             requested_tiles=REQUESTED_TILES,
                             time_budget_s=TIME_BUDGET_S) for g in graphs]
    prob = JointTilingProblem(graphs, soc, pats,
                              requested_tiles=REQUESTED_TILES)
    hint = prob.warm_start(alone)
    prob.joint._finalize()             # loads merge at solve time
    warm_obj = prob.joint.model._obj_value(hint)
    sols = prob.solve(warm=alone, time_budget_s=JOINT_BUDGET_S)
    assert sols[0].objective <= warm_obj + 1e-6


# ---------------------------------------------------------------------------
# The acceptance property: joint <= best-response <= PR-1 <= sequential
# ---------------------------------------------------------------------------


def assert_ordering(mc):
    joint = mc.plan.makespan
    br = mc.best_response_makespan_cycles
    pr1 = mc.baseline_makespan_cycles
    seq = mc.sequential_makespan_cycles
    assert joint <= br + 1e-6, (joint, br)
    assert br <= pr1 + 1e-6, (br, pr1)
    assert pr1 <= seq + 1e-6, (pr1, seq)


def test_joint_le_best_response_le_sequential(mc):
    assert_ordering(mc)


def test_best_response_plan_matches_joint_free_session():
    """Phase A of the joint session's fixpoint IS the best-response
    session: a session compiled without ``joint-cp`` lands on the same
    makespan, so 'joint <= best-response' compares against the real PR-2/3
    result, not a strawman."""
    joint_s = three_tenant_session()
    joint_mc = joint_s.compile()
    br_names = [n for n in default_strategy_names("matcha")
                if n != "joint-cp"]
    br_s = three_tenant_session(strategies=br_names)
    br_mc = br_s.compile()
    assert joint_s.best_response_plan is not None
    assert joint_s.best_response_plan.makespan == \
        pytest.approx(br_mc.plan.makespan)
    assert joint_mc.plan.makespan <= br_mc.plan.makespan + 1e-6


WIDTHS = [16, 32, 48, 64, 96]


@settings(max_examples=3, deadline=None)
@given(st.data())
def test_joint_property_random_mixes(data):
    """joint <= best-response <= PR-1 <= sequential on random 2-3 tenant
    mixes, and every stored occupancy beats its compile-alone concat."""
    l2_kib = data.draw(st.sampled_from([48, 64, 96]))
    soc, pats = two_acc_soc(l2_kib, 8.0)
    n = data.draw(st.integers(2, 3))
    graphs = [dense_chain(f"m{i}",
                          [data.draw(st.sampled_from(WIDTHS))
                           for _ in range(3)])
              for i in range(n)]
    mc = compile_multi(graphs, soc, pats, requested_tiles=REQUESTED_TILES,
                       time_budget_s=TIME_BUDGET_S,
                       joint_time_budget_s=JOINT_BUDGET_S)
    assert_ordering(mc)
    for ids in ([i] for i in range(n)):
        plan = mc.plan_for(ids)
        assert not analyze_errors(plan)
        alone = sum(mc.singles[i].plan.makespan for i in ids)
        assert plan.makespan <= alone + 1e-6


# ---------------------------------------------------------------------------
# Per-occupancy re-tiling: numerics + the no-negative-gain floor
# ---------------------------------------------------------------------------


def all_subsets(n):
    out = []
    for mask in range(1, 2 ** n):
        out.append([i for i in range(n) if mask >> i & 1])
    return out


def test_every_occupancy_beats_compile_alone_concat(mc):
    """The acceptance criterion behind the benchmark's negative-gain fix:
    every occupancy's co-schedule beats (or ties) running its members'
    compile-alone schedules back-to-back."""
    for ids in all_subsets(len(mc.graphs)):
        plan = mc.plan_for(ids)
        assert not analyze_errors(plan)
        alone = sum(mc.singles[i].plan.makespan for i in ids)
        assert plan.makespan <= alone + 1e-6, (ids, plan.makespan, alone)


def test_bitwise_numerics_every_served_occupancy(session, mc):
    """For every occupancy the store serves, the co-scheduled execution is
    bitwise the per-tenant reference execution *of the tiling that
    occupancy actually uses* (per-occupancy re-tiling must not perturb
    numerics)."""
    for ids in all_subsets(len(mc.graphs)):
        plan = mc.plan_for(ids)
        params = [init_params(mc.graphs[i], 2 * i) for i in ids]
        inputs = [init_inputs(mc.graphs[i], 2 * i + 1) for i in ids]
        outs = execute_multi_plan(plan, inputs, params)
        for pos, i in enumerate(ids):
            ref = session.reference_plan(i, plan.tenants[pos])
            want = execute_plan(ref, inputs[pos], params[pos])
            for t in mc.graphs[i].outputs:
                assert np.array_equal(np.asarray(want[t]),
                                      np.asarray(outs[pos][t])), (ids, i, t)


def test_singleton_occupancy_prefers_alone_tiling(session, mc):
    """A lone tenant's occupancy plan is never worse than its compile-alone
    schedule (the full-house re-tiling no longer taxes low occupancy)."""
    for i in range(len(mc.graphs)):
        plan = mc.plan_for([i])
        assert plan.makespan <= mc.singles[i].plan.makespan + 1e-6


# ---------------------------------------------------------------------------
# Solver time budget -> best-response fallback
# ---------------------------------------------------------------------------


def test_joint_timeout_engages_best_response_fallback():
    """With a zero joint budget every joint solve fails; the session falls
    back to best-response re-tiling and still produces a valid plan whose
    makespan keeps the ordering guarantees."""
    s = three_tenant_session(joint_time_budget_s=0.0)
    mc = s.compile()
    assert s.joint_fallbacks >= 1
    assert s.joint_solves == 0
    assert not analyze_errors(mc.plan)
    assert_ordering(mc)
    assert mc.joint_stats()["fallbacks"] == s.joint_fallbacks


def test_joint_disabled_contributes_nothing():
    s = three_tenant_session(joint_tiling=False)
    mc = s.compile()
    assert s.joint_solves == 0 and s.joint_fallbacks == 0
    assert not analyze_errors(mc.plan)


def test_joint_fallback_delegates_when_sole_retiler():
    """joint-cp as the only re-tiling strategy + exhausted budget: the
    best-response fallback is delegated to contention-retile so the
    session still re-tiles."""
    s = three_tenant_session(
        strategies=["tile-centric", "all-or-nothing", "heft", "joint-cp"],
        joint_time_budget_s=0.0)
    mc = s.compile()
    assert s.joint_fallbacks >= 1
    assert not analyze_errors(mc.plan)


# ---------------------------------------------------------------------------
# PlanStore LRU bound
# ---------------------------------------------------------------------------


def _dummy_plan(tag: int):
    """Stand-in object; the store never introspects stored plans."""
    return ("plan", tag)


def test_plan_store_lru_evicts_least_recent():
    store = PlanStore(max_entries=2)
    store.co_plan([0], lambda: _dummy_plan(0))
    store.co_plan([1], lambda: _dummy_plan(1))
    store.co_plan([0], lambda: _dummy_plan(99))      # refresh [0]
    store.co_plan([2], lambda: _dummy_plan(2))       # evicts [1], not [0]
    assert store.lru_evictions == 1
    assert [0] in store and [2] in store
    assert [1] not in store
    # the evicted occupancy recompiles on its next miss
    before = store.compiles
    store.co_plan([1], lambda: _dummy_plan(1))
    assert store.compiles == before + 1
    assert store.stats()["evictions"] == 2          # [0] went this time


def test_plan_store_never_evicts_protected_full_house():
    store = PlanStore(max_entries=1)
    store.seed([0, 1, 2], _dummy_plan(7))
    store.protect([0, 1, 2])
    store.co_plan([0], lambda: _dummy_plan(0))
    store.co_plan([1], lambda: _dummy_plan(1))
    assert [0, 1, 2] in store                        # protected survives
    assert store.stats()["co_plans"] >= 1
    # tenant reference schedules are exempt from the co-plan bound
    store.seed_tenant((0, "sig"), _dummy_plan(5))
    store.co_plan([2], lambda: _dummy_plan(2))
    assert store.has_tenant((0, "sig"))


def test_plan_store_never_evicts_just_inserted_entry():
    """At max_entries=1 with a protected full house, a miss must not evict
    the plan it just compiled — the next lookup is a hit, not an endless
    recompile loop."""
    store = PlanStore(max_entries=1)
    store.seed([0, 1], _dummy_plan(9))
    store.protect([0, 1])
    store.co_plan([0], lambda: _dummy_plan(0))
    compiles = store.compiles
    store.co_plan([0], lambda: _dummy_plan(99))
    assert store.compiles == compiles            # hit, no recompile
    assert [0] in store and [0, 1] in store


def test_plan_store_max_entries_validation():
    with pytest.raises(ValueError):
        PlanStore(max_entries=0)
    with pytest.raises(ValueError):
        CompileRequest(graphs=[dense_chain("a", [16, 16])],
                       soc=two_acc_soc(64, 8.0)[0],
                       patterns=two_acc_soc(64, 8.0)[1],
                       store_max_entries=0)


def test_session_store_bound_respected():
    s = three_tenant_session(store_max_entries=2)
    mc = s.compile()                  # full house seeded + protected
    for ids in all_subsets(len(mc.graphs)):
        mc.plan_for(ids)
    stats = s.store.stats()
    assert stats["co_plans"] <= 2 + 1            # bound + protected full house
    assert stats["evictions"] > 0
    assert frozenset(range(len(mc.graphs))) in s.store.occupancies()


# ---------------------------------------------------------------------------
# Objective tie-break chains
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Mem:
    evictions: int


@dataclasses.dataclass
class _Dma:
    bytes: int


@dataclasses.dataclass
class _FakePlan:
    makespan: float
    memory: _Mem
    dmas: list
    retile_rounds: int = 0


def _plan(makespan, evictions=0, dma_bytes=0, retile_rounds=0):
    return _FakePlan(makespan, _Mem(evictions), [_Dma(dma_bytes)],
                     retile_rounds)


def test_objective_chain_order_matters():
    obj = Objective(tie_breaks=("dma_bytes", "evictions"))
    assert obj.chain == ("dma_bytes", "evictions")
    # dma_bytes decides first even though evictions disagree
    assert obj.better(_plan(10.0, evictions=9, dma_bytes=1),
                      _plan(10.0, evictions=0, dma_bytes=2))
    # dma_bytes tied -> evictions decide
    assert obj.better(_plan(10.0, evictions=0, dma_bytes=2),
                      _plan(10.0, evictions=9, dma_bytes=2))


def test_objective_retile_rounds_key():
    obj = Objective(tie_breaks=("retile_rounds",))
    assert obj.better(_plan(10.0, retile_rounds=0),
                      _plan(10.0, retile_rounds=2))
    # plans without the attribute score 0 (ExecutionPlan has no rounds)
    del_plan = _plan(10.0)
    assert obj.value(del_plan) == (10.0, 0.0)


def test_objective_chain_validation_and_legacy():
    with pytest.raises(ValueError):
        Objective(tie_breaks=("nope",))
    legacy = Objective(tie_break="evictions")
    assert legacy.chain == ("evictions",)
    assert Objective(tie_break=None).chain == ()
    # an explicit chain overrides the legacy single key
    both = Objective(tie_break="evictions", tie_breaks=("dma_bytes",))
    assert both.chain == ("dma_bytes",)


def test_objective_chain_threads_through_schedule_multi():
    """A chained objective drives the co-schedule search end to end (the
    duck-typed ``better`` is all schedule_multi needs — unchanged)."""
    soc, pats = two_acc_soc(64, 8.0)
    graphs = [dense_chain("a", [32, 32]), dense_chain("b", [32, 32])]
    s = make_session(graphs, soc, pats)
    s.objective = Objective(tie_breaks=("evictions", "dma_bytes",
                                        "retile_rounds"))
    mc = s.compile()
    assert not analyze_errors(mc.plan)
    assert_ordering(mc)


# ---------------------------------------------------------------------------
# Registry / defaults
# ---------------------------------------------------------------------------


def test_joint_strategy_registered_and_default():
    assert get_strategy("joint-cp").name == "joint-cp"
    assert get_strategy("joint-cp").joint
    assert get_strategy("decomposed-cp").name == "decomposed-cp"
    assert get_strategy("decomposed-cp").joint
    for mode in ("matcha", "matcha_nt"):
        names = default_strategy_names(mode)
        # the joint CPs run last, after the best-response strategies
        assert names[-2:] == ["joint-cp", "decomposed-cp"]
        off = default_strategy_names(mode, retile_for_contention=False)
        assert "joint-cp" not in off and "decomposed-cp" not in off


# ---------------------------------------------------------------------------
# Engine: singleton occupancy dispatch
# ---------------------------------------------------------------------------


def test_engine_singleton_uses_occupancy_plan(mc):
    from repro.serve.engine import MultiModelEngine
    eng = MultiModelEngine(mc)
    rid = eng.submit(1)
    done = eng.step()
    assert done == [rid]
    assert eng.co_rounds == 0
    assert eng.solo_dispatches == 1
    single = mc.plan_for([1])
    assert eng.done[rid].latency_ms == pytest.approx(
        mc.soc.cycles_to_ms(single.tenant_makespans[0]))
    rep = eng.report()
    assert rep["joint_cp"] == mc.joint_stats()
    assert "evictions" in rep["plan_store"]
