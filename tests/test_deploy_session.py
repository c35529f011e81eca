"""Deployment-session API: typed objective semantics, the occupancy-indexed
``PlanStore`` (miss compiles once, then hits), subset co-schedules from
``plan_for`` (validated, never worse than the sequential concatenation of
their members, bitwise numerics vs. the ``tenant_plan`` references), the
candidate-strategy registry, the contention-hint fixpoint bound, and the
``compile_model`` alt-plan aliasing fix."""

import dataclasses

import numpy as np
import pytest

from _hypo import given, settings, st

from repro.analysis import analyze_errors
from repro.core.api import compile_model, compile_multi
from repro.core.deploy import (ASYNC_MODES, STRATEGY_REGISTRY, CandidateSpec,
                               CompileRequest, DeploymentSession, Objective,
                               PlanStore, default_strategy_names,
                               get_strategy)
from repro.core.runtime import (execute_multi_plan, execute_plan,
                                init_inputs, init_params)
from repro.core.schedule import MultiExecutionPlan
from repro.soc.testbed import dense_chain, two_acc_soc

REQUESTED_TILES = 4
TIME_BUDGET_S = 0.5


def three_tenant_session() -> DeploymentSession:
    soc, pats = two_acc_soc(64, 8.0)
    graphs = [dense_chain("a", [64, 64, 64]),
              dense_chain("b", [48, 48, 48]),
              dense_chain("c", [32, 32, 32])]
    return DeploymentSession(CompileRequest(
        graphs=graphs, soc=soc, patterns=pats,
        requested_tiles=REQUESTED_TILES, time_budget_s=TIME_BUDGET_S))


@pytest.fixture(scope="module")
def session():
    return three_tenant_session()


@pytest.fixture(scope="module")
def mc(session):
    return session.compile()


def two_subsets(n):
    return [[i, j] for i in range(n) for j in range(i + 1, n)]


# ---------------------------------------------------------------------------
# plan_for at partial occupancy (the acceptance criterion)
# ---------------------------------------------------------------------------


def test_plan_for_answers_every_two_tenant_subset(mc, session):
    """Every 2-tenant subset of a 3-tenant compile gets a real, validated
    co-schedule — no ``None`` fallback.  Since PR 4 the subset's tilings
    are re-decided per occupancy (full-house winner, compile-alone, or a
    fresh joint solve over just the subset), so each tenant's tiling must
    be one with a servable reference schedule rather than necessarily the
    full-house winner's."""
    for ids in two_subsets(len(mc.graphs)):
        plan = mc.plan_for(ids)
        assert isinstance(plan, MultiExecutionPlan)
        assert len(plan.tenants) == len(ids)
        assert not analyze_errors(plan)
        for pos, i in enumerate(ids):
            ref = session.reference_plan(i, plan.tenants[pos])
            assert ref.tiled is plan.tenants[pos]


def test_subset_makespan_beats_member_concat(mc):
    """A subset co-schedule is never worse than running its members'
    reference schedules back-to-back (the sequential-concat candidate
    inside ``schedule_multi`` guarantees it)."""
    for ids in two_subsets(len(mc.graphs)):
        plan = mc.plan_for(ids)
        seq = sum(mc.tenant_plan(i).makespan for i in ids)
        assert plan.makespan <= seq + 1e-6


def test_subset_numerics_bitmatch_tenant_plan(mc, session):
    """Subset co-scheduled execution is bitwise the members' single-model
    reference execution over the tiling each tenant uses in *that*
    occupancy — partial occupancy (now with per-occupancy re-tiling) must
    not perturb numerics any more than the full house does."""
    for ids in two_subsets(len(mc.graphs)):
        plan = mc.plan_for(ids)
        params = [init_params(mc.graphs[i], 2 * i) for i in ids]
        inputs = [init_inputs(mc.graphs[i], 2 * i + 1) for i in ids]
        multi_out = execute_multi_plan(plan, inputs, params)
        for pos, i in enumerate(ids):
            g = mc.graphs[i]
            ref = session.reference_plan(i, plan.tenants[pos])
            single_out = execute_plan(ref, inputs[pos], params[pos])
            for t in g.outputs:
                assert np.array_equal(np.asarray(single_out[t]),
                                      np.asarray(multi_out[pos][t])), \
                    (g.name, t)


def test_plan_for_full_house_is_the_compiled_plan(mc):
    assert mc.plan_for(range(len(mc.graphs))) is mc.plan
    assert mc.plan_for([1, 0, 2, 1]) is mc.plan     # dedup + any order


def test_plan_for_singleton(mc):
    for i in range(len(mc.graphs)):
        plan = mc.plan_for([i])
        assert not analyze_errors(plan)
        assert plan.makespan <= mc.tenant_plan(i).makespan + 1e-6


def test_plan_for_rejects_bad_occupancy(session, mc):
    with pytest.raises(ValueError):
        session.plan_for([])
    with pytest.raises(ValueError):
        session.plan_for([0, 99])


# ---------------------------------------------------------------------------
# PlanStore cache contract
# ---------------------------------------------------------------------------


def test_plan_store_miss_compiles_once_then_hits():
    session = three_tenant_session()
    mc = session.compile()
    store = session.store
    base = store.stats()
    p1 = mc.plan_for([0, 1])
    after_miss = store.stats()
    # one co-plan miss (plus possibly tenant-reference misses for re-tiled
    # members, derived once as part of the same subset compile)
    assert after_miss["co_plans"] == base["co_plans"] + 1
    assert after_miss["misses"] >= base["misses"] + 1
    assert after_miss["compiles"] >= base["compiles"] + 1
    compiles_after_first = after_miss["compiles"]
    p2 = mc.plan_for([0, 1])
    p3 = mc.plan_for([1, 0])
    after_hits = store.stats()
    assert p1 is p2 and p1 is p3          # same cached object, any order
    assert after_hits["compiles"] == compiles_after_first
    assert after_hits["hits"] == after_miss["hits"] + 2
    assert frozenset([0, 1]) in store.occupancies()


def test_plan_store_precompile(session, mc):
    subsets = two_subsets(len(mc.graphs))
    session.precompile(subsets)
    for ids in subsets:
        assert ids in session.store
    # everything precompiled: plan_for is now pure hits
    before = session.store.stats()
    for ids in subsets:
        session.plan_for(ids)
    after = session.store.stats()
    assert after["compiles"] == before["compiles"]
    assert after["hits"] == before["hits"] + len(subsets)


def test_tenant_plan_cached_across_rounds(mc, session):
    """Re-tiled tenants' reference schedules are derived once and reused
    (the old code rebuilt them per call path)."""
    plans1 = [mc.tenant_plan(i) for i in range(len(mc.graphs))]
    before = session.store.stats()
    plans2 = [mc.tenant_plan(i) for i in range(len(mc.graphs))]
    after = session.store.stats()
    for a, b in zip(plans1, plans2):
        assert a is b
    assert after["compiles"] == before["compiles"]


# ---------------------------------------------------------------------------
# Typed objective
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Mem:
    evictions: int


@dataclasses.dataclass
class _FakePlan:
    makespan: float
    memory: _Mem


def _plan(makespan, evictions=0):
    return _FakePlan(makespan, _Mem(evictions))


def test_objective_primary_dominates():
    obj = Objective()
    assert obj.better(_plan(10.0, 99), _plan(11.0, 0))
    assert not obj.better(_plan(11.0, 0), _plan(10.0, 99))


def test_objective_eviction_tie_break():
    obj = Objective()
    assert obj.better(_plan(10.0, 1), _plan(10.0, 3))
    assert not obj.better(_plan(10.0, 3), _plan(10.0, 1))
    assert not obj.better(_plan(10.0, 2), _plan(10.0, 2))   # full tie
    # within tolerance counts as a primary tie
    assert obj.better(_plan(10.0 + 1e-12, 1), _plan(10.0, 3))


def test_objective_no_tie_break():
    obj = Objective(tie_break=None)
    assert not obj.better(_plan(10.0, 1), _plan(10.0, 3))


def test_objective_none_handling():
    obj = Objective()
    assert obj.better(_plan(1.0), None)
    assert not obj.better(None, _plan(1.0))


def test_objective_validation():
    with pytest.raises(ValueError):
        Objective(primary="energy")
    with pytest.raises(ValueError):
        Objective(tie_break="latency")
    with pytest.raises(ValueError):
        Objective(tolerance=-1.0)


# ---------------------------------------------------------------------------
# Strategy registry + request validation
# ---------------------------------------------------------------------------


def test_registry_has_named_strategies():
    for name in ("tile-centric", "all-or-nothing", "heft",
                 "sequential-baseline", "contention-retile",
                 "complementary", "joint-cp"):
        assert name in STRATEGY_REGISTRY
        assert get_strategy(name).name == name
    with pytest.raises(KeyError):
        get_strategy("nope")


def test_default_strategy_names_by_mode():
    assert default_strategy_names("matcha") == \
        ["tile-centric", "all-or-nothing", "heft", "contention-retile",
         "complementary", "joint-cp", "decomposed-cp"]
    assert default_strategy_names("matcha_nt") == \
        ["all-or-nothing", "heft", "contention-retile", "complementary",
         "joint-cp", "decomposed-cp"]
    assert default_strategy_names("matcha", retile_for_contention=False) == \
        ["tile-centric", "all-or-nothing", "heft"]
    for mode in ("tvm", "match"):
        assert default_strategy_names(mode) == ["sequential-baseline"]


def test_candidate_spec_labels_match_legacy():
    assert CandidateSpec("matcha", 16, True).label == "matcha@T16"
    assert CandidateSpec("matcha", 16, False).label == "matcha@T16!h"
    assert CandidateSpec("heft", 8, True).label == "heft@T8"


def test_compile_request_validation():
    soc, pats = two_acc_soc(64, 8.0)
    g = dense_chain("a", [32, 32])
    with pytest.raises(ValueError):
        CompileRequest(graphs=[], soc=soc, patterns=pats)
    with pytest.raises(ValueError):
        CompileRequest(graphs=[g], soc=soc, patterns=pats, mode="xla")
    with pytest.raises(ValueError):
        CompileRequest(graphs=[g], soc=soc, patterns=pats,
                       max_hint_rounds=0)
    with pytest.raises(ValueError):
        CompileRequest(graphs=[g], soc=soc, patterns=pats,
                       budgets=[1, 2])


def test_hint_rounds_bounded(session, mc):
    # two bounded phases since PR 4: best-response rounds, then joint
    # rounds — each capped by max_hint_rounds
    assert 0 <= session.hint_rounds <= 2 * session.request.max_hint_rounds


def test_fixpoint_never_worse_than_single_round():
    """More hint rounds essentially never hurt.  Within one compile the
    incumbent carries over and is replaced only on strict improvement,
    so each *trajectory* is monotone — but a 3-round compile's joint
    phase starts from a different (better) phase-A incumbent than a
    1-round compile's, and different hints can land the joint solve in a
    marginally different basin.  Since the schedulers pin in-flight
    accesses against eviction (hazard fix), the two trajectories differ
    by a few cycles here, so the comparison carries a small relative
    tolerance rather than claiming exact cross-run dominance."""
    soc, pats = two_acc_soc(56, 12.0)
    graphs = [dense_chain("a", [96] * 4), dense_chain("b", [96] * 4)]

    def compiled(rounds):
        return compile_multi(graphs, soc, pats,
                             requested_tiles=REQUESTED_TILES,
                             time_budget_s=TIME_BUDGET_S,
                             max_hint_rounds=rounds)

    one, three = compiled(1), compiled(3)
    assert three.plan.makespan <= one.plan.makespan * 1.001


# ---------------------------------------------------------------------------
# compile_model aliasing fix
# ---------------------------------------------------------------------------


def test_winner_alt_plan_keeps_candidate_mode():
    """The winner's ``alt_plans`` entry must keep its own candidate-trial
    mode: relabelling the returned plan with the requested mode used to
    mutate the shared object, drifting the stored candidate's label."""
    soc, pats = two_acc_soc(64, 8.0)
    cm = compile_model(dense_chain("a", [64, 64, 64]), soc, pats,
                       requested_tiles=REQUESTED_TILES,
                       time_budget_s=TIME_BUDGET_S)
    assert cm.plan.mode == "matcha"
    stage_of = {"heft": "matcha_nt"}    # heft seeds schedule as matcha_nt
    for label, plan in cm.alt_plans.items():
        stage1 = label.split("@")[0]
        assert plan.mode == stage_of.get(stage1, stage1), label
    # the returned plan is a relabelled copy sharing the winning schedule
    winner = min(cm.candidates, key=lambda k: cm.candidates[k])
    assert cm.plan is not cm.alt_plans[winner]
    assert cm.plan.makespan == cm.alt_plans[winner].makespan
    assert cm.plan.tiled is cm.alt_plans[winner].tiled


# ---------------------------------------------------------------------------
# Property: random mixes, random subsets
# ---------------------------------------------------------------------------


WIDTHS = [16, 32, 48, 64]


@settings(max_examples=3, deadline=None)
@given(st.data())
def test_subset_coschedule_properties(data):
    """On random mixes, every 2-tenant subset co-schedule is feasible and
    never worse than the sequential concatenation of its members."""
    l2_kib = data.draw(st.sampled_from([48, 64, 96]))
    soc, pats = two_acc_soc(l2_kib, 8.0)
    n_tenants = data.draw(st.integers(2, 3))
    graphs = []
    for i in range(n_tenants):
        widths = [data.draw(st.sampled_from(WIDTHS)) for _ in range(3)]
        graphs.append(dense_chain(f"m{i}", widths))
    mc = compile_multi(graphs, soc, pats, requested_tiles=REQUESTED_TILES,
                       time_budget_s=TIME_BUDGET_S)
    for ids in two_subsets(n_tenants):
        plan = mc.plan_for(ids)
        assert not analyze_errors(plan)
        seq = sum(mc.tenant_plan(i).makespan for i in ids)
        assert plan.makespan <= seq + 1e-6
        # second lookup is a cache hit: same object
        assert mc.plan_for(ids) is plan


def test_mode_applies_to_async_modes_only():
    assert set(ASYNC_MODES) == {"matcha", "matcha_nt"}


# ---------------------------------------------------------------------------
# Engine at partial occupancy: subset co-rounds instead of solo fallback
# ---------------------------------------------------------------------------


def test_engine_subset_co_round(mc):
    """With work queued for 2 of 3 tenants, the engine runs the subset
    co-schedule (one round, both concurrent) instead of falling back to
    back-to-back compile-alone dispatches."""
    from repro.serve.engine import MultiModelEngine
    eng = MultiModelEngine(mc)
    r0 = eng.submit(0)
    r2 = eng.submit(2)
    done = eng.step()
    assert sorted(done) == sorted([r0, r2])
    assert eng.co_rounds == 1
    assert eng.subset_co_rounds == 1
    assert eng.solo_dispatches == 0
    sub = mc.plan_for([0, 2])
    for pos, rid in enumerate([r0, r2]):
        req = eng.done[rid]
        assert req.co_scheduled
        assert req.latency_ms == pytest.approx(
            mc.soc.cycles_to_ms(sub.tenant_makespans[pos]))
    rep = eng.report()
    assert rep["subset_co_rounds"] == 1
    assert rep["plan_store"]["co_plans"] >= 1


def test_engine_subset_outputs_match_reference(mc, session):
    """Engine-served subset-round outputs equal the direct reference-plan
    execution (over the tiling the round's occupancy actually uses) for
    the same inputs and the engine's own parameters."""
    from repro.serve.engine import MultiModelEngine
    eng = MultiModelEngine(mc, seed=5)
    xs = {i: init_inputs(mc.graphs[i], 40 + i) for i in (1, 2)}
    rids = {i: eng.submit(i, inputs=xs[i]) for i in (1, 2)}
    eng.run()
    sub = mc.plan_for([1, 2])
    for pos, i in enumerate((1, 2)):
        ref = session.reference_plan(i, sub.tenants[pos])
        want = execute_plan(ref, xs[i], eng.params[i])
        got = eng.results[rids[i]]
        for t in mc.graphs[i].outputs:
            assert np.array_equal(np.asarray(want[t]), np.asarray(got[t]))


def test_engine_lone_tenant_uses_singleton_plan(mc):
    """A lone active tenant dispatches the cached singleton occupancy plan
    (a solo dispatch, not a co-round) — never worse than the full-house
    reference schedule."""
    from repro.serve.engine import MultiModelEngine
    eng = MultiModelEngine(mc)
    rid = eng.submit(1)
    done = eng.step()
    assert done == [rid]
    assert eng.co_rounds == 0
    assert eng.solo_dispatches == 1
    single = mc.plan_for([1])
    assert single.makespan <= mc.tenant_plan(1).makespan + 1e-6
    assert eng.done[rid].latency_ms == pytest.approx(
        mc.soc.cycles_to_ms(single.tenant_makespans[0]))
