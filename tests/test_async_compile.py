"""Async background plan compiles: the serving engine never stalls on a
``plan_for`` miss.

Deterministic fake-clock tests drive a :class:`BackgroundCompiler` built
with ``start=False`` and pump it by hand (``run_pending``), so "the
background compile lands" is an explicit, reproducible event on the
engine's analytic clock — no threads, no sleeps.  The contract under
test:

  * a miss at an unseen occupancy serves the compile-alone concat floor
    *immediately* (one round, cost = sum of the members' compile-alone
    makespans — within 1.1x of the floor by construction, the acceptance
    bound) and enqueues exactly one compile job;
  * once the compile lands, the next round at that occupancy dispatches
    the real subset co-schedule, which beats or ties the floor (the
    floor is a hard candidate inside ``_compile_subset``);
  * numerics are bitwise against ``session.reference_plan`` on *both*
    sides of the swap — the floor round over the compile-alone tilings,
    the swapped round over whatever tilings the subset plan chose;
  * ``DeploymentSession.submit_compile`` compiles each occupancy exactly
    once under concurrent misses (the only test here that uses real
    threads, plus one end-to-end run with the worker thread on).
"""

import threading

import numpy as np
import pytest

from repro.analysis import analyze_errors
from repro.core.deploy import CompileRequest, DeploymentSession
from repro.core.runtime import execute_plan, init_inputs
from repro.serve.compiler_thread import BackgroundCompiler
from repro.serve.engine import MultiModelEngine
from repro.soc.testbed import dense_chain, two_acc_soc


def make_session() -> DeploymentSession:
    soc, pats = two_acc_soc(64, 8.0)
    graphs = [dense_chain("a", [64, 64, 64]),
              dense_chain("b", [48, 48, 48]),
              dense_chain("c", [32, 32, 32])]
    return DeploymentSession(CompileRequest(
        graphs=graphs, soc=soc, patterns=pats,
        requested_tiles=4, time_budget_s=0.5))


@pytest.fixture(scope="module")
def session():
    s = make_session()
    s.compile()
    return s


def floor_cycles(mc, ids):
    return sum(mc.singles[i].plan.makespan for i in ids)


# ---------------------------------------------------------------------------
# Fake-clock floor -> swap (occupancy [0, 1])
# ---------------------------------------------------------------------------


def test_floor_immediately_then_swap_after_compile_lands(session):
    """The deterministic swap story, on one engine: miss -> floor round
    now, pump the compiler, hit -> subset co-round; numerics bitwise vs
    the session's reference plans on both sides of the swap."""
    mc = session.compile()
    bg = BackgroundCompiler(session, start=False)
    eng = MultiModelEngine(mc, async_compile=bg, seed=3)
    assert session.try_plan_for([0, 1]) is None     # genuinely unseen

    xs = {i: init_inputs(mc.graphs[i], 30 + i) for i in (0, 1)}
    rids = {i: eng.submit(i, inputs=xs[i]) for i in (0, 1)}
    done = eng.step()                   # miss: floor round, no stall
    assert sorted(done) == sorted(rids.values())
    assert eng.floor_rounds == 1 and eng.co_rounds == 0
    assert bg.pending == 1              # one compile job enqueued
    assert session.try_plan_for([0, 1]) is None     # not compiled yet
    # the floor round costs exactly the compile-alone concat
    floor = floor_cycles(mc, [0, 1])
    assert eng.busy_cycles == pytest.approx(floor)
    for i in (0, 1):
        r = eng.done[rids[i]]
        assert r.served_on_floor and not r.co_scheduled
        # bitwise vs the reference plan over the compile-alone tiling
        ref = session.reference_plan(i, mc.singles[i].tiled)
        want = execute_plan(ref, xs[i], eng.params[i])
        for t in mc.graphs[i].outputs:
            assert np.array_equal(np.asarray(want[t]),
                                  np.asarray(eng.results[rids[i]][t]))

    assert bg.run_pending() == 1        # the background compile "lands"
    assert bg.compiled == 1 and bg.pending == 0
    sub = session.try_plan_for([0, 1])
    assert sub is not None
    assert not analyze_errors(sub)
    assert sub.makespan <= floor + 1e-6     # floor is a hard candidate

    rids2 = {i: eng.submit(i, inputs=xs[i]) for i in (0, 1)}
    eng.step()                          # hit: the real subset co-round
    assert eng.co_rounds == 1 and eng.subset_co_rounds == 1
    assert eng.floor_rounds == 1        # no new floor round
    for pos, i in enumerate((0, 1)):
        r = eng.done[rids2[i]]
        assert r.co_scheduled and not r.served_on_floor
        ref = session.reference_plan(i, sub.tenants[pos])
        want = execute_plan(ref, xs[i], eng.params[i])
        for t in mc.graphs[i].outputs:
            assert np.array_equal(np.asarray(want[t]),
                                  np.asarray(eng.results[rids2[i]][t]))


def test_first_round_latency_within_floor_bound(session):
    """The acceptance criterion: first-round latency at an unseen
    occupancy <= 1.1x the compile-alone concat floor (no joint-solve
    stall on the dispatch path)."""
    mc = session.compile()
    bg = BackgroundCompiler(session, start=False)
    eng = MultiModelEngine(mc, async_compile=bg, execute=False)
    assert session.try_plan_for([0, 2]) is None
    eng.submit(0)
    eng.submit(2)
    eng.step()
    floor_ms = mc.soc.cycles_to_ms(floor_cycles(mc, [0, 2]))
    worst = max(r.latency_ms for r in eng.done.values())
    assert worst <= 1.1 * floor_ms
    assert eng.clock_s * 1e3 <= 1.1 * floor_ms


# ---------------------------------------------------------------------------
# submit_compile: exactly once under concurrent misses ([1, 2])
# ---------------------------------------------------------------------------


def test_submit_compile_exactly_once_under_concurrency(session):
    """N threads race submit_compile on the same unseen occupancy: one
    compiles, the rest bounce off the in-flight set; the store gains one
    co-plan and every thread sees the same cached object afterwards."""
    assert session.try_plan_for([1, 2]) is None
    before = session.store.stats()
    lazy_before = session.lazy_compiles
    n = 6
    barrier = threading.Barrier(n)
    results = [None] * n

    def race(k):
        barrier.wait()
        results[k] = session.submit_compile([1, 2])

    threads = [threading.Thread(target=race, args=(k,)) for k in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert sum(1 for r in results if r) == 1        # exactly one compiled
    assert session.lazy_compiles == lazy_before + 1
    after = session.store.stats()
    assert after["co_plans"] == before["co_plans"] + 1
    plan = session.try_plan_for([1, 2])
    assert plan is not None and plan is session.try_plan_for([2, 1])
    # already cached: further submits are no-ops
    assert session.submit_compile([1, 2]) is False


def test_submit_compile_full_house_is_noop(session):
    assert session.submit_compile([0, 1, 2]) is False
    assert session.try_plan_for([0, 1, 2]) is session.compile().plan


def test_try_plan_for_never_compiles(session):
    before = session.store.stats()["compiles"]
    session.try_plan_for([0])           # probe only: a miss must not compile
    assert session.store.stats()["compiles"] == before


def test_background_compiler_dedupes_submits(session):
    bg = BackgroundCompiler(session, start=False)
    first = bg.submit([0])
    again = bg.submit([0])
    if first:                           # occupancy was unseen
        assert not again and bg.duplicates == 1
        bg.run_pending()
        assert bg.compiled == 1
    # cached now: submit bounces without queueing
    assert not bg.submit([0])
    assert bg.pending == 0


def test_lazy_budget_validation():
    soc, pats = two_acc_soc(64, 8.0)
    g = dense_chain("a", [32, 32])
    with pytest.raises(ValueError):
        CompileRequest(graphs=[g], soc=soc, patterns=pats,
                       lazy_joint_time_budget_s=0.0)
    req = CompileRequest(graphs=[g], soc=soc, patterns=pats)
    assert req.lazy_joint_time_budget_s < req.joint_time_budget_s


# ---------------------------------------------------------------------------
# End-to-end with the worker thread on (fresh session)
# ---------------------------------------------------------------------------


def test_threaded_compiler_end_to_end():
    """With the real worker thread, a serving burst at an unseen
    occupancy floors first, and after the compiler drains the engine
    swaps to subset co-rounds — same invariants as the fake-clock test,
    minus the determinism of *when* the swap lands."""
    session = make_session()
    mc = session.compile()
    eng = MultiModelEngine(mc, async_compile=True, execute=False)
    assert eng.compiler is not None and eng.compiler.running
    try:
        eng.submit(1)
        eng.submit(2)
        eng.step()
        assert eng.floor_rounds == 1
        assert eng.compiler.drain(timeout_s=120.0)
        assert eng.compiler.errors == []
        assert session.try_plan_for([1, 2]) is not None
        eng.submit(1)
        eng.submit(2)
        eng.step()
        assert eng.co_rounds == 1 and eng.floor_rounds == 1
        rep = eng.report()
        assert rep["async_compiler"]["compiled"] == rep["async_compiler"][
            "submitted"] == 1
        assert rep["rounds"] == rep["co_rounds"] + rep["solo_rounds"] + \
            rep["floor_rounds"]
    finally:
        eng.compiler.stop()
    assert not eng.compiler.running
