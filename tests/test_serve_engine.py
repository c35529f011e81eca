"""Multi-tenant engine: report invariants under a seeded mixed-class
soak and the per-tenant request batching of co-round slots.  (The old
single-model token-loop ``Engine`` was retired by the shape-bucket
rework — LM serving now goes through ``MultiModelEngine`` as bucketed
requests; see ``tests/test_shape_buckets.py``.)"""

import random

import pytest

from repro.core.schedule import namespaced, split_namespaced


@pytest.fixture(scope="module")
def multi_mc():
    from repro.core.deploy import CompileRequest, DeploymentSession
    from repro.soc.testbed import dense_chain, two_acc_soc
    soc, pats = two_acc_soc(64, 8.0)
    graphs = [dense_chain("a", [64, 64, 64]),
              dense_chain("b", [48, 48, 48]),
              dense_chain("c", [32, 32, 32])]
    return DeploymentSession(CompileRequest(
        graphs=graphs, soc=soc, patterns=pats,
        requested_tiles=4, time_budget_s=0.5)).compile()


@pytest.fixture(scope="module")
def contention_mc():
    from repro.core.api import compile_multi
    from repro.soc.testbed import forced_contention_setup
    soc, pats, graphs = forced_contention_setup()
    return compile_multi(graphs, soc, pats, requested_tiles=8,
                         time_budget_s=0.5)


def test_mixed_class_soak_report_invariants(multi_mc):
    """Seeded soak: >= 200 mixed-class requests over 3 tenants with
    arrivals and departures (idle service rounds drain queues between
    bursts).  The engine's report must keep its books straight:

      * per-class served + rejected == submitted, for every class and in
        aggregate;
      * round decomposition: co_rounds + solo_rounds + floor_rounds ==
        rounds (subset co-rounds are a sub-count of co_rounds);
      * no negative latencies, waits, or clocks anywhere.
    """
    from repro.serve.admission import (AdmissionController, ClassPolicy,
                                       Priority, RoundComposer)
    from repro.serve.engine import MultiModelEngine
    rng = random.Random(1234)
    adm = AdmissionController({Priority.LOW: ClassPolicy(max_queued=6)})
    eng = MultiModelEngine(multi_mc, composer=RoundComposer(),
                           admission=adm, execute=False)
    n_submitted = 0
    base_s = eng._floor_s(0)
    for burst in range(40):
        for _ in range(rng.randint(2, 8)):           # arrivals
            prio = rng.choice(list(Priority))
            dl = rng.choice([None, 2.0 * base_s, 8.0 * base_s,
                             40.0 * base_s])
            eng.submit(rng.randrange(3), priority=prio, deadline_s=dl)
            n_submitted += 1
        for _ in range(rng.randint(0, 3)):           # departures
            eng.step()
    eng.run()
    assert n_submitted >= 200
    rep = eng.report()

    # class accounting closes
    per_class = rep["per_class"]
    assert sum(c["submitted"] for c in per_class.values()) == n_submitted
    for name, c in per_class.items():
        assert c["served"] + c["rejected"] == c["submitted"], name
        assert c["p99_e2e_ms"] >= c["p50_e2e_ms"] >= 0.0, name
        assert c["max_wait_rounds"] >= 0, name
    assert rep["served"] + rep["rejected"] == n_submitted
    assert rep["served"] == len(eng.done)

    # round decomposition closes
    assert rep["rounds"] == rep["co_rounds"] + rep["solo_rounds"] + \
        rep["floor_rounds"]
    assert rep["subset_co_rounds"] <= rep["co_rounds"]

    # no negative latencies / waits / clocks
    for r in eng.done.values():
        assert r.latency_ms >= 0.0
        assert r.e2e_latency_ms >= -1e-9
        assert r.wait_rounds >= 0
        assert r.finish_s >= r.submit_s - 1e-12
    assert rep["clock_s"] >= 0.0 and rep["throughput_inf_per_s"] > 0.0
    assert rep["starvation_events"] == 0


def test_batched_co_round_slots_beat_unbatched_on_bursty_trace(
        contention_mc):
    """max_batch > 1 drains bursts in back-to-back waves inside the
    round; consecutive waves re-running the same plan pay the weights-
    resident repeat cost, so aggregate throughput on a bursty trace is
    pinned >= the unbatched engine (strictly better whenever the plan
    has parameter-load DMA traffic to save — the forced-contention mix
    does)."""
    from repro.serve.engine import MultiModelEngine
    mc = contention_mc

    def bursty(engine):
        for _ in range(4):                       # a burst per tenant
            engine.submit(0)
            engine.submit(1)
        engine.run()
        return engine.report()

    rep_un = bursty(MultiModelEngine(mc, execute=False, max_batch=1))
    rep_b = bursty(MultiModelEngine(mc, execute=False, max_batch=4))
    assert rep_b["served"] == rep_un["served"] == 8
    assert rep_b["throughput_inf_per_s"] >= rep_un["throughput_inf_per_s"]
    # the repeat discount actually engaged and stayed physical
    assert rep_b["batched_repeat_rounds"] > 0
    assert rep_b["throughput_inf_per_s"] > rep_un["throughput_inf_per_s"]
    eng = MultiModelEngine(mc, execute=False)
    assert eng._repeat_cycles(mc.plan) <= mc.plan.makespan
    assert eng._repeat_cycles(mc.plan) >= max(
        b for r, b in mc.plan.busy.items() if r != "dma")


def test_batched_waves_keep_fifo_order_and_outputs(multi_mc):
    """Batched dispatch pops each tenant's queue in FIFO order and the
    per-wave numerics equal the unbatched engine's for the same inputs."""
    import numpy as np
    from repro.core.runtime import init_inputs
    from repro.serve.engine import MultiModelEngine
    xs = [init_inputs(multi_mc.graphs[0], 70 + k) for k in range(3)]
    ref = MultiModelEngine(multi_mc, seed=11)
    got = MultiModelEngine(multi_mc, seed=11, max_batch=3)
    r_ref = [ref.submit(0, inputs=x) for x in xs]
    r_got = [got.submit(0, inputs=x) for x in xs]
    ref.run()
    got.step()                                   # ONE step drains the burst
    assert got.pending == 0 and got.rounds == 1 + 2  # 3 waves = 3 rounds
    for a, b in zip(r_ref, r_got):
        ra, rb = ref.done[a], got.done[b]
        assert ra.tenant == rb.tenant
        for t in multi_mc.graphs[0].outputs:
            assert np.array_equal(np.asarray(ref.results[a][t]),
                                  np.asarray(got.results[b][t]))


@pytest.mark.parametrize("name, want", [
    (namespaced(3, "conv1.w"), (3, "conv1.w")),
    (namespaced(12, "a/b"), (12, "a/b")),
    ("dma_in", None),
    ("t/x", None),
    ("tx/y", None),
    ("t-1/x", None),
])
def test_split_namespaced(name, want):
    """The parser inverts the builder and rejects any other name."""
    assert split_namespaced(name) == want


def test_repeat_discount_saves_namespaced_param_loads(contention_mc):
    """The repeat discount finds each parameter load through the
    namespaced tensor names: the forced-contention plan saves 19968
    DMA-in cycles of its 187872.67-cycle makespan."""
    from repro.serve.engine import MultiModelEngine
    eng = MultiModelEngine(contention_mc, execute=False)
    assert eng._param_dma_in_cycles(contention_mc.plan) == 19968.0
    assert eng._repeat_cycles(contention_mc.plan) == \
        pytest.approx(167904.6666666667)
