"""One jitted program per plan (``repro.core.runtime.PlanPrograms``).

Small ``two_acc_soc`` deployments on the CPU: a plan is traced and
compiled once, on its first execution, and every later execution of it,
with any weights, reuses that program; the program dies with its plan;
each kernel node's ops sit under their own ``repro.kernel`` named scope;
and what the engine serves through the programs matches the eager oracle
``execute_graph``.
"""

import dataclasses
import gc
import re
import weakref

import numpy as np
import pytest

from repro.core import runtime
from repro.core.deploy import CompileRequest, DeploymentSession
from repro.core.runtime import (execute_graph, execute_multi_plan,
                                execute_plan, init_inputs, init_params,
                                kernel_scope)
from repro.serve.engine import MultiModelEngine
from repro.soc.testbed import dense_chain, two_acc_soc

ATOL = RTOL = 1e-4                  # the oracle tolerances of the runtime


def make_session(**kw) -> DeploymentSession:
    soc, pats = two_acc_soc(64, 8.0)
    graphs = [dense_chain("a", [64, 64, 64]),
              dense_chain("b", [48, 48, 48]),
              dense_chain("c", [32, 32, 32])]
    s = DeploymentSession(CompileRequest(
        graphs=graphs, soc=soc, patterns=pats,
        requested_tiles=4, time_budget_s=0.05, **kw))
    s.compile()
    return s


@pytest.fixture(scope="module")
def session():
    return make_session()


def kernel_nodes(plan):
    return [n for n in plan.nodes.values()
            if n.kind == "kernel" and n.supernode is not None]


def close(got, want) -> None:
    for t, v in want.items():
        np.testing.assert_allclose(np.asarray(got[t]), np.asarray(v),
                                   atol=ATOL, rtol=RTOL)


def test_a_plan_executed_twice_builds_one_program(session):
    # a copy is a plan object that no other test has run
    plan = dataclasses.replace(session.compile().singles[0].plan)
    g = plan.tiled.graph
    params, inputs = init_params(g, 0), init_inputs(g, 1)
    built = runtime.programs.programs_built
    calls = runtime.programs.program_calls
    first = execute_plan(plan, inputs, params)
    second = execute_plan(plan, inputs, params)
    assert runtime.programs.programs_built - built == 1
    assert runtime.programs.program_calls - calls == 2
    for t in g.outputs:
        assert np.array_equal(np.asarray(first[t]), np.asarray(second[t]))


def test_new_weights_run_through_the_same_program(session):
    plan = session.compile().plan
    graphs = [tg.graph for tg in plan.tenants]
    inputs = [init_inputs(g, 10 + i) for i, g in enumerate(graphs)]
    execute_multi_plan(plan, inputs, [init_params(g, 0) for g in graphs])
    built = runtime.programs.programs_built
    for seed in (3, 4):
        params = [init_params(g, seed + i) for i, g in enumerate(graphs)]
        outs = execute_multi_plan(plan, inputs, params)
        for i, g in enumerate(graphs):
            close(outs[i], execute_graph(g, inputs[i], params[i]))
    assert runtime.programs.programs_built == built


def test_an_evicted_plan_frees_its_program():
    s = make_session(store_max_entries=1)
    graphs = [tg.graph for tg in s.compile().plan.tenants]

    def run(active):
        plan = s.plan_for(active)
        execute_multi_plan(
            plan, [init_inputs(graphs[i], 1) for i in active],
            [init_params(graphs[i], 0) for i in active])
        return plan

    first = weakref.ref(run([0, 1]))
    gc.collect()
    held = len(runtime.programs)
    run([1, 2])                      # evicts [0, 1]: the store holds one
    assert s.store.lru_evictions == 1 and [0, 1] not in s.store
    gc.collect()
    assert first() is None
    assert len(runtime.programs) == held   # one program in, one out
    run([0, 1])                      # a re-miss compiles a new plan
    gc.collect()
    assert len(runtime.programs) == held


def test_each_kernel_node_is_one_named_scope(session):
    plan = session.compile().plan
    graphs = [tg.graph for tg in plan.tenants]
    args = ([init_inputs(g, 1) for g in graphs],
            [init_params(g, 0) for g in graphs])
    text = runtime.programs.program(plan).lower(*args).as_text(
        debug_info=True)
    found = set(re.findall(r"repro\.kernel:[A-Za-z0-9_@.]+:[A-Za-z0-9_@.]+",
                           text))
    want = {kernel_scope(graphs[n.tenant].name, n.supernode)
            for n in kernel_nodes(plan)}
    assert len(want) == len(kernel_nodes(plan))
    assert found == want


def test_engine_answers_match_the_eager_oracle(session):
    mc = session.compile()
    eng = MultiModelEngine(mc, seed=9, max_batch=2, execute=True)
    xs = {}
    for occupancy in ([0, 1, 2, 0], [1], [0, 2]):
        for t in occupancy:
            x = init_inputs(mc.graphs[t], 20 + len(xs))
            xs[eng.submit(t, inputs=x)] = (t, x)
        eng.step()
    assert not eng.pending and sorted(eng.results) == sorted(xs)
    for rid, (t, x) in xs.items():
        close(eng.results[rid], execute_graph(mc.graphs[t], x,
                                              eng.params[t]))
