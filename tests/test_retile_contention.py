"""Contention-aware re-tiling (PR 2): makespan dominance chain on random
mixes, a forced-contention case where shrunk-budget re-tiling reduces
``SharedL2Allocator`` evictions, and bitwise numerics of re-tiled
co-schedules."""

import numpy as np
import pytest

from _hypo import given, settings, st

from repro.analysis import analyze_errors
from repro.core.api import compile_multi
from repro.core.runtime import (execute_multi_plan, execute_plan,
                                init_inputs, init_params,
                                multi_plan_matches_oracle)
from repro.core.schedule import (_search_coschedule, contention_hints,
                                 default_budgets)
from repro.core.tiling import Contention
from repro.soc.testbed import dense_chain, forced_contention_setup, \
    two_acc_soc


@pytest.fixture(scope="module")
def forced_contention_mc():
    """Deep dense chains whose weights cycle through a shared L2 that holds
    only ~3 of them: the compile-alone tilings split every layer across
    both accelerators, stretching each weight's residency across the
    co-tenant's interleaved kernels — contention evictions."""
    soc, pats, graphs = forced_contention_setup()
    mc = compile_multi(graphs, soc, pats, requested_tiles=8,
                       time_budget_s=0.5)
    return mc, soc


def test_forced_contention_retiling_reduces_evictions(forced_contention_mc):
    """The co-schedule of sole-occupancy tilings over-subscribes the shared
    L2; re-tiling under the shrunk, contention-adjusted budgets must win
    the makespan without paying more SharedL2Allocator evictions.  (The
    eviction comparison is <=, not <: since the schedulers pin in-flight
    accesses against eviction — a swap-out may no longer race a running
    kernel's reads — both sides' eviction counts reflect the honest,
    hazard-free residency windows, under which the two plans can tie.)"""
    mc, soc = forced_contention_mc
    forced, err = _search_coschedule([cm.tiled for cm in mc.singles], soc,
                                     default_budgets(soc, 2), 3, 0)
    assert forced is not None, err
    assert mc.retiled
    assert mc.plan.mode != "sequential"
    assert mc.plan.makespan < forced.makespan
    assert mc.plan.memory.evictions <= forced.memory.evictions
    assert mc.plan.memory.evictions > 0      # still genuinely contended
    # and the full dominance chain holds
    assert mc.plan.makespan <= mc.baseline_makespan_cycles + 1e-6
    assert mc.baseline_makespan_cycles <= \
        mc.sequential_makespan_cycles + 1e-6


def test_forced_contention_plan_feasible(forced_contention_mc):
    mc, soc = forced_contention_mc
    assert not analyze_errors(mc.plan)
    assert mc.plan.memory.peak <= soc.l2.size


def test_retiled_numerics_match_oracle(forced_contention_mc):
    """Re-tiled co-scheduled execution == per-model whole-graph oracle."""
    mc, _ = forced_contention_mc
    assert mc.retiled
    assert multi_plan_matches_oracle(mc.plan)


def test_retiled_numerics_bitmatch_tenant_plan(forced_contention_mc):
    """Interleaving re-tiled tenants must not perturb numerics at all:
    each tenant's outputs are bit-identical to executing a single-model
    plan over the SAME re-tiled graph alone."""
    mc, _ = forced_contention_mc
    params = [init_params(g, 2 * i) for i, g in enumerate(mc.graphs)]
    inputs = [init_inputs(g, 2 * i + 1) for i, g in enumerate(mc.graphs)]
    multi_out = execute_multi_plan(mc.plan, inputs, params)
    for i, g in enumerate(mc.graphs):
        single_out = execute_plan(mc.tenant_plan(i), inputs[i], params[i])
        for t in g.outputs:
            assert np.array_equal(np.asarray(single_out[t]),
                                  np.asarray(multi_out[i][t])), (g.name, t)


def test_contention_hints_shape(forced_contention_mc):
    """Hints summarize co-residency: each tenant sees its budget, its
    co-residents' (not its own) device load, and a DMA factor >= 1."""
    mc, soc = forced_contention_mc
    hints = contention_hints(mc.baseline_plan, soc)
    assert len(hints) == 2
    for h in hints:
        assert isinstance(h, Contention)
        assert h.l2_budget == mc.baseline_plan.budgets[0]
        assert h.dma_scale >= 1.0
        assert all(v >= 0.0 for v in h.device_load.values())


def test_retile_disabled_reproduces_baseline():
    """``retile_for_contention=False`` must reproduce the PR-1 behaviour
    exactly (same winning makespan as the baseline plan)."""
    soc, pats = two_acc_soc(56, 12.0)
    graphs = [dense_chain("a", [96] * 6), dense_chain("b", [96] * 6)]
    mc = compile_multi(graphs, soc, pats, requested_tiles=4,
                       time_budget_s=0.5, retile_for_contention=False)
    assert not mc.retiled
    assert mc.plan.makespan == mc.baseline_makespan_cycles


WIDTHS = [16, 32, 48, 64]


@settings(max_examples=4, deadline=None)
@given(st.data())
def test_retile_makespan_dominance_chain(data):
    """Property: on random mixes, re-tiled co-scheduled makespan <= PR-1
    co-scheduled makespan <= sequential concatenation."""
    n_layers = data.draw(st.integers(2, 3))
    l2_kib = data.draw(st.sampled_from([48, 64, 96]))
    soc, pats = two_acc_soc(l2_kib, 8.0)
    n_tenants = data.draw(st.integers(2, 3))
    graphs = []
    for i in range(n_tenants):
        widths = [data.draw(st.sampled_from(WIDTHS))
                  for _ in range(n_layers + 1)]
        graphs.append(dense_chain(f"m{i}", widths))
    mc = compile_multi(graphs, soc, pats, requested_tiles=4,
                       time_budget_s=0.5)
    assert mc.plan.makespan <= mc.baseline_makespan_cycles + 1e-6
    assert mc.baseline_makespan_cycles <= \
        mc.sequential_makespan_cycles + 1e-6
    assert not analyze_errors(mc.plan)
