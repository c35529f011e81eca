"""Stage-2 scheduler + memory plan: constraint validation on real models."""

import pytest

from repro.analysis import analyze_errors
from repro.core.api import compile_model
from repro.models import edge
from repro.soc.carfield import carfield_patterns, carfield_soc

# excluded from the fast CI lane (-m "not slow")
pytestmark = pytest.mark.slow

SOC = carfield_soc()
PATS = carfield_patterns()


@pytest.mark.parametrize("model", ["autoencoder", "ds_cnn", "resnet",
                                   "resnext50_block"])
@pytest.mark.parametrize("mode", ["match", "matcha"])
def test_schedule_constraints(model, mode):
    cm = compile_model(edge.ALL_MODELS[model](), SOC, PATS, mode=mode,
                       time_budget_s=2.0)
    errs = analyze_errors(cm.plan)
    assert not errs, errs


@pytest.mark.parametrize("model", ["autoencoder", "resnet", "mobilenet"])
def test_memory_plan_valid(model):
    cm = compile_model(edge.ALL_MODELS[model](), SOC, PATS, mode="matcha",
                       time_budget_s=2.0)
    errs = analyze_errors(cm.plan.memory)
    assert not errs, errs
    assert cm.plan.memory.peak <= SOC.l2.size


def test_sequential_modes_never_overlap_compute():
    cm = compile_model(edge.resnet(), SOC, PATS, mode="match",
                       time_budget_s=2.0)
    comp = sorted((n for n in cm.plan.nodes.values()
                   if n.resource != "dma"), key=lambda n: n.start)
    for a, b in zip(comp, comp[1:]):
        assert a.end <= b.start + 1e-6


def test_utilization_sums_sane():
    cm = compile_model(edge.resnet50_block(), SOC, PATS, mode="matcha",
                       time_budget_s=2.0)
    util = cm.plan.utilization()
    assert all(0.0 <= u <= 1.0 + 1e-9 for u in util.values())
    # the paper's whole point: both accelerators busy
    assert util.get("spatz", 0) > 0.3
    assert util.get("pulp", 0) > 0.3
