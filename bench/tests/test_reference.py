"""The plain reference against the program's whole-graph evaluation on the
CPU, for all four models, and against the served graphs' names."""

import numpy as np
import pytest

import reference

MODELS = ["autoencoder", "ds_cnn", "resnet", "mobilenet"]


@pytest.fixture(scope="module")
def weights():
    return (reference.make_params(MODELS, 2**31 + 17),
            reference.make_inputs(MODELS, 2**31 + 17, 2))


@pytest.mark.parametrize("t", range(len(MODELS)), ids=MODELS)
def test_reference_matches_execute_graph(weights, t):
    from repro.core.runtime import execute_graph
    from repro.models import edge
    params, pools = weights
    model = MODELS[t]
    ref = reference.load_model(model)
    g = edge.ALL_MODELS[model]()
    assert {n: ti.shape for n, ti in g.tensors.items()
            if ti.kind == "param"} == ref.params()
    assert g.inputs == [ref.INPUT[0]] and g.outputs == [ref.OUTPUT]
    want = reference.forward(model, params[t], pools[t][:, 0])
    for i in range(2):
        got = execute_graph(g, {ref.INPUT[0]: pools[t][i]}, params[t])
        assert reference.gap(got[ref.OUTPUT], want[i:i + 1]) < 1e-5


def test_weights_and_inputs_follow_the_seed():
    a = reference.make_params(["ds_cnn"], 5)[0]
    b = reference.make_params(["ds_cnn"], 5)[0]
    c = reference.make_params(["ds_cnn"], 6)[0]
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["conv0_w"], c["conv0_w"])
    x = reference.make_inputs(["ds_cnn"], 2**40 + 1, 3)[0]
    y = reference.make_inputs(["ds_cnn"], 2**40 + 1, 3)[0]
    assert x.shape == (3, 1, 49, 10, 1) and np.array_equal(x, y)


def test_gap():
    want = np.array([[1.0, -2.0]])
    assert reference.gap(want, want) == 0.0
    assert reference.gap(want + [[0.0, 0.02]], want) == pytest.approx(0.01)
    assert reference.gap(want[:, :1], want) == float("inf")
    assert reference.gap([[np.nan, 0.0]], want) == float("inf")


def test_check_answers_counts_and_missing_models():
    want = [np.ones((2, 1, 3)), np.ones((2, 1, 3))]
    answers = [(0, 0, np.ones((1, 3))), (0, 1, np.full((1, 3), 1.5))]
    worst, over = reference.check_answers(["a", "b"], answers, want, 0.1)
    assert worst == {"a": 0.5, "b": float("inf")} and over == 1
