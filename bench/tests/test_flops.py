"""Useful MACs counted from the reference's shapes, against the counts of
the served graphs and the published counts of the MLPerf Tiny reference
models."""

import json
import os

import pytest

import flops

CONFIG = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs",
                      "mlperf-tiny4-carfield.json")
with open(CONFIG) as _f:
    _CONFIG = json.load(_f)
PUBLISHED = {m: v["macs"] for m, v in _CONFIG["published"].items()}
SERVED = _CONFIG["served_macs"]


@pytest.mark.parametrize("model", sorted(SERVED))
def test_macs_match_the_served_count(model):
    assert flops.macs(model) == SERVED[model]
    assert flops.flops(model) == 2 * SERVED[model]


def test_served_departs_from_published_only_by_the_kws_kernel():
    """Only DS-CNN's first conv differs from the source: 5x5 served,
    10x4 published, over its 25x5x64 output from one input channel."""
    kh, kw = _CONFIG["published"]["ds_cnn"]["conv1_kernel"]
    sh, sw = _CONFIG["ds_cnn_conv1_kernel"]
    assert PUBLISHED["ds_cnn"] - SERVED["ds_cnn"] == 25 * 5 * 64 * (
        kh * kw - sh * sw)
    assert {m: v for m, v in PUBLISHED.items() if m != "ds_cnn"} == {
        m: v for m, v in SERVED.items() if m != "ds_cnn"}


def test_macs_agree_with_the_program_ir():
    """The program's own IR counts the same MACs for its conv, depthwise
    and dense ops (an independent witness of the shapes)."""
    from repro.models import edge
    for model in SERVED:
        g = edge.ALL_MODELS[model]()
        ir = 0
        for op in g.topo_ops():
            out = g.tensors[op.output].shape
            w = g.tensors[op.inputs[1]].shape if len(op.inputs) > 1 else None
            n = 1
            for d in out:
                n *= d
            if op.op_type == "conv2d":
                ir += n * w[0] * w[1] * w[2]
            elif op.op_type == "dwconv2d":
                ir += n * w[0] * w[1]
            elif op.op_type == "dense":
                ir += n * w[0]
        assert ir == flops.macs(model), model
