"""The trace reduction: on planes built by hand, and on a small trace
recorded on a TPU v5e (``data/v5e_small.xplane.pb``)."""

import os
from types import SimpleNamespace as NS

import pytest

import xplane

DATA = os.path.join(os.path.dirname(__file__), "data", "v5e_small.xplane.pb")


def _ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end)


def _planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.step", 0, 400), _ev("bench.fetch", 400, 600),
        _ev("other.span", 0, 1000), _ev("bench.wait_for_arrival", 600,
                                        1000)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_a", 100, 300),
                                       _ev("jit_b", 500, 700),
                                       _ev("jit_c", 900, 1100)]),
        NS(name="XLA Ops", events=[_ev("dot", 100, 250),
                                   _ev("add", 200, 300),
                                   _ev("dot", 500, 700),
                                   _ev("tanh", 900, 1100)])])
    return [host, dev, NS(name="/device:TPU:1", lines=[])]


def test_reduction_by_hand():
    r = xplane.reduce_planes(_planes())
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: [100,300] + [500,700] + [900,1000] (clipped to the window)
    assert r["busy_s"] == pytest.approx(500e-9)
    assert r["devices"] == 1                # the idle second plane is not used
    assert r["programs"] == 2               # jit_c ends after the window
    assert r["device_ops"][0] == ["dot", pytest.approx(350e-9)]
    gaps = dict(r["idle_gaps"])
    # idle: [0,100] and [300,400] in step, [400,500] in fetch,
    # [700,900] in wait_for_arrival
    assert gaps == {"step": pytest.approx(200e-9),
                    "fetch": pytest.approx(100e-9),
                    "wait_for_arrival": pytest.approx(200e-9)}
    assert xplane.idle_share({"trace": r}) == pytest.approx(50.0)


def test_no_spans_no_reduction():
    planes = _planes()
    planes[0].lines[0].events = []
    assert xplane.reduce_planes(planes) is None
    assert xplane.idle_share({"trace": None}) is None


def test_reduction_of_a_chip_trace():
    """Recorded on one TPU v5e: five rounds of a small matmul, tanh, add
    and dynamic_update_slice in ``bench.step``, a copy to the host in
    ``bench.fetch`` and a 2 ms sleep in ``bench.wait_for_arrival``."""
    r = xplane.reduce_file(DATA)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.028545738)
    assert r["busy_s"] == pytest.approx(1.6893e-05)
    assert r["programs"] == 37
    assert r["device_ops"][0][0].startswith("%dynamic_update_slice")
    gaps = dict(r["idle_gaps"])
    assert set(gaps) == {"step", "wait_for_arrival", "fetch", "other"}
    assert gaps["step"] == pytest.approx(0.013570744)
    assert gaps["wait_for_arrival"] == pytest.approx(0.01214117)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])
