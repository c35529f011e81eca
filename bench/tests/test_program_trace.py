"""The reduction of the program's spans (``program_trace.py``): on planes
built by hand, and on the recorded v5e trace, which holds no program
span."""

import os
from types import SimpleNamespace as NS

import pytest

import program_trace
import xplane

DATA = os.path.join(os.path.dirname(__file__), "data", "v5e_small.xplane.pb")


def _ev(name, start, end, **stats):
    return NS(name=name, start_ns=start, end_ns=end,
              stats=list(stats.items()))


def _planes():
    """Harness spans with the program's inside them: two submits, one
    step of one wave over both requests (plan lookup, then the executor
    with two kernels), a fetch; and a step outside the window."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.submit", 0, 50),
        _ev("repro.submit", 5, 20, rid=0, tenant=0),
        _ev("repro.submit", 25, 45, rid=1, tenant=1),
        _ev("bench.step", 50, 450),
        _ev("repro.step", 60, 440, active="0;1"),
        _ev("repro.wave", 70, 430, occupancy=2, rids="0;1",
            analytic_us=3.5),
        _ev("repro.plan", 80, 100, hit=1),
        _ev("repro.execute", 110, 420, requests=2),
        _ev("repro.kernel", 120, 200, tenant="a", supernode="sn0",
            resource="acc", analytic_cycles=10.0),
        _ev("repro.kernel", 250, 400, tenant="b", supernode="sn1",
            resource="dma", analytic_cycles=30.0),
        _ev("bench.fetch", 450, 500),
        _ev("other.span", 0, 500),
        _ev("repro.step", 600, 700, active="0")])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_a", 150, 180),
                                       _ev("jit_b", 300, 350)]),
        NS(name="XLA Ops", events=[_ev("dot", 150, 180),
                                   _ev("add", 300, 350)])])
    return [host, dev, NS(name="/device:TPU:1", lines=[])]


def test_gaps_go_to_the_innermost_span():
    r = program_trace.reduce_planes(_planes())
    gaps = dict(r["idle_gaps"])
    # idle [0,150], [180,300], [350,500] of the window [0,500]; each
    # instant goes to the latest-started span over it
    want = {"submit": 15, "repro.submit": 35, "step": 20,
            "repro.step": 20, "repro.wave": 30, "repro.plan": 20,
            "repro.execute": 80, "repro.kernel": 150, "fetch": 50}
    assert gaps == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    busy = xplane.reduce_planes(_planes())["busy_s"]
    assert sum(gaps.values()) + busy == pytest.approx(500e-9)


def test_self_time_queue_wait_and_executed():
    r = program_trace.reduce_planes(_planes())
    spans = r["spans"]
    # the step at 600-700 lies outside the window
    assert {n: s["count"] for n, s in spans.items()} == {
        "repro.submit": 2, "repro.step": 1, "repro.wave": 1,
        "repro.plan": 1, "repro.execute": 1, "repro.kernel": 2}
    self_ns = {"repro.submit": 35, "repro.step": 380 - 360,
               "repro.wave": 360 - 20 - 310, "repro.plan": 20,
               "repro.execute": 310 - 80 - 150, "repro.kernel": 230}
    for name, v in self_ns.items():
        assert spans[name]["self_s"] == pytest.approx(v * 1e-9), name
    assert spans["repro.kernel"]["total_s"] == pytest.approx(230e-9)
    assert spans["repro.kernel"]["longest_s"] == pytest.approx(150e-9)
    assert spans["repro.step"]["total_s"] == pytest.approx(380e-9)
    # rid 0: wave start 70 - submit end 20; rid 1: 70 - 45
    assert r["queue_wait_s"] == [pytest.approx(50e-9), pytest.approx(25e-9)]
    assert r["executed"] == 2


def test_device_time_goes_to_the_kernel_that_started_before_it():
    kernels = program_trace.reduce_planes(_planes())["kernels"]
    assert [(k["tenant"], k["supernode"]) for k in kernels] == [
        ("b", "sn1"), ("a", "sn0")]              # by host self time
    b, a = kernels
    assert a["device_s"] == pytest.approx(30e-9) and a["programs"] == 1
    assert b["device_s"] == pytest.approx(50e-9) and b["programs"] == 1
    assert a["host_self_s"] == pytest.approx(80e-9)
    assert (a["count"], a["analytic_cycles"]) == (1, 10.0)
    assert (b["count"], b["analytic_cycles"]) == (1, 30.0)


def test_queue_wait_needs_both_spans_in_the_window():
    planes = _planes()
    planes[0].lines[0].events[1] = _ev("repro.submit", -20, -5, rid=0,
                                       tenant=0)
    r = program_trace.reduce_planes(planes)
    assert r["queue_wait_s"] == [pytest.approx(25e-9)]


def test_the_per_layer_numbers():
    r = program_trace.reduce_planes(_planes())
    assert program_trace.queue_wait_ms(r) == pytest.approx(37.5e-6)
    # (20 + 30 + 20) ns of self time over one step
    assert program_trace.engine_self_ms(r) == pytest.approx(70e-6)
    # 310 ns of executor over 2 requests
    assert program_trace.execute_host_ms(r) == pytest.approx(155e-6)


def test_no_harness_span_no_reduction():
    planes = _planes()
    planes[0].lines[0].events = [
        e for e in planes[0].lines[0].events if not e.name.startswith(
            "bench.")]
    assert program_trace.reduce_planes(planes) is None


def test_a_trace_without_program_spans():
    """The recorded chip trace: the gaps are ``xplane.py``'s, and the
    per-layer numbers read nothing."""
    r = program_trace.reduce_file(DATA)
    want = dict(xplane.reduce_file(DATA)["idle_gaps"])
    assert dict(r["idle_gaps"]) == {k: pytest.approx(v)
                                    for k, v in want.items()}
    assert (r["spans"], r["queue_wait_s"], r["executed"], r["kernels"]) == (
        {}, [], 0, [])
    for read in (program_trace.queue_wait_ms, program_trace.engine_self_ms,
                 program_trace.execute_host_ms):
        assert read(r) is None and read(None) is None
