"""The program's spans (``repro.core.spans``) in a profiler trace.

A small ``two_acc_soc`` deployment is served through
``MultiModelEngine(execute=True)`` under ``jax.profiler.start_trace``,
and the ``.xplane.pb`` is read back with ``ProfileData``: every span is
there, nested ``step ⊃ wave ⊃ {plan, execute}`` on one host line, its
arguments tie it to its request and its plan, and a running profiler
changes neither the answers nor the engine's counters.  A plan runs as
one jitted program, so ``repro.kernel`` is a named scope inside it and no
host span (``tests/test_plan_program.py``); ``repro.execute`` says which
call built its plan's program, and how many kernel nodes that build
folded.  A language-model stage puts its tokens on ``repro.submit`` and
``repro.wave``, and its mechanisms' ops in ``repro.op`` scopes inside
their kernels'.
"""

import glob
import os
import re

import jax
import numpy as np
import pytest
from _plans import folded_nodes

from repro.core import runtime, spans
from repro.core.deploy import CompileRequest, DeploymentSession
from repro.core.runtime import init_inputs
from repro.serve.compiler_thread import BackgroundCompiler
from repro.serve.engine import MultiModelEngine
from repro.models import edge
from repro.models.granite import granite_h_micro_stage4
from repro.soc.carfield import carfield_patterns, carfield_soc
from repro.soc.testbed import dense_chain, two_acc_soc

NAMES = {spans.SUBMIT, spans.STEP, spans.WAVE, spans.PLAN, spans.EXECUTE}
PARENT = {spans.WAVE: spans.STEP, spans.PLAN: spans.WAVE,
          spans.EXECUTE: spans.WAVE}
COUNTERS = ("rounds", "co_rounds", "subset_co_rounds", "solo_rounds",
            "floor_rounds", "batched_repeat_rounds", "solo_dispatches",
            "busy_cycles", "clock_s")


def make_session() -> DeploymentSession:
    soc, pats = two_acc_soc(64, 8.0)
    graphs = [dense_chain("a", [64, 64, 64]),
              dense_chain("b", [48, 48, 48]),
              dense_chain("c", [32, 32, 32])]
    s = DeploymentSession(CompileRequest(
        graphs=graphs, soc=soc, patterns=pats,
        requested_tiles=4, time_budget_s=0.05))
    s.compile()
    return s


def serve(session, bg=None):
    """Four steps: the full house with a repeat wave for tenant 0, the
    occupancy [0, 1] twice (a floor round, then, once the background
    compile has landed, its co-round) and tenant 2 alone.  Returns the
    engine."""
    mc = session.compile()
    eng = MultiModelEngine(mc, seed=5, max_batch=2, execute=True,
                           async_compile=bg if bg is not None else False)
    xs = [init_inputs(g, 40 + i) for i, g in enumerate(mc.graphs)]
    for occupancy in ([0, 1, 2, 0], [0, 1], [0, 1], [2]):
        for t in occupancy:
            eng.submit(t, inputs=xs[t])
        eng.step()
        if bg is not None:
            bg.run_pending()
    assert not eng.pending
    return eng


def traced(tmp_path, fn):
    """Runs ``fn()`` under the profiler; (its result, the repro. events
    of each host line as (name, start, end, stats))."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    lines = []
    for plane in jax.profiler.ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                   for e in line.events if e.name.startswith("repro.")]
            if evs:
                lines.append(evs)
    return out, lines


def parents(events):
    """For each event, the name of the innermost other event on its line
    that covers it (None for a root)."""
    out = []
    for i, (_, s, e, _) in enumerate(events):
        cover = [(s0, -e0, n) for j, (n, s0, e0, _) in enumerate(events)
                 if j != i and s0 <= s and e <= e0
                 and (s0, -e0) != (s, -e)]
        out.append(max(cover)[2] if cover else None)
    return out


def rids_of(stats) -> list:
    return [int(x) for x in str(stats["rids"]).split(spans.SEP)]


@pytest.mark.parametrize("background", [True, False],
                         ids=["background-compiler", "blocking-lookup"])
def test_spans_nest_and_tie_to_requests_and_plans(tmp_path, monkeypatch,
                                                  background):
    """A store miss floors the round with a background compiler and
    compiles on the dispatch path without one; either way its
    ``repro.plan`` span reads ``hit`` false."""
    session = make_session()
    assert session.try_plan_for([0, 1]) is None
    ran = []                            # the plan of each executor call
    for name in ("execute_plan", "execute_multi_plan"):
        inner = getattr(runtime, name)

        def record(plan, *args, _inner=inner):
            ran.append(plan)
            return _inner(plan, *args)
        monkeypatch.setattr(runtime, name, record)
    bg = BackgroundCompiler(session, start=False) if background else None
    eng, lines = traced(tmp_path, lambda: serve(session, bg))

    assert len(lines) == 1                  # one host line holds them all
    events = lines[0]
    assert {n for n, *_ in events} == NAMES
    for (name, *_), parent in zip(events, parents(events)):
        assert parent == PARENT.get(name), name

    by = {n: [ev for ev in events if ev[0] == n] for n in NAMES}
    submitted = [ev[3]["rid"] for ev in by[spans.SUBMIT]]
    assert sorted(submitted) == sorted(eng.done)
    assert [ev[3]["tenant"] for ev in by[spans.SUBMIT]] == [
        eng.done[r].tenant for r in submitted]
    waved = [r for ev in by[spans.WAVE] for r in rids_of(ev[3])]
    assert sorted(waved) == sorted(submitted)   # each rid in one wave
    for _, _, _, st in by[spans.WAVE]:
        assert st["occupancy"] == len(rids_of(st))
        assert st["analytic_us"] > 0
    assert len(by[spans.STEP]) == 4
    assert len(by[spans.WAVE]) == eng.rounds == len(by[spans.PLAN])
    # the store misses the first wave at each occupancy short of the full
    # house (the background compiler lands each miss before the next step)
    seen, want = set(), []
    for _, _, _, st in by[spans.WAVE]:
        occ = tuple(sorted(eng.done[r].tenant for r in rids_of(st)))
        want.append(occ in seen or len(occ) == 3)
        seen.add(occ)
    assert [bool(ev[3]["hit"]) for ev in by[spans.PLAN]] == want
    assert eng.floor_rounds == (want.count(False) if background else 0)
    assert sum(ev[3]["requests"] for ev in by[spans.EXECUTE]) == len(
        eng.done)

    # each call builds its plan's program the first time that plan runs
    assert len(by[spans.EXECUTE]) == len(ran)
    firsts, seen = [], set()
    for plan in ran:
        firsts.append(id(plan) not in seen)
        seen.add(id(plan))
    assert [bool(ev[3]["built"]) for ev in by[spans.EXECUTE]] == firsts
    assert firsts.count(True) < len(firsts)     # some call reused one
    # and says how many kernel nodes that build folded
    folded = [len(folded_nodes(p)) if first else 0
              for p, first in zip(ran, firsts)]
    assert [ev[3]["folded"] for ev in by[spans.EXECUTE]] == folded
    assert any(folded)


def test_a_running_profiler_changes_no_answer_and_no_counter(tmp_path):
    session = make_session()
    session.precompile([[0], [0, 1], [2]])
    plain = serve(session)
    with_trace, lines = traced(tmp_path, lambda: serve(session))
    assert lines                        # the spans were recorded
    for k in COUNTERS:
        assert getattr(with_trace, k) == getattr(plain, k), k
    assert with_trace.report()["plan_store"] is not None
    assert sorted(with_trace.results) == sorted(plain.results)
    for rid, out in plain.results.items():
        for t, v in out.items():
            assert np.array_equal(np.asarray(with_trace.results[rid][t]),
                                  np.asarray(v))


def stage_session() -> DeploymentSession:
    """A small granite stage (16 tokens) beside the autoencoder, which
    takes no tokens, on the Carfield preset."""
    stage = granite_h_micro_stage4(
        seq=16, chunk=8, d=32, heads=2, head_dim=16, state=8, q_heads=2,
        kv_heads=1, attn_head_dim=16, ffn=64, vocab=64, pattern="ma")
    s = DeploymentSession(CompileRequest(
        graphs=[stage, edge.autoencoder()], soc=carfield_soc(),
        patterns=carfield_patterns(), time_budget_s=0.001,
        joint_time_budget_s=0.001, lazy_joint_time_budget_s=0.001,
        incremental_time_budget_s=0.001))
    s.compile()
    s.precompile([[0]])
    return s


def test_spans_carry_tokens_and_the_program_scopes_each_mechanism(tmp_path):
    session = stage_session()
    mc = session.compile()

    def run():
        eng = MultiModelEngine(mc, seed=3, execute=True, max_batch=1)
        for occupancy in ([0, 1], [0]):
            for t in occupancy:
                eng.submit(t)
            eng.step()
        return eng

    eng, lines = traced(tmp_path, run)
    events = [ev for line in lines for ev in line]
    submits = [ev[3] for ev in events if ev[0] == spans.SUBMIT]
    assert [(s["tenant"], s["tokens"]) for s in submits] == [
        (0, 16), (1, 0), (0, 16)]
    waves = [ev[3] for ev in events if ev[0] == spans.WAVE]
    assert [w["tokens"] for w in waves] == [16, 16]
    assert [len(rids_of(w)) for w in waves] == [2, 1]

    # each mechanism's ops sit in a scope of their own inside their
    # kernel's, in the op metadata of the compiled program
    plan = session.try_plan_for([0])
    g = plan.tenants[0].graph
    args = ([runtime.init_inputs(g, 1)], [runtime.init_params(g, 2)])
    text = runtime.programs.program(plan).lower(*args).compile().as_text()
    found = set(re.findall(r"repro\.kernel:[^/\"]*/(repro\.op:\w+)", text))
    assert found == {f"{spans.OP}:{t}"
                     for t in ("ssd", "causal_conv1d", "softmax")}
