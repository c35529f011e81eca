"""The sequence-model ops and granite-4.0-h-micro's last pipeline stage
on the CPU, at small widths, against plain references.

* ``ssd`` (chunked) against Mamba-2's sequential recurrence in float64,
  for sequences shorter than a chunk, of whole chunks and of a part
  chunk, computed whole and by every tiling of its heads;
* ``causal_conv1d`` against its shifted taps, by every channel tiling;
  a causal softmax by row tiles against the whole;
* the stage's graph against the benchmark's reference
  (``bench/models/granite_h_micro_stage4.py``, loaded by path), whole
  and served through ``DeploymentSession`` and
  ``MultiModelEngine(execute=True)``;
* the MLPerf-Tiny deployments' plans, which the new ops must leave as
  they were, and their tile semantics: every segment of the three-model
  deployment's plans through ``runtime.run_supernode``, joined, against
  the whole graph.

Tolerances.  Every comparison is float32 against float32 or float64 on
the CPU's exact float32 matmuls: what differs is summation order, about
1e-7 of the largest magnitude compared.  The chunked scan sums its decays
in another order than the recurrence multiplies them: held to 1e-6 of
the largest output (1.6e-7 read over the nine cases).
The stage's logits are held to 1e-6 of their largest: above the 1e-7 to
4e-7 they read, and a fifth of the 5e-6 that three-pass bfloat16 (the
benchmark's control) reads at these widths.
"""

import dataclasses
import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from repro.core import runtime
from repro.core.deploy import CompileRequest, DeploymentSession
from repro.core.ir import Graph, max_tiles, op_macs, ssd_macs, tile_axis
from repro.core.rewrite import Supernode
from repro.models import edge
from repro.models.granite import NAME, granite_h_micro_stage4
from repro.serve.compiler_thread import BackgroundCompiler
from repro.serve.engine import MultiModelEngine
from repro.soc.carfield import carfield_patterns, carfield_soc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")

# a small stage: layers "mam" (attention in the middle), every width cut
SMALL = dict(d=64, heads=4, head_dim=16, state=8, conv=4, q_heads=4,
             kv_heads=2, attn_head_dim=16, ffn=128, vocab=512)
CHUNK = 16
# the stage's logits: other summation orders, a few float32 roundings of
# the largest magnitude
STAGE_GAP = 1e-6


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("bench_model_granite",
                 os.path.join(BENCH, "models", f"{NAME}.py"))


@pytest.fixture(scope="module")
def nn():
    return _load("bench_reference", os.path.join(BENCH, "reference.py")).NN


def _seeded(shapes, seed):
    """The benchmark's scales: 0.1 for 1-D, He over the first axis."""
    rng = np.random.default_rng(seed)
    return {n: ((0.1 if len(s) == 1 else np.sqrt(2.0 / s[0]))
                * rng.standard_normal(s)).astype(np.float32)
            for n, s in shapes.items()}


def _gap(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _tiled(g, op, env, T):
    """``op`` computed as T tiles of its tile axis by the plan executor's
    supernode path, stitched back together."""
    ax = tile_axis(g, op)
    tiles = []
    for i in range(T):
        sn = Supernode(f"t{i}", None, (op.name,), "host", i, i + 1, T)
        tiles.append(runtime.run_supernode(g, sn, env)[op.output])
    return np.concatenate([np.asarray(t) for t in tiles], axis=ax)


# ---------------------------------------------------------------------------
# ssd
# ---------------------------------------------------------------------------

HEADS, HEAD_DIM, STATE = 4, 8, 6


def _ssd_graph(seq):
    g = Graph("ssd")
    x = g.add_input("x", (1, seq, HEADS * HEAD_DIM))
    dt = g.add_input("dt", (1, seq, HEADS))
    b = g.add_input("B", (1, seq, STATE))
    c = g.add_input("C", (1, seq, STATE))
    ps = [g.add_param(n, (HEADS,)) for n in ("A_log", "dt_bias", "D")]
    g.mark_output(g.add_op("ssd", [x, dt, b, c, *ps], name="ssd",
                           chunk=CHUNK))
    return g


def _ssd_inputs(seq, seed=0):
    rng = np.random.default_rng(seed)
    env = {"x": rng.standard_normal((1, seq, HEADS * HEAD_DIM)),
           "dt": rng.standard_normal((1, seq, HEADS)) * 0.5,
           "B": rng.standard_normal((1, seq, STATE)),
           "C": rng.standard_normal((1, seq, STATE)),
           "A_log": rng.standard_normal(HEADS) * 0.5,
           "dt_bias": rng.standard_normal(HEADS) * 0.5,
           "D": rng.standard_normal(HEADS)}
    return {k: v.astype(np.float32) for k, v in env.items()}


def _recurrence(env):
    """Mamba-2's recurrence, token by token, in float64."""
    e = {k: v.astype(np.float64) for k, v in env.items()}
    seq = e["x"].shape[1]
    x = e["x"][0].reshape(seq, HEADS, HEAD_DIM)
    dt = np.log1p(np.exp(e["dt"][0] + e["dt_bias"]))
    a = -np.exp(e["A_log"])
    h = np.zeros((HEADS, HEAD_DIM, STATE))
    y = np.zeros_like(x)
    for t in range(seq):
        h = (np.exp(dt[t] * a)[:, None, None] * h
             + (dt[t][:, None] * x[t])[:, :, None] * e["B"][0, t])
        y[t] = h @ e["C"][0, t] + e["D"][:, None] * x[t]
    return y.reshape(1, seq, HEADS * HEAD_DIM)


@pytest.mark.parametrize("seq", [11, 48, 41],
                         ids=["under-a-chunk", "whole-chunks", "part-chunk"])
@pytest.mark.parametrize("tiles", [1, 2, 4])
def test_ssd_matches_the_sequential_recurrence(seq, tiles):
    g = _ssd_graph(seq)
    op = g.ops["ssd"]
    assert max_tiles(g, op, tiles) == tiles
    env = _ssd_inputs(seq)
    want = _recurrence(env)
    if tiles == 1:
        got = runtime.execute_graph(g, {k: env[k] for k in g.inputs},
                                    {k: env[k] for k in env
                                     if k not in g.inputs})["ssd:out"]
    else:
        got = _tiled(g, op, env, tiles)
    assert _gap(got, want) < 1e-6


def test_ssd_tiles_whole_heads_and_counts_the_chunked_work():
    g = _ssd_graph(48)
    op = g.ops["ssd"]
    # 32 channels, but at most 4 tiles: one head's channels stay together
    assert tile_axis(g, op) == 2 and max_tiles(g, op, 16) == 4
    assert max_tiles(g, op, 3) == 2
    # 3 chunks of 16: C.B^T 16*16*6, the masked product 4*16*16*8, and
    # the chunk states and their read-out 2*4*16*8*6
    per_chunk = 16 * 16 * STATE + HEADS * 16 * 16 * HEAD_DIM \
        + 2 * HEADS * 16 * HEAD_DIM * STATE
    assert op_macs(g, op) == ssd_macs(48, HEADS, HEAD_DIM, STATE, CHUNK) \
        == 3 * per_chunk
    # a sequence under a chunk is one chunk of its own length
    assert ssd_macs(11, HEADS, HEAD_DIM, STATE, CHUNK) == (
        11 * 11 * STATE + HEADS * 11 * (11 * HEAD_DIM
                                        + 2 * HEAD_DIM * STATE))


def test_segment_sums_are_sums_of_the_segment():
    """The decays inside a chunk come from sums of each segment's own
    terms: with one huge term before a segment, a difference of running
    sums would lose the segment."""
    a = np.array([-1e4, -0.5, -0.25, -0.125], np.float32)
    seg = np.asarray(runtime._segsum(a))
    assert seg[3, 1] == np.float32(-0.375) and seg[2, 1] == -0.25
    assert seg[1, 1] == 0.0 and seg[1, 0] == -0.5
    assert np.all(np.isneginf(seg[np.triu_indices(4, 1)]))


# ---------------------------------------------------------------------------
# causal conv and causal softmax
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tiles", [1, 2, 3, 6])
def test_causal_conv1d_matches_shifted_taps(tiles):
    seq, ch, k = 10, 6, 4
    g = Graph("conv")
    x = g.add_input("x", (1, seq, ch))
    y = g.add_op("causal_conv1d", [x, g.add_param("w", (k, ch)),
                                   g.add_param("b", (ch,))], name="conv")
    g.mark_output(y)
    op = g.ops["conv"]
    assert tile_axis(g, op) == 2 and max_tiles(g, op, tiles) == tiles
    rng = np.random.default_rng(3)
    env = {"x": rng.standard_normal((1, seq, ch)).astype(np.float32),
           "w": rng.standard_normal((k, ch)).astype(np.float32),
           "b": rng.standard_normal(ch).astype(np.float32)}
    want = np.tile(env["b"], (seq, 1)).astype(np.float64)
    for t in range(seq):
        for j in range(k):
            if t - (k - 1) + j >= 0:
                want[t] += env["w"][j] * env["x"][0, t - (k - 1) + j]
    got = (runtime.execute_graph(g, {"x": env["x"]},
                                 {"w": env["w"], "b": env["b"]})[y]
           if tiles == 1 else _tiled(g, op, env, tiles))
    assert _gap(got, want[None]) < 1e-6


@pytest.mark.parametrize("tiles", [2, 4])
def test_causal_softmax_row_tiles_see_their_own_rows(tiles):
    """Two heads' query rows stacked over one key axis: row r is query
    position r mod keys, and a row tile knows its first row."""
    keys = 6
    g = Graph("attn")
    s = g.add_input("s", (3, 2 * keys, keys))
    g.mark_output(g.add_op("softmax", [s], name="p", causal=True))
    op = g.ops["p"]
    assert tile_axis(g, op) == 1
    x = np.random.default_rng(4).standard_normal((3, 2 * keys, keys))
    env = {"s": x.astype(np.float32)}
    pos = np.arange(2 * keys) % keys
    masked = np.where(np.arange(keys)[None, :] <= pos[:, None], x, -np.inf)
    want = np.exp(masked - masked.max(-1, keepdims=True))
    want /= want.sum(-1, keepdims=True)
    assert _gap(_tiled(g, op, env, tiles), want) < 1e-6
    whole = runtime.execute_graph(g, env, {})["p:out"]
    assert _gap(whole, want) < 1e-6


def test_causal_gqa_attention_matches_the_reference(ref, nn):
    """One attention layer (4 query heads over 2 key/value heads) of the
    stage, whole-graph, against the reference's repeated-key layout."""
    seq = 24
    widths = dict(SMALL, pattern="a")
    g = granite_h_micro_stage4(seq=seq, chunk=CHUNK, **widths)
    params = _seeded(ref.params(**widths), 7)
    x = np.random.default_rng(8).standard_normal((1, seq, 64))
    x = x.astype(np.float32)
    want = ref.forward(params, x, nn("highest"), attn_head_dim=16)
    got = runtime.execute_graph(g, {"h": x}, params)["logits:out"]
    assert _gap(got, want) < STAGE_GAP


# ---------------------------------------------------------------------------
# the stage
# ---------------------------------------------------------------------------


def test_graph_names_and_shapes_match_the_reference(ref):
    g = granite_h_micro_stage4()
    have = {n: t.shape for n, t in g.tensors.items() if t.kind == "param"}
    assert have == ref.params()
    assert g.inputs == [ref.INPUT[0]] and g.outputs == [ref.OUTPUT]
    assert g.tensors["h"].shape == (1, *ref.INPUT[1])
    assert g.tensors[g.outputs[0]].shape == (1, 100352)
    assert g.tokens == 2048 and edge.ALL_MODELS[NAME] is granite_h_micro_stage4
    # layers 30-39, attention at 35, 952 M parameters at published widths
    assert "l35_q" in have and "l34_q" not in have and "l39_D" in have
    assert g.total_params() == 951_991_232
    # every multiplier is an attribute, never a parameter
    scales = sorted({op.attrs["scale"] for op in g.topo_ops()
                     if op.op_type == "mul" and "scale" in op.attrs})
    assert scales == [0.015625, 0.125, 0.22]
    assert {op.attrs.get("eps") for op in g.topo_ops()
            if op.op_type == "rmsnorm"} == {1e-5}


@pytest.fixture(scope="module")
def small_stage(ref):
    seq = 40                       # two whole chunks and a half
    widths = dict(SMALL, pattern="mam")
    g = granite_h_micro_stage4(seq=seq, chunk=CHUNK, **widths)
    params = _seeded(ref.params(**widths), 11)
    xs = np.random.default_rng(12).standard_normal((3, 1, seq, 64))
    return g, params, xs.astype(np.float32)


def test_stage_graph_matches_the_reference(ref, nn, small_stage):
    g, params, xs = small_stage
    want = np.asarray(ref.forward(params, xs[:, 0], nn("highest"),
                                  attn_head_dim=16))
    for i in range(len(xs)):
        got = runtime.execute_graph(g, {"h": xs[i]}, params)["logits:out"]
        assert _gap(got, want[i:i + 1]) < STAGE_GAP


def test_served_stage_matches_the_reference(ref, nn, small_stage):
    """The stage through the normal path: a Carfield deployment session,
    its plan store, and the engine's one jitted program per plan."""
    g, params, xs = small_stage
    session = DeploymentSession(CompileRequest(
        graphs=[g], soc=carfield_soc(), patterns=carfield_patterns(),
        time_budget_s=0.001, joint_time_budget_s=0.001,
        lazy_joint_time_budget_s=0.001, incremental_time_budget_s=0.001))
    compiled = session.compile()
    plan = session.try_plan_for([0])
    kinds = {g.ops[o].op_type for sn in plan.tenants[0].supernodes
             if not sn.full for o in sn.op_names}
    assert {"ssd", "dense"} <= kinds       # split over devices, by tiles
    eng = MultiModelEngine(compiled, params_list=[params], execute=True,
                           max_batch=2,
                           async_compile=BackgroundCompiler(session,
                                                            start=False))
    rids = [eng.submit(0, inputs={"h": x}) for x in xs]
    while eng.pending:
        eng.step()
    assert eng.floor_rounds == 0 and eng.batched_repeat_rounds >= 1
    want = np.asarray(ref.forward(params, xs[:, 0], nn("highest"),
                                  attn_head_dim=16))
    for i, rid in enumerate(rids):
        assert _gap(eng.results[rid]["logits:out"], want[i:i + 1]) \
            < STAGE_GAP
    # and the control the benchmark sets its limit against reads above
    ctl = np.asarray(ref.forward(params, xs[:, 0], nn("high"),
                                 attn_head_dim=16))
    assert _gap(ctl, want) > 2 * STAGE_GAP


# ---------------------------------------------------------------------------
# the MLPerf-Tiny deployments' plans are as they were
# ---------------------------------------------------------------------------


def _tiny_session(config):
    """The benchmark's co-compiled MLPerf-Tiny deployment ``config``."""
    with open(os.path.join(BENCH, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    session = DeploymentSession(CompileRequest(
        graphs=[edge.ALL_MODELS[m]() for m in cfg["models"]],
        soc=carfield_soc(), patterns=carfield_patterns(), **cfg["compile"]))
    session.compile()
    return session, len(cfg["models"])


@pytest.fixture(scope="module")
def tiny3():
    return _tiny_session("mlperf-tiny3-carfield")[0]


@pytest.mark.parametrize("config,kernels,makespan", [
    ("mlperf-tiny3-carfield", 63, 18_876_312.5),
    ("mlperf-tiny4-carfield", 124, 31_210_071.7),
])
def test_tiny_full_house_plans_are_unchanged(config, kernels, makespan):
    """The benchmark's full-house plans, as its runs print them: kernel
    nodes and analytic makespan (cycles, to the printed 0.1)."""
    session, models = _tiny_session(config)
    plan = session.try_plan_for(list(range(models)))
    got = sum(1 for n in plan.order if plan.nodes[n].kind == "kernel"
              and plan.nodes[n].supernode is not None)
    assert (got, round(float(plan.makespan), 1)) == (kernels, makespan)


TINY3_PLANS = ["full-house", "autoencoder", "ds_cnn", "resnet"]


def _tiny3_plan(session, which):
    if which == "full-house":
        return session.try_plan_for([0, 1, 2])
    return session.compile().singles[TINY3_PLANS.index(which) - 1].plan


@pytest.mark.parametrize("which", TINY3_PLANS)
def test_tiny_plan_segments_stitch_to_the_oracle(tiny3, which):
    """The plan's tile semantics, which the plan's program folds away:
    the oracle drives each kernel node's segment through
    ``runtime.run_supernode`` in scheduled order (conv halos,
    neuron-tiled dense layers) and joins each op's segments in tile
    order (``runtime.execute_plan_tiles``).  The full-house plan and each
    model's compile-alone plan, all of which split some op."""
    plan = _tiny3_plan(tiny3, which)
    sns = [{s.name: s for s in tg.supernodes}
           for tg in runtime.plan_tenants(plan)]
    assert any(not sns[t][n].full for t, n in runtime.plan_kernels(plan))
    if which == "full-house":
        assert runtime.multi_plan_matches_oracle(plan)
    else:
        assert runtime.plan_matches_oracle(plan)


@pytest.mark.parametrize("fault", ["overlap", "gap"])
def test_segments_that_do_not_tile_an_op_are_refused(tiny3, fault):
    """A plan whose segments of a split op overlap (one runs twice) or
    leave a gap (one is left out) fails the oracle's tile execution,
    where the program, which computes the op whole, would not see it."""
    plan = _tiny3_plan(tiny3, "full-house")
    sns = [{s.name: s for s in tg.supernodes}
           for tg in runtime.plan_tenants(plan)]
    order = list(plan.order)
    k = next(i for i, name in enumerate(order)
             if plan.nodes[name].kind == "kernel"
             and plan.nodes[name].supernode is not None
             and not sns[plan.nodes[name].tenant][
                 plan.nodes[name].supernode].full)
    if fault == "overlap":
        order.insert(k, order[k])
    else:
        del order[k]
    bad = dataclasses.replace(plan, order=order)
    tgs = runtime.plan_tenants(bad)
    args = ([runtime.init_inputs(tg.graph, 2 * i + 1)
             for i, tg in enumerate(tgs)],
            [runtime.init_params(tg.graph, 2 * i)
             for i, tg in enumerate(tgs)])
    # a gap shows where a consumer reads the op, or at the end
    with pytest.raises((RuntimeError, KeyError)):
        runtime.execute_plan_tiles(bad, *args)


def test_default_attributes_leave_existing_ops_bitwise():
    """``rmsnorm`` without ``eps`` is the 1e-6 it always was."""
    g = Graph("norm")
    x = g.add_input("x", (2, 8))
    w = g.add_param("w", (8,))
    g.mark_output(g.add_op("rmsnorm", [x, w], name="n"))
    env = {"x": np.random.default_rng(1).standard_normal((2, 8))
           .astype(np.float32)}
    p = {"w": np.ones(8, np.float32)}
    got = np.asarray(runtime.execute_graph(g, env, p)["n:out"])
    xj = jax.numpy.asarray(env["x"])
    want = np.asarray(xj / jax.numpy.sqrt(
        jax.numpy.mean(xj * xj, axis=-1, keepdims=True) + 1e-6))
    assert np.array_equal(got, want)
