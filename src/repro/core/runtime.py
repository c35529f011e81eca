"""Numeric execution of compiled plans in JAX (the asynchronous runtime, §3.3).

Two executors:

* :func:`execute_graph` — direct whole-graph evaluation (the oracle).
* :func:`execute_plan` — tile-by-tile execution of an :class:`ExecutionPlan`:
  every supernode computes exactly its tile segment of the fused chain
  (including conv halos and the slice/concat helper semantics), and the
  segments are stitched back into the full tensors, mirroring what the
  generated multi-device binary does on the SoC.

A plan's structure (kernel order, tile ranges, slices, pads, stitch
offsets) is Python fixed when the plan is compiled, so the tile-stitching
executor is traced once per plan into one jitted XLA program whose
arguments are the inputs and the weights (:class:`PlanPrograms`): a plan
execution is one dispatch.  :func:`execute_graph` stays eager.

``execute_plan(plan) ≈ execute_graph(graph)`` (allclose) is the correctness
contract of the whole compiler and is asserted by the tests for every
benchmark model and every toolchain mode.

Everything here runs in float32 regardless of the deployment dtype: the
numerics validate *plan structure* (tiling, halos, segment stitching), not
reduced-precision kernels.  Every conv and matmul passes
``precision=PRECISION`` (``HIGHEST``): a TPU otherwise computes a float32
conv or matmul from bfloat16 inputs, and the ``1e-4`` oracle tolerances
would mean something different on the chip than on the CPU.  Moving the
device path to the deployment dtype (ROADMAP Speed item 5) replaces this
with per-dtype tolerances.
"""

from __future__ import annotations

import functools
import math
import re
import threading
import weakref
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.ir import Graph, Op, tile_axis
from repro.core.rewrite import Supernode, TiledGraph
from repro.core.schedule import ExecutionPlan
from repro.core.spans import KERNEL

Arrays = Dict[str, jnp.ndarray]

PRECISION = lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Parameter / input initialization
# ---------------------------------------------------------------------------


def init_params(g: Graph, seed: int = 0) -> Arrays:
    rng = np.random.default_rng(seed)
    out: Arrays = {}
    for name, t in g.tensors.items():
        if t.kind == "param":
            fan_in = int(np.prod(t.shape[:-1])) or 1
            scale = 1.0 / math.sqrt(fan_in)
            out[name] = jnp.asarray(
                rng.normal(0.0, scale, size=t.shape).astype(np.float32))
    return out


def init_inputs(g: Graph, seed: int = 1) -> Arrays:
    rng = np.random.default_rng(seed)
    return {n: jnp.asarray(rng.normal(0.0, 1.0, size=g.tensors[n].shape)
                           .astype(np.float32)) for n in g.inputs}


# ---------------------------------------------------------------------------
# Full-op semantics
# ---------------------------------------------------------------------------


def _conv_pads(h: int, kh: int, stride: int, padding: str) -> Tuple[int, int]:
    if padding != "same":
        return 0, 0
    out = math.ceil(h / stride)
    total = max((out - 1) * stride + kh - h, 0)
    return total // 2, total - total // 2


def _pad_nhwc(x: jnp.ndarray, kh: int, kw: int, stride: int,
              padding: str) -> jnp.ndarray:
    if padding != "same":
        return x
    _, h, w, _ = x.shape
    pt, pb = _conv_pads(h, kh, stride, padding)
    pl_, pr = _conv_pads(w, kw, stride, padding)
    return jnp.pad(x, ((0, 0), (pt, pb), (pl_, pr), (0, 0)))


def _conv(ot: str, xp: jnp.ndarray, w: jnp.ndarray,
          stride: int) -> jnp.ndarray:
    """VALID conv2d / dwconv2d of an already padded NHWC input."""
    groups = xp.shape[-1] if ot == "dwconv2d" else 1
    if ot == "dwconv2d":
        # HWIO with I=1: reshape to (kh, kw, 1, C*mult) grouped conv
        w = w.reshape(w.shape[0], w.shape[1], 1, -1)
    return lax.conv_general_dilated(
        xp, w, window_strides=(stride, stride), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=PRECISION)


def _matmul(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    return jnp.matmul(x, w, precision=PRECISION)


def run_op(g: Graph, op: Op, ins: Sequence[jnp.ndarray]) -> jnp.ndarray:
    a = op.attrs
    ot = op.op_type
    if ot in ("conv2d", "dwconv2d"):
        x, w = ins[0], ins[1]
        stride = a.get("stride", 1)
        padding = a.get("padding", "same")
        xp = _pad_nhwc(x, w.shape[0], w.shape[1], stride, padding)
        return _conv(ot, xp, w, stride)
    if ot in ("dense", "matmul", "batch_matmul"):
        return _matmul(ins[0], ins[1])
    if ot == "add":
        return ins[0] + ins[1]
    if ot == "sub":
        return ins[0] - ins[1]
    if ot == "mul":
        return ins[0] * ins[1]
    if ot == "bias_add":
        return ins[0] + ins[1]
    if ot == "relu":
        return jnp.maximum(ins[0], 0.0)
    if ot == "relu6":
        return jnp.clip(ins[0], 0.0, 6.0)
    if ot == "gelu":
        return jax.nn.gelu(ins[0], approximate=False)
    if ot == "sigmoid":
        return jax.nn.sigmoid(ins[0])
    if ot == "tanh":
        return jnp.tanh(ins[0])
    if ot == "erf":
        return lax.erf(ins[0])
    if ot == "softmax":
        return jax.nn.softmax(ins[0], axis=-1)
    if ot == "layernorm":
        x = ins[0]
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mu) / jnp.sqrt(var + 1e-5)
        if len(ins) >= 3:
            y = y * ins[1] + ins[2]
        return y
    if ot == "rmsnorm":
        x = ins[0]
        y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
        if len(ins) >= 2:
            y = y * ins[1]
        return y
    if ot in ("avg_pool2d", "max_pool2d"):
        k = a["pool_size"]
        s = a.get("stride", k)
        pad = a.get("padding", "valid").upper()
        x = ins[0]
        if ot == "max_pool2d":
            return lax.reduce_window(x, -jnp.inf, lax.max,
                                     (1, k, k, 1), (1, s, s, 1), pad)
        summed = lax.reduce_window(x, 0.0, lax.add,
                                   (1, k, k, 1), (1, s, s, 1), pad)
        return summed / float(k * k)
    if ot == "global_avg_pool":
        return jnp.mean(ins[0], axis=(1, 2))
    if ot == "reshape":
        return jnp.reshape(ins[0], tuple(g.tensors[op.output].shape))
    if ot == "flatten":
        n = ins[0].shape[0]
        return jnp.reshape(ins[0], (n, -1))
    if ot == "transpose":
        return jnp.transpose(ins[0], a["perm"])
    if ot == "slice":
        idx = [slice(None)] * ins[0].ndim
        idx[a["axis"]] = slice(a["begin"], a["end"])
        return ins[0][tuple(idx)]
    if ot == "concat":
        return jnp.concatenate(ins, axis=a["axis"])
    if ot == "pad":
        pads = [(0, 0)] * ins[0].ndim
        for ax, (lo, hi) in a["paddings"].items():
            pads[int(ax)] = (lo, hi)
        return jnp.pad(ins[0], pads)
    if ot == "identity":
        return ins[0]
    raise NotImplementedError(ot)


def execute_graph(g: Graph, inputs: Arrays, params: Arrays) -> Arrays:
    """Direct whole-graph evaluation (the numeric oracle)."""
    env: Arrays = {**inputs, **params}
    for op in g.topo_ops():
        env[op.output] = run_op(g, op, [env[t] for t in op.inputs])
    return {t: env[t] for t in g.outputs}


# ---------------------------------------------------------------------------
# Tiled execution
# ---------------------------------------------------------------------------


def _coord_range(g: Graph, op: Op, lo: int, hi: int, T: int,
                 ax: int) -> Tuple[int, int]:
    extent = g.tensors[op.output].shape[ax]
    assert extent % T == 0, (op.name, extent, T)
    step = extent // T
    return lo * step, hi * step


def _slice_axis(x: jnp.ndarray, ax: int, c0: int, c1: int) -> jnp.ndarray:
    idx = [slice(None)] * x.ndim
    idx[ax] = slice(c0, c1)
    return x[tuple(idx)]


def _conv_row_tile(g: Graph, op: Op, ins: Sequence[jnp.ndarray],
                   r0: int, r1: int) -> jnp.ndarray:
    """Rows [r0, r1) of a conv2d / dwconv2d / pool output, computed from an
    input slice with halo — the slice helper semantics of §3.1."""
    a = op.attrs
    ot = op.op_type
    x = ins[0]
    if ot in ("conv2d", "dwconv2d"):
        w = ins[1]
        kh, kw = w.shape[0], w.shape[1]
        stride = a.get("stride", 1)
        padding = a.get("padding", "same")
        xp = _pad_nhwc(x, kh, kw, stride, padding)
        i0 = r0 * stride
        i1 = (r1 - 1) * stride + kh
        return _conv(ot, xp[:, i0:i1, :, :], w, stride)
    if ot in ("avg_pool2d", "max_pool2d"):
        k = a["pool_size"]
        s = a.get("stride", k)
        i0, i1 = r0 * s, (r1 - 1) * s + k
        xs = x[:, i0:i1, :, :]
        sub = Op(op.name + ":t", ot, op.inputs, op.output,
                 {**a, "padding": "valid"})
        return run_op(g, sub, [xs])
    raise NotImplementedError(ot)


def _chain_is_neuron_tiled(g: Graph, head: Op) -> bool:
    ax = tile_axis(g, head)
    out = g.tensors[head.output]
    return ax is not None and ax == len(out.shape) - 1


def run_supernode(g: Graph, sn: Supernode, env: Arrays) -> Dict[str, jnp.ndarray]:
    """Computes this supernode's tile segment for every op of its chain.
    Returns {output tensor name: tile array} (to be stitched by the caller).
    Reads full input tensors from ``env`` (slice helpers are applied here)."""
    lo, hi, T = sn.tile_lo, sn.tile_hi, sn.T
    results: Dict[str, jnp.ndarray] = {}
    prev_tile: Optional[jnp.ndarray] = None
    prev_out: Optional[str] = None
    for name in sn.op_names:
        op = g.ops[name]
        ax = tile_axis(g, op)
        full = (lo, hi) == (0, T)
        ins_full = []
        for t in op.inputs:
            if t == prev_out and prev_tile is not None:
                ins_full.append(None)        # consumed as the running tile
            else:
                ins_full.append(env[t])
        if ax is None or full:
            # untiled op (or the full-range segment): plain execution
            ins = [prev_tile if v is None else v for v in ins_full]
            tile = run_op(g, op, ins)
        else:
            c0, c1 = _coord_range(g, op, lo, hi, T, ax)
            out_shape = g.tensors[op.output].shape
            if op.op_type in ("conv2d", "dwconv2d", "avg_pool2d",
                              "max_pool2d"):
                assert prev_tile is None, "conv must head its chain"
                tile = _conv_row_tile(g, op, ins_full, c0, c1)
            elif op.op_type in ("dense", "matmul", "batch_matmul"):
                assert prev_tile is None, "gemm must head its chain"
                x, w = ins_full[0], ins_full[1]
                tile = _matmul(x, _slice_axis(w, w.ndim - 1, c0, c1))
            else:
                # elementwise / normalization: slice every full input along
                # the tile axis; 1-D bias broadcasts slice on the last axis
                # only when that *is* the tile axis (neuron tiling).
                ins = []
                for v, t in zip(ins_full, op.inputs):
                    if v is None:
                        ins.append(prev_tile)
                        continue
                    ti = g.tensors[t]
                    if len(ti.shape) == len(out_shape):
                        if ti.shape[ax] == out_shape[ax]:
                            ins.append(_slice_axis(v, ax, c0, c1))
                        else:
                            ins.append(v)            # broadcast dim
                    elif (len(ti.shape) == 1
                          and ax == len(out_shape) - 1
                          and ti.shape[0] == out_shape[-1]):
                        ins.append(v[c0:c1])         # sliced bias (neuron)
                    else:
                        ins.append(v)
                tile = run_op(g, op, ins)
        results[op.output] = tile
        prev_tile, prev_out = tile, op.output
    return results


class _TenantExecutor:
    """Tile-stitching execution state for ONE model (tenant).

    Runs supernode kernels in whatever order the schedule dictates and
    stitches tile segments back into full tensors with
    ``dynamic_update_slice`` (the concat-helper semantics).  Segments are
    disjoint, so any interleaving with other tenants' kernels produces
    bitwise-identical outputs to running this model alone."""

    def __init__(self, tg: TiledGraph, inputs: Arrays, params: Arrays
                 ) -> None:
        self.g = tg.graph
        self.env: Arrays = {**inputs, **params}
        self.buf: Dict[str, jnp.ndarray] = {}
        self.filled: Dict[str, int] = {}
        self.sn_by_name = {s.name: s for s in tg.supernodes}

    def run_kernel(self, supernode: str) -> None:
        g = self.g
        sn = self.sn_by_name[supernode]
        tiles = run_supernode(g, sn, self.env)
        for out_t, tile in tiles.items():
            op = g.producer_of(out_t)
            ax = tile_axis(g, op)
            if ax is None or sn.full:
                self.env[out_t] = tile
                continue
            if out_t not in self.buf:
                self.buf[out_t] = jnp.zeros(g.tensors[out_t].shape,
                                            dtype=tile.dtype)
                self.filled[out_t] = 0
            c0, _ = _coord_range(g, op, sn.tile_lo, sn.tile_hi, sn.T, ax)
            start = [0] * self.buf[out_t].ndim
            start[ax] = c0
            self.buf[out_t] = lax.dynamic_update_slice(self.buf[out_t],
                                                       tile, start)
            self.filled[out_t] += sn.tiles
            if self.filled[out_t] == sn.T:
                self.env[out_t] = self.buf.pop(out_t)

    def outputs(self) -> Arrays:
        missing = [t for t in self.g.outputs if t not in self.env]
        if missing:
            raise RuntimeError(f"plan did not produce outputs: {missing}")
        return {t: self.env[t] for t in self.g.outputs}


def kernel_scope(tenant: str, supernode: str) -> str:
    """Name of the ``jax.named_scope`` that holds one kernel node's ops,
    in the op metadata (``op_name``) of the device ops it compiles to.
    Characters other than letters, digits, ``_``, ``.`` and ``-`` are
    written ``_``: XLA's metadata cuts a scope at an ``@``, which host
    supernodes (``..._wildcard@host``) carry."""
    return ":".join([KERNEL] + [re.sub(r"[^\w.\-]", "_", part)
                                for part in (tenant, supernode)])


def _plan_program(programs: "PlanPrograms", tenants: Sequence[TiledGraph],
                  kernels: Sequence[Tuple[int, str]]) -> Callable:
    """The jitted program of one plan: its tenants' graphs and its kernel
    nodes as ``(tenant position, supernode)`` in scheduled order, closed
    over as static Python.  Inputs and weights are the arguments."""

    def plan_program(inputs_list: List[Arrays], params_list: List[Arrays]
                     ) -> List[Arrays]:
        programs._count_build()          # runs only while tracing
        execs = [_TenantExecutor(tg, inputs_list[i], params_list[i])
                 for i, tg in enumerate(tenants)]
        for t, supernode in kernels:
            ex = execs[t]
            with jax.named_scope(kernel_scope(ex.g.name, supernode)):
                ex.run_kernel(supernode)
        return [ex.outputs() for ex in execs]

    return jax.jit(plan_program)


class PlanPrograms:
    """One jitted program per plan, built on the plan's first execution
    and reused after.

    Keyed by ``id(plan)`` beside a weak reference to the plan (plans
    compare by value and are unhashable): the program is dropped when its
    plan is collected, as after a ``PlanStore`` eviction.  The program
    closes over the plan's tenants and kernel list, never the plan.
    ``programs_built`` counts traces (each followed by an XLA compile or
    a persistent-cache load), ``program_calls`` plan executions."""

    def __init__(self) -> None:
        # re-entrant: a weak-reference callback may run while held
        self._lock = threading.RLock()
        self._by_id: Dict[int, Tuple[weakref.ref, Callable]] = {}
        self.programs_built = 0
        self.program_calls = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._by_id)

    def program(self, plan) -> Callable:
        """The jitted program of ``plan`` (an :class:`ExecutionPlan` or a
        :class:`~repro.core.schedule.MultiExecutionPlan`), taking
        ``(inputs_list, params_list)``, one entry per tenant."""
        key = id(plan)
        with self._lock:
            entry = self._by_id.get(key)
            if entry is not None and entry[0]() is plan:
                return entry[1]
            single = isinstance(plan, ExecutionPlan)
            tenants = [plan.tiled] if single else list(plan.tenants)
            kernels = []
            for name in plan.order:
                n = plan.nodes[name]
                if n.kind == "kernel" and n.supernode is not None:
                    kernels.append((0 if single else n.tenant, n.supernode))
            prog = _plan_program(self, tenants, kernels)
            ref = weakref.ref(plan, functools.partial(self._drop, key))
            self._by_id[key] = (ref, prog)
            return prog

    def run(self, plan, inputs_list: Sequence[Arrays],
            params_list: Sequence[Arrays]) -> List[Arrays]:
        prog = self.program(plan)
        with self._lock:
            self.program_calls += 1
        return prog(list(inputs_list), list(params_list))

    def _count_build(self) -> None:
        with self._lock:
            self.programs_built += 1

    def _drop(self, key: int, ref: weakref.ref) -> None:
        with self._lock:
            entry = self._by_id.get(key)
            if entry is not None and entry[0] is ref:
                del self._by_id[key]


# the process's plan programs, which ``execute_plan`` and
# ``execute_multi_plan`` run
programs = PlanPrograms()


def execute_plan(plan: ExecutionPlan, inputs: Arrays, params: Arrays
                 ) -> Arrays:
    """Tile-by-tile execution following the compiled plan, as the plan's
    one jitted program.

    Segments are stitched with ``dynamic_update_slice`` (the concat helper);
    supernodes run in the plan's scheduled order, which respects data
    dependencies by construction (validated by ``validate_schedule``)."""
    return programs.run(plan, [inputs], [params])[0]


def execute_multi_plan(plan, inputs_list: Sequence[Arrays],
                       params_list: Sequence[Arrays]) -> List[Arrays]:
    """Interleaved-tenant execution of a
    :class:`repro.core.schedule.MultiExecutionPlan`, as the plan's one
    jitted program.

    Kernels run in global scheduled order; each dispatches into its
    tenant's private executor, so N models make progress concurrently the
    way the co-schedule interleaves them on the SoC.  Numerics are
    identical to running each model alone (asserted by
    :func:`multi_plan_matches_oracle`)."""
    return programs.run(plan, inputs_list, params_list)


def plan_matches_oracle(plan: ExecutionPlan, seed: int = 0,
                        atol: float = 1e-4, rtol: float = 1e-4) -> bool:
    g = plan.tiled.graph
    params = init_params(g, seed)
    inputs = init_inputs(g, seed + 1)
    want = execute_graph(g, inputs, params)
    got = execute_plan(plan, inputs, params)
    for t in g.outputs:
        np.testing.assert_allclose(np.asarray(got[t]), np.asarray(want[t]),
                                   atol=atol, rtol=rtol)
    return True


def multi_plan_matches_oracle(plan, seed: int = 0, atol: float = 1e-4,
                              rtol: float = 1e-4) -> bool:
    """Multi-tenant correctness contract: the interleaved co-scheduled
    execution matches every tenant's single-model oracle."""
    inputs_list, params_list = [], []
    for i, tg in enumerate(plan.tenants):
        params_list.append(init_params(tg.graph, seed + 2 * i))
        inputs_list.append(init_inputs(tg.graph, seed + 2 * i + 1))
    got = execute_multi_plan(plan, inputs_list, params_list)
    for i, tg in enumerate(plan.tenants):
        g = tg.graph
        want = execute_graph(g, inputs_list[i], params_list[i])
        for t in g.outputs:
            np.testing.assert_allclose(
                np.asarray(got[i][t]), np.asarray(want[t]),
                atol=atol, rtol=rtol,
                err_msg=f"tenant {i} ({g.name}) output {t}")
    return True
