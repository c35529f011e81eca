"""Numeric execution of compiled plans in JAX (the asynchronous runtime, §3.3).

Two executors:

* :func:`execute_graph` — direct whole-graph evaluation (the oracle).
* :func:`execute_plan` — execution of an :class:`ExecutionPlan`: its
  kernel nodes run in the plan's scheduled order, traced once per plan
  into one jitted XLA program whose arguments are the inputs and the
  weights (:class:`PlanPrograms`), so a plan execution is one dispatch.
  The first kernel node that covers an op computes that op whole; the
  op's later tile segments find its output computed (see
  :class:`_TenantExecutor`).  :func:`execute_graph` stays eager.

:func:`run_supernode` is the plan's tile semantics: what one kernel node
computes of its fused chain over its tile segment (conv halos, the
slice/concat helpers, a causal softmax's first row, whole-head ``ssd``
tiles), as the generated multi-device binary does on the SoC.
:func:`execute_plan_tiles` runs a plan that way, eagerly: every kernel
node computes its segment and each op's segments are joined in tile
order, which fails unless they tile the op exactly.

``execute_plan(plan) ≈ execute_graph(graph)`` and
``execute_plan_tiles(plan) ≈ execute_graph(graph)`` (allclose) are the
correctness contract of the whole compiler (:func:`plan_matches_oracle`),
asserted by the tests for every benchmark model and every toolchain mode.

Everything here runs in float32 regardless of the deployment dtype: the
numerics validate *plan structure* (kernel order, tiling, halos), not
reduced-precision kernels.  Every conv and matmul passes
``precision=PRECISION`` (``HIGHEST``): a TPU otherwise computes a float32
conv or matmul from bfloat16 inputs, and the ``1e-4`` oracle tolerances
would mean something different on the chip than on the CPU.  Moving the
device path to the deployment dtype (ROADMAP Speed item 5) replaces this
with per-dtype tolerances.
"""

from __future__ import annotations

import contextlib
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

from repro.core.ir import Graph, Op, ssd_chunk, tile_axis
from repro.core.rewrite import Supernode, TiledGraph
from repro.core.schedule import ExecutionPlan
from repro.core.spans import KERNEL, OP

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


def _einsum(spec: str, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum(spec, a, b, precision=PRECISION)


def _softmax(x: jnp.ndarray, causal: bool, row0: int) -> jnp.ndarray:
    """Softmax over the last axis; ``causal`` masks the columns above row
    ``(row0 + r) mod columns`` of each row ``r`` of the last two axes
    (``row0`` is a row tile's first row)."""
    if causal:
        rows, cols = x.shape[-2:]
        pos = (row0 + jnp.arange(rows)) % cols
        x = jnp.where(jnp.arange(cols)[None, :] <= pos[:, None], x, -jnp.inf)
    return jax.nn.softmax(x, axis=-1)


def _causal_conv1d(x: jnp.ndarray, w: jnp.ndarray,
                   b: jnp.ndarray) -> jnp.ndarray:
    """Depthwise conv of (batch, seq, channels) along the sequence, with
    zeros before the first token: ``w`` is (taps, channels)."""
    k, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = b
    for j in range(k):
        y = y + w[j] * xp[:, j:j + s]
    return y


def _segsum(a: jnp.ndarray) -> jnp.ndarray:
    """(..., L) -> (..., L, L): entry [i, j] is ``sum(a[j+1 : i+1])`` for
    j <= i and -inf above the diagonal.  Mamba-2's masked cumulative sum:
    each entry sums its own segment's terms, never the difference of two
    running sums, which loses the short segments' digits."""
    n = a.shape[-1]
    rep = jnp.broadcast_to(a[..., :, None], a.shape + (n,))
    below = jnp.tril(jnp.ones((n, n), bool), -1)
    seg = jnp.cumsum(jnp.where(below, rep, 0.0), axis=-2)
    return jnp.where(jnp.tril(jnp.ones((n, n), bool)), seg, -jnp.inf)


def _ssd(x, dt_raw, B, C, A_log, dt_bias, D, chunk: int) -> jnp.ndarray:
    """The ``ssd`` op in chunked form: within a chunk, masked matmuls of
    the decays; across chunks, each chunk's final state carried by a
    ``lax.scan``.  The sequence is padded with zero tokens to whole
    chunks (dt 0 there: no decay and no input) and cut back after."""
    b, s, hp = x.shape
    heads, state = A_log.shape[0], B.shape[-1]
    p = hp // heads
    n = ssd_chunk(s, chunk)
    pad = -s % n
    c = (s + pad) // n
    dt = jax.nn.softplus(dt_raw + dt_bias)                  # (b, s, H)

    def chunks(v):
        v = jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        return v.reshape(b, c, n, *v.shape[2:])

    xdt = chunks(x.reshape(b, s, heads, p) * dt[..., None])  # (b,c,L,H,P)
    Bc, Cc = chunks(B), chunks(C)                             # (b,c,L,N)
    dA = jnp.moveaxis(chunks(dt * -jnp.exp(A_log)), 2, 3)     # (b,c,H,L)
    seg = _segsum(dA)                                         # (b,c,H,L,L)
    # outputs from the chunk's own tokens
    cb = _einsum("bcln,bcsn->bcls", Cc, Bc)
    y = _einsum("bchls,bcshp->bclhp", cb[:, :, None] * jnp.exp(seg), xdt)
    # each chunk's final state, then the state entering every chunk
    to_end = jnp.moveaxis(jnp.exp(seg[..., -1, :]), 2, 3)     # (b,c,L,H)
    ends = _einsum("bcsn,bcshp->bchpn", Bc, xdt * to_end[..., None])
    whole = jnp.exp(jnp.sum(dA, axis=-1))                     # (b,c,H)

    def carry(h, chunk_end):
        end, decay = chunk_end
        return decay[..., None, None] * h + end, h

    _, into = lax.scan(carry, jnp.zeros((b, heads, p, state), x.dtype),
                       (jnp.moveaxis(ends, 1, 0), jnp.moveaxis(whole, 1, 0)))
    into = jnp.moveaxis(into, 0, 1)                           # (b,c,H,P,N)
    from_start = jnp.moveaxis(jnp.exp(jnp.cumsum(dA, axis=-1)), 2, 3)
    y = y + _einsum("bcln,bchpn->bclhp", Cc, into) * from_start[..., None]
    y = y.reshape(b, c * n, hp)[:, :s]
    return y + x * jnp.repeat(D, p)


def run_op(g: Graph, op: Op, ins: Sequence[jnp.ndarray],
           row0: int = 0) -> jnp.ndarray:
    """One op on whole (or tile-sliced) inputs; ``row0`` is the first row
    of a row tile, which a causal softmax needs."""
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
        return ins[0] * (ins[1] if len(ins) > 1 else a["scale"])
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
    if ot == "silu":
        return jax.nn.silu(ins[0])
    if ot == "softmax":
        return _softmax(ins[0], a.get("causal", False), row0)
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
        y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                         + a.get("eps", 1e-6))
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
    if ot == "causal_conv1d":
        return _causal_conv1d(*ins)
    if ot == "ssd":
        return _ssd(*ins, chunk=a["chunk"])
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


def run_supernode(g: Graph, sn: Supernode, env: Arrays) -> Dict[str, jnp.ndarray]:
    """Computes this supernode's tile segment for every op of its chain.
    Returns {output tensor name: tile array}; an op's segments, joined in
    tile order along its tile axis, make its whole output.  Reads full
    input tensors from ``env`` (slice helpers are applied here)."""
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
        with op_scope(op):
            tile = _run_tile(g, op, ins_full, prev_tile, ax,
                             None if ax is None or full
                             else _coord_range(g, op, lo, hi, T, ax))
        results[op.output] = tile
        prev_tile, prev_out = tile, op.output
    return results


def op_scope(op: Op):
    """A ``jax.named_scope`` ``repro.op:<op type>`` around the ops of a
    sequence-model mechanism (``ssd``, ``causal_conv1d``, a causal
    softmax) inside its kernel's scope, so that device time can be put
    down to the mechanism; no scope for other ops."""
    if op.op_type in ("ssd", "causal_conv1d") or (
            op.op_type == "softmax" and op.attrs.get("causal")):
        return jax.named_scope(f"{OP}:{op.op_type}")
    return contextlib.nullcontext()


def _run_tile(g: Graph, op: Op, ins_full: List[Optional[jnp.ndarray]],
              prev_tile: Optional[jnp.ndarray], ax: Optional[int],
              coords: Optional[Tuple[int, int]]) -> jnp.ndarray:
    """One op of a supernode's chain over the tile ``coords`` of its tile
    axis ``ax`` (None: the whole output); ``None`` in ``ins_full`` is the
    chain's running tile ``prev_tile``."""
    if coords is None:
        # untiled op (or the full-range segment): plain execution
        ins = [prev_tile if v is None else v for v in ins_full]
        return run_op(g, op, ins)
    c0, c1 = coords
    out_shape = g.tensors[op.output].shape
    if op.op_type in ("conv2d", "dwconv2d", "avg_pool2d", "max_pool2d"):
        assert prev_tile is None, "conv must head its chain"
        return _conv_row_tile(g, op, ins_full, c0, c1)
    if op.op_type in ("dense", "matmul", "batch_matmul"):
        assert prev_tile is None, "gemm must head its chain"
        x, w = ins_full[0], ins_full[1]
        return _matmul(x, _slice_axis(w, w.ndim - 1, c0, c1))
    if op.op_type == "causal_conv1d":
        assert prev_tile is None, "causal_conv1d must head its chain"
        x, w, b = ins_full
        return _causal_conv1d(x[..., c0:c1], w[:, c0:c1], b[c0:c1])
    if op.op_type == "ssd":
        # a tile of whole heads: their channels of x, their dt_raw,
        # A_log, dt_bias and D; B and C are shared by every head
        assert prev_tile is None, "ssd must head its chain"
        x, dt_raw, B, C, A_log, dt_bias, D = ins_full
        p = out_shape[-1] // A_log.shape[0]
        h0, h1 = c0 // p, c1 // p
        return _ssd(x[..., c0:c1], dt_raw[..., h0:h1], B, C, A_log[h0:h1],
                    dt_bias[h0:h1], D[h0:h1], chunk=op.attrs["chunk"])
    # elementwise / normalization: slice every full input along the tile
    # axis; 1-D bias broadcasts slice on the last axis only when that *is*
    # the tile axis (neuron tiling).
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
    return run_op(g, op, ins, row0=c0 if ax == len(out_shape) - 2 else 0)


class _TenantExecutor:
    """Execution state for ONE model (tenant) inside its plan's program.

    Runs kernel nodes in whatever order the schedule dictates.  The first
    kernel node that covers an op computes the op whole, on its full
    operands, as :func:`execute_graph` does; the op's later tile segments
    find its output in ``env`` and compute nothing (they are *folded*).
    The plan spreads an op's segments over the SoC's engines; inside one
    jitted program on one device they would only add zero-filled buffers
    to stitch into and a re-read of the op's inputs per segment, and XLA
    orders the program by its dataflow, not by the trace.  An op's inputs
    are whole in ``env`` by its first segment: every segment reads them
    whole (:func:`run_supernode`).  Tenants share no tensors, so any
    interleaving with other tenants' kernels gives each tenant the
    outputs it gets alone."""

    def __init__(self, tg: TiledGraph, inputs: Arrays, params: Arrays
                 ) -> None:
        self.g = tg.graph
        self.env: Arrays = {**inputs, **params}
        self.sn_by_name = {s.name: s for s in tg.supernodes}

    def run_kernel(self, supernode: str) -> bool:
        """Computes, whole, the ops of ``supernode``'s chain that no
        earlier kernel node computed; False when there were none."""
        g = self.g
        todo = [g.ops[n] for n in self.sn_by_name[supernode].op_names
                if g.ops[n].output not in self.env]
        for op in todo:
            with op_scope(op):
                self.env[op.output] = run_op(
                    g, op, [self.env[t] for t in op.inputs])
        return bool(todo)

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


def plan_tenants(plan) -> List[TiledGraph]:
    """The tiled graphs of ``plan`` (an :class:`ExecutionPlan` or a
    :class:`~repro.core.schedule.MultiExecutionPlan`), one per tenant
    position."""
    if isinstance(plan, ExecutionPlan):
        return [plan.tiled]
    return list(plan.tenants)


def plan_kernels(plan) -> List[Tuple[int, str]]:
    """The kernel nodes of ``plan`` as ``(tenant position, supernode)``,
    in scheduled order."""
    single = isinstance(plan, ExecutionPlan)
    nodes = [plan.nodes[name] for name in plan.order]
    return [(0 if single else n.tenant, n.supernode) for n in nodes
            if n.kind == "kernel" and n.supernode is not None]


def _plan_program(programs: "PlanPrograms", tenants: Sequence[TiledGraph],
                  kernels: Sequence[Tuple[int, str]]) -> Callable:
    """The jitted program of one plan: its tenants' graphs and its kernel
    nodes as ``(tenant position, supernode)`` in scheduled order, closed
    over as static Python.  Inputs and weights are the arguments."""

    def plan_program(inputs_list: List[Arrays], params_list: List[Arrays]
                     ) -> List[Arrays]:
        execs = [_TenantExecutor(tg, inputs_list[i], params_list[i])
                 for i, tg in enumerate(tenants)]
        folded = 0
        for t, supernode in kernels:
            ex = execs[t]
            with jax.named_scope(kernel_scope(ex.g.name, supernode)):
                folded += not ex.run_kernel(supernode)
        programs._count_build(folded)    # runs only while tracing
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
    a persistent-cache load), ``program_calls`` plan executions, and
    ``segments_folded``, over those traces, the kernel nodes that
    computed nothing: tile segments of ops an earlier segment computed
    whole (:class:`_TenantExecutor`)."""

    def __init__(self) -> None:
        # re-entrant: a weak-reference callback may run while held
        self._lock = threading.RLock()
        self._by_id: Dict[int, Tuple[weakref.ref, Callable]] = {}
        self.programs_built = 0
        self.program_calls = 0
        self.segments_folded = 0

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
            prog = _plan_program(self, plan_tenants(plan),
                                 plan_kernels(plan))
            ref = weakref.ref(plan, functools.partial(self._drop, key))
            self._by_id[key] = (ref, prog)
            return prog

    def run(self, plan, inputs_list: Sequence[Arrays],
            params_list: Sequence[Arrays]) -> List[Arrays]:
        prog = self.program(plan)
        with self._lock:
            self.program_calls += 1
        return prog(list(inputs_list), list(params_list))

    def _count_build(self, folded: int) -> None:
        with self._lock:
            self.programs_built += 1
            self.segments_folded += folded

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
    """Execution following the compiled plan, as the plan's one jitted
    program.

    Kernel nodes run in the plan's scheduled order, which respects data
    dependencies by construction (validated by ``validate_schedule``);
    each op is computed whole by the first that covers it."""
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


def execute_plan_tiles(plan, inputs_list: Sequence[Arrays],
                       params_list: Sequence[Arrays]) -> List[Arrays]:
    """The plan's tile semantics, eagerly: each kernel node, in scheduled
    order, computes its tile segment (:func:`run_supernode`), and each
    op's segments are joined in tile order along its tile axis.  Raises
    unless every split op's segments cover its tiles ``[0, T)`` once each,
    in contiguous ranges.  Takes and returns one entry per tenant, for an
    :class:`ExecutionPlan` as for a multi-tenant plan."""
    tgs = plan_tenants(plan)
    envs = [{**inputs_list[i], **params_list[i]} for i in range(len(tgs))]
    sns = [{s.name: s for s in tg.supernodes} for tg in tgs]
    # per tenant: split op output -> [(tile_lo, tile_hi, segment)]
    parts: List[Dict[str, list]] = [{} for _ in tgs]
    for t, name in plan_kernels(plan):
        g, env, sn = tgs[t].graph, envs[t], sns[t][name]
        for out, tile in run_supernode(g, sn, env).items():
            ax = tile_axis(g, g.producer_of(out))
            if ax is None or sn.full:
                env[out] = tile
                continue
            segs = parts[t].setdefault(out, [])
            segs.append((sn.tile_lo, sn.tile_hi, tile))
            if sum(hi - lo for lo, hi, _ in segs) < sn.T:
                continue
            segs.sort(key=lambda seg: seg[0])
            ranges = [(lo, hi) for lo, hi, _ in segs]
            ends = [0] + [hi for _, hi in ranges]
            if ends[-1] != sn.T or [lo for lo, _ in ranges] != ends[:-1]:
                raise RuntimeError(f"{g.name}: segments {ranges} of {out} "
                                   f"do not tile [0, {sn.T})")
            env[out] = jnp.concatenate([v for _, _, v in segs], axis=ax)
            if env[out].shape != tuple(g.tensors[out].shape):
                raise RuntimeError(f"{g.name}: {out} joins to "
                                   f"{env[out].shape}")
            del parts[t][out]
    outs = []
    for tg, env, left in zip(tgs, envs, parts):
        missing = [o for o in tg.graph.outputs if o not in env]
        if left or missing:
            raise RuntimeError(f"{tg.graph.name}: segments missing for "
                               f"{sorted(left)}, outputs {missing}")
        outs.append({o: env[o] for o in tg.graph.outputs})
    return outs


def _matches_oracle(plan, seed: int, atol: float, rtol: float) -> bool:
    tgs = plan_tenants(plan)
    params_list = [init_params(tg.graph, seed + 2 * i)
                   for i, tg in enumerate(tgs)]
    inputs_list = [init_inputs(tg.graph, seed + 2 * i + 1)
                   for i, tg in enumerate(tgs)]
    runs = {"program": programs.run(plan, inputs_list, params_list),
            "tiles": execute_plan_tiles(plan, inputs_list, params_list)}
    for i, tg in enumerate(tgs):
        g = tg.graph
        want = execute_graph(g, inputs_list[i], params_list[i])
        for how, got in runs.items():
            for t in g.outputs:
                np.testing.assert_allclose(
                    np.asarray(got[i][t]), np.asarray(want[t]),
                    atol=atol, rtol=rtol,
                    err_msg=f"{how}: tenant {i} ({g.name}) output {t}")
    return True


def plan_matches_oracle(plan: ExecutionPlan, seed: int = 0,
                        atol: float = 1e-4, rtol: float = 1e-4) -> bool:
    """Correctness contract: the plan's program (:func:`execute_plan`) and
    its tile semantics (:func:`execute_plan_tiles`) both match the
    direct whole-graph evaluation."""
    return _matches_oracle(plan, seed, atol, rtol)


def multi_plan_matches_oracle(plan, seed: int = 0, atol: float = 1e-4,
                              rtol: float = 1e-4) -> bool:
    """Multi-tenant correctness contract: the interleaved co-scheduled
    program (:func:`execute_multi_plan`) and the plan's tile semantics
    both match every tenant's single-model oracle."""
    return _matches_oracle(plan, seed, atol, rtol)
