"""Pallas TPU chunked WKV6 scan (RWKV6 / Finch).

TPU adaptation of the (GPU-recurrent) WKV kernel: instead of one thread per
channel stepping token-by-token, the sequence is split into chunks of L
tokens and each chunk is evaluated with dense MXU matmuls (the
chunked-parallel linear-attention form), carrying the (D x D) state in VMEM
scratch across the sequential chunk axis of the grid:

    A_t      = prod_{s<=t} w_s            (per-channel cumulative decay)
    rt~      = r_t * A_{t-1}
    kt~      = k_t / A_t
    intra    = (tril_strict(R~ K~^T) + diag(r_t . (u*k_t))) V
    y        = intra + R~ @ S_prev
    S_new    = diag(A_{L-1}) (S_prev + K~^T V)

Chunk length L=32 keeps the 1/A_t rescaling inside float32 range for the
decay magnitudes RWKV6 produces (w = exp(-exp(x)) is bounded away from 0 by
the log-decay parameterization); the kernel asserts nothing silently — the
sweep tests drive realistic decay ranges against the exact scan oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Every dot of the kernel runs at full f32 precision.  r~ and k~ carry the
# decay rescaling (A_{t-1} and 1/A_t span many orders of magnitude), and at
# Mosaic's default precision a v5e rounded them so far that y was off by
# 0.5 at rwkv6-3b widths; the log-decay prefix sums need it as well.
_HI = jax.lax.Precision.HIGHEST


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, sout_ref, s_ref,
                *, L: int, D: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)          # (L, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)          # (1, D) -> broadcast

    ti = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    logw = jnp.log(jnp.maximum(w, 1e-20))
    # within-chunk prefix sum as a product with the lower-triangular ones
    # matrix (Mosaic has no cumsum)
    tril = jnp.where(ti >= si, 1.0, 0.0).astype(jnp.float32)
    logA = jax.lax.dot_general(tril, logw, (((1,), (0,)), ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)
    A_prev = jnp.exp(logA - logw)             # A_{t-1} = A_t / w_t
    r_t = r * A_prev
    k_t = k * jnp.exp(-logA)

    s = jax.lax.dot_general(r_t, k_t, (((1,), (1,)), ((), ())),
                            precision=_HI,
                            preferred_element_type=jnp.float32)  # (L, L)
    s = jnp.where(ti > si, s, 0.0)            # strictly lower triangular
    diag = jnp.sum(r * (u * k), axis=1)       # (L,)
    y = jax.lax.dot_general(s, v, (((1,), (0,)), ((), ())),
                            precision=_HI, preferred_element_type=jnp.float32)
    y += diag[:, None] * v
    y += jax.lax.dot_general(r_t, s_ref[...], (((1,), (0,)), ((), ())),
                             precision=_HI,
                             preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    ktv = jax.lax.dot_general(k_t, v, (((0,), (0,)), ((), ())),
                              precision=_HI,
                              preferred_element_type=jnp.float32)  # (D, D)
    # log A_{L-1} as a (D, 1) column: the chunk's column sums of log w,
    # contracted on the MXU (no row indexing or transpose in the kernel)
    log_a_last = jax.lax.dot_general(
        logw, jnp.ones((L, 1), jnp.float32), (((0,), (0,)), ((), ())),
        precision=_HI,
        preferred_element_type=jnp.float32)
    s_ref[...] = jnp.exp(log_a_last) * (s_ref[...] + ktv)

    @pl.when(ci == pl.num_programs(1) - 1)
    def _done():
        sout_ref[0] = s_ref[...]


def wkv6_pallas(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                w: jnp.ndarray, u: jnp.ndarray, chunk: int = 32,
                interpret: bool = False):
    """r,k,v,w: (B,T,H,D); u: (H,D) -> (y (B,T,H,D), S (B,H,D,D))."""
    B, T, H, D = r.shape
    L = min(chunk, T)
    assert T % L == 0, (T, L)
    BH = B * H

    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(BH, T, D)

    rf, kf, vf, wf = flat(r), flat(k), flat(v), flat(w)
    uf = jnp.broadcast_to(u[None], (B, H, D)).reshape(BH, 1, D)

    y, s = pl.pallas_call(
        functools.partial(_wkv_kernel, L=L, D=D),
        grid=(BH, T // L),
        in_specs=[
            pl.BlockSpec((1, L, D), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, L, D), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, L, D), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, L, D), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, D), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, L, D), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, D, D), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), r.dtype),
            jax.ShapeDtypeStruct((BH, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rf, kf, vf, wf, uf)
    y = y.reshape(B, H, T, D).transpose(0, 2, 1, 3)
    return y, s.reshape(B, H, D, D)
