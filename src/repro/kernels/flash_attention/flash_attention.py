"""Pallas TPU flash attention: online-softmax over KV tiles in VMEM.

Grid = (batch*heads, q_tiles, kv_tiles); the kv axis is the innermost
(sequential) grid dimension, accumulating the running (m, l, acc) state in
VMEM scratch and finalizing the output tile on the last kv step — the
standard TPU flash-attention schedule.  GQA is handled in the k/v
index_maps (query head h reads kv head h // group_size), so no k/v
broadcast materializes in HBM.  Causal + sliding-window masks are applied
in-kernel; fully-masked kv tiles still run (TPU grids are dense) but only
move already-resident VMEM data.

Block sizes default to (128, 128): MXU-aligned on the (bq x bk) logits
matmul and the (bk x Dh) value matmul.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, window: Optional[int],
               bq: int, bk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)          # (bq, dh)
    k = k_ref[0].astype(jnp.float32)          # (bk, dh)
    v = v_ref[0].astype(jnp.float32)          # (bk, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
        if not causal:
            mask &= (kpos - qpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                        # (bq,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           causal: bool = True,
                           window: Optional[int] = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False) -> jnp.ndarray:
    """q: (B,S,H,Dh); k,v: (B,S,KV,Dh).  Returns (B,S,H,Dh)."""
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    groups = H // KV
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    scale = 1.0 / math.sqrt(Dh)

    # flatten heads into the leading grid dim: (B*H, S, Dh) / (B*KV, S, Dh)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, S, Dh)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, S, Dh)

    def kv_row(b):                       # query row b -> kv row
        return (b // H) * KV + (b % H) // groups

    grid = (B * H, S // bq, S // bk)
    out = pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, Dh), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, Dh), lambda b, qi, ki: (kv_row(b), ki, 0)),
            pl.BlockSpec((1, bk, Dh), lambda b, qi, ki: (kv_row(b), ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dh), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, Dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, Dh).transpose(0, 2, 1, 3)
