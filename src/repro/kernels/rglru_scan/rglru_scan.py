"""Pallas TPU RG-LRU scan: chunked diagonal linear recurrence.

TPU adaptation of Griffin's (GPU) linear-scan kernel: the time axis is the
sequential grid dimension in chunks of L steps; within a chunk the
recurrence is stepped with a fori_loop of vector FMAs over a (bd,)-channel
block — the VPU handles the channel parallelism, and the carried state
lives in VMEM scratch.  No warp shuffles / shared-memory tricks needed (or
available): the diagonal recurrence maps directly onto vector lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h_ref, hout_ref, state_ref, a_s, b_s, ys_s,
                  *, L: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    # f32 copies in scratch: the loop reads and writes one (1, bd) row per
    # step through ref slices, which Mosaic lowers (value indexing it
    # does not)
    a_s[...] = a_ref[0].astype(jnp.float32)   # (L, bd)
    b_s[...] = b_ref[0].astype(jnp.float32)

    def step(t, h):
        h = a_s[pl.ds(t, 1), :] * h + b_s[pl.ds(t, 1), :]
        ys_s[pl.ds(t, 1), :] = h
        return h

    hT = jax.lax.fori_loop(0, L, step, state_ref[...])   # (1, bd)
    h_ref[0] = ys_s[...].astype(h_ref.dtype)
    state_ref[...] = hT

    @pl.when(ci == pl.num_programs(1) - 1)
    def _done():
        hout_ref[0] = hT


def rglru_pallas(a: jnp.ndarray, b: jnp.ndarray, chunk: int = 64,
                 block_d: int = 256, interpret: bool = False):
    """a, b: (B,T,D) -> (h (B,T,D), h_last (B,D))."""
    B, T, D = a.shape
    L = min(chunk, T)
    assert T % L == 0
    bd = min(block_d, D)
    while D % bd != 0:
        bd -= 1
    grid = (B * (D // bd), T // L)
    nd = D // bd

    af = a.reshape(B, T, nd, bd).transpose(0, 2, 1, 3).reshape(B * nd, T, bd)
    bf = b.reshape(B, T, nd, bd).transpose(0, 2, 1, 3).reshape(B * nd, T, bd)

    h, hT = pl.pallas_call(
        functools.partial(_rglru_kernel, L=L),
        grid=grid,
        in_specs=[pl.BlockSpec((1, L, bd), lambda g, c: (g, c, 0)),
                  pl.BlockSpec((1, L, bd), lambda g, c: (g, c, 0))],
        out_specs=[pl.BlockSpec((1, L, bd), lambda g, c: (g, c, 0)),
                   pl.BlockSpec((1, 1, bd), lambda g, c: (g, 0, 0))],
        # h_last carries a unit middle axis so its block's last two dims
        # (1, bd) equal the array's and are (8, 128)-legal on the chip
        out_shape=[jax.ShapeDtypeStruct((B * nd, T, bd), a.dtype),
                   jax.ShapeDtypeStruct((B * nd, 1, bd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32),
                        pltpu.VMEM((L, bd), jnp.float32),
                        pltpu.VMEM((L, bd), jnp.float32),
                        pltpu.VMEM((L, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(af, bf)
    h = h.reshape(B, nd, T, bd).transpose(0, 2, 1, 3).reshape(B, T, D)
    return h, hT.reshape(B, D)
