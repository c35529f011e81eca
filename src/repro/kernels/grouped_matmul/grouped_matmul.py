"""Pallas TPU grouped matmul for MoE expert FFNs.

Capacity-dispatched layout: x (E, C, D) holds each expert's tokens (padded
to capacity C), w (E, D, F) the per-expert weights.  Grid = (E, C/bc, F/bf,
D/bd) with the contraction innermost, accumulating in VMEM scratch — the
expert axis rides the grid so each expert's weight tile is fetched once per
(bc, bf) output tile, never broadcast through HBM.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul_pallas(x: jnp.ndarray, w: jnp.ndarray,
                          block_c: int = 128, block_f: int = 128,
                          block_d: int = 128,
                          interpret: bool = False) -> jnp.ndarray:
    E, C, D = x.shape
    E2, D2, F = w.shape
    assert E == E2 and D == D2
    bc, bf, bd = min(block_c, C), min(block_f, F), min(block_d, D)
    assert C % bc == 0 and F % bf == 0 and D % bd == 0
    return pl.pallas_call(
        _gmm_kernel,
        grid=(E, C // bc, F // bf, D // bd),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, c, f, d: (e, c, d)),
            pl.BlockSpec((1, bd, bf), lambda e, c, f, d: (e, d, f)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, c, f, d: (e, c, f)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
