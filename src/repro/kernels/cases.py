"""The six Pallas kernels at the widths of the model configs.

One table serves the chip-compile tests (``tests/test_tpu_compile.py``)
and the kernel phase of ``chip_smoke.py``.  Each case builds seeded
inputs at the widths of the config its builder names, in the LM
stack's dtype (bfloat16, ``ModelConfig.dtype``), and carries the
tolerance its compiled output must meet against the kernel's ``ref.py``
together with the reason for it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.grouped_matmul.grouped_matmul import grouped_matmul_pallas
from repro.kernels.grouped_matmul.ref import grouped_matmul_ref
from repro.kernels.matmul.matmul import matmul_pallas
from repro.kernels.matmul.ref import matmul_ref
from repro.kernels.rglru_scan.ref import rglru_ref
from repro.kernels.rglru_scan.rglru_scan import rglru_pallas
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.rmsnorm.rmsnorm import rmsnorm_pallas
from repro.kernels.rwkv_scan.ref import wkv6_ref
from repro.kernels.rwkv_scan.rwkv_scan import wkv6_pallas

DTYPE = jnp.bfloat16

# A bfloat16 output is an f32 result rounded once (2**-8 ~ 3.9e-3
# relative).  Kernel and reference accumulate in f32 in different orders,
# so their roundings can land one ulp apart: 1e-2 allows about 2.5 ulps.
BF16_TOL = 1e-2
BF16_WHY = ("bf16 output: one rounding is 2^-8 relative and a different "
            "f32 accumulation order can move it one ulp")


@dataclasses.dataclass(frozen=True)
class KernelCase:
    name: str
    make_inputs: Callable[[jax.Array], Tuple[jnp.ndarray, ...]]
    kernel: Callable[..., Any]                    # (*inputs, interpret=)
    ref: Callable[..., Any]                       # (*inputs)
    tols: Tuple[Tuple[float, str], ...]           # (atol = rtol, why) per output

    def input_shapes(self) -> Tuple[jax.ShapeDtypeStruct, ...]:
        return jax.eval_shape(self.make_inputs, jax.random.PRNGKey(0))


def _normal(key, shape, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(DTYPE)


def _attention_inputs(key):
    # internlm2-1.8b: 16 query heads, 8 KV heads, head dim 128; S = 2048
    kq, kk, kv = jax.random.split(key, 3)
    return (_normal(kq, (1, 2048, 16, 128)), _normal(kk, (1, 2048, 8, 128)),
            _normal(kv, (1, 2048, 8, 128)))


def _matmul_inputs(key):
    # internlm2-1.8b FFN up-projection of 2048 tokens: 2048 -> 8192
    ka, kb = jax.random.split(key)
    return (_normal(ka, (2048, 2048)), _normal(kb, (2048, 8192), 2048 ** -0.5))


def _rmsnorm_inputs(key):
    # internlm2-1.8b: d_model 2048, 2048 tokens
    kx, kg = jax.random.split(key)
    return (_normal(kx, (1, 2048, 2048)), 1.0 + _normal(kg, (2048,), 0.1))


def _grouped_matmul_inputs(key):
    # olmoe-1b-7b: 64 experts, d_model 2048 -> expert d_ff 1024.  Capacity
    # 256 is 2048 tokens x top-8 / 64 experts, the even-routing load.
    kx, kw = jax.random.split(key)
    return (_normal(kx, (64, 256, 2048)),
            _normal(kw, (64, 2048, 1024), 2048 ** -0.5))


def _wkv6_inputs(key):
    # rwkv6-3b: d_model 2560 = 40 heads x head dim 64; T = 1024
    ks = jax.random.split(key, 5)
    shape = (1, 1024, 40, 64)
    # Finch decay w = exp(-exp(x)) in (0, 1), the spread tests/ use
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], shape) * 0.5))
    return (_normal(ks[0], shape), _normal(ks[1], shape),
            _normal(ks[2], shape), w.astype(DTYPE),
            _normal(ks[4], (40, 64), 0.5))


def _rglru_inputs(key):
    # recurrentgemma-2b: rnn_width 2560; T = 1024
    ka, kb = jax.random.split(key)
    a = jax.nn.sigmoid(jax.random.normal(ka, (1, 1024, 2560))) * 0.98
    return a.astype(DTYPE), _normal(kb, (1, 1024, 2560), 0.3)


CASES: Tuple[KernelCase, ...] = (
    KernelCase(
        "flash_attention", _attention_inputs,
        lambda q, k, v, interpret: flash_attention_pallas(
            q, k, v, interpret=interpret),
        attention_ref, ((BF16_TOL, BF16_WHY),)),
    KernelCase(
        "matmul", _matmul_inputs,
        lambda a, b, interpret: matmul_pallas(a, b, interpret=interpret),
        matmul_ref, ((BF16_TOL, BF16_WHY),)),
    KernelCase(
        "rmsnorm", _rmsnorm_inputs,
        lambda x, g, interpret: rmsnorm_pallas(x, g, interpret=interpret),
        rmsnorm_ref, ((BF16_TOL, BF16_WHY),)),
    KernelCase(
        "grouped_matmul", _grouped_matmul_inputs,
        lambda x, w, interpret: grouped_matmul_pallas(
            x, w, interpret=interpret),
        grouped_matmul_ref, ((BF16_TOL, BF16_WHY),)),
    KernelCase(
        "rwkv_scan", _wkv6_inputs,
        lambda r, k, v, w, u, interpret: wkv6_pallas(
            r, k, v, w, u, interpret=interpret),
        wkv6_ref,
        ((BF16_TOL, BF16_WHY),
         (5e-3, "f32 state: the chunked form rescales by 1/A_t where the "
                "scan multiplies step by step; the interpret-mode sweep's "
                "bound"))),
    KernelCase(
        "rglru_scan", _rglru_inputs,
        lambda a, b, interpret: rglru_pallas(a, b, interpret=interpret),
        rglru_ref,
        ((BF16_TOL, BF16_WHY),
         (1e-4, "f32 state: the same multiply-add per step as the scan; "
                "the interpret-mode sweep's bound"))),
)
