"""Plain float32 reference of every served model, and the check that
decides ``correct``.

The models live one per file in ``bench/models/<name>.py``: ``INPUT``
(name and per-request shape), ``OUTPUT`` (the output the engine returns),
``params()`` (parameter name -> shape) and ``forward(p, x, nn)`` written
against the few layers of :class:`NN`.  Nothing here imports the program:
the program is handed the weights made here, and the reference reads
nothing the program made.

Precision.  The configurations state float32 at ``HIGHEST``, which a TPU
computes as six bfloat16 passes.  ``NN("highest")`` is that reference.
``NN("high")`` is the control: each conv and matmul operand is split into
a bfloat16 high part and a bfloat16 low part, and the three products
hi*hi + hi*lo + lo*hi are summed, which is what ``Precision.HIGH`` does on
a TPU.  Each partial product of two bfloat16 values is exact in float32,
so the control means the same on the CPU as on the chip.
"""

from __future__ import annotations

import importlib.util
import os
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HERE = os.path.dirname(os.path.abspath(__file__))
HIGHEST = lax.Precision.HIGHEST
# "high_native" passes Precision.HIGH to XLA: three passes on a TPU, float32
# on a CPU; readings.py reads it beside the emulated control as a witness
PRECISIONS = ("highest", "high", "high_native")


@lru_cache(maxsize=None)
def load_model(name: str):
    """The reference module ``bench/models/<name>.py``."""
    path = os.path.join(HERE, "models", f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no reference model {path}")
    spec = importlib.util.spec_from_file_location(f"bench_model_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bf16(a):
    """``a`` rounded to bfloat16, kept in float32.  ``reduce_precision``
    and not a pair of converts: XLA on a TPU may drop a convert pair
    (excess precision is allowed there), which leaves no low part."""
    return lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


class NN:
    """The layers the reference models are written in, at one precision."""

    def __init__(self, precision: str = "highest"):
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        self.precision = precision

    def _three_pass(self, f, a, b):
        if self.precision == "highest":
            return f(a, b)
        if self.precision == "high_native":
            return f(a, b, lax.Precision.HIGH)
        ah, al = _split(a)
        bh, bl = _split(b)
        return f(ah, bh) + f(ah, bl) + f(al, bh)

    def conv(self, x, w, stride, groups=1):
        """'same'-padded NHWC conv with an HWIO kernel."""
        def f(a, b, precision=HIGHEST):
            return lax.conv_general_dilated(
                a, b, (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=groups, precision=precision)
        return self._three_pass(f, x, w)

    def dwconv(self, x, w, stride):
        """Depthwise conv; ``w`` is (kh, kw, channels, 1)."""
        kh, kw, c, _ = w.shape
        return self.conv(x, w.reshape(kh, kw, 1, c), stride, groups=c)

    def dense(self, x, w):
        return self._three_pass(
            lambda a, b, precision=HIGHEST: jnp.matmul(a, b,
                                                       precision=precision),
            x, w)

    @staticmethod
    def relu(x):
        return jnp.maximum(x, 0.0)

    @staticmethod
    def mean_hw(x):
        return jnp.mean(x, axis=(1, 2))

    @staticmethod
    def softmax(x):
        return jax.nn.softmax(x, axis=-1)


# ---------------------------------------------------------------------------
# Weights and inputs, from the seed
# ---------------------------------------------------------------------------

def _words(seed: int) -> np.ndarray:
    """Any whole number as two uint32 words (low, high)."""
    s = int(seed) % (1 << 64)
    return np.array([s & 0xFFFFFFFF, s >> 32], dtype=np.uint32)


def _scale(shape) -> float:
    """He-normal scale for weights; biases get 0.1."""
    if len(shape) == 1:
        return 0.1
    if len(shape) == 4:
        kh, kw, cin, cout = shape
        fan_in = kh * kw * (1 if cout == 1 else cin)   # depthwise: kh*kw
    else:
        fan_in = shape[0]
    return float(np.sqrt(2.0 / fan_in))


@lru_cache(maxsize=None)
def _param_maker(models: tuple):
    specs = [sorted(load_model(m).params().items()) for m in models]

    @jax.jit
    def make(words):
        base = jax.random.fold_in(jax.random.key(words[0]), words[1])
        out = []
        for t, spec in enumerate(specs):
            tk = jax.random.fold_in(base, t)
            out.append({name: _scale(shape) * jax.random.normal(
                jax.random.fold_in(tk, j), shape, jnp.float32)
                for j, (name, shape) in enumerate(spec)})
        return out
    return make


def make_params(models, seed: int):
    """Every tenant's float32 weights, made on the device in one jitted
    call from ``seed``."""
    return _param_maker(tuple(models))(jnp.asarray(_words(seed)))


def make_inputs(models, seed: int, pool: int):
    """``pool`` distinct host inputs per tenant, float32 standard normal,
    shaped (pool, 1, *per-request shape)."""
    w = _words(seed)
    out = []
    for t, m in enumerate(models):
        shape = load_model(m).INPUT[1]
        rng = np.random.default_rng([int(w[0]), int(w[1]), t])
        out.append(rng.standard_normal((pool, 1, *shape), np.float32))
    return out


# ---------------------------------------------------------------------------
# The reference forward pass and the comparison
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _forward_fn(model: str, precision: str):
    mod = load_model(model)
    nn = NN(precision)
    return jax.jit(partial(mod.forward, nn=nn))


def forward(model: str, params, xs, precision: str = "highest"):
    """Reference outputs for a batch ``xs`` of shape (n, *per-request
    shape), as a host array of shape (n, *output shape)."""
    return np.asarray(_forward_fn(model, precision)(params, jnp.asarray(xs)))


def gap(got, want) -> float:
    """Widest gap of one answer, as a share of the reference's largest
    magnitude: max |got - want| / max |want|.  A wrong shape or a value
    that is not finite reads infinite."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    scale = float(np.max(np.abs(want)))
    return float(np.max(np.abs(got - want))) / max(scale, 1e-30)


def reference_outputs(models, params, pools, precision: str = "highest"):
    """Reference answer of every pooled input: per tenant an array of
    shape (pool, 1, *output shape)."""
    out = []
    for m, p, xs in zip(models, params, pools):
        ys = forward(m, p, xs[:, 0], precision)
        out.append(ys[:, None])
    return out


def check_answers(models, answers, want, limit: float):
    """Widest gap per model over the answers served, and the number of
    answers whose gap exceeds ``limit``.

    ``answers`` is a list of (tenant, pool index, output array); ``want``
    the reference outputs by tenant.  Every model of the mix is in the
    result; one never served reads infinite."""
    worst = dict.fromkeys(models)
    over = 0
    for tenant, idx, out in answers:
        g = gap(out, want[tenant][idx])
        over += g > limit
        m = models[tenant]
        worst[m] = g if worst[m] is None else max(worst[m], g)
    return ({m: float("inf") if g is None else g for m, g in worst.items()},
            int(over))
