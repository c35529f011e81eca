"""Useful multiply-accumulates of a reference model, counted from shapes.

The reference forward pass (``bench/models/<name>.py`` at ``highest``) is
traced to a jaxpr for one request, and every conv and matmul in it is
counted: a conv costs output elements x kernel height x kernel width x
input channels per group, and a matmul output elements x contracted size.
Depthwise convs are convs with one input channel per group.  Elementwise
work, pooling and softmax are not counted: they are not what the peak
FLOP/s of the table measures.  A FLOP is half a MAC.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

import reference


def _eqn_macs(eqn) -> int:
    name = eqn.primitive.name
    if name == "conv_general_dilated":
        out = eqn.outvars[0].aval.shape
        rhs = eqn.invars[1].aval.shape
        dn = eqn.params["dimension_numbers"]
        spec = dn.rhs_spec           # (out feature, in feature, spatial...)
        per_group_in = rhs[spec[1]]
        window = int(np.prod([rhs[d] for d in spec[2:]]))
        return int(np.prod(out)) * window * per_group_in
    if name == "dot_general":
        (lhs_contract, _), _ = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval.shape
        k = int(np.prod([lhs[d] for d in lhs_contract]))
        return int(np.prod(eqn.outvars[0].aval.shape)) * k
    return 0


def _jaxpr_macs(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        total += _eqn_macs(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _jaxpr_macs(sub)
    return total


@lru_cache(maxsize=None)
def macs(model: str) -> int:
    """MACs of one request (batch 1) of ``model``."""
    mod = reference.load_model(model)
    nn = reference.NN("highest")
    params = {k: jax.ShapeDtypeStruct(s, jnp.float32)
              for k, s in mod.params().items()}
    x = jax.ShapeDtypeStruct((1, *mod.INPUT[1]), jnp.float32)
    closed = jax.make_jaxpr(lambda p, x: mod.forward(p, x, nn))(params, x)
    return _jaxpr_macs(closed.jaxpr)


def flops(model: str) -> int:
    """Useful FLOPs of one request: two per MAC."""
    return 2 * macs(model)
