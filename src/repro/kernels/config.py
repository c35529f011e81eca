"""Kernel dispatch policy.

On a TPU the Pallas kernels are the production path and run compiled.
Elsewhere the models use the pure-jnp references, which run and lower on
every backend (the multi-pod dry-run lowers for the CPU, where Mosaic
kernels cannot run); the tests drive the kernels on the CPU in interpret
mode.  Policy:

  * default: Pallas on a TPU, the jnp reference elsewhere;
  * ``REPRO_USE_PALLAS=1`` / ``=0`` forces the choice; off a TPU the
    kernels then run in interpret mode.

Backend detection raises what JAX raises: a process that cannot reach
its backend fails rather than being moved to the reference path.
"""

from __future__ import annotations

import os

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_pallas() -> bool:
    env = os.environ.get("REPRO_USE_PALLAS")
    if env is not None:
        return env not in ("", "0", "false")
    return on_tpu()


def interpret() -> bool:
    return not on_tpu()
