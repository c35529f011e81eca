"""Compile the six Pallas kernels for a described TPU v5e at model widths.

Nothing runs: the TPU compiler, installed with JAX, compiles each kernel
for a chip that is described and not attached, and refuses what the chip
would refuse (blocks not (8, 128)-aligned, primitives Mosaic cannot
lower, too much VMEM).  Interpret-mode tests cannot see any of that.
The widths are those of ``repro.kernels.cases``, which the kernel phase
of ``chip_smoke.py`` runs on the chip.
"""

import os

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels.cases import CASES


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("case", CASES, ids=[c.name for c in CASES])
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
            for s in case.input_shapes()]
    compiled = jax.jit(
        lambda *a: case.kernel(*a, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
