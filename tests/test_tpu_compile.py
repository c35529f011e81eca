"""Compile the six Pallas kernels for a described TPU v5e at model widths,
and one plan's jitted program.

Nothing runs: the TPU compiler, installed with JAX, compiles each kernel
for a chip that is described and not attached, and refuses what the chip
would refuse (blocks not (8, 128)-aligned, primitives Mosaic cannot
lower, too much VMEM).  Interpret-mode tests cannot see any of that.
The widths are those of ``repro.kernels.cases``, which the kernel phase
of ``chip_smoke.py`` runs on the chip.  The plan program is that of a
small ``two_acc_soc`` co-schedule: what the executor hands the chip's
compiler for every plan it runs.
"""

import os
import re

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.core import runtime
from repro.core.deploy import CompileRequest, DeploymentSession
from repro.kernels.cases import CASES
from repro.soc.testbed import dense_chain, gelu_chain, two_acc_soc


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


def test_plan_program_compiles_for_v5e(one_chip, no_persistent_cache):
    soc, pats = two_acc_soc(64, 8.0)
    session = DeploymentSession(CompileRequest(
        graphs=[dense_chain("a", [64, 64, 64]), gelu_chain("b", [48, 48])],
        soc=soc, patterns=pats, requested_tiles=4, time_budget_s=0.05))
    plan = session.compile().plan
    graphs = [tg.graph for tg in plan.tenants]

    def shapes(g, names):
        return {n: jax.ShapeDtypeStruct(g.tensors[n].shape, "float32",
                                        sharding=one_chip) for n in names}

    inputs = [shapes(g, g.inputs) for g in graphs]
    params = [shapes(g, [n for n, t in g.tensors.items()
                         if t.kind == "param"]) for g in graphs]
    compiled = runtime.programs.program(plan).lower(inputs,
                                                    params).compile()
    text = compiled.as_text()
    assert "dot" in text
    # the kernels' scopes reach the chip's compiled instructions, whose
    # names a trace's device ops carry; a fusion carries the scope of one
    # of the ops it fused, so a kernel whose ops all fold into a
    # neighbour's fusion names none of its own
    want = {runtime.kernel_scope(graphs[n.tenant].name, n.supernode)
            for n in plan.nodes.values()
            if n.kind == "kernel" and n.supernode is not None}
    found = set(re.findall(r"repro\.kernel:[^/\"]*", text))
    assert found and found <= want, (found, want)
