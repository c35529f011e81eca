"""Public compile entry points — thin wrappers over the deployment session.

The full MATCHA pipeline (Fig. 1)

    pre-process -> tile-centric CP pattern matching (stage 1, core.tiling)
                -> IR rewrite (supernodes + helpers, core.rewrite)
                -> scheduling & memory planning (stage 2, core.schedule)
                -> (optionally) code generation (core.codegen)

lives in :mod:`repro.core.deploy`: a :class:`~repro.core.deploy.
DeploymentSession` over a typed :class:`~repro.core.deploy.CompileRequest`
runs one unified candidate search (a registry of named
:class:`~repro.core.deploy.CandidateStrategy` entries: tile-centric at
several granularities, the all-or-nothing corner, HEFT, contention-priced
re-runs, complementary selections, and the joint cross-tenant CP — one
constraint program over every tenant's tile variables), arbitrates every
candidate under the exact stage-2 model with a typed
:class:`~repro.core.deploy.Objective` (makespan-primary, configurable
ordered tie-break chain), iterates the contention-hint loop to a bounded
fixpoint, and caches co-schedules per occupancy in an LRU-bounded
:class:`~repro.core.deploy.PlanStore` — so
``MultiCompiledModel.plan_for(active)`` answers *partial* occupancy with
tilings re-decided for that occupancy.

This module keeps the historical free-function surface:

  * ``compile_model(graph, soc, patterns, mode)`` — one model, returns a
    :class:`CompiledModel` whose ``plan`` carries the executable schedule +
    memory plan and whose ``run`` method executes the plan numerically in
    JAX.  For ``mode="matcha"`` the session evaluates several stage-1
    candidates under the exact stage-2 model and keeps the best,
    reproducing the Table-2 behaviour where depthwise-dominated nets
    reject tiling while ResNet/AutoEncoder embrace it (§3.1).
  * ``compile_multi(graphs, soc, patterns, **options)`` — N models
    co-scheduled onto one SoC, returns a :class:`MultiCompiledModel`.

Both construct a session internally and return its artifacts unchanged, so
callers that need the richer API (subset pre-compilation, explicit
objectives, strategy selection) can build the session directly instead.
"""

from __future__ import annotations

from typing import Sequence

from repro.core.deploy import (MODES, CandidateSpec, CandidateStrategy,
                               CompiledModel, CompileRequest,
                               DeploymentSession, MultiCompiledModel,
                               Objective, PlanStore, default_strategy_names,
                               get_strategy, register_strategy)
from repro.core.ir import Graph
from repro.core.patterns import Pattern
from repro.soc.device import SoC

__all__ = [
    "MODES", "CandidateSpec", "CandidateStrategy", "CompileRequest",
    "CompiledModel", "DeploymentSession", "MultiCompiledModel", "Objective",
    "PlanStore", "compile_model", "compile_multi",
    "default_strategy_names", "get_strategy", "register_strategy",
]


def compile_model(g: Graph, soc: SoC, patterns: Sequence[Pattern],
                  mode: str = "matcha", requested_tiles: int = 16,
                  time_budget_s: float = 8.0) -> CompiledModel:
    """Compile ONE model: a single-graph deployment session's
    compile-alone artifact."""
    assert mode in MODES, mode
    request = CompileRequest(graphs=[g], soc=soc, patterns=patterns,
                             mode=mode, requested_tiles=requested_tiles,
                             time_budget_s=time_budget_s)
    return DeploymentSession(request).compile_single(0)


def compile_multi(graphs: Sequence[Graph], soc: SoC,
                  patterns: Sequence[Pattern], **options
                  ) -> MultiCompiledModel:
    """Compile N independent models into one multi-tenant co-schedule.

    ``options`` are :class:`CompileRequest`'s other fields, by keyword;
    returns the session's :class:`MultiCompiledModel`."""
    request = CompileRequest(graphs=list(graphs), soc=soc,
                             patterns=patterns, **options)
    return DeploymentSession(request).compile()
