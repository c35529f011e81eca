#!/usr/bin/env python3
"""Serve the MLPerf-Tiny co-schedule on one TPU, and run the LM kernels.

    python3 chip_smoke.py [--seed N]

Needs a TPU: with none (``JAX_PLATFORMS=cpu`` included) the device check
raises before any work, and nothing falls back to the CPU.  Everything
runs in this one process, which holds the chip.

Phase "serve": the paper's own workload.  ``autoencoder``, ``ds_cnn`` and
``resnet`` (``models/edge.py``, published widths, batch 1) are co-compiled
for the Carfield SoC by a ``DeploymentSession`` and served by
``MultiModelEngine(execute=True)`` with a background compiler pumped
between rounds.  The requests make a full-house co-round, a subset
co-round and a solo round (plus the compile-alone floor rounds of the
first visit to each occupancy).  Every output is checked against
``execute_graph`` on the same inputs under highest matmul precision,
within the plan executor's 1e-4 oracle tolerance.

Phase "kernels": each Pallas kernel of ``repro.kernels.cases`` runs
compiled (``interpret=False``) at model widths and is checked against
its ``ref.py`` within the tolerance written beside it.

The analytic makespans printed per round are the schedule model's
prediction for the Carfield board, not a measurement of this chip; the
wall times are host-clock times of whole rounds, first-use compiles
included.  Neither is a benchmark metric.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
a failed phase exits 1 and prints no such line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

MIX = ("autoencoder", "ds_cnn", "resnet")
# tenant sets submitted before each round: full house, a subset twice
# (floor round, then its compiled co-schedule), a singleton twice
WAVES = ((0, 1, 2), (0, 1), (0, 1), (2,), (2,))
SERVE_TOL = 1e-4          # atol = rtol, as runtime.plan_matches_oracle


def require_tpu():
    """The first device, if it is a TPU; raises otherwise."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; JAX found {dev.platform} "
            f"({dev.device_kind}) and this script does not fall back")
    return dev


def compare(got, want, tol: float):
    """(ok, max abs error, max relative error) of ``got`` against
    ``want``; the relative error divides by ``max(|want|, tol)`` so that
    entries near zero do not dominate it."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        return False, float("inf"), float("inf")
    err = np.abs(got - want)
    ok = bool(np.all(np.isfinite(got))
              and np.all(err <= tol + tol * np.abs(want)))
    rel = err / np.maximum(np.abs(want), tol)
    return ok, float(err.max(initial=0.0)), float(rel.max(initial=0.0))


class CacheCounter:
    """Persistent-cache hits and misses, from JAX's monitoring events."""

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


ROUND_COUNTERS = ("co_rounds", "subset_co_rounds", "solo_rounds",
                  "floor_rounds")


def _round_kind(eng, before) -> str:
    """Kind of the round just dispatched, from the engine's counters
    (``before`` is their snapshot ahead of the round)."""
    d = {k: getattr(eng, k) - before[k] for k in ROUND_COUNTERS}
    if d["floor_rounds"]:
        return "floor"
    if d["subset_co_rounds"]:
        return "subset"
    if d["co_rounds"]:
        return "co"
    return "solo"


def serve_phase(seed: int) -> list:
    """Returns the list of failures (empty when the phase passed)."""
    from repro.core.deploy import CompileRequest, DeploymentSession
    from repro.core.runtime import execute_graph
    from repro.models import edge
    from repro.serve.compiler_thread import BackgroundCompiler
    from repro.serve.engine import MultiModelEngine
    from repro.soc.carfield import carfield_patterns, carfield_soc

    t0 = time.perf_counter()
    graphs = [edge.ALL_MODELS[m]() for m in MIX]
    soc = carfield_soc()
    session = DeploymentSession(CompileRequest(
        graphs=graphs, soc=soc, patterns=carfield_patterns(),
        time_budget_s=1.0, joint_time_budget_s=1.0,
        lazy_joint_time_budget_s=0.5, incremental_time_budget_s=0.5))
    mc = session.compile()
    compiler = BackgroundCompiler(session, start=False)
    eng = MultiModelEngine(mc, seed=seed, execute=True,
                           async_compile=compiler)
    print(f"[serve] co-compiled {' + '.join(MIX)} for {soc.name} in "
          f"{time.perf_counter() - t0:.1f} s (host)")

    kinds = {"co": 0, "subset": 0, "solo": 0, "floor": 0}
    for wave in WAVES:
        for t in wave:
            eng.submit(t, seed=seed)
        before = {k: getattr(eng, k) for k in ROUND_COUNTERS}
        clock0 = eng.clock_s
        t1 = time.perf_counter()
        done = eng.step()
        for rid in done:
            jax.block_until_ready(eng.results[rid])
        wall_ms = (time.perf_counter() - t1) * 1e3
        kind = _round_kind(eng, before)
        kinds[kind] += 1
        print(f"[serve] round {eng.rounds}: {kind:6s} tenants "
              f"{[MIX[t] for t in wave]}  wall {wall_ms:.1f} ms  "
              f"model prediction (Carfield makespan) "
              f"{(eng.clock_s - clock0) * 1e3:.3f} ms")
        compiler.run_pending()
    print(f"[serve] rounds by kind: {kinds}")

    failures = []
    if kinds["co"] < 1 or kinds["subset"] < 1 or kinds["solo"] < 1:
        failures.append(f"serve: wanted >= 1 co, subset and solo round, "
                        f"got {kinds}")
    if compiler.errors or compiler.stats()["failed_occupancies"]:
        failures.append(f"serve: background compiler errors "
                        f"{compiler.errors}")
    worst = {m: [0.0, 0.0] for m in MIX}
    for rid, req in sorted(eng.done.items()):
        g = graphs[req.tenant]
        with jax.default_matmul_precision("highest"):
            want = execute_graph(g, req.inputs, eng.params[req.tenant])
        for name in g.outputs:
            got = eng.results[rid][name]
            if got.devices() != {jax.devices()[0]}:
                failures.append(f"serve: request {rid} output {name} is on "
                                f"{got.devices()}")
            ok, abs_err, rel_err = compare(got, want[name], SERVE_TOL)
            w = worst[g.name]
            w[0], w[1] = max(w[0], abs_err), max(w[1], rel_err)
            if not ok:
                failures.append(f"serve: request {rid} ({g.name}) output "
                                f"{name}: max abs {abs_err:.3e}, max rel "
                                f"{rel_err:.3e} > {SERVE_TOL}")
    served = {m: 0 for m in MIX}
    for req in eng.done.values():
        served[MIX[req.tenant]] += 1
    for m in MIX:
        print(f"[serve] {m:12s} served {served[m]}  max abs err "
              f"{worst[m][0]:.3e}  max rel err {worst[m][1]:.3e}  "
              f"(tol {SERVE_TOL})")
        if not served[m]:
            failures.append(f"serve: {m} was never served")
    return failures


def kernels_phase(seed: int) -> list:
    """Returns the list of failures (empty when the phase passed)."""
    from repro.kernels.cases import CASES

    failures = []
    for i, case in enumerate(CASES):
        inputs = jax.jit(case.make_inputs)(jax.random.PRNGKey(seed + i))
        kernel = jax.jit(lambda *a, c=case: c.kernel(*a, interpret=False))
        if "tpu_custom_call" not in kernel.lower(*inputs).as_text():
            failures.append(f"kernels: {case.name} lowered without a "
                            f"Mosaic kernel")
        t0 = time.perf_counter()
        got = jax.block_until_ready(kernel(*inputs))
        wall_ms = (time.perf_counter() - t0) * 1e3
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(jax.jit(case.ref)(*inputs))
        got = got if isinstance(got, (tuple, list)) else (got,)
        want = want if isinstance(want, (tuple, list)) else (want,)
        for j, (g, w, (tol, why)) in enumerate(zip(got, want, case.tols)):
            ok, abs_err, rel_err = compare(g, w, tol)
            print(f"[kernels] {case.name:15s} out{j} {tuple(g.shape)} "
                  f"{jnp.dtype(g.dtype).name}: max abs err {abs_err:.3e}  "
                  f"max rel err {rel_err:.3e}  tol {tol} ({why})  "
                  f"first call {wall_ms:.1f} ms incl. compile")
            if not ok:
                failures.append(f"kernels: {case.name} output {j} exceeds "
                                f"{tol}: max abs {abs_err:.3e}")
        if len(got) != len(case.tols):
            failures.append(f"kernels: {case.name} returned {len(got)} "
                            f"outputs, expected {len(case.tols)}")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of parameters and inputs")
    args = ap.parse_args()

    dev = require_tpu()
    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    cache = CacheCounter()
    print(f"[smoke] device {dev.platform} {dev.device_kind} x"
          f"{len(jax.devices())}; compile cache {cache_dir}")

    failures = []
    for name, phase in (("serve", serve_phase), ("kernels", kernels_phase)):
        t0 = time.perf_counter()
        try:
            problems = phase(args.seed)
        except Exception:
            traceback.print_exc()
            problems = [f"{name}: raised"]
        failures += problems
        print(f"[smoke] phase {name}: {'FAIL' if problems else 'pass'} in "
              f"{time.perf_counter() - t0:.1f} s wall")
    print(f"[smoke] persistent cache: {cache.hits} hits, "
          f"{cache.misses} misses")
    if failures:
        for f in failures:
            print(f"[smoke] FAIL {f}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
