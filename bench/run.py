#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip this process starts on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration (``bench/configs/<config>.json``) names the co-resident
models, the SoC the deployment compiler plans for, the compile settings
and the engine's; its traffic (``bench/traffic/<traffic>.json``) is read
by ``arrivals.py``.  Everything runs in this one process, which holds the
chip:

1. set-up: find the TPU (or exit 1 and print no result), turn on JAX's
   persistent compilation cache, load the compiled deployment from
   ``bench/.cache`` or build it (``artifact.py``), make the weights on
   the device and the inputs on the host from ``--seed``, and run every
   plan the traffic can use once so that the window compiles nothing;
2. the window: one loop in one thread submits each request as it comes
   due (its input goes to the device then), calls
   ``MultiModelEngine.step()`` and takes each finished request's output
   to the host.  Latency runs from a request's due time to that moment.
   Requests still queued when the window closes are served after it with
   no new arrivals;
3. the check: every answer is compared with the plain reference
   (``reference.py``) on the same weights and inputs, once the engine is
   freed.

With ``--trace 1`` the first ``TRACE_S`` seconds of the window run under
the profiler, and the line carries the cell's per-layer metrics
(``bench/metrics/<name>.py``) instead of its end-to-end ones.

Earlier lines of standard output are diagnostics; the last is the
result, one JSON object.  The last lines of standard error are the
numbers compared, each beside its limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

import arrivals  # noqa: E402
import artifact  # noqa: E402
import flops  # noqa: E402
import reference  # noqa: E402
import xplane  # noqa: E402

TRACE_S = 5.0          # traced part of the window in --trace 1 runs
DRAIN_S = 60.0         # how long past the close queued requests may take
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def now() -> float:
    return time.perf_counter()


# ---------------------------------------------------------------------------
# The cell, from BENCHMARK.json
# ---------------------------------------------------------------------------

def load_cell(name: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"have {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    path = os.path.join(ROOT, entry["file"])
    with open(path) as f:
        config = json.load(f)

    def listed(m):
        return name in m["workloads"] if "workloads" in m else None

    e2e = [m for m in bench["end_to_end"] if listed(m) is not False]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if listed(m) or (listed(m) is None and m["moves"] in names)]
    return {"cell": cell, "config_name": entry["name"], "config_path": path,
            "config": config, "traffic": arrivals.load(cell["traffic"]),
            "end_to_end": e2e, "per_layer": per_layer}


def load_reader(metric: str):
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics, record) -> dict:
    out = {}
    for m in metrics:
        value = load_reader(m["name"])(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# The chip and what JAX does on it
# ---------------------------------------------------------------------------

def require_chips(n: int):
    """The devices, if there are ``n`` TPU chips or more; exits 1 (and
    prints no result) otherwise."""
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"device: platform {d.platform}, kind {d.device_kind}, "
        f"count {len(devs)}")
    if d.platform != "tpu" or len(devs) < n:
        raise SystemExit(f"this cell needs {n} TPU chip(s); JAX found "
                         f"{len(devs)} {d.platform} device(s) "
                         f"({d.device_kind}); no fallback")
    return devs


def peak_flops(kind: str, chips: int) -> float:
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return chips * float(peaks[kind]["bf16_flops_per_s"])


class CompileCounter:
    """Executables built or loaded (XLA compiles and persistent-cache
    loads alike), from JAX's monitoring events."""

    def __init__(self) -> None:
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1


class Spans:
    """Harness spans: host time per name on the host clock, and a
    ``TraceAnnotation`` per span so that a trace shows them."""

    def __init__(self) -> None:
        import jax
        self._annotate = jax.profiler.TraceAnnotation
        self.total = {}
        self.count = {}
        self.longest = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t = now()
        with self._annotate(f"bench.{name}"):
            yield
        d = now() - t
        self.total[name] = self.total.get(name, 0.0) + d
        self.count[name] = self.count.get(name, 0) + 1
        self.longest[name] = max(self.longest.get(name, 0.0), d)

    def clear(self) -> None:
        self.total.clear()
        self.count.clear()
        self.longest.clear()


# ---------------------------------------------------------------------------
# Requests
# ---------------------------------------------------------------------------

class Driver:
    """Submits requests to the engine and collects their answers."""

    def __init__(self, engine, models, pools, order, spans):
        import jax
        self._put = jax.device_put
        self.engine = engine
        self.pools = pools
        self.order = order
        self.spans = spans
        mods = [reference.load_model(m) for m in models]
        self.input_names = [m.INPUT[0] for m in mods]
        self.output_names = [m.OUTPUT for m in mods]
        self.flops = [flops.flops(m) for m in models]
        self.reset()

    def reset(self) -> None:
        self.sent = [0] * len(self.pools)
        self.meta = {}             # rid -> (tenant, pool index, due)
        self.answers = []          # (tenant, pool index, host output)
        self.finished = []         # (due, done, tenant)

    def submit(self, tenant: int, due: float) -> None:
        k = self.sent[tenant]
        self.sent[tenant] += 1
        idx = int(self.order[tenant][k % len(self.order[tenant])])
        x = self._put(self.pools[tenant][idx])
        rid = self.engine.submit(tenant, inputs={self.input_names[tenant]: x})
        self.meta[rid] = (tenant, idx, due)

    def step(self, clock) -> list:
        """One engine step; returns the (due, done, tenant) it finished."""
        with self.spans("step"):
            done = self.engine.step()
        out = []
        with self.spans("fetch"):
            for rid in done:
                tenant, idx, due = self.meta.pop(rid)
                result = self.engine.results.pop(rid)
                self.engine.done.pop(rid, None)
                host = np.asarray(result[self.output_names[tenant]])
                out.append((due, clock(), tenant))
                self.answers.append((tenant, idx, host))
        self.finished.extend(out)
        return out


def warm_up(driver, occupancies, max_batch: int) -> None:
    """Run every plan the traffic can use once, and the largest
    occupancy's repeat waves, so that the window compiles nothing."""
    for j, occ in enumerate(occupancies):
        for t in occ:
            for _ in range(max_batch if j == 0 else 1):
                driver.submit(t, 0.0)
        while driver.engine.pending:
            driver.step(now)
    driver.reset()


class PlanCounter:
    """Counts the kernels of every plan the engine resolves for a round,
    by wrapping the session's ``try_plan_for`` on this instance."""

    def __init__(self, session) -> None:
        self.kernels = 0
        self.used = {}
        self._sizes = {}
        inner = session.try_plan_for
        # a session already wrapped (a second seed in one process) keeps
        # its one unwrapped lookup
        self._inner = getattr(getattr(inner, "__self__", None), "_inner",
                              inner)
        session.try_plan_for = self._lookup

    def _lookup(self, active, touch=False, shapes=None):
        plan = self._inner(active, touch=touch, shapes=shapes)
        if touch and plan is not None:
            occ = tuple(sorted(active))
            size = self._sizes.get(id(plan))
            if size is None:
                size = self._sizes[id(plan)] = artifact.kernels(plan)
            self.kernels += size
            self.used[occ] = self.used.get(occ, 0) + 1
        return plan

    def reset(self) -> None:
        self.kernels = 0
        self.used = {}


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------

def _trace_start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def run_window(driver, traffic, n_tenants, seconds, seed, trace_dir):
    """Drives the window; returns what the metrics read of it."""
    import jax
    open_loop = traffic["kind"] == "poisson"
    schedule = (arrivals.open_schedule(traffic, n_tenants, seconds, seed)
                if open_loop else [])
    spans = driver.spans
    tracing = trace_dir is not None
    if tracing:
        _trace_start(trace_dir)
    traced_done = []
    t0 = now()

    def clock():
        return now() - t0

    lateness = []
    i = 0
    if not open_loop:
        with spans("submit"):
            for t in range(n_tenants):
                for _ in range(int(traffic["outstanding"])):
                    driver.submit(t, 0.0)
    window_end = None
    while True:
        c = clock()
        if tracing and c >= min(TRACE_S, seconds):
            traced_done = list(driver.finished)
            jax.profiler.stop_trace()
            tracing = False
            c = clock()
        if window_end is None and c >= seconds:
            window_end = c
            completed_in_window = len(driver.finished)
        if window_end is not None and (c > window_end + DRAIN_S or (
                not driver.engine.pending and i == len(schedule))):
            break
        if open_loop and i < len(schedule) and schedule[i][0] <= c:
            with spans("submit"):
                while i < len(schedule) and schedule[i][0] <= c:
                    driver.submit(schedule[i][1], schedule[i][0])
                    lateness.append(c - schedule[i][0])
                    i += 1
        if driver.engine.pending:
            finished = driver.step(clock)
            if not open_loop and window_end is None:
                with spans("submit"):
                    for _, done, tenant in finished:
                        driver.submit(tenant, done)
        elif open_loop and i < len(schedule):
            with spans("wait_for_arrival"):
                time.sleep(max(0.0, schedule[i][0] - clock()))
        elif window_end is not None:
            break
        else:
            with spans("wait_for_arrival"):
                time.sleep(max(0.0, seconds - clock()))
    if tracing:
        traced_done = list(driver.finished)
        jax.profiler.stop_trace()
    lat = [1e3 * (done - due) for due, done, _ in driver.finished
           if due < seconds] if open_loop else None
    return {
        "open_loop": open_loop,
        "window_s": window_end,
        "completed_in_window": completed_in_window,
        "latencies_ms": lat,
        "lateness_s": lateness,
        "unanswered": len(driver.meta),
        "traced_completed": len(traced_done),
        "traced_flops": float(sum(driver.flops[t]
                                  for _, _, t in traced_done)),
    }


# ---------------------------------------------------------------------------
# Set-up, window and check
# ---------------------------------------------------------------------------

def load_deployment(spec):
    """(compiled deployment, deploy_compile_s, occupancies), with the plan
    fingerprint printed and the served graphs checked against the
    references' names and shapes."""
    models = spec["config"]["models"]
    occupancies = arrivals.occupancies(spec["traffic"], len(models))
    t = now()
    compiled, deploy_s, built = artifact.load_or_build(
        spec["config_name"], spec["config_path"], spec["config"],
        occupancies)
    log(f"deployment {'built' if built else 'loaded'} in {now() - t:.3f} s "
        f"(deploy_compile_s {deploy_s:.3f} when built)")
    for occ, k, makespan in artifact.fingerprint(compiled, occupancies):
        log(f"plan {[models[i] for i in occ]}: {k} kernels, analytic "
            f"makespan {makespan:.1f} cycles")
    for g in compiled.graphs:
        ref = reference.load_model(g.name)
        have = {n: tuple(ti.shape) for n, ti in g.tensors.items()
                if ti.kind == "param"}
        if (have != {n: tuple(s) for n, s in ref.params().items()}
                or g.outputs != [ref.OUTPUT] or g.inputs != [ref.INPUT[0]]):
            raise SystemExit(f"served graph {g.name} does not match its "
                             f"reference in bench/models")
    return compiled, deploy_s, occupancies


def prepare(spec, compiled, occupancies, seed: int):
    """Weights, inputs, engine and warm-up for one seed; returns the
    driver (whose ``engine`` is ready for the window), the plan counter
    and the weights and inputs the check needs."""
    from repro.serve.compiler_thread import BackgroundCompiler
    from repro.serve.engine import MultiModelEngine
    config, traffic = spec["config"], spec["traffic"]
    models = list(config["models"])
    params = reference.make_params(models, seed)
    pools = reference.make_inputs(models, seed, int(traffic["pool"]))
    order = arrivals.input_order(traffic, len(models), seed)
    max_batch = int(config["engine"]["max_batch"])
    # The compiler thread is attached and never started: every occupancy
    # the traffic can visit is precompiled, so no round waits on it.  The
    # engine then resolves each round's plan through ``try_plan_for``,
    # which ``PlanCounter`` counts.
    engine = MultiModelEngine(
        compiled, params_list=params, execute=True, max_batch=max_batch,
        async_compile=BackgroundCompiler(compiled.session, start=False))
    plans = PlanCounter(compiled.session)
    driver = Driver(engine, models, pools, order, Spans())
    t = now()
    warm_up(driver, occupancies, max_batch)
    log(f"warm-up {now() - t:.3f} s")
    plans.reset()
    driver.spans.clear()
    driver.rounds_before = {k: getattr(engine, k) for k in ROUND_COUNTERS}
    return driver, plans, params, pools


ROUND_COUNTERS = ("rounds", "co_rounds", "subset_co_rounds", "solo_rounds",
                  "floor_rounds", "batched_repeat_rounds")


def report_window(driver, plans, win, models, compiles_in_window) -> None:
    eng = driver.engine
    d = {k: getattr(eng, k) - driver.rounds_before[k] for k in ROUND_COUNTERS}
    lateness = np.asarray(win["lateness_s"] or [0.0]) * 1e3
    log(f"window {win['window_s']:.3f} s: {win['completed_in_window']} "
        f"requests completed in it, {len(driver.answers)} with the drain; "
        f"executables built or loaded in window and drain: "
        f"{compiles_in_window}")
    log(f"generator lateness: p50 {np.percentile(lateness, 50):.3f} ms, "
        f"max {lateness.max():.3f} ms over {len(win['lateness_s'])} "
        f"open-loop arrivals")
    used = {"+".join(models[i] for i in o): n for o, n in plans.used.items()}
    log(f"engine rounds in window and drain: {d}; plans used {used}")
    log("longest span: " + ", ".join(
        f"{k} {1e3 * v:.1f} ms"
        for k, v in sorted(driver.spans.longest.items())))


def check(models, answers, unanswered, params, pools, limit):
    """(correct, attempted, failed, checks, widest gap by model)."""
    want = reference.reference_outputs(models, params, pools)
    worst, over = reference.check_answers(models, answers, want, limit)
    gap = max(worst.values())
    checks = {"gap": {"value": gap, "limit": limit},
              "unanswered": {"value": unanswered, "limit": 0}}
    failed = over + unanswered
    return (bool(failed == 0 and gap <= limit), len(answers) + unanswered,
            failed, checks, worst)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_cell(args.workload)
    cell, config = spec["cell"], spec["config"]
    models = list(config["models"])
    log(f"imports done {now() - T_START:.3f} s after start")
    devs = require_chips(int(cell["chips"]))
    dev = devs[0]
    log(f"TPU up {now() - T_START:.3f} s after start")
    from repro.launch.cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    compiles = CompileCounter()
    peak = peak_flops(dev.device_kind, int(cell["chips"]))

    compiled, deploy_s, occupancies = load_deployment(spec)
    driver, plans, params, pools = prepare(spec, compiled, occupancies,
                                           args.seed)
    setup_s = now() - T_START
    log(f"set-up {setup_s:.3f} s")

    before = compiles.count
    with tempfile.TemporaryDirectory() as tmp:
        trace_dir = os.path.join(tmp, "trace") if args.trace else None
        win = run_window(driver, spec["traffic"], len(models), args.seconds,
                         args.seed, trace_dir)
        in_window = compiles.count - before
        reduced = (xplane.reduce_file(xplane.find_xplane(trace_dir))
                   if trace_dir else None)
    report_window(driver, plans, win, models, in_window)
    mem = dev.memory_stats() or {}
    spans = driver.spans
    record = {
        **win, "setup_s": setup_s, "deploy_compile_s": deploy_s,
        "steps": spans.count.get("step", 0),
        "step_s": spans.total.get("step", 0.0),
        "plan_kernels": plans.kernels, "served": len(driver.answers),
        "trace": reduced, "peak_flops": peak,
    }

    answers = driver.answers
    del driver, plans, compiled
    gc.collect()
    correct, attempted, failed, checks, worst = check(
        models, answers, win["unanswered"], params, pools,
        float(config["limits"]["gap"]))
    log("widest gap by model: " + ", ".join(f"{m} {g:.6g}"
                                            for m, g in worst.items()))

    metrics = read_metrics(spec["per_layer"] if args.trace
                           else spec["end_to_end"], record)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
