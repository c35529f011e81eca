"""The cell's compiled deployment, built once per checkout and cached.

``build`` runs the program's ``DeploymentSession.compile`` and
``precompile`` of every occupancy the cell's traffic can visit, as the
configuration file states them, and times the two on the host clock
(``deploy_compile_s``).  ``load_or_build`` keeps the result in
``bench/.cache/<config>-<digest>.pkl``.  The digest covers the
configuration file, the occupancies, this file and every file of the
program's ``src/repro`` tree, so a change to any of them builds afresh.

Threading locks do not pickle; the pickler writes each as a fresh lock,
which is what an unlocked lock in a loaded object has to be.
"""

from __future__ import annotations

import hashlib
import importlib
import io
import os
import pickle
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(HERE, ".cache")

_LOCK_TYPES = {type(threading.Lock()): threading.Lock,
               type(threading.RLock()): threading.RLock}


class _Pickler(pickle.Pickler):
    def reducer_override(self, obj):
        make = _LOCK_TYPES.get(type(obj))
        return (make, ()) if make is not None else NotImplemented


def dumps(obj) -> bytes:
    buf = io.BytesIO()
    _Pickler(buf, protocol=pickle.HIGHEST_PROTOCOL).dump(obj)
    return buf.getvalue()


def digest(config_path: str, occupancies) -> str:
    h = hashlib.sha256()
    with open(config_path, "rb") as f:
        h.update(f.read())
    h.update(repr(sorted(tuple(o) for o in occupancies)).encode())
    with open(os.path.abspath(__file__), "rb") as f:
        h.update(f.read())
    src = os.path.join(ROOT, "src", "repro")
    for dirpath, dirnames, filenames in os.walk(src):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for name in sorted(filenames):
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                h.update(os.path.relpath(path, src).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
    return h.hexdigest()[:16]


def graphs(config: dict):
    from repro.models import edge
    return [edge.ALL_MODELS[m]() for m in config["models"]]


def build(config: dict, occupancies):
    """(compiled deployment, seconds spent in compile + precompile)."""
    from repro.core.deploy import CompileRequest, DeploymentSession
    soc = config["soc"]
    mod = importlib.import_module(soc["module"])
    request = CompileRequest(graphs=graphs(config),
                             soc=getattr(mod, soc["soc"])(),
                             patterns=getattr(mod, soc["patterns"])(),
                             **config["compile"])
    t0 = time.perf_counter()
    session = DeploymentSession(request)
    compiled = session.compile()
    everyone = list(range(len(config["models"])))
    session.precompile([o for o in occupancies if list(o) != everyone])
    return compiled, time.perf_counter() - t0


def load_or_build(config_name: str, config_path: str, config: dict,
                  occupancies):
    """(compiled deployment, deploy_compile_s, built in this run?)."""
    path = os.path.join(
        CACHE, f"{config_name}-{digest(config_path, occupancies)}.pkl")
    if os.path.isfile(path):
        with open(path, "rb") as f:   # written by build() below only
            saved = pickle.load(f)
        return saved["compiled"], saved["deploy_compile_s"], False
    compiled, seconds = build(config, occupancies)
    os.makedirs(CACHE, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        f.write(dumps({"compiled": compiled, "deploy_compile_s": seconds}))
    os.replace(tmp, path)
    return compiled, seconds, True


def kernels(plan) -> int:
    """Kernel nodes of a plan: the supernodes the executor dispatches."""
    return sum(1 for name in plan.order
               if plan.nodes[name].kind == "kernel"
               and plan.nodes[name].supernode is not None)


def fingerprint(compiled, occupancies) -> list:
    """[(occupancy, kernels, analytic makespan in cycles)] of each plan
    the traffic can use, from the store; no compile."""
    out = []
    for occ in occupancies:
        plan = compiled.session.try_plan_for(list(occ))
        if plan is None:
            raise RuntimeError(f"occupancy {occ} was not precompiled")
        out.append((tuple(occ), kernels(plan), float(plan.makespan)))
    return out
