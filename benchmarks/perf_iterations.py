"""§Perf hillclimbing harness: hypothesis -> change -> re-lower -> measure.

Runs named variants of the three chosen (arch x shape) pairs against the
single-pod mesh and reports the roofline-term deltas.  Each experiment is
a knob wired through the real system (strategy overrides into the meshplan
CP, interior sharding hints, microbatch counts) — not a fork of the model.

Chosen pairs (from the baseline §Roofline table):
  A. granite-moe-3b-a800m x train_4k  — most collective-bound cell
     (460 s collective vs 3.2 s compute: the MoE dispatch buffers were
     re-gathered around every grouped matmul).
  B. internlm2-1.8b x train_4k        — worst train-cell roofline fraction
     (useful ratio 0.18: the CP kept attention replicated on the model
     axis; also the most paper-representative knob — it IS the device-
     allocation decision of MATCHA Eq. 2, on TPU lanes).
  C. qwen3-32b x decode_32k           — serving-latency cell, collective-
     bound decode (the sequence-sharded KV cache was all-gathered on
     every step's cache update).
"""

# MUST precede any jax import (device count locks on first init)
import os  # noqa: E402
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse      # noqa: E402
import json          # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

from repro.configs import registry                      # noqa: E402
from repro.configs.shapes import SHAPES                  # noqa: E402
from repro.launch import dryrun                          # noqa: E402
from repro.launch.cache import enable_compile_cache       # noqa: E402
from repro.launch.mesh import make_production_mesh       # noqa: E402

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9


def measure(arch: str, shape_name: str, override: Optional[Dict] = None,
            use_hints: bool = True, label: str = "") -> Dict:
    """Lower one variant, return roofline terms (with the while-body
    correction from the probe cache)."""
    from repro.core import meshplan
    if override and "__scatter__" in override:
        override = {k: v for k, v in override.items()
                    if k != "__scatter__"} or None
        meshplan.DECODE_SCATTER_UPDATE = True
    else:
        meshplan.DECODE_SCATTER_UPDATE = False
    cfg = registry.get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=False)
    lowered, aux = dryrun._build_and_lower(cfg, shape, mesh,
                                           override=override,
                                           use_hints=use_hints)
    compiled = lowered.compile()
    flops, nbytes, coll = dryrun._cost_of(compiled)
    G = cfg.n_layers // cfg.unit
    micro = aux.get("micro", 1)
    # NOTE: the probe cache is keyed (arch, shape); variants that change
    # the sharding change the probes too -> bust the cache per variant.
    dryrun._BODY_COST_CACHE.clear()
    from repro.models import stacking as ST
    from repro.core import hints as hintmod
    # probes must run under the same variant settings
    body = None
    try:
        import dataclasses as dc
        pshape = shape if micro == 1 else dc.replace(
            shape, global_batch=max(shape.global_batch // micro, 1))
        costs = []
        ST.FORCE_UNROLL = True
        for n in (cfg.unit, 2 * cfg.unit):
            scfg = dc.replace(cfg, n_layers=n)
            low2, _ = dryrun._build_and_lower(scfg, pshape, mesh,
                                              micro_override=1,
                                              override=override,
                                              use_hints=use_hints)
            costs.append(dryrun._cost_of(low2.compile()))
        ST.FORCE_UNROLL = False
        (f1, b1, c1), (f2, b2, c2) = costs
        body = {"p1": (f1, b1, c1),
                "d": (max(f2 - f1, 0), max(b2 - b1, 0),
                      {k: max(c2.get(k, 0) - c1.get(k, 0), 0)
                       for k in set(c1) | set(c2)})}
    finally:
        ST.FORCE_UNROLL = False
        hintmod.set_hints(None)
    if body is not None:
        (f1, b1, c1) = body["p1"]
        (df, db, dcoll) = body["d"]
        flops = micro * (f1 + df * (G - 1))
        nbytes = micro * (b1 + db * (G - 1))
        coll = {k: micro * (c1.get(k, 0) + dcoll.get(k, 0) * (G - 1))
                for k in set(c1) | set(dcoll)}
    ma = compiled.memory_analysis()
    out = {
        "label": label, "arch": arch, "shape": shape_name,
        "strategy": aux["plan"].strategy,
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": nbytes / HBM_BW,
        "collective_s": sum(coll.values()) / LINK_BW,
        "collectives": coll,
        "temp_gib": getattr(ma, "temp_size_in_bytes", 0) / 2**30,
        "args_gib": getattr(ma, "argument_size_in_bytes", 0) / 2**30,
        "micro": micro,
    }
    out["dominant"] = max(("compute_s", "memory_s", "collective_s"),
                          key=lambda k: out[k])
    return out


def show(r: Dict) -> None:
    print(f"  {r['label']:34s} compute={r['compute_s']:8.3f}s "
          f"memory={r['memory_s']:8.3f}s collective={r['collective_s']:8.3f}s "
          f"dom={r['dominant'][:-2]:10s} temp={r['temp_gib']:6.1f}GiB",
          flush=True)


EXPERIMENTS = {
    "A": [
        ("granite-moe-3b-a800m", "train_4k", None, False,
         "A0 baseline (no dispatch hints)"),
        ("granite-moe-3b-a800m", "train_4k", None, True,
         "A1 +dispatch sharding hints"),
    ],
    "B": [
        ("internlm2-1.8b", "train_4k", None, True,
         "B0 baseline (CP: attention=dp_replicated)"),
        ("internlm2-1.8b", "train_4k", {"attention": "head_tp"}, True,
         "B1 override attention=head_tp"),
    ],
    "C": [
        ("qwen3-32b", "decode_32k", None, False,
         "C0 baseline (no cache hints)"),
        ("qwen3-32b", "decode_32k", None, True,
         "C1 +decode-cache layout hint"),
        ("qwen3-32b", "decode_32k", {"__scatter__": "on"}, True,
         "C2 +scatter cache update"),
    ],
}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=list(EXPERIMENTS))
    ap.add_argument("--out", default="artifacts/perf_iterations.json")
    args = ap.parse_args()
    results: List[Dict] = []
    for key, variants in EXPERIMENTS.items():
        if args.only and key != args.only:
            continue
        print(f"=== experiment {key} ===", flush=True)
        for arch, shp, override, use_hints, label in variants:
            r = measure(arch, shp, override=override, use_hints=use_hints,
                        label=label)
            results.append(r)
            show(r)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "a") as f:
        json.dump(results, f, indent=1)
        f.write("\n")


if __name__ == "__main__":
    main()
