"""Benchmark aggregator: one section per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--fast]

Sections:
  * Table 2 (MLPerf-Tiny x 4 toolchains)   — benchmarks/table2_mlperf.py
  * Fig. 7  (block FLOPS comparison)       — benchmarks/fig7_blocks.py
  * Fig. 6  (timeline + breakdown)         — benchmarks/fig6_timeline.py
  * Roofline (from the dry-run artifacts)  — benchmarks/roofline.py

The multi-pod dry-run itself is launched separately
(``python -m repro.launch.dryrun``) because it needs 512 virtual devices.
"""

from __future__ import annotations

import argparse
import os
import time

from repro.launch.cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the numeric allclose re-validation")
    args = ap.parse_args()
    t0 = time.time()

    from benchmarks import fig6_timeline, fig7_blocks, table2_mlperf

    print("=" * 72)
    print("Table 2 — MLPerf-Tiny x {TVM, MATCH, MATCHA-nt, MATCHA}")
    print("=" * 72)
    table2_mlperf.run(check_numerics=not args.fast, verbose=True)

    print()
    print("=" * 72)
    print("Fig. 7 — DNN block FLOPS comparison")
    print("=" * 72)
    fig7_blocks.run(check_numerics=not args.fast, verbose=True)

    print()
    print("=" * 72)
    print("Fig. 6 — ResNet inference timeline / per-device breakdown")
    print("=" * 72)
    fig6_timeline.run(verbose=True)

    print()
    print("=" * 72)
    print("Multi-tenant co-scheduling — co-scheduled vs. sequential")
    print("=" * 72)
    from benchmarks import multi_tenant
    multi_tenant.run(mixes=multi_tenant.MIXES[:2],
                     check_numerics=not args.fast, verbose=True)

    print()
    print("=" * 72)
    print("Roofline — per (arch x shape x mesh), from the dry-run")
    print("=" * 72)
    dr = os.path.join("artifacts", "dryrun", "dryrun.json")
    if os.path.exists(dr):
        from benchmarks import roofline
        roofline.main()
    else:
        print(f"({dr} missing — run `python -m repro.launch.dryrun` first)")

    print(f"\ntotal benchmark wall time: {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
