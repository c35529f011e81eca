"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Device planes are those named ``/device:TPU:<n>``.  On each, the events of
the ``XLA Ops`` line are the intervals in which an operation ran; their
union is the device's busy time.  The events of the ``XLA Modules`` line
are program executions.  Host spans are the harness's own
``jax.profiler.TraceAnnotation`` events, named ``bench.<span>``; they put
what the host was doing on the trace's clock.

The traced window runs from the start of the first harness span to the
end of the last.  Busy time is clipped to it and averaged over the device
planes that ran anything.  Each idle gap of a device is charged to the
harness spans that overlap it (``other`` for the rest).
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

SPAN_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _overlap(a0, a1, b0, b1):
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce_planes(planes) -> dict | None:
    """The reduction, from planes as ``jax.profiler.ProfileData`` gives
    them.  None when the trace holds no harness span."""
    spans = []
    devices = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops, programs = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.start_ns, e.end_ns, e.name)
                           for e in line.events]
                elif line.name == PROGRAMS_LINE:
                    programs = [(e.start_ns, e.end_ns) for e in line.events]
            devices.append((plane.name, ops, programs))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.end_ns,
                                      e.name[len(SPAN_PREFIX):]))
    if not spans:
        return None
    lo = min(s[0] for s in spans)
    hi = max(s[1] for s in spans)
    busy_total, used, programs = 0.0, 0, 0
    op_time = defaultdict(float)
    gap_time = defaultdict(float)
    for _, ops, progs in devices:
        if not ops:
            continue
        used += 1
        programs += sum(1 for a, b in progs if a >= lo and b <= hi)
        for a, b, name in ops:
            op_time[name] += _overlap(a, b, lo, hi)
        busy = _union(_clip([(a, b) for a, b, _ in ops], lo, hi))
        busy_total += sum(b - a for a, b in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            covered = 0.0
            for s0, s1, name in spans:
                ov = _overlap(g0, g1, s0, s1)
                if ov:
                    gap_time[name] += ov
                    covered += ov
            if g1 - g0 > covered:
                gap_time["other"] += g1 - g0 - covered
    ns = 1e-9
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(gap_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy_total * ns / used if used else 0.0,
        "devices": used,
        "programs": programs,
        "device_ops": [[n, t * ns] for n, t in top],
        "idle_gaps": [[n, t * ns / max(used, 1)] for n, t in gaps],
    }


def reduce_file(path: str) -> dict | None:
    import jax
    return reduce_planes(jax.profiler.ProfileData.from_file(path).planes)


def idle_share(record) -> float | None:
    """Idle share of the device in the traced window, %: 100 x (1 - busy
    / window), busy averaged over the chips used."""
    tr = record.get("trace")
    if not tr or not tr["window_s"] or not tr["devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
