"""Reduce the program's own spans in a profiler trace.

The program marks its layers with ``repro.<span>`` annotations
(``src/repro/core/spans.py``): ``submit``, ``step``, ``wave``, ``plan``,
``execute`` and ``kernel``, with the ids that tie each to its request and
its plan among the event's stats.  This module reads them beside the
harness's ``bench.<span>`` spans and the device planes that ``xplane.py``
reduces, over the same traced window (first harness span to last):

* ``spans`` — for each ``repro.`` name in the window: ``count``,
  ``total_s``, ``self_s`` (duration less the spans nested directly in it
  on its thread) and ``longest_s``;
* ``idle_gaps`` — each idle gap of a device charged to the innermost host
  span over it, the latest-started one that covers it (``other`` where
  none does), averaged over the devices used.  Harness spans keep the
  names ``xplane.py`` gives them (``step``), program spans their full
  name (``repro.kernel``).  With no program span this equals
  ``xplane.py``'s ``idle_gaps`` wherever harness spans do not overlap;
* ``queue_wait_s`` — for each ``rid`` whose ``repro.submit`` and
  ``repro.wave`` both lie in the window: wave start less submit end;
* ``executed`` — the ``requests`` of the ``repro.execute`` spans;
* ``kernels`` — device op time and programs charged to the latest
  ``repro.kernel`` span that started before each began, by ``(tenant,
  supernode)``, beside the spans' count, host self time and
  ``analytic_cycles``: a diagnostic, ordered by host self time.

``queue_wait_ms``, ``engine_self_ms`` and ``execute_host_ms`` give the
per-layer numbers these keys define, or None where the spans are absent,
as in a trace of a program without them.
"""

from __future__ import annotations

import bisect
import heapq
import statistics
from collections import defaultdict

import xplane

PREFIX = "repro."
SEP = ";"       # joins the ids of one span argument, as the program does
NS = 1e-9


def _self_times(line):
    """Self time of each span of one host line, ns: its duration less the
    spans nested directly in it."""
    order = sorted(range(len(line)), key=lambda i: (line[i][0], -line[i][1]))
    own = [b - a for a, b, *_ in line]
    stack = []
    for i in order:
        a, b = line[i][0], line[i][1]
        while stack and line[stack[-1]][1] <= a:
            stack.pop()
        if stack and b <= line[stack[-1]][1]:
            own[stack[-1]] -= b - a
        stack.append(i)
    return own


def _innermost(spans, lo, hi):
    """``[lo, hi]`` cut into pieces ``[a, b, name]``, each named after the
    latest-started span that covers it (``other`` where none does)."""
    spans = sorted(spans)
    cuts = sorted({lo, hi} | {t for s in spans for t in s[:2]
                              if lo < t < hi})
    heap, k, out = [], 0, []
    for a, b in zip(cuts, cuts[1:]):
        while k < len(spans) and spans[k][0] <= a:
            s0, s1, name = spans[k][:3]
            heapq.heappush(heap, (-s0, s1, name))
            k += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        name = heap[0][2] if heap else "other"
        if out and out[-1][2] == name:
            out[-1][1] = b
        else:
            out.append([a, b, name])
    return out


def _charge(gaps, pieces, into) -> None:
    """Adds each gap's overlap with each piece to ``into[piece name]``;
    both lists sorted and disjoint."""
    j = 0
    for g0, g1 in gaps:
        while j < len(pieces) and pieces[j][1] <= g0:
            j += 1
        m = j
        while m < len(pieces) and pieces[m][0] < g1:
            into[pieces[m][2]] += xplane._overlap(g0, g1, pieces[m][0],
                                                  pieces[m][1])
            m += 1


def _ids(value) -> list:
    return [int(x) for x in str(value).split(SEP) if x != ""]


def _read(planes):
    """(harness spans, host lines of harness and program spans, devices
    with their ops and programs)."""
    harness, lines, devices = [], [], []
    for plane in planes:
        if plane.name.startswith(xplane.DEVICE_PREFIX):
            ops, progs = [], []
            for line in plane.lines:
                if line.name == xplane.OPS_LINE:
                    ops = [(e.start_ns, e.end_ns) for e in line.events]
                elif line.name == xplane.PROGRAMS_LINE:
                    progs = [(e.start_ns, e.end_ns) for e in line.events]
            if ops:
                devices.append((ops, progs))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                held = []
                for e in line.events:
                    if e.name.startswith(xplane.SPAN_PREFIX):
                        held.append((e.start_ns, e.end_ns,
                                     e.name[len(xplane.SPAN_PREFIX):], {}))
                        harness.append(held[-1])
                    elif e.name.startswith(PREFIX):
                        held.append((e.start_ns, e.end_ns, e.name,
                                     dict(e.stats)))
                if held:
                    lines.append(held)
    return harness, lines, devices


def _program(lines, lo, hi):
    """Per-name counts and times of the program spans in the window, the
    queue waits, the requests executed and the kernel spans."""
    per = defaultdict(lambda: {"count": 0, "total_s": 0.0, "self_s": 0.0,
                               "longest_s": 0.0})
    submitted, waved, kernels = {}, {}, []
    executed = 0
    for line in lines:
        for (a, b, name, args), own in zip(line, _self_times(line)):
            if not name.startswith(PREFIX) or a < lo or b > hi:
                continue
            p = per[name]
            p["count"] += 1
            p["total_s"] += (b - a) * NS
            p["self_s"] += own * NS
            p["longest_s"] = max(p["longest_s"], (b - a) * NS)
            if name == PREFIX + "submit" and "rid" in args:
                submitted[int(args["rid"])] = b
            elif name == PREFIX + "wave" and "rids" in args:
                for rid in _ids(args["rids"]):
                    waved[rid] = min(waved.get(rid, a), a)
            elif name == PREFIX + "execute":
                executed += int(args.get("requests", 0))
            elif name == PREFIX + "kernel":
                kernels.append((a, (str(args.get("tenant")),
                                    str(args.get("supernode"))),
                                own, float(args.get("analytic_cycles", 0))))
    waits = [(waved[r] - submitted[r]) * NS for r in sorted(submitted)
             if r in waved]
    return dict(per), waits, executed, sorted(kernels)


def _by_kernel(kernels, devices, lo, hi):
    """Device op time and programs charged to the latest kernel span that
    started before each began, summed by (tenant, supernode)."""
    rows = {}
    for _, key, own, cycles in kernels:
        r = rows.setdefault(key, {"tenant": key[0], "supernode": key[1],
                                  "count": 0, "host_self_s": 0.0,
                                  "device_s": 0.0, "programs": 0,
                                  "analytic_cycles": 0.0})
        r["count"] += 1
        r["host_self_s"] += own * NS
        r["analytic_cycles"] += cycles
    starts = [k[0] for k in kernels]

    def owner(t):
        i = bisect.bisect_right(starts, t) - 1
        return rows[kernels[i][1]] if i >= 0 else None

    for ops, progs in devices:
        for a, b in ops:
            r = owner(a) if lo <= a < hi else None
            if r is not None:
                r["device_s"] += xplane._overlap(a, b, lo, hi) * NS
        for a, b in progs:
            r = owner(a) if a >= lo and b <= hi else None
            if r is not None:
                r["programs"] += 1
    return sorted(rows.values(), key=lambda r: -r["host_self_s"])


def reduce_planes(planes) -> dict | None:
    """The reduction, from planes as ``jax.profiler.ProfileData`` gives
    them.  None when the trace holds no harness span."""
    harness, lines, devices = _read(planes)
    if not harness:
        return None
    lo = min(s[0] for s in harness)
    hi = max(s[1] for s in harness)
    pieces = _innermost([h[:3] for line in lines for h in line], lo, hi)
    gap_time = defaultdict(float)
    for ops, _ in devices:
        busy = xplane._union(xplane._clip(ops, lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        _charge([(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                 if g1 > g0], pieces, gap_time)
    spans, waits, executed, kernels = _program(lines, lo, hi)
    used = max(len(devices), 1)
    return {
        "idle_gaps": sorted(([n, t * NS / used] for n, t in gap_time.items()),
                            key=lambda kv: -kv[1]),
        "spans": spans,
        "queue_wait_s": waits,
        "executed": executed,
        "kernels": _by_kernel(kernels, devices, lo, hi),
    }


def reduce_file(path: str) -> dict | None:
    import jax
    return reduce_planes(jax.profiler.ProfileData.from_file(path).planes)


def queue_wait_ms(p) -> float | None:
    """Median wait of a request in the engine's queue, ms."""
    waits = (p or {}).get("queue_wait_s")
    return 1e3 * statistics.median(waits) if waits else None


def engine_self_ms(p) -> float | None:
    """Host ms per engine step outside the executor: self time of
    ``repro.step`` (composition), ``repro.wave`` (bookkeeping and the
    analytic repeat cost) and ``repro.plan`` (plan lookup) over the
    steps."""
    spans = (p or {}).get("spans") or {}
    step = spans.get(PREFIX + "step")
    if not step:
        return None
    own = sum(spans[PREFIX + n]["self_s"] for n in ("step", "wave", "plan")
              if PREFIX + n in spans)
    return 1e3 * own / step["count"]


def execute_host_ms(p) -> float | None:
    """Host ms of the plan executor per request: ``repro.execute`` total
    over the requests those spans executed."""
    ex = ((p or {}).get("spans") or {}).get(PREFIX + "execute")
    if not ex or not p.get("executed"):
        return None
    return 1e3 * ex["total_s"] / p["executed"]
