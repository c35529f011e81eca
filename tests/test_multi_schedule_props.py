"""Property-based co-scheduler invariants on randomized small graphs x
random 2-3-device SoCs (via the _hypo shim):

  * no two nodes overlap on the same device / the DMA engine,
  * every predecessor finishes before a node starts,
  * shared-L2 occupancy never exceeds capacity (overlap-free packing),
  * each tenant's makespan >= that tenant's critical path.
"""

from _hypo import given, settings, st

from repro.analysis import analyze_errors
from repro.core.patterns import chain, wildcard
from repro.core.rewrite import rewrite
from repro.core.schedule import (_upward_rank, build_dag, default_budgets,
                                 schedule_multi)
from repro.core.ir import Graph
from repro.core.tiling import optimize_tiling
from repro.soc.device import Device, MemoryLevel, SoC

KiB = 1024
WIDTHS = [8, 16, 32, 48, 64]


def _rand_soc(draw):
    n_acc = draw(st.integers(1, 2))           # host + 1..2 accelerators
    devices = {}
    devices["host"] = Device(
        name="host", alpha=2.0,
        l1=MemoryLevel("host_l1", 16 * KiB, 8.0),
        dma_bandwidth=8.0, is_host=True, copy_bandwidth=1.0)
    for j in range(n_acc):
        name = f"acc{j}"
        devices[name] = Device(
            name=name, alpha=0.4 + 0.4 * draw(st.integers(0, 2)),
            l1=MemoryLevel(f"{name}_l1", 32 * KiB, 16.0),
            dma_bandwidth=8.0)
    l2_size = draw(st.sampled_from([48 * KiB, 64 * KiB, 128 * KiB]))
    soc = SoC(name="randsoc", devices=devices,
              l2=MemoryLevel("l2", l2_size, 16.0),
              l3=MemoryLevel("l3", 16 * 1024 * KiB, 8.0),
              dma_l3_bandwidth=8.0, mailbox_latency=100.0, freq_mhz=50.0)
    pats = []
    for d in devices:
        eta = 0.3 + 0.1 * draw(st.integers(0, 4))
        pats.append(chain(d, f"{d}_dense", ["dense"], eta, 200.0))
        pats.append(chain(d, f"{d}_dense_relu", ["dense", "relu"],
                          eta, 200.0))
    pats.append(wildcard("host", eta=0.25, delta=100.0))
    return soc, pats


def _rand_graph(draw, idx: int) -> Graph:
    g = Graph(f"m{idx}")
    w0 = draw(st.sampled_from(WIDTHS))
    x = g.add_input("x", (1, w0), "float16")
    depth = draw(st.integers(2, 4))
    cin = w0
    for li in range(depth):
        cout = draw(st.sampled_from(WIDTHS))
        w = g.add_param(f"l{li}_w", (cin, cout), "float16")
        x = g.add_op("dense", [x, w], name=f"l{li}")
        if draw(st.integers(0, 1)):
            x = g.add_op("relu", [x], name=f"l{li}_relu")
        cin = cout
    g.mark_output(x)
    return g


@settings(max_examples=8, deadline=None)
@given(st.data())
def test_coschedule_invariants(data):
    soc, pats = _rand_soc(data.draw)
    n_tenants = data.draw(st.integers(2, 3))
    tgs = []
    for i in range(n_tenants):
        g = _rand_graph(data.draw, i)
        sol = optimize_tiling(g, soc, pats, mode="matcha_nt",
                              requested_tiles=data.draw(
                                  st.sampled_from([2, 4])),
                              time_budget_s=0.5)
        tgs.append(rewrite(g, soc, sol))
    plan = schedule_multi(tgs, soc)

    # precedence + per-device / per-DMA mutual exclusion
    assert not analyze_errors(plan)

    # shared-L2 occupancy: overlap-free rectangles within capacity
    assert not analyze_errors(plan.memory)
    assert plan.memory.peak <= soc.l2.size

    # per-tenant makespan >= that tenant's critical path
    budgets = default_budgets(soc, n_tenants)
    for i, tg in enumerate(tgs):
        rank = _upward_rank(build_dag(tg, soc, budgets[i]))
        cp = max(rank.values(), default=0.0)
        assert plan.tenant_makespans[i] >= cp - 1e-6, (i, cp)

    # every tenant's every node is inside [0, makespan]
    for n in plan.nodes.values():
        assert n.start >= -1e-9
        assert n.end <= plan.makespan + 1e-6


def _dense_chain(name, widths):
    g = Graph(name)
    x = g.add_input("x", (1, widths[0]), "float16")
    cin = widths[0]
    for i, cout in enumerate(widths[1:]):
        w = g.add_param(f"l{i}_w", (cin, cout), "float16")
        x = g.add_op("dense", [x, w], name=f"l{i}")
        x = g.add_op("relu", [x], name=f"l{i}_r")
        cin = cout
    g.mark_output(x)
    return g


def test_contention_eviction_packing_stays_valid():
    """Regression: with an L2 so small that tenants must evict each other,
    the shared packing must stay overlap-free.  (Double-buffered DMA lets
    reservation times run backwards relative to allocator order; the
    mem_clock clamp in _MultiSimState keeps the rectangles consistent.)"""
    host = Device("host", 2.0, MemoryLevel("hl1", 8 * KiB, 8.0), 8.0,
                  is_host=True, copy_bandwidth=1.0)
    acc = Device("acc0", 0.5, MemoryLevel("al1", 16 * KiB, 16.0), 8.0)
    pats = [chain("host", "h_d", ["dense"], 0.4, 100.0),
            chain("acc0", "a_d", ["dense"], 0.5, 200.0),
            wildcard("host", eta=0.25, delta=100.0)]
    soc = SoC("tiny", {"host": host, "acc0": acc},
              l2=MemoryLevel("l2", 6 * KiB, 16.0),
              l3=MemoryLevel("l3", 16 * 1024 * KiB, 8.0),
              dma_l3_bandwidth=8.0, mailbox_latency=100.0, freq_mhz=50.0)
    gs = [_dense_chain("a", [32, 32, 32, 32]),
          _dense_chain("b", [32, 32, 32, 32]),
          _dense_chain("c", [16, 32, 16, 32])]
    tgs = []
    for g in gs:
        sol = optimize_tiling(g, soc, pats, mode="matcha_nt",
                              requested_tiles=2, time_budget_s=0.5)
        tgs.append(rewrite(g, soc, sol))
    plan = schedule_multi(tgs, soc)
    assert not analyze_errors(plan)
    assert not analyze_errors(plan.memory)
    assert plan.memory.peak <= soc.l2.size
    evictions = [s for s in plan.memory.swaps if s.direction == "out"]
    assert evictions, "scenario must actually exercise eviction traffic"
