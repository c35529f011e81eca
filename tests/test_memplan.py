"""Memory planner invariants (property-based 2-D packing checks)."""

from _hypo import given, settings, st

from repro.analysis import analyze_errors
from repro.core.memplan import Allocation, L2Allocator, MemoryPlan


@settings(max_examples=50, deadline=None)
@given(st.lists(st.tuples(st.integers(1, 4096),      # size
                          st.integers(0, 50),        # alloc time
                          st.integers(1, 30)),       # lifetime
                min_size=1, max_size=40))
def test_allocator_never_overlaps(reqs):
    """Drive the first-fit allocator through arbitrary alloc/free traffic;
    the resulting rectangle set must be overlap-free and in-range."""
    alloc = L2Allocator(capacity=16 * 1024)
    live = []
    t = 0.0
    for i, (size, at, life) in enumerate(reqs):
        t = max(t, float(at))
        # free everything that expired
        for name, t_end in list(live):
            if t_end <= t:
                alloc.free(name, t_end)
                live.remove((name, t_end))
        a = alloc.alloc(f"t{i}", size, t)
        if a is not None:
            live.append((f"t{i}", t + life))
    for name, t_end in live:
        alloc.free(name, t_end)
    plan = MemoryPlan(capacity=alloc.capacity, allocations=alloc.history,
                      swaps=[], peak=alloc.peak)
    assert not analyze_errors(plan)
    assert alloc.used() == 0


def test_fits_all_matches_reality():
    alloc = L2Allocator(capacity=1024)
    a = alloc.alloc("a", 512, 0.0)
    assert a is not None
    segs = alloc.segments_assuming_freed([])
    assert L2Allocator.fits_all(segs, [448])
    assert not L2Allocator.fits_all(segs, [640])
    # hypothetically freeing "a" makes 640 fit
    segs2 = alloc.segments_assuming_freed(["a"])
    assert L2Allocator.fits_all(segs2, [640, 256])


def test_free_list_merging():
    alloc = L2Allocator(capacity=1024)
    names = []
    for i in range(4):
        alloc.alloc(f"x{i}", 256, 0.0)
        names.append(f"x{i}")
    for n in names:
        alloc.free(n, 1.0)
    assert alloc._free == [(0, 1024)]
