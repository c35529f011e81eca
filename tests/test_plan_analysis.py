"""Static plan analyzer (PR 7): clean plans analyze clean at strict,
every rule's seeded mutation is caught (the analyzer has teeth), on
multi, single and bare memory plans, the deployment session enforces
the strict/warn knob, and the concurrency lint holds on the serving
layer."""

import itertools
import pathlib

import pytest

from repro.analysis import Severity, analyze, analyze_errors
from repro.analysis.lockcheck import check_paths, check_source
from repro.analysis.mutate import MUTATORS, check_rules, clone_plan, mutate
from repro.analysis.scan_mixes import mixes_from_baseline, plans_for_mix
from repro.core.api import compile_multi
from repro.core.deploy import CompileRequest, DeploymentSession
from repro.soc.testbed import dense_chain, two_acc_soc

REPO = pathlib.Path(__file__).resolve().parent.parent
REQUESTED_TILES = 4
TIME_BUDGET_S = 0.5


@pytest.fixture(scope="module")
def mc():
    """Cheap three-tenant testbed compile: full-house co-schedule plus
    lazily compiled occupancy subsets — the mutation substrate."""
    soc, pats = two_acc_soc(64, 8.0)
    graphs = [dense_chain("a", [64, 64, 64]),
              dense_chain("b", [48, 48, 48]),
              dense_chain("c", [32, 32, 32])]
    return compile_multi(graphs, soc, pats,
                         requested_tiles=REQUESTED_TILES,
                         time_budget_s=TIME_BUDGET_S)


# ---------------------------------------------------------------------------
# Clean plans analyze clean
# ---------------------------------------------------------------------------


def test_testbed_plans_have_no_error_diagnostics(mc):
    """Full house, every occupancy subset, and every compile-alone plan
    carry zero ERROR-severity diagnostics."""
    plans = {"full": mc.plan}
    for r in (1, 2):
        for ids in itertools.combinations(range(3), r):
            plans[str(ids)] = mc.plan_for(list(ids))
    for i, cm in enumerate(mc.singles):
        plans[f"single{i}"] = cm.plan
    for label, plan in plans.items():
        assert analyze_errors(plan) == [], label


def test_session_strict_analysis_counts(mc):
    """The session analyzed every plan it stored (strict is the default)
    and found no errors."""
    mc.plan_for([0, 1])               # force at least one subset compile
    stats = mc.session.analysis_stats()
    assert stats["mode"] == "strict"
    assert stats["plans_analyzed"] >= 2   # full house + the subset
    assert stats["errors"] == 0


BASELINE = REPO / "benchmarks" / "baseline.json"


@pytest.mark.parametrize(
    "mix", [pytest.param(m, id="+".join(m))
            for m in mixes_from_baseline(str(BASELINE))])
def test_benchmark_mix_plans_analyze_clean(mix):
    """Every schedule the session emits for the benchmark mixes — full
    house, all PlanStore occupancies, compile-alone plans — analyzes
    with zero ERROR diagnostics (the same sweep the CI ``scan_mixes``
    lane runs)."""
    for label, plan in plans_for_mix(mix, TIME_BUDGET_S):
        assert analyze_errors(plan) == [], (mix, label)


# ---------------------------------------------------------------------------
# Mutation harness: every rule has teeth
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rule", sorted(MUTATORS))
def test_rule_catches_its_mutation(mc, rule):
    """Each analyzer rule must flag the hazard its mutator injects into
    an otherwise-clean co-schedule (ERROR severity, correct rule id)."""
    mutated = mutate(mc.plan, rule)
    diags = analyze(mutated)
    assert any(d.rule == rule and d.severity >= Severity.ERROR
               for d in diags), (rule, [str(d) for d in diags])
    # and the mutation did not leak into the shared fixture plan
    assert analyze_errors(mc.plan) == []


def test_check_rules_all_fire_on_multi(mc):
    fired = check_rules(mc.plan)
    assert set(fired) == set(MUTATORS)
    assert all(fired.values()), fired


def test_check_rules_all_fire_on_single(mc):
    """Single-model plans exercise every rule except tenant isolation
    (PA006 needs budgets, which only multi plans carry)."""
    fired = check_rules(mc.singles[0].plan)
    assert set(fired) == set(MUTATORS) - {"PA006"}
    assert all(fired.values()), fired


def test_clone_plan_is_deep_enough(mc):
    """Mutating a clone must never write through to the original."""
    clone = clone_plan(mc.plan)
    first = mc.plan.order[0]
    clone.nodes[first].start += 1.0
    clone.memory.allocations[0].addr += 64
    assert mc.plan.nodes[first].start != clone.nodes[first].start
    assert mc.plan.memory.allocations[0].addr != \
        clone.memory.allocations[0].addr


# ---------------------------------------------------------------------------
# analyze_errors names the rule, on every plan shape
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape, rule", [
    ("multi", "PA001"),
    ("single", "PA002"),
    ("multi", "PA005"),      # L2 address aliasing across tenants
    ("memory", "PA005"),     # a bare MemoryPlan
])
def test_analyze_errors_names_the_mutated_rule(mc, shape, rule):
    """A clean plan has no findings; its mutation is an ERROR that names
    the rule, whether the analyzer gets a co-schedule, a single-model
    plan or a bare memory plan."""
    plan = mc.singles[0].plan if shape == "single" else mc.plan
    bad = mutate(plan, rule)
    if shape == "memory":
        plan, bad = plan.memory, bad.memory
    assert not analyze_errors(plan)
    errs = [str(d) for d in analyze_errors(bad)]
    assert errs and any(rule in e for e in errs)


# ---------------------------------------------------------------------------
# Session wiring: the strict/warn knob
# ---------------------------------------------------------------------------


def _bare_session(analysis):
    soc, pats = two_acc_soc(64, 8.0)
    req = CompileRequest(graphs=[dense_chain("a", [32, 32])], soc=soc,
                         patterns=pats, time_budget_s=TIME_BUDGET_S,
                         analysis=analysis)
    return DeploymentSession(req)


def test_strict_mode_raises_on_error_diagnostics(mc):
    session = _bare_session("strict")
    with pytest.raises(RuntimeError, match="PA001"):
        session._analyze(mutate(mc.plan, "PA001"), "infeasible co-schedule")
    assert session.analysis_stats()["errors"] >= 1


def test_warn_mode_records_instead_of_raising(mc):
    session = _bare_session("warn")
    bad = mutate(mc.plan, "PA003")
    assert session._analyze(bad, "ctx") is bad      # plan still ships
    stats = session.analysis_stats()
    assert stats["mode"] == "warn"
    assert stats["errors"] >= 1
    assert stats["by_rule"].get("PA003", 0) >= 1
    assert any("PA003" in f for f in stats["findings"])


def test_off_mode_skips_the_analyzer(mc):
    session = _bare_session("off")
    assert session._analyze(mutate(mc.plan, "PA001"), "ctx") is not None
    assert session.analysis_stats()["plans_analyzed"] == 0


def test_invalid_analysis_mode_rejected():
    soc, pats = two_acc_soc(64, 8.0)
    with pytest.raises(ValueError, match="analysis"):
        CompileRequest(graphs=[dense_chain("a", [32, 32])], soc=soc,
                       patterns=pats, analysis="lenient")


# ---------------------------------------------------------------------------
# Concurrency lint
# ---------------------------------------------------------------------------


def test_lockcheck_clean_on_serving_layer():
    assert check_paths([str(REPO / "src" / "repro" / "serve")]) == []


def test_lockcheck_flags_unlocked_write():
    src = (
        "import threading\n"
        "class Store:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.items = {}\n"
        "    def put(self, k, v):\n"
        "        with self._lock:\n"
        "            self.items[k] = v\n"
        "    def drop(self, k):\n"
        "        del self.items[k]\n"
    )
    vs = check_source(src, "snippet.py")
    assert any(v.method == "drop" and v.field == "items" for v in vs)


def test_lockcheck_honors_caller_holds_the_lock_marker():
    src = (
        "import threading\n"
        "class Store:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.items = {}\n"
        "    def put(self, k, v):\n"
        "        with self._lock:\n"
        "            self._put(k, v)\n"
        "    def _put(self, k, v):\n"
        "        \"\"\"Caller holds the lock.\"\"\"\n"
        "        self.items[k] = v\n"
    )
    assert check_source(src, "snippet.py") == []


def test_lockcheck_enforces_docstring_declared_guards():
    """A field the class docstring declares lock-guarded is enforced
    even when no locked write is ever seen (the inference blind spot the
    worker-pool state exposed)."""
    src = (
        "import threading\n"
        "class Pool:\n"
        "    \"\"\"Worker pool.\n"
        "\n"
        "    Lock-guarded: _recent, _hints\n"
        "    \"\"\"\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._recent = {}\n"
        "        self._hints = {}\n"
        "    def peek(self):\n"
        "        return len(self._recent)\n"
        "    def ok(self):\n"
        "        with self._lock:\n"
        "            return len(self._hints)\n"
    )
    vs = check_source(src, "snippet.py")
    assert [(v.method, v.field, v.access) for v in vs] == \
        [("peek", "_recent", "read")]
    # without the declaration the same read is invisible to inference
    undeclared = src.replace("    Lock-guarded: _recent, _hints\n", "")
    assert check_source(undeclared, "snippet.py") == []


def test_lockcheck_declared_guards_on_background_compiler():
    """The real ``BackgroundCompiler`` declares its pool + prefetcher
    state; corrupting one of its lock blocks must trip the lint."""
    path = REPO / "src" / "repro" / "serve" / "compiler_thread.py"
    src = path.read_text()
    assert "Lock-guarded: _queued" in src
    assert check_source(src, str(path)) == []
    broken = src.replace("        with self._lock:\n"
                         "            self._recent.pop(key, None)",
                         "        if True:\n"
                         "            self._recent.pop(key, None)")
    assert broken != src
    vs = check_source(broken, str(path))
    assert any(v.field == "_recent" and v.access == "write" for v in vs)
