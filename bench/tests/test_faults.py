"""A whole run on the CPU, past the harness's look for a chip, with the
timed path sound and then broken underneath: ``correct`` has to come out
true, then false.

Faults planted in the executor the engine calls
(``repro.core.runtime.execute_multi_plan``):
  * ``altered``: one answer of each round scaled by 1 + 10 x the limit,
    where it is produced;
  * ``stale``: each tenant's answer of the round before handed back in
    place of this round's (an answer routed to the wrong request).
"""

import json

import jax
import pytest

import artifact
import run

CELL = "tiny3-steady"


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return str(tmp_path_factory.mktemp("bench-cache"))


def _plant(monkeypatch, fault, limit):
    from repro.core import runtime
    inner = runtime.execute_multi_plan
    last = {}

    def altered(plan, inputs_list, params_list):
        outs = inner(plan, inputs_list, params_list)
        outs[0] = {k: v * (1.0 + 10 * limit) for k, v in outs[0].items()}
        return outs

    def stale(plan, inputs_list, params_list):
        outs = inner(plan, inputs_list, params_list)
        served = []
        for tg, out in zip(plan.tenants, outs):
            name = tg.graph.name
            served.append(last.get(name, out))
            last[name] = out
        return served

    monkeypatch.setattr(runtime, "execute_multi_plan",
                        {"altered": altered, "stale": stale}[fault])


@pytest.mark.parametrize("fault", [None, "altered", "stale"])
def test_run_catches_the_fault(fault, cache, monkeypatch, capsys):
    monkeypatch.setattr(artifact, "CACHE", cache)
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(run, "peak_flops", lambda kind, chips: 197e12)
    limit = float(run.load_cell(CELL)["config"]["limits"]["gap"])
    if fault:
        _plant(monkeypatch, fault, limit)
    # this seed's one-second window has requests for all three tenants
    # (a tenant never served reads an infinite gap)
    assert run.main(["--workload", CELL, "--seed", "2147483723",
                     "--seconds", "1", "--trace", "0"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert line["correct"] is (fault is None), line["checks"]
    if fault:
        assert line["failed"] > 0
