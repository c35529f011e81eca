"""The cached deployment: built once, pickled with fresh locks, loaded
again, and able to serve a co-round through the engine."""

import json

import numpy as np
import pytest

import artifact
import reference

MODELS = ["autoencoder", "ds_cnn"]
CONFIG = {
    "models": MODELS,
    "soc": {"module": "repro.soc.carfield", "soc": "carfield_soc",
            "patterns": "carfield_patterns"},
    "compile": {"mode": "matcha", "time_budget_s": 0.001,
                "joint_time_budget_s": 0.001,
                "lazy_joint_time_budget_s": 0.001,
                "incremental_time_budget_s": 0.001},
}
OCC = [[0, 1], [0], [1]]


@pytest.fixture
def cache(tmp_path, monkeypatch):
    monkeypatch.setattr(artifact, "CACHE", str(tmp_path / "cache"))
    path = tmp_path / "small.json"
    path.write_text(json.dumps(CONFIG))
    return str(path)


def test_round_trip_serves_a_co_round(cache):
    from repro.serve.compiler_thread import BackgroundCompiler
    from repro.serve.engine import MultiModelEngine
    built, secs, was_built = artifact.load_or_build("small", cache, CONFIG,
                                                    OCC)
    loaded, secs2, was_built2 = artifact.load_or_build("small", cache,
                                                       CONFIG, OCC)
    assert was_built and not was_built2 and secs2 == secs > 0
    assert (artifact.fingerprint(loaded, OCC)
            == artifact.fingerprint(built, OCC))
    params = reference.make_params(MODELS, 3)
    pools = reference.make_inputs(MODELS, 3, 1)
    eng = MultiModelEngine(
        loaded, params_list=params, execute=True,
        async_compile=BackgroundCompiler(loaded.session, start=False))
    rids = [eng.submit(t, inputs={"x": pools[t][0]}) for t in range(2)]
    assert sorted(eng.step()) == sorted(rids)
    assert eng.co_rounds == 1 and eng.floor_rounds == 0
    want = reference.reference_outputs(MODELS, params, pools)
    for t, rid in enumerate(rids):
        out = reference.load_model(MODELS[t]).OUTPUT
        assert reference.gap(np.asarray(eng.results[rid][out]),
                             want[t][0]) < 1e-5


def test_digest_follows_config_and_occupancies(cache, tmp_path):
    d = artifact.digest(cache, OCC)
    assert d == artifact.digest(cache, list(reversed(OCC)))
    assert d != artifact.digest(cache, OCC[:1])
    other = tmp_path / "other.json"
    other.write_text(json.dumps({**CONFIG, "models": MODELS[::-1]}))
    assert d != artifact.digest(str(other), OCC)
