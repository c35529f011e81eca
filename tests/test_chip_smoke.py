"""chip_smoke.py refuses to run without a TPU instead of falling back.

The tests run with ``JAX_PLATFORMS=cpu``; the script's device check is
called in this process (a child would need the chip the parent holds).
"""

import importlib.util
import os
import sys

import jax
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_device_check_raises_on_cpu(smoke):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(RuntimeError, match="needs a TPU"):
        smoke.require_tpu()


def test_main_prints_no_result_on_cpu(smoke, monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    with pytest.raises(RuntimeError, match="needs a TPU"):
        smoke.main()
    assert '"ok"' not in capsys.readouterr().out
