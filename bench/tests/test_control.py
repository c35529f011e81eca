"""The control of ``correct``: the reference at ``high`` (three bfloat16
passes) in the program's place has to fail the cell's limit, on three
seeds, at a size a test run holds; the readings on the chip at the cells'
own size are in PERF.md."""

import pytest

import readings
import reference
import run

SEEDS = (2147483711, 2147483712, 2147483713)


@pytest.mark.parametrize("cell", ["tiny3-steady", "tiny4-saturated"])
def test_control_fails_the_limit(cell):
    spec = run.load_cell(cell)
    models = spec["config"]["models"]
    limit = float(spec["config"]["limits"]["gap"])
    for seed in SEEDS:
        params = reference.make_params(models, seed)
        pools = reference.make_inputs(models, seed, 8)
        gaps = readings.control_gaps(models, params, pools,
                                     [set(range(8))] * len(models))
        assert max(gaps.values()) > limit, (seed, gaps)
