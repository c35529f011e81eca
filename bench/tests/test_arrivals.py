"""The traffic generator: seeded, and with the rates and shares it states."""

import numpy as np
import pytest

import arrivals

STEADY = {"kind": "poisson", "rate_per_s": 12.5, "shares": "equal",
          "arrivals_seed": 11, "pool": 8}


def _gaps(schedule):
    due = np.array([d for d, _ in schedule])
    return np.diff(due)


def test_same_seed_same_schedule():
    a = arrivals.open_schedule(STEADY, 3, 30.0, 2**31 + 5)
    b = arrivals.open_schedule(STEADY, 3, 30.0, 2**31 + 5)
    assert a == b


def test_seeds_share_arrivals_not_tenants():
    a = arrivals.open_schedule(STEADY, 3, 30.0, 1)
    b = arrivals.open_schedule(STEADY, 3, 30.0, 2)
    assert [d for d, _ in a] == [d for d, _ in b]
    assert [t for _, t in a] != [t for _, t in b]
    assert (np.bincount([t for _, t in a])
            == np.bincount([t for _, t in b])).all()
    c = arrivals.open_schedule(dict(STEADY, arrivals_seed=12), 3, 30.0, 1)
    assert [d for d, _ in a] != [d for d, _ in c]


def test_a_shorter_window_is_a_prefix():
    short = arrivals.open_schedule(STEADY, 3, 10.0, 5)
    long = arrivals.open_schedule(STEADY, 3, 30.0, 5)
    assert [d for d, _ in short] == [d for d, _ in long][:len(short)]


def test_rate_and_window():
    s = arrivals.open_schedule(STEADY, 3, 300.0, 2**31 + 9)
    due = [d for d, _ in s]
    assert due == sorted(due)
    assert 0.0 < due[0] and due[-1] < 300.0
    # a Poisson count: mean rate x window, standard deviation its root
    assert abs(len(s) - 12.5 * 300) < 3 * (12.5 * 300) ** 0.5
    gaps = _gaps(s)
    # exponential gaps: mean 1/rate and a coefficient of variation near 1
    assert abs(gaps.mean() - 1 / 12.5) < 0.005
    assert 0.9 < gaps.std() / gaps.mean() < 1.1


@pytest.mark.parametrize("shares", ["equal", [0.4, 0.3, 0.2, 0.1]])
def test_shares(shares):
    t = dict(STEADY, shares=shares, rate_per_s=20.0)
    s = arrivals.open_schedule(t, 4, 30.0, 3)
    counts = np.bincount([x for _, x in s], minlength=4)
    want = arrivals.shares(t, 4) * len(s)
    assert np.all(np.abs(counts - want) < 1)


def test_occupancies():
    assert arrivals.occupancies({"kind": "closed"}, 4) == [[0, 1, 2, 3]]
    occ = arrivals.occupancies(STEADY, 3)
    assert occ[0] == [0, 1, 2] and len(occ) == 7
    assert sorted(map(tuple, occ)) == sorted(
        {(0,), (1,), (2,), (0, 1), (0, 2), (1, 2), (0, 1, 2)})


def test_input_order_is_a_seeded_permutation():
    a = arrivals.input_order(STEADY, 3, 11)
    b = arrivals.input_order(STEADY, 3, 11)
    assert all((x == y).all() for x, y in zip(a, b))
    assert all(sorted(x) == list(range(8)) for x in a)
    assert any((x != y).any() for x, y in zip(a, arrivals.input_order(
        STEADY, 3, 12)))


@pytest.mark.parametrize("name", ["steady", "saturated"])
def test_traffic_files_load(name):
    t = arrivals.load(name)
    assert t["kind"] in arrivals.KINDS and t["pool"] >= 1
