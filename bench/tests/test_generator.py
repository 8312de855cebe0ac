"""The traffic generator: deterministic in the seed, the same work for every
seed, integer time, and the fitted parameters' structure and framework mix
within sampling error."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from traffic import generator as g  # noqa: E402

ROUTING = {0: 0, 1: 1, 2: 0, 3: 1, 4: 1, 5: 0}
DATASTORE = dict(latency=0.15, read_bandwidth=400e6, write_bandwidth=250e6)
DAY = 86400.0


@pytest.fixture(scope="module")
def params():
    return g.load_params()


def make(params, seed, horizon_s=DAY, factor=1.0, base_seed=17, level=0):
    return g.make_workload(params, horizon_s=horizon_s,
                           interarrival_factor=factor, base_seed=base_seed,
                           seed=seed, routing=ROUTING, datastore=DATASTORE,
                           level=level)


def test_same_seed_same_workload(params):
    a, b = make(params, 2 ** 31 + 77), make(params, 2 ** 31 + 77)
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


def test_seeds_reorder_the_same_work(params):
    """Row i is the same pipeline for every seed; only the arrival times
    move, among neighbours in arrival order."""
    a, b = make(params, 1), make(params, 2)
    for k in a:
        if k != "arrival":
            np.testing.assert_array_equal(a[k], b[k])
    assert not np.array_equal(a["arrival"], b["arrival"])
    np.testing.assert_array_equal(np.sort(a["arrival"]),
                                  np.sort(b["arrival"]))
    ranks = np.argsort(np.argsort(a["arrival"], kind="stable"),
                       kind="stable")
    assert np.abs(ranks - np.arange(ranks.size)).max() < 64


def test_integer_time(params):
    w = make(params, 5)
    assert np.all(w["arrival"] == np.floor(w["arrival"]))
    assert w["arrival"].max() < DAY
    assert np.all(w["exec_time"] == np.ceil(w["exec_time"]))
    assert not w["read_bytes"].any() and not w["write_bytes"].any()
    live = w["task_type"] >= 0
    assert np.all(w["exec_time"][live] >= 1) and not w["exec_time"][~live].any()
    assert np.all(live.sum(1) == w["n_tasks"])
    want = np.array([ROUTING[t] for t in range(6)])
    assert np.all(w["task_res"][live] == want[w["task_type"][live]])


def test_arrival_rate_scales_with_the_factor(params):
    n1 = make(params, 3, factor=1.0)["arrival"].size
    n2 = make(params, 3, factor=0.5, level=1)["arrival"].size
    assert 1.7 < n2 / n1 < 2.3


def test_structure_and_framework_mix(params):
    """Per-type presence and the framework mix within 5 sigma of the fitted
    probabilities (train always present, deploy only after evaluate)."""
    w = make(params, 9, horizon_s=7 * DAY)
    n = w["arrival"].size
    tt = w["task_type"]
    present = np.stack([(tt == t).any(1) for t in range(6)], 1)
    p = params["structure_probs"].copy()
    p[1] = 1.0
    p[5] = p[5] * p[2]            # deploy requires evaluate
    sigma = np.sqrt(p * (1 - p) / n) + 1e-12
    assert np.all(np.abs(present.mean(0) - p) < 5 * sigma + 1e-9)
    assert not (present[:, 5] & ~present[:, 2]).any()
    mix = params["framework_mix"] / params["framework_mix"].sum()
    freq = np.bincount(w["framework"], minlength=5) / n
    assert np.all(np.abs(freq - mix) < 5 * np.sqrt(mix * (1 - mix) / n)
                  + 1e-9)


def test_task_order_is_canonical(params):
    """Tasks of a pipeline run in preprocess, train, evaluate, compress,
    harden, deploy order, packed from slot 0."""
    tt = make(params, 4)["task_type"]
    for row in tt[:2000]:
        live = row[row >= 0]
        assert list(live) == sorted(live)
        assert np.all(row[len(live):] == -1)
