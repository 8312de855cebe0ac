"""The failure-and-recovery deployment (``bench/configs/pipesim-faults.json``)
through the program's own API on the CPU: its rates and their derivations,
the batched JAX engine against the numpy heap engine bit for bit at a
one-day horizon, the per-row failure and recovery counters of
``batching.batch_trace``, and the nested wave-loop scopes ``retry``,
``attempts`` and ``reliability``, which change no operation."""
import contextlib
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batching, vdes
from repro.core import model as M
from repro.core.experiment import ExperimentSpec, Sweep
from repro.ops.accounting import down_node_seconds
from repro.ops.failures import FailureModel, RetryPolicy
from repro.ops.scenario import Scenario
from repro.reliability import (DomainOutageModel, RepairSpec,
                               ReliabilitySpec, TopologySpec)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
with open(os.path.join(BENCH, "configs", "pipesim-faults.json")) as f:
    CFG = json.load(f)
with open(os.path.join(BENCH, "cells", "week-faults8.json")) as f:
    MIX = json.load(f)
DAY_S = 86400.0
# a one-day horizon would see about 40 racks x 1 / 132 days = 0.3 rack
# outages a row: the test raises the rack rate 40-fold (3.3 days a rack,
# about 12 outages a day over 40 racks) so that some fire; every other
# value is the configuration's own
RACK_RATE_X = 40.0


def _platform():
    p = CFG["platform"]
    return M.PlatformConfig(
        resources=tuple(M.ResourceConfig(**r) for r in p["resources"]),
        routing={int(k): int(v) for k, v in p["routing"].items()},
        datastore=M.DataStoreConfig(**p["datastore"]))


def _generator():
    spec = importlib.util.spec_from_file_location(
        "faults_traffic", os.path.join(BENCH, "traffic", "generator.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def day_workload():
    """The cell's traffic at its fitted rate, cut to one day (integer
    time, so the float32 engine is exact)."""
    gen = _generator()
    p = CFG["platform"]
    cols = gen.make_workload(
        gen.load_params(os.path.join(BENCH, CFG["params"])),
        horizon_s=DAY_S, interarrival_factor=MIX["interarrival_factors"][0],
        base_seed=MIX["base_seed"], seed=3000000019,
        routing={int(k): int(v) for k, v in p["routing"].items()},
        datastore=p["datastore"])
    return M.Workload(**{k: cols[k] for k in (
        "arrival", "n_tasks", "task_type", "task_res", "exec_time",
        "read_bytes", "write_bytes", "framework", "priority", "model_perf",
        "model_size", "model_clever")})


def _sweep(engine: str, wl) -> Sweep:
    f, r = CFG["failures"], CFG["reliability"]
    fm = FailureModel(p_fail_by_type=tuple(f["p_fail_by_type"]),
                      framework_mult=tuple(f["framework_mult"]),
                      retry=RetryPolicy(**f["retry"]))
    outages = dict(r["outages"],
                   rack_mtbf_s=r["outages"]["rack_mtbf_s"] / RACK_RATE_X)
    rel = ReliabilitySpec(topology=TopologySpec(**r["topology"]),
                          outages=DomainOutageModel(**outages),
                          repair=RepairSpec(**r["repair"]), spot=None,
                          time_quantum_s=r["time_quantum_s"])
    base = ExperimentSpec(name="faults-day", platform=_platform(),
                          horizon_s=DAY_S, seed=0, engine=engine,
                          scenario=Scenario(name="faults", failures=fm),
                          reliability=rel, workload=wl)
    return Sweep(base, {"capacity:learning_cluster": [16, 32]})


# ------------------------------------------------------------ the config

def test_config_names_its_source_and_derives_its_rates():
    assert "2407.21783" in CFG["source"]
    assert "3.3.1" in CFG["source"] and "3.3.4" in CFG["source"]
    assert len(CFG["source"]) <= 200 and CFG["reduced"] == []
    # 419 interruptions in 54 days over 16,384 GPUs / 16 GPUs a rack
    racks = 16384 // 16
    assert CFG["reliability"]["outages"]["rack_mtbf_s"] == \
        round(54 * DAY_S * racks / 419)
    # one slot is one 8-GPU host, two hosts a rack
    hosts = sum(r["capacity"] for r in CFG["platform"]["resources"])
    topo = CFG["reliability"]["topology"]
    assert topo["zones"] * topo["racks_per_zone"] * 2 == hosts
    assert CFG["reliability"]["outages"]["zone_mtbf_s"] >= 1e12
    assert "spot" not in CFG["reliability"]
    # the platform and traffic are pipesim-paper's
    with open(os.path.join(BENCH, "configs", "pipesim-paper.json")) as f:
        paper = json.load(f)
    assert CFG["platform"] == paper["platform"]
    assert CFG["params"] == paper["params"]
    # every value the source does not give is listed with its reason
    for key in ("zone_mtbf_s", "mttr_s", "repair", "failures", "retry",
                "hosts", "rack_mtbf_s", "left_out"):
        assert CFG["assumed"][key]


def test_retry_delays_are_whole_seconds():
    retry = RetryPolicy(**CFG["failures"]["retry"])
    delays = [retry.delay(k) for k in range(retry.max_retries)]
    assert delays == [30.0, 60.0, 120.0]
    assert all(float(np.float32(d)) == d for d in delays)


# ---------------------------------------------- engines, bit for bit

@pytest.fixture(scope="module")
def day_results(day_workload):
    return (_sweep("jax", day_workload).run(),
            _sweep("numpy", day_workload).run())


BUFFERS = ("start", "finish", "ready", "attempts", "completed", "att_start",
           "att_finish", "rel_times", "rel_caps")


@pytest.mark.parametrize("row", [0, 1], ids=["learning16", "learning32"])
def test_jax_sweep_equals_numpy_bit_for_bit(day_results, row):
    jx, npy = (res[row].trace for res in day_results)
    for name in BUFFERS:
        a, b = getattr(jx, name), getattr(npy, name)
        assert a is not None and b is not None, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    assert jx.waves == npy.waves
    assert day_results[0][row].summary["availability"] == \
        day_results[1][row].summary["availability"]


@pytest.mark.parametrize("row", [0, 1], ids=["learning16", "learning32"])
def test_retries_and_rack_outages_fire(day_results, row):
    tr = day_results[0][row].trace
    assert tr.retries > 0
    assert tr.retries == int(np.maximum(tr.attempts - 1, 0).sum())
    # some outage took slots down
    assert np.asarray(tr.rel_caps).min() < 0
    assert tr.rel_slot_s > 0
    # the row's own per-attempt width, and a retry log wide enough for
    # the busiest pipeline of the batch
    assert tr.attempt_slots == tr.att_start.shape[2]
    assert tr.attempt_log_slots == max(
        int(np.maximum(r.trace.attempts - 1, 0).sum(axis=1).max())
        for r in day_results[0])


# ---------------------------------------------------- the counters

def _hand_out():
    """A one-row ``simulate_ensemble`` output by hand: 2 pipelines of 2
    tasks, a retry log of one column; pipeline 0's first task ran twice
    ([0, 5] failed, [20, 25] succeeded), pipeline 1 never ran its second
    task. Reliability: 2 slots down at t=10, 1 back at t=40, the rest back
    at t=70; horizon 100."""
    nan = np.nan
    log_start = np.full((1, 3, 1), nan)        # one padding pipeline
    log_finish = np.full((1, 3, 1), nan)
    log_start[0, 0, 0], log_finish[0, 0, 0] = 0, 5
    rel_act = np.full((1, 4, 3), nan, np.float32)
    rel_act[0, :3] = [[10, -2, 0], [40, -1, 0], [70, 0, 0]]
    return dict(
        start=np.array([[[20, 25], [1, nan], [nan, nan]]]),
        finish=np.array([[[25, 30], [2, nan], [nan, nan]]]),
        ready=np.zeros((1, 3, 2)), waves=np.array([9]),
        attempts=np.array([[[2, 1], [1, 0], [0, 0]]], np.int32),
        done=np.array([[True, False, False]]),
        att_log_start=log_start, att_log_finish=log_finish,
        rel_act=rel_act, rel_n=np.array([3]))


def _hand_wl():
    n, T = 2, 2
    return M.Workload(
        arrival=np.zeros(n), n_tasks=np.full(n, 2, np.int32),
        task_type=np.zeros((n, T), np.int32),
        task_res=np.zeros((n, T), np.int32), exec_time=np.ones((n, T)),
        read_bytes=np.zeros((n, T)), write_bytes=np.zeros((n, T)),
        framework=np.zeros(n, np.int32), priority=np.zeros(n, np.float32),
        model_perf=np.zeros(n, np.float32),
        model_size=np.zeros(n, np.float32),
        model_clever=np.zeros(n, np.float32))


class _Rel:
    n_events = 3
    horizon_s = 100.0


def test_counters_of_a_hand_built_row():
    tr = batching.batch_trace(_hand_out(), 0, _hand_wl(), np.array([2, 1]),
                              reliability=_Rel(), n_attempts=2)
    assert tr.retries == 1                 # pipeline 0's first task
    assert tr.attempt_slots == 2           # the row's own width
    assert tr.attempt_log_slots == 1       # the call's retry log
    # 2 slots x 30 s + 1 slot x 30 s, then nothing down until 100
    assert tr.rel_slot_s == 90.0
    # the row's own two attempt columns, as a one-row run records them
    assert tr.att_start.shape == (2, 2, 2)
    np.testing.assert_array_equal(tr.att_start[0, 0], [0, 20])
    np.testing.assert_array_equal(tr.att_finish[0, 0], [5, 25])
    np.testing.assert_array_equal(tr.att_start[0, 1], [25, np.nan])
    np.testing.assert_array_equal(tr.att_finish[1],
                                  [[2, np.nan], [np.nan, np.nan]])
    np.testing.assert_array_equal(tr.rel_times, [10, 40, 70])


def test_counters_where_the_row_has_none():
    out = _hand_out()
    for k in ("att_log_start", "att_log_finish", "rel_act", "rel_n"):
        del out[k]
    tr = batching.batch_trace(out, 0, _hand_wl(), np.array([2, 1]),
                              with_scenario=False)
    assert (tr.retries, tr.attempt_slots, tr.attempt_log_slots,
            tr.rel_slot_s) == (None, None, None, None)
    # a row that retries nothing keeps no per-attempt buffers
    tr = batching.batch_trace(_hand_out(), 0, _hand_wl(), np.array([2, 1]),
                              n_attempts=1)
    assert tr.att_start is None and tr.attempt_slots == 1


def test_down_node_seconds_holds_the_last_event_to_the_horizon():
    np.testing.assert_array_equal(
        down_node_seconds([10.0, 30.0], [[-1, -2], [0, -1]], 50.0),
        [20.0, 60.0])
    np.testing.assert_array_equal(
        down_node_seconds(np.zeros(0), np.zeros((0, 2)), 50.0), [0.0, 0.0])


# ------------------------------------------------------ the scopes

_STATIC = ("policy", "n_retry_slots", "admission_sort", "n_ctrl_slots",
           "n_probe_slots", "n_rel_slots", "return_state", "batch_axis")


def _lower(args, kwargs):
    raw = vdes.simulate.__wrapped__
    # a new function object: a program traced afresh, not a cached one
    return jax.jit(lambda *a, **k: raw(*a, **k),
                   static_argnames=_STATIC).lower(*args, **kwargs)


def test_nested_scopes_change_no_operation():
    """The loop compiled with its scopes and with every scope a no-op: the
    same program, the same outputs bit for bit; the nested scopes sit under
    their stages in the program's op names."""
    n, T = 6, 2
    vwl = vdes.VWorkload(
        jnp.arange(n, dtype=jnp.float32), jnp.full((n,), 2, jnp.int32),
        jnp.zeros((n, T), jnp.int32), jnp.full((n, T), 3.0, jnp.float32),
        jnp.zeros((n,), jnp.float32),
        jnp.asarray(np.tile([[1, 3]], (n, 1)), jnp.int32))
    args = (vwl, jnp.asarray([2], jnp.int32))
    kwargs = dict(backoff=jnp.asarray([30.0, 2.0, 1800.0]),
                  n_retry_slots=2,
                  rel_times=jnp.asarray([2.0, 9.0]),
                  rel_deltas=jnp.asarray([[-1], [1]], jnp.int32),
                  n_rel_slots=2)
    scoped = _lower(args, kwargs)
    names = scoped.as_text(debug_info=True)
    for scope in ("completion/retry", "admission/attempts",
                  "control/reliability"):
        assert re.search(rf"/{scope}/", names), scope
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        bare = _lower(args, kwargs)
    finally:
        jax.named_scope = real
    assert not re.search(r"/(retry|attempts|reliability)/",
                         bare.as_text(debug_info=True))
    assert scoped.as_text() == bare.as_text()
    dyn = {k: v for k, v in kwargs.items() if k not in _STATIC}
    a, b = scoped.compile()(*args, **dyn), bare.compile()(*args, **dyn)
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)
    assert int(np.asarray(a["rel_n"])) == 2
    assert int(np.asarray(a["attempts"]).max()) == 3
