"""Declarative ExperimentSpec / Engine protocol / batched Sweep:

  - vectorized Sweep grids produce the same summaries as serial per-point
    run_experiment calls (both engines, with and without scenarios, with a
    heterogeneous policy axis in one jit+vmap call);
  - the deprecated two-resource Experiment shim is fully removed;
  - ragged platform grids auto-pad to the common resource superset and stay
    on the batched path (only genuinely incompatible grids — e.g. mixed
    max_tasks — warn and fall back to the numpy serial loop);
  - retry resampling (per-attempt service times) with engine parity and the
    flag-off escape hatch;
  - per-attempt start/finish records and exact busy-time accounting.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import des, trace, vdes
from repro.core import model as M
from repro.core.batching import pad_workloads, stack_scenarios
from repro.core.engines import JaxEngine, NumpyEngine, get_engine
from repro.core.experiment import (ExperimentSpec, Sweep, as_spec,
                                   run_experiment)
from repro.ops import (CompiledScenario, FailureModel, MaintenanceWindows,
                       RetryPolicy, Scenario, SLOConfig, busy_node_seconds,
                       static_schedule)
from test_des_engines import make_workload, platform


@pytest.fixture()
def rng():
    """Module-local generator (suite order independence)."""
    return np.random.default_rng(20260801)


def int_workload(rng, n=80, horizon=300.0, **kw):
    return make_workload(rng, n, integer_time=True, horizon=horizon, **kw)


def _fail_scenario(max_retries=2):
    return Scenario(
        name="fail",
        failures=FailureModel(p_fail_by_type=(0.3,) * M.N_TASK_TYPES,
                              retry=RetryPolicy(max_retries=max_retries,
                                                base_s=4.0, mult=2.0,
                                                cap_s=16.0)),
        slo=SLOConfig())


def _maint_scenario():
    return Scenario(name="maint", slo=SLOConfig(),
                    capacity=MaintenanceWindows(
                        windows=((50.0, 150.0, 0, 0.5),)))


# --------------------------------------------------------------- spec basics

def test_spec_arbitrary_resources(rng):
    """Three resources, per-resource costs — beyond the legacy two."""
    plat = M.PlatformConfig(resources=(
        M.ResourceConfig("a", 3, 1.0), M.ResourceConfig("b", 2, 3.0),
        M.ResourceConfig("gpu_pool", 2, 7.5)))
    wl = int_workload(rng, n=50)
    wl.task_res = (wl.task_res + (np.arange(wl.n) % 3)[:, None]) % 3
    spec = ExperimentSpec(name="n3", platform=plat, horizon_s=300.0,
                          workload=wl, scenario=Scenario(slo=SLOConfig()))
    for engine in ("numpy", "jax"):
        res = run_experiment(dataclasses.replace(spec, engine=engine))
        assert res.summary["n_pipelines"] == 50
        assert set(res.summary["utilization"]) == {"compute_cluster",
                                                   "learning_cluster",
                                                   "datastore"} or \
            len(res.summary["utilization"]) == 3
        assert res.summary["total_cost"] > 0.0


def test_with_capacity_axis_helper():
    plat = M.PlatformConfig()
    p2 = plat.with_capacity("learning_cluster", 7)
    assert p2.capacities.tolist() == [48, 7]
    assert plat.capacities.tolist() == [48, 32]       # original untouched
    assert p2.with_capacity(0, 5).capacities.tolist() == [5, 7]
    with pytest.raises(KeyError):
        plat.with_capacity("nope", 1)
    spec = ExperimentSpec(name="s").with_(**{"capacity:learning_cluster": 9})
    assert spec.platform.capacities.tolist() == [48, 9]


def test_engine_protocol_registry():
    assert isinstance(get_engine("numpy"), NumpyEngine)
    assert isinstance(get_engine("jax"), JaxEngine)
    with pytest.raises(KeyError):
        get_engine("fortran")


# ---------------------------------------------------- shim removal (PR 3)

def test_legacy_experiment_shim_is_gone():
    """The deprecated two-resource Experiment and the serial sweep() helper
    were removed after their one-release deprecation window; as_spec still
    normalizes anything exposing to_spec."""
    import repro.core.experiment as ex
    assert not hasattr(ex, "Experiment")
    assert not hasattr(ex, "sweep")
    assert as_spec(ExperimentSpec(name="s")).name == "s"


# ------------------------------------------------- batched vs serial parity

SWEEP_KEYS = ("mean_wait_s", "p95_wait_s", "n_pipelines", "n_tasks")
SCEN_KEYS = ("mean_attempts", "deadline_miss_rate", "total_cost",
             "stranded_task_frac")


def _assert_summaries_match(batched, serial):
    for b, s in zip(batched, serial):
        assert b.experiment.name == s.experiment.name
        for k in SWEEP_KEYS:
            assert b.summary[k] == pytest.approx(s.summary[k], abs=1e-2), \
                (b.experiment.name, k)
        for k in SCEN_KEYS:
            assert (k in b.summary) == (k in s.summary), (b.experiment.name, k)
            if k in s.summary:
                assert b.summary[k] == pytest.approx(s.summary[k],
                                                     abs=1e-6, rel=1e-5), \
                    (b.experiment.name, k)


def test_sweep_batched_matches_serial_jax(rng):
    """The acceptance parity: a policy x capacity x scenario grid in ONE
    jit+vmap call equals per-point serial run_experiment (integer times)."""
    wl = int_workload(rng)
    base = ExperimentSpec(name="g", platform=platform(), horizon_s=300.0,
                          engine="jax", workload=wl, seed=5)
    sw = Sweep(base, {
        "capacity:a": [2, 3],
        "policy": [des.POLICY_FIFO, des.POLICY_SJF],
        "scenario": [None, _fail_scenario(), _maint_scenario()],
    })
    points = sw.points()
    assert len(points) == 12
    assert len({p.name for p in points}) == 12
    batched = sw.run()
    serial = [run_experiment(p) for p in points]
    _assert_summaries_match(batched, serial)


def test_sweep_numpy_fallback_matches_jax_batched(rng):
    wl = int_workload(rng, n=60)
    axes = {"policy": [des.POLICY_FIFO, des.POLICY_PRIORITY],
            "scenario": [None, _fail_scenario()]}
    base = ExperimentSpec(name="g", platform=platform(), horizon_s=300.0,
                          engine="jax", workload=wl)
    batched = Sweep(base, axes).run()
    serial_np = Sweep(base.with_(engine="numpy"), axes).run()
    _assert_summaries_match(batched, serial_np)


def test_sweep_with_replicas_matches_ensemble(rng):
    """Grid points with n_replicas > 1 aggregate exactly like the legacy
    ensemble path (which now routes through the same batching module)."""
    wl = int_workload(rng, n=50)
    base = ExperimentSpec(name="mc", platform=platform(), horizon_s=300.0,
                          engine="jax", workload=wl, n_replicas=3,
                          scenario=_fail_scenario())
    res = Sweep(base, {"capacity:b": [1, 2]}).run()
    assert len(res) == 2
    for r in res:
        assert r.summary["n_replicas"] == 3
        assert len(r.replica_summaries) == 3
        assert r.summary["wait_ci95_halfwidth"] >= 0.0
        # replicas share the pinned workload but draw scenario seeds
        # independently; the mean matches a direct single-spec run
        direct = run_experiment(dataclasses.replace(
            r.experiment, name="direct"))
        assert r.summary["mean_wait_s"] == pytest.approx(
            direct.summary["mean_wait_s"], abs=1e-2)


def test_sweep_engine_axis_dispatches_per_point(rng):
    """An "engine" axis must route each point to its own backend (the
    legacy sweep() did, via per-point run_experiment)."""
    wl = int_workload(rng, n=40)
    base = ExperimentSpec(name="g", platform=platform(), horizon_s=300.0,
                          workload=wl)
    res = Sweep(base, {"engine": ["numpy", "jax"]}).run()
    assert [r.experiment.engine for r in res] == ["numpy", "jax"]
    # numpy records are f64 heap output; jax came through the batched path —
    # physics agrees on integer times either way
    assert res[0].summary["mean_wait_s"] == pytest.approx(
        res[1].summary["mean_wait_s"], abs=1e-2)


def test_sweep_single_point_throughput_counts_pipelines(rng):
    wl = int_workload(rng, n=40)
    base = ExperimentSpec(name="g", platform=platform(), horizon_s=300.0,
                          engine="jax", workload=wl)
    res = Sweep(base, {"policy": [des.POLICY_FIFO]}).run()
    assert res[0].summary["pipelines_per_s"] == pytest.approx(
        wl.n / res[0].summary["wall_s"], rel=1e-6)


def test_sweep_ragged_platforms_auto_pad_onto_batched_path(rng):
    """A ragged platform grid (2- and 3-resource points) is auto-padded to
    the common resource superset with inert zero-capacity/zero-cost pools:
    no warning, no numpy fallback, and every point matches its own numpy
    serial run exactly."""
    import warnings as _warnings
    wl = int_workload(rng, n=20)
    p3 = M.PlatformConfig(resources=(
        M.ResourceConfig("a", 3), M.ResourceConfig("b", 2),
        M.ResourceConfig("c", 2)))
    base = ExperimentSpec(name="g", platform=platform(), horizon_s=300.0,
                          engine="jax", workload=wl,
                          scenario=Scenario(name="s", slo=SLOConfig()))
    sw = Sweep(base, {"platform": [platform(), p3]})
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")        # any warning fails the test
        res = sw.run()
    assert len(res) == 2
    serial = [run_experiment(p.with_(engine="numpy")) for p in sw.points()]
    for b, s in zip(res, serial):
        assert b.summary["mean_wait_s"] == pytest.approx(
            s.summary["mean_wait_s"], abs=1e-2)
        # accounting unchanged by the inert padding: the cost of the padded
        # point equals the unpadded serial run's
        assert b.summary["total_cost"] == pytest.approx(
            s.summary["total_cost"], abs=1e-9)
        assert "platform=" in b.experiment.name


def test_sweep_genuinely_incompatible_grid_warns_and_falls_back(rng):
    """Pinned workloads disagreeing on max_tasks cannot share one
    rectangular batch even with platform padding: that still warns and
    falls back to the exact numpy serial loop."""
    wl_a = int_workload(rng, n=20, max_tasks=3)
    wl_b = int_workload(rng, n=20, max_tasks=5)
    base = ExperimentSpec(name="g", platform=platform(), horizon_s=300.0,
                          engine="jax")
    specs = [base.with_(workload=wl_a, name="a"),
             base.with_(workload=wl_b, name="b")]
    with pytest.warns(RuntimeWarning, match="max_tasks"):
        res = get_engine("jax").run_sweep(specs)
    assert len(res) == 2
    serial = [run_experiment(p.with_(engine="numpy")) for p in specs]
    for b, s in zip(res, serial):
        assert b.summary["mean_wait_s"] == pytest.approx(
            s.summary["mean_wait_s"])


# ------------------------------------------------------- retry resampling

def test_resample_flag_off_keeps_attempt_service_none(rng):
    wl = int_workload(rng, n=30)
    comp = _fail_scenario().compile(wl, platform(), 300.0, seed=1)
    assert comp.attempt_service is None


def test_resample_flag_on_samples_per_attempt_services(rng):
    wl = int_workload(rng, n=30)
    fm = FailureModel(resample_service=True, resample_sigma=0.5)
    sc = Scenario(failures=fm)
    comp = sc.compile(wl, platform(), 300.0, seed=1)
    svc = wl.service_time(platform().datastore)
    assert comp.attempt_service.shape == svc.shape + (fm.retry.max_retries + 1,)
    # attempt 0 keeps the synthesized duration; retries are fresh draws
    assert np.allclose(comp.attempt_service[..., 0], svc)
    live = wl.task_type >= 0
    assert not np.allclose(comp.attempt_service[..., 1][live], svc[live])
    # deterministic per seed
    comp2 = sc.compile(wl, platform(), 300.0, seed=1)
    assert np.array_equal(comp.attempt_service, comp2.attempt_service)


def test_no_retry_resample_records_consistent_across_engines(rng):
    """resample_service with max_retries=0 (A=1, no retries): both engines
    must agree that per-attempt columns are unnecessary."""
    wl = int_workload(rng, n=20)
    sc = Scenario(failures=FailureModel(
        p_fail_by_type=(0.0,) * M.N_TASK_TYPES, resample_service=True,
        retry=RetryPolicy(max_retries=0)))
    comp = sc.compile(wl, platform(), 300.0, seed=1)
    assert comp.attempt_service.shape[2] == 1
    t_np = des.simulate(wl, platform(), scenario=comp)
    t_jx = vdes.simulate_to_trace(wl, platform(), scenario=comp)
    assert t_np.att_start is None and t_jx.att_start is None


def test_legacy_stack_wrapper_keeps_recording_off(rng):
    """stack_compiled_scenarios (the pre-Sweep API) must not silently turn
    on per-attempt recording for callers that never read it."""
    from repro.ops import stack_compiled_scenarios
    wls = [int_workload(rng, n=20) for _ in range(2)]
    comps = [_fail_scenario().compile(w, platform(), 300.0, seed=i)
             for i, w in enumerate(wls)]
    legacy = stack_compiled_scenarios(comps, 20, 300.0)
    assert "n_retry_slots" not in legacy
    exact = stack_scenarios(comps, 20, 300.0)
    assert exact["n_retry_slots"] > 0


def test_resample_engine_parity_integer_times(rng):
    """Both engines agree under resampled (integer) per-attempt durations."""
    wl = int_workload(rng)
    svc = wl.service_time(platform().datastore)
    asvc = np.repeat(svc[..., None], 3, axis=2)
    asvc[..., 1] = np.ceil(svc * 0.5) + 1.0
    asvc[..., 2] = np.ceil(svc * 2.0)
    fm = FailureModel(p_fail_by_type=(0.4,) * M.N_TASK_TYPES,
                      retry=RetryPolicy(max_retries=2, base_s=4.0,
                                        mult=2.0, cap_s=16.0))
    att = fm.sample_attempts(np.random.default_rng(9), wl)
    comp = CompiledScenario(schedule=static_schedule(np.array([3, 2])),
                            attempts=att, backoff=(4.0, 2.0, 16.0),
                            attempt_service=asvc)
    live = np.arange(wl.max_tasks)[None, :] < wl.n_tasks[:, None]
    for policy in (des.POLICY_FIFO, des.POLICY_SJF):
        t_np = des.simulate(wl, platform(), policy, scenario=comp)
        t_jx = vdes.simulate_to_trace(wl, platform(), policy, scenario=comp)
        for f in ("start", "finish", "ready"):
            a = np.where(live, getattr(t_np, f), 0.0)
            b = np.where(live, getattr(t_jx, f), 0.0)
            assert np.allclose(a, b, atol=1e-3, equal_nan=True), (policy, f)


def test_resample_hand_computed_single_job():
    """One server, one job, 2 attempts: attempt 1 runs 10s, backoff 5s,
    attempt 2 runs 3s (resampled) -> finish 18, per-attempt records exact."""
    wl = M.Workload(
        arrival=np.zeros(1), n_tasks=np.ones(1, np.int32),
        task_type=np.zeros((1, 1), np.int32),
        task_res=np.zeros((1, 1), np.int32),
        exec_time=np.full((1, 1), 10.0),
        read_bytes=np.zeros((1, 1)), write_bytes=np.zeros((1, 1)),
        framework=np.zeros(1, np.int32), priority=np.zeros(1, np.float32),
        model_perf=np.zeros(1, np.float32), model_size=np.zeros(1, np.float32),
        model_clever=np.zeros(1, np.float32))
    plat = M.PlatformConfig(resources=(M.ResourceConfig("s", 1),))
    asvc = np.array([[[10.0, 3.0]]])
    comp = CompiledScenario(schedule=static_schedule(plat.capacities),
                            attempts=np.full((1, 1), 2, np.int64),
                            backoff=(5.0, 2.0, 5.0), attempt_service=asvc)
    for tr in (des.simulate(wl, plat, scenario=comp),
               vdes.simulate_to_trace(wl, plat, scenario=comp)):
        assert tr.finish[0, 0] == pytest.approx(18.0)
        assert tr.att_start[0, 0].tolist() == pytest.approx([0.0, 15.0])
        assert tr.att_finish[0, 0].tolist() == pytest.approx([10.0, 18.0])
        rec = trace.flatten_trace(tr, wl)
        # exact busy time: 10 + 3, NOT duration*attempts = 3*2
        busy = busy_node_seconds(rec, 1)
        assert busy[0] == pytest.approx(13.0)


# --------------------------------------------------- per-attempt records

def test_attempt_records_cover_all_executed_attempts(rng):
    wl = int_workload(rng)
    comp = CompiledScenario(
        schedule=static_schedule(np.array([3, 2])),
        attempts=FailureModel(
            p_fail_by_type=(0.3,) * M.N_TASK_TYPES,
            retry=RetryPolicy(max_retries=2, base_s=4.0, mult=2.0,
                              cap_s=16.0)).sample_attempts(
                                  np.random.default_rng(4), wl),
        backoff=(4.0, 2.0, 16.0))
    for tr in (des.simulate(wl, platform(), scenario=comp),
               vdes.simulate_to_trace(wl, platform(), scenario=comp)):
        live = np.arange(wl.max_tasks)[None, :] < wl.n_tasks[:, None]
        n_rec = (~np.isnan(tr.att_start)).sum(2)
        assert (n_rec[live] == tr.attempts[live]).all()
        # final recorded attempt equals the task's finish
        last = np.where(live & (tr.attempts > 0),
                        np.nanmax(np.where(np.isnan(tr.att_finish), -np.inf,
                                           tr.att_finish), 2), np.nan)
        ok = live & (tr.attempts > 0)
        assert np.allclose(last[ok], tr.finish[ok], atol=1e-3)


def test_busy_node_seconds_exact_under_retry(rng):
    """Exact per-attempt accounting vs an event-sweep ground truth."""
    wl = int_workload(rng, n=60)
    comp = _fail_scenario().compile(wl, platform(), 300.0, seed=3)
    tr = des.simulate(wl, platform(), scenario=comp)
    rec = trace.flatten_trace(tr, wl)
    busy = busy_node_seconds(rec, 2)
    # ground truth: integrate every recorded attempt window per resource
    truth = np.zeros(2)
    live = np.arange(wl.max_tasks)[None, :] < wl.n_tasks[:, None]
    for r in range(2):
        m = live & (tr.task_res == r)
        s, f = tr.att_start[m], tr.att_finish[m]
        truth[r] = np.nansum(f - s)
    assert np.allclose(busy, truth)


def test_concat_records_pads_attempt_columns(rng):
    wl = int_workload(rng, n=20)
    comp = _fail_scenario(max_retries=3).compile(wl, platform(), 300.0, seed=2)
    tr = des.simulate(wl, platform(), scenario=comp)
    rec_a = trace.flatten_trace(tr, wl)          # has att columns
    rec_b = trace.flatten_trace(des.simulate(wl, platform()), wl)  # none
    cat = trace.concat_records([rec_a, rec_b])
    E_a = rec_a.start.shape[0]
    assert cat.att_start.shape == (E_a + rec_b.start.shape[0],
                                   rec_a.att_start.shape[1])
    # column-less rows ran once over (start, finish): that interval lands in
    # slot 0 (all-NaN rows would under-charge attempt-window accounting)
    assert np.array_equal(cat.att_start[E_a:, 0], rec_b.start)
    assert np.array_equal(cat.att_finish[E_a:, 0], rec_b.finish)
    assert np.isnan(cat.att_start[E_a:, 1:]).all()
    assert np.allclose(cat.att_start[:E_a], rec_a.att_start, equal_nan=True)


def test_records_roundtrip_with_attempt_columns(rng, tmp_path):
    wl = int_workload(rng, n=30)
    comp = _fail_scenario().compile(wl, platform(), 300.0, seed=6)
    rec = trace.flatten_trace(des.simulate(wl, platform(), scenario=comp), wl)
    path = str(tmp_path / "r.npz")
    rec.save(path)
    back = trace.TaskRecords.load(path)
    assert np.allclose(back.att_start, rec.att_start, equal_nan=True)
    # records without the columns still roundtrip (None stays None)
    rec2 = trace.flatten_trace(des.simulate(wl, platform()), wl)
    rec2.save(path)
    assert trace.TaskRecords.load(path).att_start is None


# ------------------------------------------------------- batching helpers

def test_pad_workloads_and_stack_scenarios_shapes(rng):
    wls = [int_workload(rng, n=n) for n in (30, 45)]
    plat = platform()
    cols = pad_workloads(wls, plat)
    assert cols["arrival"].shape == (2, 45)
    assert cols["service"].shape == (2, 45, wls[0].max_tasks)
    comps = [_fail_scenario().compile(w, plat, 300.0, seed=i)
             for i, w in enumerate(wls)]
    kw = stack_scenarios(comps, 45, 300.0)
    assert kw["attempts"].shape == (2, 45, wls[0].max_tasks)
    assert kw["cap_times"].shape[0] == 2
    # the retry log holds every non-final attempt of the busiest pipeline
    assert kw["n_retry_slots"] == int((kw["attempts"] - 1).sum(axis=2).max())
    # padded rows are inert single-attempt tasks
    assert (kw["attempts"][0, 30:] == 1).all()


def test_stack_scenarios_mixed_resampling_needs_services(rng):
    wls = [int_workload(rng, n=20) for _ in range(2)]
    plat = platform()
    resample = Scenario(failures=FailureModel(resample_service=True))
    comps = [resample.compile(wls[0], plat, 300.0, seed=0),
             _fail_scenario().compile(wls[1], plat, 300.0, seed=1)]
    with pytest.raises(ValueError, match="services"):
        stack_scenarios(comps, 20, 300.0)
    svcs = [w.service_time(plat.datastore) for w in wls]
    kw = stack_scenarios(comps, 20, 300.0, services=svcs)
    A = kw["attempt_service"].shape[3]
    assert kw["attempt_service"].shape[:3] == (2, 20, wls[0].max_tasks)
    # the non-resampling entry broadcasts its base service to every slot
    assert np.allclose(kw["attempt_service"][1][..., 0],
                       kw["attempt_service"][1][..., A - 1])
