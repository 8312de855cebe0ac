"""Model lifecycle as a first-class experiment API (Fig 7 in-engine):

  - wave-for-wave numpy-vs-JAX parity of the fleet stage on integer-time
    workloads (drift timelines, trigger times, redeploy times, task
    schedules), alone and composed with failure scenarios + controllers;
  - a >= 12-point trigger/fleet Sweep grid lowers to exactly ONE jit+vmap
    ``simulate_ensemble`` call, each point matching its own serial numpy
    run bit-for-bit;
  - the thin :func:`run_feedback_simulation` reference wrapper agrees with
    the in-engine JAX path on trigger counts and redeploy times;
  - hypothesis property tests for the drift algebra (staleness in [0, 1],
    performance monotone between redeploys, redeploy resets state), with
    seeded deterministic twins that always run;
  - retrain durations drawn per-pipeline from the fitted distributions
    (regression for the old max(1)/min(1)-over-one-row hack);
  - trigger/redeploy actions visible on the shared SimTrace action
    timeline.
"""
import dataclasses

import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from repro.core import des, vdes
from repro.core import model as M
from repro.core.experiment import ExperimentSpec, Sweep, run_experiment
from repro.core.metrics import (FLEET_FIELDS, DeployedModel,
                                fleet_performance, fleet_performance_acc,
                                fleet_staleness, pack_fleet)
from repro.core.runtime import (FeedbackResult, FleetSpec, TriggerSpec,
                                lifecycle_result, run_feedback_simulation,
                                synthesize_retrain_workload)
from repro.ops import ReactiveController, Scenario
from repro.ops.scenario import compile_fleet
from test_des_engines import make_workload, platform


@pytest.fixture()
def rng():
    """Module-local generator (suite order independence)."""
    return np.random.default_rng(20260731)


def int_workload(rng, n=60, horizon=300.0, **kw):
    return make_workload(rng, n, integer_time=True, horizon=horizon, **kw)


def fleet_params(perf0, grad, jump_rate=0.0, jump_scale=0.0, seas_amp=0.0):
    """Explicit [M, FLEET_FIELDS] tensor (seasonal off by default — the
    bit-parity configuration; the cos backend may differ otherwise)."""
    m = len(perf0)
    fl = np.zeros((m, FLEET_FIELDS), np.float32)
    fl[:, 0] = perf0
    fl[:, 1] = grad
    fl[:, 2] = jump_rate
    fl[:, 3] = jump_scale
    fl[:, 4] = seas_amp
    fl[:, 5] = 7 * 24 * 3600.0
    return fl


FLEET4 = fleet_params([0.9, 0.8, 0.95, 0.7], [2e-3, 1e-3, 5e-4, 3e-3])
TRIG = TriggerSpec(drift_threshold=0.05, cooldown_s=60.0, obs_noise=0.01,
                   interval_s=20.0, retrain_durations=(40.0, 5.0, 15.0))


def lifecycle_spec(wl, engine="jax", trigger=TRIG, fleet_tensor=FLEET4,
                   **kw):
    return ExperimentSpec(name="lc", platform=platform(), horizon_s=300.0,
                          workload=wl, engine=engine, trigger=trigger,
                          fleet=FleetSpec(params=fleet_tensor), **kw)


def assert_traces_match(t_np, t_jx, wl):
    live = np.arange(wl.max_tasks)[None, :] < wl.n_tasks[:, None]
    live = live & np.isfinite(t_np.arrival)[:, None]
    assert t_np.waves == t_jx.waves, "wave-for-wave parity"
    assert np.allclose(np.where(live, t_np.start, 0),
                       np.where(live, t_jx.start, 0), atol=1e-3,
                       equal_nan=True)
    assert np.allclose(np.where(live, t_np.finish, 0),
                       np.where(live, t_jx.finish, 0), atol=1e-3,
                       equal_nan=True)
    assert np.allclose(t_np.arrival, t_jx.arrival, equal_nan=True)
    # the fleet stage is f32 in both engines: timelines must be BIT-equal
    assert np.array_equal(t_np.fleet_perf, t_jx.fleet_perf, equal_nan=True)
    assert np.array_equal(t_np.fleet_stale, t_jx.fleet_stale,
                          equal_nan=True)
    assert np.array_equal(t_np.fleet_times, t_jx.fleet_times)
    assert np.array_equal(t_np.fleet_kind, t_jx.fleet_kind)
    assert np.array_equal(t_np.fleet_model, t_jx.fleet_model)


# ------------------------------------------------ engine-level parity

def fleet_need_waves(tr):
    """The waves at which the fleet stage has work in a numpy trace: drift
    ticks the run reached and redeploys (a retraining pipeline finished),
    counted once where both fall in one wave."""
    reached = ~np.isnan(tr.fleet_perf).all(axis=1)
    redeploys = tr.fleet_times[tr.fleet_kind == des.FLEET_ACT_REDEPLOY]
    return len(set(tr.fleet_ticks[reached].tolist())
               | set(redeploys.tolist()))


def _ensemble_rows(rng, plat):
    """Three fleet rows of one width whose drift ticks and redeploys fall
    at different waves: tick intervals 20, 35 and 15 s, and the third row's
    traffic and horizon end at 120 s, so its loop ends long before the
    others'."""
    fl_t = fleet_params([0.9, 0.8, 0.95, 0.7], [2e-3, 1e-3, 5e-4, 3e-3],
                        jump_rate=[0.01, 0.02, 0.0, 0.005],
                        jump_scale=[0.05, 0.02, 0.0, 0.1])
    rows = []
    for interval, horizon, seed in ((20.0, 300.0, 3), (35.0, 300.0, 4),
                                    (15.0, 120.0, 5)):
        trig = dataclasses.replace(TRIG, interval_s=interval, max_retrains=6)
        wl = int_workload(rng, horizon=horizon)
        rows.append(compile_fleet(FleetSpec(params=fl_t), trig, wl, plat,
                                  horizon, seed=seed))
    return rows


@pytest.mark.parametrize("rows", [1, 3], ids=["single", "ensemble3"])
def test_fleet_stage_wave_parity(rng, rows):
    """Numpy and JAX engines agree wave-for-wave with the feedback stage
    enabled: same schedules, same drift timelines, same trigger/redeploy
    actions — including presampled observation noise and sudden drift.
    The JAX engine runs the stage only in waves with a drift tick or a
    finished retrain (``fleet_waves`` counts them). In a batch the stage
    runs where any still-running row needs it, so a row's count lies
    between its own need and the batch's summed need; rows whose loop has
    ended hold the gate shut."""
    plat = platform()
    if rows == 1:
        wl = int_workload(rng)
        fl_t = fleet_params([0.9, 0.8, 0.95, 0.7], [2e-3, 1e-3, 5e-4, 3e-3],
                            jump_rate=[0.01, 0.02, 0.0, 0.005],
                            jump_scale=[0.05, 0.02, 0.0, 0.1])
        cf, ext = compile_fleet(FleetSpec(params=fl_t), TRIG, wl, plat,
                                300.0, seed=3)
        t_np = des.simulate(ext, plat, scenario=None, fleet=cf)
        t_jx = vdes.simulate_to_trace(ext, plat, fleet=cf)
        assert_traces_match(t_np, t_jx, ext)
        assert (t_np.fleet_kind == des.FLEET_ACT_TRIGGER).sum() >= 2
        assert (t_np.fleet_kind == des.FLEET_ACT_REDEPLOY).sum() >= 1
        assert t_jx.fleet_waves == fleet_need_waves(t_np)
        return
    from repro.core import batching
    pairs = _ensemble_rows(rng, plat)
    exts = [ext for _, ext in pairs]
    cols = batching.pad_workloads(exts, plat)
    assert all(ext.n == cols["n_max"] for ext in exts)   # no padding rows
    out = vdes.simulate_ensemble(
        *(cols[k] for k in ("arrival", "n_tasks", "task_res", "service",
                            "priority")),
        np.tile(np.asarray(plat.capacities, np.int32), (rows, 1)),
        **batching.stack_fleets([cf for cf, _ in pairs], cols["n_max"]))
    out = {k: np.asarray(v) for k, v in out.items()}
    t_nps = [des.simulate(ext, plat, scenario=None, fleet=cf)
             for cf, ext in pairs]
    t_jxs = [batching.batch_trace(out, i, ext, plat.capacities,
                                  with_scenario=False, fleet=cf)
             for i, (cf, ext) in enumerate(pairs)]
    for (cf, ext), t_np, t_jx in zip(pairs, t_nps, t_jxs):
        assert_traces_match(t_np, t_jx, ext)
        assert (t_np.fleet_kind == des.FLEET_ACT_REDEPLOY).sum() >= 1
    waves = [t.waves for t in t_nps]
    assert waves[2] < min(waves[:2])          # one row ends early
    redeploys = [set(t.fleet_times[t.fleet_kind == des.FLEET_ACT_REDEPLOY])
                 for t in t_nps]
    assert redeploys[0] != redeploys[1]
    need = [fleet_need_waves(t) for t in t_nps]
    counts = [t.fleet_waves for t in t_jxs]
    for own, got in zip(need, counts):
        assert own <= got <= sum(need)
    assert max(need) <= max(counts) <= sum(need)
    # the rows' needs overlap only in part: the gate ran in more waves
    # than any one row needed
    assert max(counts) > max(need)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("fleet", [True, False], ids=["fleet", "no_fleet"])
def test_fleet_gate_is_a_conditional_under_vmap(rng, fleet):
    """Under ``simulate_ensemble``'s vmap the fleet stage sits behind a
    control-flow op with an unbatched (scalar) predicate, reduced over the
    rows with ``pmax``. A batched predicate would turn it into a select
    that runs the stage in every wave: still exact, but as slow as no gate.
    Without a fleet the wave loop holds no gate, no ``cond`` and no
    ``pmax``."""
    import jax
    from repro.core import batching
    plat = platform()
    pairs = _ensemble_rows(rng, plat)
    exts = [ext for _, ext in pairs]
    cols = batching.pad_workloads(exts, plat)
    kw = (batching.stack_fleets([cf for cf, _ in pairs], cols["n_max"])
          if fleet else {})
    closed = jax.make_jaxpr(lambda *a: vdes.simulate_ensemble(*a, **kw))(
        *(cols[k] for k in ("arrival", "n_tasks", "task_res", "service",
                            "priority")),
        np.tile(np.asarray(plat.capacities, np.int32), (len(pairs), 1)))
    loop = next(e for e in _eqns(closed.jaxpr) if e.primitive.name == "while")
    body = list(_eqns(loop.params["body_jaxpr"].jaxpr))
    names = [e.primitive.name for e in body]
    scalar_gates = [e for e in body if e.primitive.name == "while"
                    and e.params["cond_jaxpr"].out_avals[0].shape == ()]
    assert "cond" not in names
    if fleet:
        assert len(scalar_gates) == 1
        assert names.count("pmax") == 1
    else:
        assert scalar_gates == [] and "pmax" not in names


def test_fleet_stage_parity_under_failure_scenario(rng):
    """Fleet stage composes with failure/retry injection (attempts cover
    the retraining pipelines too) — parity holds."""
    from repro.ops import FailureModel, RetryPolicy
    wl = int_workload(rng, n=40)
    plat = platform()
    sc = Scenario(name="fail", failures=FailureModel(
        p_fail_by_type=(0.3,) * M.N_TASK_TYPES,
        retry=RetryPolicy(max_retries=2, base_s=4.0, mult=2.0, cap_s=16.0)))
    cf, ext = compile_fleet(FleetSpec(params=FLEET4), TRIG, wl, plat, 300.0,
                            seed=5)
    comp = sc.compile(ext, plat, 300.0, seed=5)
    t_np = des.simulate(ext, plat, scenario=comp, fleet=cf)
    t_jx = vdes.simulate_to_trace(ext, plat, scenario=comp, fleet=cf)
    assert_traces_match(t_np, t_jx, ext)


def test_fleet_stage_parity_with_controller(rng):
    """Fleet + closed-loop controller in the same wave loop: both in-engine
    actors stay parity-exact, and both appear on the action timeline."""
    wl = int_workload(rng, n=50)
    plat = platform(2, 2)
    sc = Scenario(name="ctrl", controller=ReactiveController(
        high_watermark=0.3, step=0.5, max_scale=4.0, interval_s=10.0))
    cf, ext = compile_fleet(FleetSpec(params=FLEET4), TRIG, wl, plat, 300.0,
                            seed=7)
    comp = sc.compile(ext, plat, 300.0, seed=7)
    t_np = des.simulate(ext, plat, scenario=comp, fleet=cf)
    t_jx = vdes.simulate_to_trace(ext, plat, scenario=comp, fleet=cf)
    assert_traces_match(t_np, t_jx, ext)
    assert np.allclose(t_np.ctrl_times, t_jx.ctrl_times)
    kinds = {k for k, _, _ in t_np.action_timeline()}
    assert {"scale", "trigger", "redeploy"} <= kinds


def test_action_timeline_shared_and_sorted(rng):
    wl = int_workload(rng)
    cf, ext = compile_fleet(FleetSpec(params=FLEET4), TRIG, wl, platform(),
                            300.0, seed=3)
    tr = des.simulate(ext, platform(), fleet=cf)
    tl = tr.action_timeline()
    assert len(tl) == tr.fleet_times.shape[0]
    times = [t for _, t, _ in tl]
    assert times == sorted(times)
    assert all(k in ("trigger", "redeploy") for k, _, _ in tl)


def test_latent_pool_rows_never_pollute_records(rng):
    """Unfired pool slots are invisible: records and summaries only see
    exogenous + activated retraining pipelines."""
    wl = int_workload(rng, n=30)
    spec = lifecycle_spec(wl, engine="numpy",
                          trigger=dataclasses.replace(
                              TRIG, drift_threshold=0.9))  # never fires
    res = run_experiment(spec)
    assert res.lifecycle.n_triggered == 0
    assert res.summary["n_pipelines"] == 30
    assert res.records.start.shape[0] == int(wl.n_tasks.sum())


def test_injection_budget_bounds_triggers(rng):
    wl = int_workload(rng, n=30)
    trig = dataclasses.replace(TRIG, max_retrains=2, cooldown_s=0.0)
    for engine in ("numpy", "jax"):
        res = run_experiment(lifecycle_spec(wl, engine=engine, trigger=trig))
        assert res.lifecycle.n_triggered == 2, engine


def test_drift_keeps_loop_alive_past_last_pipeline(rng):
    """Models keep drifting (and timelines keep recording) after every
    pipeline drained — the tick grid holds the wave loop open."""
    wl = int_workload(rng, n=5, horizon=20.0)   # drains long before t=300
    res = run_experiment(lifecycle_spec(wl, engine="numpy"))
    assert not np.isnan(res.lifecycle.perf_timeline).any()
    assert res.lifecycle.tick_times[-1] == pytest.approx(300.0)


# ------------------------------------------------ the batched grid

def test_trigger_fleet_sweep_lowers_to_one_call(rng):
    """Acceptance: a 16-point trigger/fleet lifecycle-policy grid lowers to
    exactly ONE jit+vmap simulate_ensemble call, and every point matches
    its own serial numpy run bit-for-bit (timelines, trigger and redeploy
    times) — wave-for-wave parity drift 0.0."""
    wl = int_workload(rng)
    base = lifecycle_spec(wl, engine="jax")
    calls = [0]
    orig = vdes.simulate_ensemble

    def counting(*a, **k):
        calls[0] += 1
        return orig(*a, **k)

    sw = Sweep(base, {"trigger:drift_threshold": [0.03, 0.05, 0.08, 0.2],
                      "trigger:cooldown_s": [40.0, 120.0],
                      "fleet:drift_scale": [1.0, 1.5]})
    points = sw.points()
    assert len(points) == 16
    assert len({p.name for p in points}) == 16
    vdes.simulate_ensemble = counting
    try:
        batched = sw.run()
    finally:
        vdes.simulate_ensemble = orig
    assert calls[0] == 1, "grid must lower to ONE simulate_ensemble call"
    serial = [run_experiment(p.with_(engine="numpy")) for p in points]
    for b, s in zip(batched, serial):
        assert b.summary["n_pipelines"] == s.summary["n_pipelines"]
        assert b.summary["n_triggered"] == s.summary["n_triggered"], \
            b.experiment.name
        assert b.summary["n_retrained"] == s.summary["n_retrained"]
        assert b.summary["mean_wait_s"] == pytest.approx(
            s.summary["mean_wait_s"], abs=1e-2), b.experiment.name
        assert np.array_equal(b.lifecycle.perf_timeline,
                              s.lifecycle.perf_timeline), b.experiment.name
        assert np.array_equal(b.lifecycle.trigger_times,
                              s.lifecycle.trigger_times)
        assert np.array_equal(b.lifecycle.redeploy_times,
                              s.lifecycle.redeploy_times)
        assert b.summary["mean_staleness"] == s.summary["mean_staleness"]


def test_mixed_fleet_and_plain_points_share_one_batch(rng):
    """A grid mixing fleet-less points with lifecycle points still lowers
    to one batch: the padding row disables the stage (trig interval 0) and
    the plain point stays bit-identical to a run with no fleet at all."""
    wl = int_workload(rng, n=40)
    base = ExperimentSpec(name="mix", platform=platform(), horizon_s=300.0,
                          workload=wl, engine="jax")
    sw = Sweep(base, {"fleet": [None, FleetSpec(params=FLEET4)],
                      "trigger": [TRIG]})
    batched = sw.run()
    assert batched[0].lifecycle is None
    assert batched[1].lifecycle.n_triggered >= 1
    serial = [run_experiment(p.with_(engine="numpy")) for p in sw.points()]
    assert batched[0].summary["n_pipelines"] == \
        serial[0].summary["n_pipelines"] == 40
    assert "lifecycle" not in batched[0].summary
    assert np.array_equal(batched[1].lifecycle.perf_timeline,
                          serial[1].lifecycle.perf_timeline)
    assert batched[0].summary["mean_wait_s"] == pytest.approx(
        serial[0].summary["mean_wait_s"], abs=1e-2)


def test_lifecycle_summary_block(rng):
    wl = int_workload(rng)
    res = run_experiment(lifecycle_spec(wl, engine="numpy"))
    lc = res.summary["lifecycle"]
    assert lc["n_models"] == 4
    assert lc["n_retrained"] <= lc["n_triggered"]
    assert 0.0 <= lc["mean_staleness"] <= 1.0
    assert lc["staleness_integral_s"] >= 0.0
    assert res.summary["mean_staleness"] == lc["mean_staleness"]
    # replica ensembles aggregate the lifecycle scalars
    res3 = run_experiment(dataclasses.replace(
        lifecycle_spec(wl, engine="jax"), n_replicas=3))
    assert "mean_staleness" in res3.summary
    assert res3.summary["n_replicas"] == 3


def test_trigger_axis_shorthand_creates_default_specs():
    spec = ExperimentSpec(name="s").with_(**{"trigger:drift_threshold": 0.5})
    assert spec.trigger.drift_threshold == 0.5
    assert spec.fleet is None
    spec = spec.with_(**{"fleet:n_models": 7})
    assert spec.fleet.n_models == 7


# ------------------------------------- reference wrapper vs in-engine

def test_wrapper_agrees_with_in_engine_jax():
    """run_feedback_simulation (numpy reference path) vs the same spec on
    the batched JAX path: identical trigger counts and redeploy times
    (seasonal off so the drift algebra stays bit-parity)."""
    from benchmarks.common import fitted_params
    params = fitted_params()
    fl = FleetSpec(params=fleet_params(
        [0.9, 0.85, 0.8, 0.92], [2e-5, 4e-5, 1e-5, 3e-5]))
    trig = TriggerSpec(drift_threshold=0.04, cooldown_s=12 * 3600.0,
                       obs_noise=0.005, interval_s=6 * 3600.0,
                       retrain_durations=(1800.0, 120.0, 60.0))
    kw = dict(seed=3, horizon_s=2 * 86400.0, n_models=4,
              window_s=6 * 3600.0, trigger=trig, fleet=fl)
    ref = run_feedback_simulation(params, **kw)
    fast = run_feedback_simulation(params, engine="jax", **kw)
    assert isinstance(ref, FeedbackResult)
    assert ref.n_triggered == fast.n_triggered
    assert ref.n_exogenous == fast.n_exogenous
    assert np.allclose(ref.retrain_times, fast.retrain_times, atol=0.5)
    assert np.allclose(ref.perf_timeline, fast.perf_timeline, atol=1e-5)


# ------------------------------------------------ retrain durations

def test_retrain_durations_drawn_from_fitted_distributions():
    """Satellite regression: each retraining pipeline gets its own draws
    from the per-task-type fitted distributions — no more max/min over one
    unrelated row, no verbatim replicate-concat."""
    import jax
    from benchmarks.common import fitted_params
    params = fitted_params()
    wl = synthesize_retrain_workload(params, jax.random.PRNGKey(0), 32,
                                     M.PlatformConfig(), 6)
    wl.validate()
    assert wl.n == 32
    assert (wl.n_tasks == 3).all()
    assert (wl.task_type[:, :3] == [M.TRAIN, M.EVALUATE, M.DEPLOY]).all()
    t_train = wl.exec_time[:, 0]
    assert (t_train > 0).all()
    # independent per-pipeline draws: the old bug replicated rows verbatim
    assert np.unique(np.round(t_train, 6)).shape[0] > 16
    assert np.unique(np.round(wl.exec_time[:, 1], 6)).shape[0] > 16
    assert np.unique(wl.model_size).shape[0] > 16


def test_compile_fleet_requires_duration_source(rng):
    wl = int_workload(rng, n=10)
    with pytest.raises(ValueError, match="retrain durations"):
        compile_fleet(FleetSpec(params=FLEET4),
                      TriggerSpec(interval_s=20.0, retrain_durations=None),
                      wl, platform(), 300.0)
    with pytest.raises(ValueError, match="exceeds the horizon"):
        compile_fleet(FleetSpec(params=FLEET4), TriggerSpec(), wl,
                      platform(), 300.0)
    # retraining pipelines have 3 tasks: narrow task tensors fail loudly
    # on BOTH duration paths (pinned template shown here)
    narrow = int_workload(rng, n=8, max_tasks=2)
    with pytest.raises(ValueError, match="max_tasks >= 3"):
        compile_fleet(FleetSpec(params=FLEET4), TRIG, narrow, platform(),
                      300.0)


def test_lifecycle_summary_rejects_fleetless_trace(rng):
    from repro.ops import lifecycle_summary
    wl = int_workload(rng, n=10)
    tr = des.simulate(wl, platform())
    with pytest.raises(ValueError, match="no fleet columns"):
        lifecycle_summary(tr)


def test_pipelines_per_s_excludes_latent_pool_rows(rng):
    """Throughput counts pipelines that entered the platform, not the
    preallocated (possibly never-activated) retraining pool."""
    wl = int_workload(rng, n=30)
    res = run_experiment(lifecycle_spec(
        wl, engine="numpy",
        trigger=dataclasses.replace(TRIG, drift_threshold=0.9)))
    assert res.summary["pipelines_per_s"] == pytest.approx(
        30 / res.summary["wall_s"], rel=1e-6)


# ------------------------------------------------ drift algebra props

def check_staleness_bounds(perf0, grad, jump, dt):
    fl = fleet_params([perf0], [grad])
    p = fleet_performance(np.float32([perf0]), np.float32([jump]),
                          np.float32(dt), fl)
    s = fleet_staleness(np.float32([perf0]), p)
    assert 0.0 <= float(p[0]) <= 1.0
    assert 0.0 <= float(s[0]) <= 1.0
    # acc formulation agrees with the closed form when acc = grad*dt + jump
    acc = np.float32(np.float32(grad) * np.float32(dt) + np.float32(jump))
    p2 = fleet_performance_acc(np.float32([perf0]), np.float32([acc]),
                               np.float32(dt), fl)
    assert float(p2[0]) == pytest.approx(float(p[0]), abs=1e-6)


def check_monotone_between_redeploys(perf0, grad, dts):
    fl = fleet_params([perf0], [grad])
    dts = np.sort(np.asarray(dts, np.float64))
    ps = [float(fleet_performance(np.float64(perf0), np.float64(0.0),
                                  dt, fl[0])) for dt in dts]
    assert all(a >= b - 1e-12 for a, b in zip(ps, ps[1:])), \
        "performance must be monotone nonincreasing between redeploys"


def test_drift_algebra_seeded_deterministic():
    r = np.random.default_rng(0)
    for _ in range(50):
        check_staleness_bounds(float(r.uniform(0.3, 0.995)),
                               float(r.uniform(0, 1e-3)),
                               float(r.uniform(0, 0.5)),
                               float(r.uniform(0, 1e6)))
        check_monotone_between_redeploys(float(r.uniform(0.3, 0.995)),
                                         float(r.uniform(0, 1e-4)),
                                         r.uniform(0, 1e6, 8))


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@settings(max_examples=60, deadline=None)
@given(perf0=st.floats(0.3, 0.995), grad=st.floats(0, 1e-3),
       jump=st.floats(0, 0.5), dt=st.floats(0, 1e6))
def test_staleness_in_unit_interval(perf0, grad, jump, dt):
    check_staleness_bounds(perf0, grad, jump, dt)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@settings(max_examples=40, deadline=None)
@given(perf0=st.floats(0.3, 0.995), grad=st.floats(0, 1e-4),
       dts=st.lists(st.floats(0, 1e6), min_size=2, max_size=8))
def test_performance_monotone_between_redeploys(perf0, grad, dts):
    check_monotone_between_redeploys(perf0, grad, dts)


def test_redeploy_resets_drift_state(rng):
    """After a retraining pipeline completes, the model's drift state
    resets: staleness at the first evaluation tick after the redeploy is
    exactly 0 (seasonal off), and performance is restored to the new
    perf0."""
    wl = int_workload(rng, n=30)
    res = run_experiment(lifecycle_spec(wl, engine="numpy"))
    lc = res.lifecycle
    assert lc.n_retrained >= 1
    ticks = lc.tick_times
    for t_r, m in zip(lc.redeploy_times, lc.redeploy_models):
        after = np.searchsorted(ticks, t_r)
        if after >= ticks.shape[0]:
            continue
        stale = lc.staleness_timeline[int(m), after]
        assert stale == 0.0, (t_r, m, stale)


def test_deployed_model_delegates_to_vectorized_algebra():
    m = DeployedModel(model_id=0, perf0=0.9, deployed_at=0.0,
                      gradual_rate=1e-7, jump_rate=0.0, jump_scale=0.0)
    fl = pack_fleet([m])
    assert fl.shape == (1, FLEET_FIELDS)
    t = 20 * 86400.0
    p_vec = float(np.ravel(fleet_performance(
        np.float64(m.perf0), np.float64(m.last_jumps), np.float64(t),
        fl.astype(np.float64)))[0])
    assert m.performance(t) == pytest.approx(p_vec, abs=1e-7)
    assert m.staleness(t) == pytest.approx(m.perf0 - m.performance(t),
                                           abs=1e-12)


def test_lifecycle_result_roundtrip(rng):
    wl = int_workload(rng)
    for engine in ("numpy", "jax"):
        res = run_experiment(lifecycle_spec(wl, engine=engine))
        lc = res.lifecycle
        assert lc is not None
        assert lc.perf_timeline.shape == (4, lc.tick_times.shape[0])
        assert lc.n_exogenous == wl.n
        assert lc.n_triggered == lc.trigger_times.shape[0]
        assert lc.n_retrained == lc.redeploy_times.shape[0]
        # scenario-less spec: no lifecycle -> None
        plain = run_experiment(ExperimentSpec(name="p", workload=wl,
                                              platform=platform(),
                                              horizon_s=300.0,
                                              engine=engine))
        assert plain.lifecycle is None
