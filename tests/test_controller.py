"""Closed-loop in-engine control plane (PR 3):

  - numpy-vs-JAX *wave-for-wave* parity for the ReactiveController on
    integer-time workloads, including cooldown boundaries, min/max clamp
    saturation, controller + maintenance-window composition, and
    capacity-to-zero stall/termination;
  - fused lax.sort(num_keys=3) admission ranking == the 3-argsort reference
    for all three policies and the traced policy_dyn path;
  - partial-progress failures (fail_holds_frac) with exact per-attempt
    busy_node_seconds accounting;
  - controller-gain grids as ONE batched ensemble / Sweep call.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import des, trace, vdes
from repro.core import model as M
from repro.core.des import CTRL_FIELDS, CTRL_HEADER
from repro.core.experiment import ExperimentSpec, Sweep, run_experiment
from repro.ops import (CompiledScenario, FailureModel, MaintenanceWindows,
                       ReactiveController, RetryPolicy, Scenario,
                       busy_node_seconds, disabled_controller,
                       static_schedule)
from test_des_engines import make_workload, platform

jnp_i32 = jnp.int32


@pytest.fixture()
def rng():
    """Module-local generator (suite order independence)."""
    return np.random.default_rng(20260901)


def int_workload(rng, n=120, horizon=400.0, **kw):
    return make_workload(rng, n, integer_time=True, horizon=horizon, **kw)


def _ctrl_scenario(wl, plat, controller, horizon=400.0, capacity=None,
                   failures=None):
    return Scenario(name="ctrl", controller=controller, capacity=capacity,
                    failures=failures).compile(wl, plat, horizon, seed=3)


def assert_wave_parity(wl, plat, policy, scenario):
    """Both engines agree on every timestamp AND on the wave count."""
    t_np = des.simulate(wl, plat, policy, scenario=scenario)
    t_jx = vdes.simulate_to_trace(wl, plat, policy, scenario=scenario)
    live = np.arange(wl.max_tasks)[None, :] < wl.n_tasks[:, None]
    for field in ("start", "finish", "ready"):
        a = np.where(live, getattr(t_np, field), 0.0)
        b = np.where(live, getattr(t_jx, field), 0.0)
        assert np.allclose(a, b, atol=1e-3, equal_nan=True), field
        assert (np.isnan(a) == np.isnan(b)).all(), field
    assert t_np.waves == t_jx.waves, "wave-level divergence"
    return t_np, t_jx


def _single_res_workload(n, svc, arrivals=None):
    return M.Workload(
        arrival=np.zeros(n) if arrivals is None
        else np.asarray(arrivals, np.float64),
        n_tasks=np.ones(n, np.int32),
        task_type=np.zeros((n, 1), np.int32),
        task_res=np.zeros((n, 1), np.int32),
        exec_time=np.full((n, 1), float(svc)),
        read_bytes=np.zeros((n, 1)), write_bytes=np.zeros((n, 1)),
        framework=np.zeros(n, np.int32), priority=np.zeros(n, np.float32),
        model_perf=np.zeros(n, np.float32), model_size=np.zeros(n, np.float32),
        model_clever=np.zeros(n, np.float32))


# ------------------------------------------------------- controller parity

@pytest.mark.parametrize("policy", [des.POLICY_FIFO, des.POLICY_SJF,
                                    des.POLICY_PRIORITY])
def test_controller_wave_parity_all_policies(rng, policy):
    wl = int_workload(rng)
    plat = platform(2, 2)
    comp = _ctrl_scenario(wl, plat, ReactiveController(
        high_watermark=0.5, low_watermark=0.05, step=0.25,
        min_scale=0.5, max_scale=4.0, interval_s=20.0))
    assert_wave_parity(wl, plat, policy, comp)


def test_controller_reacts_to_live_congestion(rng):
    """Closed loop beats open loop's blind spot: capacity actually rises
    above the static baseline and queueing drops."""
    wl = int_workload(rng, n=150, horizon=300.0)
    plat = platform(2, 2)
    comp = _ctrl_scenario(wl, plat, ReactiveController(
        high_watermark=0.5, step=0.5, max_scale=8.0, interval_s=10.0))
    t_ctrl, _ = assert_wave_parity(wl, plat, des.POLICY_FIFO, comp)
    t_static = des.simulate(wl, plat)
    live = np.arange(wl.max_tasks)[None, :] < wl.n_tasks[:, None]
    w_ctrl = np.nansum(np.where(live, t_ctrl.wait, 0))
    w_static = np.nansum(np.where(live, t_static.wait, 0))
    assert w_ctrl < w_static
    # some instant runs more jobs than the static capacity allows
    m = live & (t_ctrl.task_res == 0) & ~np.isnan(t_ctrl.start)
    starts, finishes = t_ctrl.start[m], t_ctrl.finish[m]
    peak = max(((starts <= t) & (finishes > t)).sum() for t in starts)
    assert peak > plat.capacities[0]


def test_controller_cooldown_boundary_hand_computed():
    """5 jobs x 100 s on one base slot, doubling controller every 10 s tick:
    with cooldown=25 the t=20/t=30 ticks are suppressed and the second
    scale-up lands exactly on the t=40 evaluation."""
    wl = _single_res_workload(5, 100.0)
    plat = M.PlatformConfig(resources=(M.ResourceConfig("s", 1),))
    mk = lambda cd: _ctrl_scenario(wl, plat, ReactiveController(
        high_watermark=0.4, low_watermark=-1.0, step=1.0, min_scale=1.0,
        max_scale=4.0, interval_s=10.0, cooldown_s=cd), horizon=1000.0)
    hot, _ = assert_wave_parity(wl, plat, des.POLICY_FIFO, mk(0.0))
    cool, _ = assert_wave_parity(wl, plat, des.POLICY_FIFO, mk(25.0))
    assert sorted(hot.start[:, 0].tolist()) == [0.0, 10.0, 20.0, 20.0, 100.0]
    assert sorted(cool.start[:, 0].tolist()) == [0.0, 10.0, 40.0, 40.0, 100.0]


def test_controller_max_clamp_saturation(rng):
    """Concurrency never exceeds round(max_scale * base) even under
    permanent congestion; saturated evaluations do not reset the cooldown."""
    wl = int_workload(rng, n=200, horizon=100.0)
    plat = platform(2, 2)
    comp = _ctrl_scenario(wl, plat, ReactiveController(
        high_watermark=0.1, step=1.0, max_scale=2.0, interval_s=5.0))
    t_np, _ = assert_wave_parity(wl, plat, des.POLICY_FIFO, comp)
    live = np.arange(wl.max_tasks)[None, :] < wl.n_tasks[:, None]
    for r in range(2):
        cap_max = round(plat.capacities[r] * 2.0)
        m = live & (t_np.task_res == r) & ~np.isnan(t_np.start)
        starts, finishes = t_np.start[m], t_np.finish[m]
        for t in starts:
            assert ((starts <= t) & (finishes > t)).sum() <= cap_max


def test_controller_capacity_to_zero_stall_terminates():
    """A scale-to-zero controller strands late arrivals; the finite
    evaluation grid keeps both engines terminating, in parity."""
    wl = _single_res_workload(2, 3.0, arrivals=[0.0, 50.0])
    plat = M.PlatformConfig(resources=(M.ResourceConfig("s", 1),))
    comp = _ctrl_scenario(wl, plat, ReactiveController(
        high_watermark=1e9, low_watermark=10.0, step=0.6, min_scale=0.0,
        max_scale=1.0, interval_s=5.0), horizon=100.0)
    t_np, t_jx = assert_wave_parity(wl, plat, des.POLICY_FIFO, comp)
    assert t_np.start[0, 0] == 0.0                 # ran before scale-down
    assert np.isnan(t_np.start[1, 0])              # stranded forever
    assert not t_np.completed[1] and not t_jx.completed[1]


def test_controller_composes_with_maintenance_schedule(rng):
    """Schedule = baseline, controller = delta: both active at once, with
    exact parity (the control stage applies schedule step then delta)."""
    wl = int_workload(rng)
    plat = platform(3, 2)
    comp = _ctrl_scenario(
        wl, plat,
        ReactiveController(high_watermark=0.3, step=0.5, max_scale=3.0,
                           interval_s=25.0),
        capacity=MaintenanceWindows(windows=((50.0, 150.0, 0, 1.0 / 3.0),)))
    assert comp.cap_times.shape[0] > 1             # window made it in
    assert_wave_parity(wl, plat, des.POLICY_FIFO, comp)


def test_controller_with_failures_and_retries(rng):
    wl = int_workload(rng, n=100)
    plat = platform(2, 2)
    comp = _ctrl_scenario(wl, plat, ReactiveController(
        high_watermark=0.5, step=0.25, max_scale=4.0, interval_s=20.0),
        failures=FailureModel(p_fail_by_type=(0.3,) * M.N_TASK_TYPES,
                              retry=RetryPolicy(max_retries=2, base_s=4.0,
                                                mult=2.0, cap_s=16.0)))
    t_np, t_jx = assert_wave_parity(wl, plat, des.POLICY_FIFO, comp)
    live = np.arange(wl.max_tasks)[None, :] < wl.n_tasks[:, None]
    assert (t_np.attempts[live] == t_jx.attempts[live]).all()


def test_controller_tensor_layout_and_inert_resources():
    ctrl = ReactiveController(resources=(1,), interval_s=60.0,
                              cooldown_s=120.0).compile(
                                  np.array([8, 4]), 3600.0)
    assert ctrl.shape == (CTRL_HEADER + CTRL_FIELDS * 2,)
    assert ctrl[0] == 60.0 and ctrl[1] == 120.0
    assert ctrl[2] == 60.0 and ctrl[3] == 3600.0
    # resource 0 uncontrolled: unreachable watermarks, zero step
    assert ctrl[CTRL_HEADER + 0] > 1e30 and ctrl[CTRL_HEADER + 2] == 0.0
    # resource 1 controlled: clamp bounds scale the base
    o = CTRL_HEADER + CTRL_FIELDS
    assert ctrl[o + 3] == 4 * 0.5 and ctrl[o + 4] == 4 * 2.0
    assert ctrl[o + 5] == 4.0
    with pytest.raises(ValueError):
        ReactiveController(interval_s=0.0).compile(np.array([1]), 10.0)
    assert (disabled_controller(2) == 0).all()


def test_controller_inert_row_matches_no_controller(rng):
    """An all-zero controller row must be byte-identical to running with no
    controller at all (the batched-padding invariant)."""
    wl = int_workload(rng, n=60)
    plat = platform()
    base = CompiledScenario(schedule=static_schedule(plat.capacities),
                            attempts=np.ones(wl.task_type.shape, np.int64))
    with_row = dataclasses.replace(base, controller=disabled_controller(2))
    for eng in (des.simulate, vdes.simulate_to_trace):
        a = eng(wl, plat, scenario=base)
        b = eng(wl, plat, scenario=with_row)
        assert np.array_equal(np.nan_to_num(a.start), np.nan_to_num(b.start))
        assert a.waves == b.waves


# ---------------------------------------------------- fused admission sort

def _rand_keys(rng, n, nres):
    res_q = jnp.asarray(rng.integers(0, nres + 1, n), jnp.int32)
    pkey = jnp.asarray(rng.integers(0, 4, n), jnp.float32)  # heavy ties
    wave = jnp.asarray(rng.integers(0, 6, n), jnp.int32)
    return res_q, pkey, wave


def test_fused_sort_equals_chained_reference(rng):
    for n in (1, 7, 64, 501):
        res_q, pkey, wave = _rand_keys(rng, n, 3)
        r_f, o_f = vdes.admission_order(res_q, pkey, wave)
        r_c, o_c = vdes.admission_order_chained(res_q, pkey, wave)
        assert np.array_equal(np.asarray(o_f), np.asarray(o_c)), n
        assert np.array_equal(np.asarray(r_f), np.asarray(r_c)), n


@pytest.mark.parametrize("policy", [des.POLICY_FIFO, des.POLICY_SJF,
                                    des.POLICY_PRIORITY])
def test_fused_sort_full_sim_equivalence(rng, policy):
    wl = int_workload(rng, n=150)
    plat = platform()
    v = vdes.VWorkload.from_workload(wl, plat)
    caps = jnp.asarray(plat.capacities, jnp.int32)
    rf = vdes.simulate(v, caps, policy, admission_sort="fused")
    rc = vdes.simulate(v, caps, policy, admission_sort="chained")
    for k in ("start", "finish", "ready"):
        assert np.array_equal(np.asarray(rf[k]), np.asarray(rc[k]),
                              equal_nan=True), k
    assert int(rf["waves"]) == int(rc["waves"])


@pytest.mark.parametrize("policy", [des.POLICY_FIFO, des.POLICY_SJF,
                                    des.POLICY_PRIORITY])
def test_select_full_sim_equivalence(rng, policy):
    """The default lex-min ranking replays the fused sort's schedule
    wave for wave, with retries and a capacity schedule in play."""
    wl = int_workload(rng, n=150)
    plat = platform()
    v = vdes.VWorkload.from_workload(wl, plat)
    v = dataclasses.replace(v, attempts=jnp.asarray(
        rng.integers(1, 3, np.shape(v.service)), jnp.int32))
    caps = jnp.asarray(plat.capacities, jnp.int32)
    kw = dict(cap_times=jnp.asarray([0.0, 200.0], jnp.float32),
              cap_vals=jnp.asarray([caps, caps + 2], jnp.int32),
              backoff=jnp.asarray([5.0, 2.0, 40.0], jnp.float32))
    rf = vdes.simulate(v, caps, policy, admission_sort="fused", **kw)
    rs = vdes.simulate(v, caps, policy, admission_sort="select", **kw)
    for k in ("start", "finish", "ready", "attempts"):
        assert np.array_equal(np.asarray(rf[k]), np.asarray(rs[k]),
                              equal_nan=True), k
    assert int(rf["waves"]) == int(rs["waves"])


def test_fused_sort_traced_policy_dyn_equivalence(rng):
    """The traced-policy path (vmapped heterogeneous schedulers) uses the
    same fused ranking."""
    wl = int_workload(rng, n=120)
    plat = platform()
    v = vdes.VWorkload.from_workload(wl, plat)
    caps = jnp.asarray(plat.capacities, jnp.int32)
    for pol in (des.POLICY_FIFO, des.POLICY_SJF, des.POLICY_PRIORITY):
        rf = vdes.simulate(v, caps, des.POLICY_FIFO,
                           policy_dyn=jnp.int32(pol), admission_sort="fused")
        rc = vdes.simulate(v, caps, des.POLICY_FIFO,
                           policy_dyn=jnp.int32(pol),
                           admission_sort="chained")
        rs = vdes.simulate(v, caps, pol)     # static-policy cross-check
        for k in ("start", "finish"):
            assert np.array_equal(np.asarray(rf[k]), np.asarray(rc[k]),
                                  equal_nan=True), (pol, k)
            assert np.array_equal(np.asarray(rf[k]), np.asarray(rs[k]),
                                  equal_nan=True), (pol, k)


def test_simulate_rejects_unknown_admission_sort(rng):
    wl = int_workload(rng, n=5)
    v = vdes.VWorkload.from_workload(wl, platform())
    with pytest.raises(ValueError, match="admission_sort"):
        vdes.simulate(v, jnp.asarray(platform().capacities, jnp.int32),
                      admission_sort="bogo")


# ------------------------------------------------- partial-progress failures

def test_fail_holds_frac_hand_computed():
    """One server, 2 attempts, svc 10, backoff 5, frac 0.5: the failing
    attempt holds [0, 5], re-queues at 10, succeeds [10, 20]; busy time is
    15 (not 20) in both engines."""
    wl = _single_res_workload(1, 10.0)
    plat = M.PlatformConfig(resources=(M.ResourceConfig("s", 1),))
    comp = CompiledScenario(schedule=static_schedule(plat.capacities),
                            attempts=np.full((1, 1), 2, np.int64),
                            backoff=(5.0, 2.0, 5.0), fail_holds_frac=0.5)
    for tr in (des.simulate(wl, plat, scenario=comp),
               vdes.simulate_to_trace(wl, plat, scenario=comp)):
        assert tr.finish[0, 0] == pytest.approx(20.0)
        assert tr.att_start[0, 0].tolist() == pytest.approx([0.0, 10.0])
        assert tr.att_finish[0, 0].tolist() == pytest.approx([5.0, 20.0])
        rec = trace.flatten_trace(tr, wl)
        assert busy_node_seconds(rec, 1)[0] == pytest.approx(15.0)


def test_fail_holds_frac_default_preserves_traces(rng):
    """frac = 1.0 must be bit-identical to the pre-PR-3 semantics."""
    wl = int_workload(rng, n=80)
    plat = platform()
    fm = FailureModel(p_fail_by_type=(0.3,) * M.N_TASK_TYPES,
                      retry=RetryPolicy(max_retries=2, base_s=4.0, mult=2.0,
                                        cap_s=16.0))
    attempts = fm.sample_attempts(np.random.default_rng(5), wl)
    base = CompiledScenario(schedule=static_schedule(plat.capacities),
                            attempts=attempts, backoff=fm.retry.backoff)
    assert base.fail_holds_frac == 1.0
    expl = dataclasses.replace(base, fail_holds_frac=1.0)
    for eng in (des.simulate, vdes.simulate_to_trace):
        a, b = eng(wl, plat, scenario=base), eng(wl, plat, scenario=expl)
        assert np.array_equal(np.nan_to_num(a.finish), np.nan_to_num(b.finish))


def test_fail_holds_frac_parity_and_accounting(rng):
    """Engines agree under frac = 0.5 and busy_node_seconds integrates the
    shortened failing-attempt windows exactly."""
    wl = int_workload(rng, n=100)
    plat = platform()
    sc = Scenario(failures=FailureModel(
        p_fail_by_type=(0.4,) * M.N_TASK_TYPES, fail_holds_frac=0.5,
        retry=RetryPolicy(max_retries=2, base_s=4.0, mult=2.0, cap_s=16.0)))
    comp = sc.compile(wl, plat, 400.0, seed=9)
    assert comp.fail_holds_frac == 0.5
    t_np, _ = assert_wave_parity(wl, plat, des.POLICY_FIFO, comp)
    rec = trace.flatten_trace(t_np, wl)
    busy = busy_node_seconds(rec, 2)
    live = np.arange(wl.max_tasks)[None, :] < wl.n_tasks[:, None]
    truth = np.zeros(2)
    for r in range(2):
        m = live & (t_np.task_res == r)
        truth[r] = np.nansum(t_np.att_finish[m] - t_np.att_start[m])
    assert np.allclose(busy, truth)
    # shortening holds must strictly reduce busy time vs full holds
    full = des.simulate(wl, plat, scenario=dataclasses.replace(
        comp, fail_holds_frac=1.0))
    rec_full = trace.flatten_trace(full, wl)
    assert busy_node_seconds(rec_full, 2).sum() > busy.sum()


# -------------------------------------------- batched grids in one call

def test_controller_ensemble_batches_per_replica(rng):
    """Per-replica ControllerParams rows in ONE jit+vmap call, each row
    matching its own single-replica numpy simulation."""
    R, n = 3, 60
    wl = int_workload(rng, n=n, horizon=300.0)
    plat = platform(2, 2)
    gains = [None,
             ReactiveController(high_watermark=0.3, step=0.5, max_scale=4.0,
                                interval_s=10.0),
             ReactiveController(high_watermark=1.0, step=0.25, max_scale=2.0,
                                interval_s=40.0, cooldown_s=80.0)]
    comps = [Scenario(name=f"g{i}", controller=g).compile(wl, plat, 300.0)
             for i, g in enumerate(gains)]
    from repro.core.batching import pad_workloads, stack_scenarios
    cols = pad_workloads([wl] * R, plat)
    cols.pop("n_max")
    scen_kw = stack_scenarios(comps, n, 300.0)
    assert scen_kw["controllers"].shape == (R, CTRL_HEADER + CTRL_FIELDS * 2)
    assert (scen_kw["controllers"][0] == 0).all()   # None -> disabled row
    caps = np.tile(plat.capacities[None], (R, 1)).astype(np.int32)
    out = vdes.simulate_ensemble(
        *[jnp.asarray(cols[k]) for k in ("arrival", "n_tasks", "task_res",
                                         "service", "priority")],
        jnp.asarray(caps), des.POLICY_FIFO, **scen_kw)
    live = np.arange(wl.max_tasks)[None, :] < wl.n_tasks[:, None]
    for r, comp in enumerate(comps):
        t_np = des.simulate(wl, plat, scenario=comp)
        assert np.allclose(np.where(live, t_np.start, 0),
                           np.where(live, np.asarray(out["start"][r]), 0),
                           atol=1e-3, equal_nan=True), f"replica {r}"
        assert t_np.waves == int(out["waves"][r]), f"replica {r} waves"


def test_controller_gain_grid_lowers_to_one_sweep_call(rng):
    """The acceptance grid: controller gains x capacities through Sweep on
    the JAX engine — one jit+vmap call — equals per-point numpy runs."""
    wl = int_workload(rng, n=60, horizon=300.0)
    base = ExperimentSpec(name="cg", platform=platform(), horizon_s=300.0,
                          engine="jax", workload=wl)
    axes = {"controller": [None,
                           ReactiveController(high_watermark=0.3, step=0.5,
                                              max_scale=4.0, interval_s=20.0),
                           ReactiveController(high_watermark=0.8, step=0.25,
                                              max_scale=2.0, interval_s=50.0)],
            "capacity:a": [2, 3]}
    sw = Sweep(base, axes)
    points = sw.points()
    assert len(points) == 6
    assert len({p.name for p in points}) == 6       # controller names label
    batched = sw.run()
    serial = [run_experiment(p.with_(engine="numpy")) for p in points]
    for b, s in zip(batched, serial):
        assert b.summary["mean_wait_s"] == pytest.approx(
            s.summary["mean_wait_s"], abs=1e-2), b.experiment.name
        assert b.summary["n_pipelines"] == s.summary["n_pipelines"]


def test_controller_axis_none_keeps_point_scenarioless():
    spec = ExperimentSpec(name="x").with_(controller=None)
    assert spec.scenario is None
    ctrl = ReactiveController()
    spec2 = ExperimentSpec(name="x").with_(controller=ctrl)
    assert spec2.scenario is not None
    assert spec2.scenario.controller is ctrl


def test_controller_axis_composes_regardless_of_kwarg_order():
    """controller= is applied after scenario=, so a scenario axis listed
    after the controller axis must not silently drop the controller."""
    ctrl = ReactiveController()
    sc = Scenario(name="fail", failures=FailureModel())
    a = ExperimentSpec(name="x").with_(controller=ctrl, scenario=sc)
    b = ExperimentSpec(name="x").with_(scenario=sc, controller=ctrl)
    for spec in (a, b):
        assert spec.scenario.controller is ctrl
        assert spec.scenario.failures is sc.failures


def test_controller_names_distinguish_all_gain_fields():
    """Sweep point names must not collide for controllers differing only in
    cooldown / clamp range / controlled-resource subset."""
    variants = [ReactiveController(),
                ReactiveController(cooldown_s=600.0),
                ReactiveController(max_scale=3.0),
                ReactiveController(min_scale=0.25),
                ReactiveController(resources=(1,))]
    names = {c.name for c in variants}
    assert len(names) == len(variants), names


def test_controller_interval_below_f32_ulp_rejected_and_guarded():
    """An interval below the f32 clock ulp at the horizon can never advance
    the tick grid: compile fails loudly, and a hand-built tensor hits the
    engines' exhaust-the-grid guard instead of spinning forever."""
    with pytest.raises(ValueError, match="ulp"):
        ReactiveController(interval_s=0.05).compile(
            np.array([1]), 30 * 86400.0)
    # hand-built tensor: first tick at 2^25 where the f32 ulp is 4 > 1
    wl = _single_res_workload(2, 1.0, arrivals=[0.0, 10.0])
    plat = M.PlatformConfig(resources=(M.ResourceConfig("s", 1),))
    ctrl = np.zeros(CTRL_HEADER + CTRL_FIELDS, np.float32)
    ctrl[0], ctrl[1], ctrl[2], ctrl[3] = 1.0, 0.0, 2.0 ** 25, 1.0e9
    ctrl[CTRL_HEADER:CTRL_HEADER + CTRL_FIELDS] = (1e9, -1e9, 0.0, 1.0,
                                                   1.0, 1.0)
    from repro.ops import normalize
    comp = CompiledScenario(         # cap drops to 0 -> job 1 strands
        schedule=normalize(np.array([0.0, 5.0]), np.array([[1], [0]])),
        attempts=np.ones((2, 1), np.int64), controller=ctrl)
    t_np, t_jx = assert_wave_parity(wl, plat, des.POLICY_FIFO, comp)
    assert np.isnan(t_np.start[1, 0]) and np.isnan(t_jx.start[1, 0])


def test_fail_holds_frac_validated():
    with pytest.raises(ValueError, match="fail_holds_frac"):
        FailureModel(fail_holds_frac=-0.5)
    with pytest.raises(ValueError, match="fail_holds_frac"):
        FailureModel(fail_holds_frac=0.0)
    with pytest.raises(ValueError, match="fail_holds_frac"):
        CompiledScenario(schedule=static_schedule(np.array([1])),
                         attempts=np.ones((1, 1), np.int64),
                         fail_holds_frac=1.5)
