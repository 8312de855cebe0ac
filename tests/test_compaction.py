"""Active-set compaction + fused admission kernel (PR 8).

  - admission-round parity properties: the pallas kernel (interpret mode
    on CPU), the fused ``lax.sort`` ranking, the chained-argsort reference
    and the sort-free dense mask all produce the SAME admitted set as a
    straightforward numpy reference, on random rounds with heavy ties —
    and the fused/chained permutations agree element-for-element
    (stability);
  - seeded twin tests: the windowed compaction driver
    (:func:`repro.core.compaction.simulate_ensemble_compacted`) is
    bit-identical to the uncompacted ``vdes.simulate_ensemble`` — tensor
    level across policies (static, mixed ``policies`` rows) and small
    segment budgets that force many boundaries, and engine level across a
    full-stack Sweep (controller + failures/retries + fleet/trigger +
    probe) where every timeline/summary key except the wall-derived ones
    must match exactly;
  - the driver terminates (and twins) on starved runs the engine halts
    with QUEUED rows — the liveness rule must not spin on them;
  - the ``time_budget`` guard is a consistent cut: a guarded run resumed
    to completion equals the single-shot run bit-for-bit.
"""
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import batching, compaction, des, vdes
from repro.core import model as M
from repro.core.experiment import ExperimentSpec, Sweep, run_experiment
from repro.core.metrics import FLEET_FIELDS
from repro.core.runtime import FleetSpec, TriggerSpec
from repro.kernels.queue_scan import fused_admission
from repro.obs import ProbeSpec
from repro.ops import FailureModel, ReactiveController, RetryPolicy, Scenario
from test_des_engines import make_workload, platform


@pytest.fixture()
def rng():
    return np.random.default_rng(20260807)


# ------------------------------------------------ admission-round parity

def _admitted_ref(res_q, pkey, wave, free):
    """Numpy reference: stable lexicographic rank by (resource, policy key,
    enqueue wave, pipeline id); seat = position within the resource
    segment; admitted = seat < free[res]. Sentinel rows (res == nres)
    never admit."""
    nres = len(free)
    n = len(res_q)
    order = np.lexsort((np.arange(n), wave, pkey, res_q))
    admitted = np.zeros(n, bool)
    count = np.zeros(nres + 1, np.int64)
    for idx in order:
        r = int(res_q[idx])
        if r < nres and count[r] < free[r]:
            admitted[idx] = True
        count[r] += 1
    return admitted


def _sorted_seat_admit(rank_fn, res_q, pkey, wave, free):
    """The engine's seat computation applied to a ranking function's
    output (mirrors ``vdes._admission_stage``'s fused/chained branch)."""
    r_s, o = rank_fn(np.asarray(res_q), np.asarray(pkey), np.asarray(wave))
    r_s, o = np.asarray(r_s), np.asarray(o)
    n = len(r_s)
    pos = np.arange(n)
    is_start = np.r_[True, r_s[1:] != r_s[:-1]]
    seg_start = np.maximum.accumulate(np.where(is_start, pos, -1))
    seat = pos - seg_start
    free_ext = np.r_[free, 0]
    admitted = np.zeros(n, bool)
    admitted[o] = seat < free_ext[r_s]
    return admitted


def _round_case(seed, n):
    """One random admission round with heavy ties in every key."""
    g = np.random.default_rng(seed)
    nres = int(g.integers(1, 4))
    res_q = g.integers(0, nres + 1, n).astype(np.int32)   # incl. sentinel
    pkey = g.integers(0, 3, n).astype(np.float32)          # f32 tie groups
    wave = g.integers(0, 4, n).astype(np.int32)
    free = g.integers(0, max(2, n // 2), nres).astype(np.int32)
    return res_q, pkey, wave, free


def _assert_all_paths_agree(res_q, pkey, wave, free):
    ref = _admitted_ref(res_q, pkey, wave, free)
    a_fused = _sorted_seat_admit(vdes.admission_order,
                                 res_q, pkey, wave, free)
    a_chain = _sorted_seat_admit(vdes.admission_order_chained,
                                 res_q, pkey, wave, free)
    a_dense = np.asarray(vdes.admission_mask_dense(
        res_q, pkey, wave, free))
    a_select = np.asarray(vdes.admission_mask_select(
        res_q, pkey, wave, free))
    a_pallas = np.asarray(fused_admission(res_q, pkey, wave, free))
    assert np.array_equal(a_select, ref)
    assert np.array_equal(a_fused, ref)
    assert np.array_equal(a_chain, ref)
    assert np.array_equal(a_dense, ref)
    assert np.array_equal(a_pallas, ref)
    # stability: the two sort-based paths agree on the full permutation,
    # not just on the admitted set
    _, o_f = vdes.admission_order(res_q, pkey, wave)
    _, o_c = vdes.admission_order_chained(res_q, pkey, wave)
    assert np.array_equal(np.asarray(o_f), np.asarray(o_c))


def test_admission_paths_agree_seeded():
    """Deterministic sweep of the property (runs with or without
    hypothesis installed)."""
    for seed in range(12):
        for n in (1, 2, 17, 64, 130, 200):
            _assert_all_paths_agree(*_round_case(seed, n))


def test_admission_fifo_skip_pkey_identical():
    """The static-FIFO fast path (pkey compares dropped) is bit-identical
    when every pkey is equal."""
    for seed in range(6):
        res_q, _, wave, free = _round_case(seed, 80)
        pkey = np.zeros(80, np.float32)
        full = np.asarray(vdes.admission_mask_dense(res_q, pkey, wave, free))
        fast = np.asarray(vdes.admission_mask_dense(res_q, pkey, wave, free,
                                                    skip_pkey=True))
        assert np.array_equal(full, fast)
        sel = np.asarray(vdes.admission_mask_select(res_q, pkey, wave, free,
                                                    skip_pkey=True))
        assert np.array_equal(full, sel)


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**32 - 1), n=st.integers(1, 250))
def test_admission_paths_agree_property(seed, n):
    """pallas(interpret) == fused lax.sort == chained argsorts == dense
    mask == lex-min select == numpy reference on arbitrary admission
    rounds."""
    _assert_all_paths_agree(*_round_case(seed, n))


# ------------------------------------------------------ tensor-level twins

def _ensemble_args(rng, R=3, n=50, nres=2, caps=(3, 2)):
    plat = platform(*caps) if nres == 2 else platform()
    wls = [make_workload(rng, n - 3 * i, nres=nres, integer_time=True,
                         horizon=400.0) for i in range(R)]
    cols = batching.pad_workloads(wls, plat)
    cols.pop("n_max")
    capacities = np.tile(np.asarray(plat.capacities, np.int32)[None], (R, 1))
    return cols, capacities


def _assert_twin(out_a, out_b):
    for k in out_b:
        assert np.array_equal(np.asarray(out_a[k]), np.asarray(out_b[k]),
                              equal_nan=True), k


@pytest.mark.parametrize("policy", [des.POLICY_FIFO, des.POLICY_SJF])
def test_compacted_twin_static_policy(rng, policy):
    cols, caps = _ensemble_args(rng)
    out_a = vdes.simulate_ensemble(
        cols["arrival"], cols["n_tasks"], cols["task_res"], cols["service"],
        cols["priority"], caps, policy, admission_sort="dense")
    # tiny budgets/windows force many boundaries and width changes
    out_b = compaction.simulate_ensemble_compacted(
        cols["arrival"], cols["n_tasks"], cols["task_res"], cols["service"],
        cols["priority"], caps, policy, admission_sort="dense",
        segment_waves=17, drain_waves=9, min_rows=4, lookahead=5)
    _assert_twin(out_a, out_b)


def test_compacted_twin_mixed_policies(rng):
    """Per-replica ``policies`` rows ride the traced policy_dyn path."""
    cols, caps = _ensemble_args(rng)
    pol = np.asarray([des.POLICY_FIFO, des.POLICY_SJF, des.POLICY_PRIORITY],
                     np.int32)
    out_a = vdes.simulate_ensemble(
        cols["arrival"], cols["n_tasks"], cols["task_res"], cols["service"],
        cols["priority"], caps, policies=pol, admission_sort="fused")
    out_b = compaction.simulate_ensemble_compacted(
        cols["arrival"], cols["n_tasks"], cols["task_res"], cols["service"],
        cols["priority"], caps, policies=pol, admission_sort="fused",
        segment_waves=23, drain_waves=23, min_rows=4, lookahead=7)
    _assert_twin(out_a, out_b)


def test_compacted_twin_starved_capacity(rng):
    """A zero-capacity resource leaves QUEUED rows forever: the engine
    halts over them (t* = inf) and the driver must terminate with the
    identical final state instead of spinning on the dead replicas."""
    cols, caps = _ensemble_args(rng, caps=(3, 2))
    caps = caps.copy()
    caps[:, 1] = 0                        # starve resource "b" everywhere
    out_a = vdes.simulate_ensemble(
        cols["arrival"], cols["n_tasks"], cols["task_res"], cols["service"],
        cols["priority"], caps, des.POLICY_FIFO, admission_sort="dense")
    log = compaction.CompactionLog()
    out_b = compaction.simulate_ensemble_compacted(
        cols["arrival"], cols["n_tasks"], cols["task_res"], cols["service"],
        cols["priority"], caps, des.POLICY_FIFO, admission_sort="dense",
        segment_waves=16, drain_waves=16, min_rows=4, lookahead=4, log=log)
    _assert_twin(out_a, out_b)
    assert not bool(out_b["done"].all()), "starvation must leave work undone"
    assert log.n_compactions >= 1


def test_compaction_log_records_schedule(rng):
    cols, caps = _ensemble_args(rng, R=2)
    log = compaction.CompactionLog()
    compaction.simulate_ensemble_compacted(
        cols["arrival"], cols["n_tasks"], cols["task_res"], cols["service"],
        cols["priority"], caps, des.POLICY_FIFO, admission_sort="dense",
        segment_waves=16, drain_waves=16, min_rows=4, lookahead=4, log=log)
    assert log.n_compactions >= 1
    assert log.n_segments == log.n_compactions + 1     # + the init segment
    assert len(log.shapes) == log.n_segments
    assert log.shapes[0] == cols["arrival"].shape      # full-width init
    # windowed widths never exceed the allocation, and the live-width
    # timeline is recorded per boundary
    assert all(w <= cols["arrival"].shape[1] for _, w in log.shapes[1:])
    assert len(log.live_rows) == log.n_compactions
    assert 1 <= log.distinct_shapes <= log.n_segments


def test_time_budget_is_consistent_cut(rng):
    """Stopping at a time guard and resuming equals the single-shot run."""
    cols, caps = _ensemble_args(rng, R=2)
    args = (cols["arrival"], cols["n_tasks"], cols["task_res"],
            cols["service"], cols["priority"], caps)
    full = vdes.simulate_ensemble(*args, des.POLICY_FIFO,
                                  admission_sort="dense")
    guard = np.full(2, float(np.median(cols["arrival"])), np.float32)
    part = vdes.simulate_ensemble(*args, des.POLICY_FIFO,
                                  admission_sort="dense",
                                  time_budget=guard, return_state=True)
    assert np.all(np.asarray(part["state"]["wave"])
                  <= np.asarray(full["waves"]))
    rest = vdes.simulate_ensemble(*args, des.POLICY_FIFO,
                                  admission_sort="dense",
                                  resume=part["state"])
    for k in ("start", "finish", "ready", "attempts", "done", "waves"):
        assert np.array_equal(np.asarray(rest[k]), np.asarray(full[k]),
                              equal_nan=True), k


# ------------------------------------------------------ engine-level twins

def fleet_tensor():
    fl = np.zeros((3, FLEET_FIELDS), np.float32)
    fl[:, 0] = [0.9, 0.8, 0.95]
    fl[:, 1] = [2e-3, 1e-3, 5e-4]
    fl[:, 5] = 7 * 24 * 3600.0
    return fl


TRIG = TriggerSpec(drift_threshold=0.05, cooldown_s=60.0, obs_noise=0.01,
                   interval_s=20.0, retrain_durations=(40.0, 5.0, 15.0))
CTRL = ReactiveController(high_watermark=0.3, step=0.5, max_scale=4.0,
                          interval_s=10.0)

#: summary keys legitimately derived from the wall clock (or from the
#: compaction driver itself) — everything else must twin exactly
WALL_DERIVED = {"wall_s", "waves_per_s", "pipelines_per_s",
                "n_compactions", "compaction_segments"}


def _assert_summaries_twin(sa, sb):
    assert set(sa) - WALL_DERIVED == set(sb) - WALL_DERIVED

    def eq(a, b, key):
        if isinstance(a, dict):
            assert set(a) == set(b), key
            for k in a:
                eq(a[k], b[k], f"{key}.{k}")
        else:
            assert np.array_equal(np.asarray(a, dtype=np.float64),
                                  np.asarray(b, dtype=np.float64),
                                  equal_nan=True), key

    for k in set(sa) - WALL_DERIVED:
        eq(sa[k], sb[k], k)


def test_engine_twin_full_stack_sweep(rng):
    """jax vs jax-compact across a mixed full-stack grid: controller +
    failures/retries + fleet/trigger lifecycle + probe timelines. Every
    physics output — task records, probe timelines, summaries — must be
    bit-identical; only wall-derived keys may differ."""
    wl = make_workload(rng, 50, integer_time=True, horizon=300.0)
    sc = Scenario(
        name="fs", controller=CTRL,
        failures=FailureModel(
            p_fail_by_type=(0.3,) * M.N_TASK_TYPES,
            retry=RetryPolicy(max_retries=2, base_s=4.0, mult=2.0,
                              cap_s=16.0)))
    base = ExperimentSpec(name="twin", platform=platform(), horizon_s=300.0,
                          workload=wl, engine="jax", scenario=sc,
                          probe=ProbeSpec(interval_s=40.0),
                          fleet=FleetSpec(params=fleet_tensor()),
                          trigger=TRIG)
    axes = {"capacity:a": [3, 4], "policy": [des.POLICY_FIFO, des.POLICY_SJF]}
    res_a = Sweep(base, axes).run()
    res_b = Sweep(base.with_(engine="jax-compact"), axes).run()
    assert len(res_a) == len(res_b) == 4
    for a, b in zip(res_a, res_b):
        assert np.array_equal(a.records.start, b.records.start,
                              equal_nan=True)
        assert np.array_equal(a.records.finish, b.records.finish,
                              equal_nan=True)
        assert np.array_equal(a.timeline.times, b.timeline.times)
        assert np.array_equal(a.timeline.values, b.timeline.values,
                              equal_nan=True)
        _assert_summaries_twin(a.summary, b.summary)
        # the driver annotates its work on the compacted side only
        assert b.summary["n_compactions"] >= 0
        assert b.summary["compaction_segments"] >= 1


def test_compact_engine_single_run_matches_numpy(rng):
    """jax-compact through the single-spec path twins the serial numpy
    engine's schedule (transitively: numpy == jax == jax-compact)."""
    wl = make_workload(rng, 40, integer_time=True, horizon=300.0)
    spec = ExperimentSpec(name="one", platform=platform(), horizon_s=300.0,
                          workload=wl, engine="jax-compact")
    res_b = run_experiment(spec)
    res_np = run_experiment(spec.with_(engine="numpy"))
    assert np.array_equal(res_np.records.start, res_b.records.start,
                          equal_nan=True)
    assert np.array_equal(res_np.records.finish, res_b.records.finish,
                          equal_nan=True)
