"""The per-pipeline retry log that records every attempt of a retrying run.

The wave loop keeps a task's last executed attempt in ``start``/``finish``
and logs every earlier one of the pipeline in ``att_log_start``/
``att_log_finish [N, R]``, R the most non-final attempts any pipeline of
the batch requests; ``batching.attempt_records`` places both into the
``[N, T, A]`` per-attempt arrays on the host. Checked here:

- the host placement on a hand-built row;
- ``jax`` (one replica and a batched sweep), ``jax-compact`` and
  ``jax-stream`` against the numpy heap engine, bit for bit, on
  integer-time workloads whose attempts are set by hand: retries in tasks
  that are not adjacent, a pipeline that fills every slot of the log, a
  task stranded mid-retry when capacity drops to zero, failing attempts
  that hold their slot for a fraction of their service, resampled retry
  durations; and spot-eviction retries folded into the attempts (``jax``
  only: the other two engines reject reliability);
- a batch without retries carries no log and no offset, and no output of
  a retrying batch is a ``[B, N, T, A]`` array.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.harness import smoke_platform, smoke_workload
from repro.core import batching, compaction, des, trace, vdes
from repro.core import model as M
from repro.core.experiment import ExperimentSpec, Sweep, run_experiment
from repro.ops import CompiledScenario, normalize, static_schedule
from repro.reliability import ReliabilitySpec, SpotPoolSpec, TopologySpec
from repro.stream import stream_simulate
from test_des_engines import make_workload, platform

nan = np.nan


# ------------------------------------------------------- host placement

def test_attempt_records_places_the_log_and_the_final_attempts():
    """Pipeline 0: task 0 ran twice, task 1 once, task 2 three times (its
    log holds slots 0 and 1, 2); pipeline 1: task 0 ran once and its
    second task was stranded after one failed attempt (logged at slot 0),
    its third never ran."""
    attempts = np.array([[2, 1, 3], [1, 1, 0]])
    start = np.array([[10., 20., 50.], [1., 5., nan]])
    finish = np.array([[12., 25., 55.], [2., 6., nan]])
    log_start = np.array([[0., 30., 40.], [nan, 5., nan]])
    log_finish = np.array([[3., 33., 44.], [nan, 6., nan]])
    s, f = batching.attempt_records(start, finish, attempts, log_start,
                                    log_finish, 3)
    np.testing.assert_array_equal(
        s, [[[0, 10, nan], [20, nan, nan], [30, 40, 50]],
            [[1, nan, nan], [5, nan, nan], [nan, nan, nan]]])
    np.testing.assert_array_equal(
        f, [[[3, 12, nan], [25, nan, nan], [33, 44, 55]],
            [[2, nan, nan], [6, nan, nan], [nan, nan, nan]]])
    # an empty log: every executed attempt is final
    s, _ = batching.attempt_records(start[1:], finish[1:],
                                    np.array([[1, 1, 0]]), np.zeros((1, 0)),
                                    np.zeros((1, 0)), 2)
    np.testing.assert_array_equal(s, [[[1, nan], [5, nan], [nan, nan]]])


def test_retry_width_sums_a_pipelines_retries():
    att = np.array([[2, 1, 3], [4, 1, 1], [1, 1, 1]])
    assert batching.retry_width(att) == 3
    assert batching.retry_width(np.ones((2, 3))) == 0


# ------------------------------------------ engines against numpy, bit for bit

N, T = 24, 4
BUSY = 5          # the pipeline that fills every slot of the log


def _workload():
    wl = make_workload(np.random.default_rng(20261022), N, max_tasks=T,
                       integer_time=True, horizon=300.0)
    n_tasks = wl.n_tasks.copy()
    n_tasks[:8] = T                      # some pipelines run every task
    task_type = np.where(np.arange(T)[None, :] < n_tasks[:, None],
                         np.maximum(wl.task_type, 0), -1)
    exec_time = np.where(task_type >= 0, np.maximum(wl.exec_time, 3.0), 0.0)
    return dataclasses.replace(wl, n_tasks=n_tasks, task_type=task_type,
                               exec_time=exec_time,
                               task_res=wl.task_res * (task_type >= 0))


def _attempts(wl):
    """Hand-set: retries in tasks 0 and 2 (not 1) of pipelines 0-3, and
    pipeline BUSY retries most, so it alone fills the log."""
    att = np.ones((N, T), np.int64)
    att[:4, 0], att[:4, 2] = 2, 3
    att[BUSY] = [3, 2, 1, 4]
    att[10:14, 1] = 2
    return np.where(wl.task_type >= 0, att, 1)


def _case(name):
    wl = _workload()
    att = _attempts(wl)
    plat = platform()
    sched = static_schedule(plat.capacities)
    kw = {}
    if name == "stranded":
        # resource 1 goes to zero slots at t=150: retries still owed there
        # are never admitted
        sched = normalize(np.array([0.0, 150.0]), np.array([[3, 2], [3, 0]]))
    if name == "holds_frac":
        kw["fail_holds_frac"] = 0.5
    if name == "resampled":
        svc = wl.service_time(plat.datastore)
        A = int(att.max())
        extra = 1.0 + np.arange(A)[None, None, :]
        kw["attempt_service"] = np.ceil(svc[..., None] * extra)
    comp = CompiledScenario(schedule=sched, attempts=att,
                            backoff=(4.0, 2.0, 16.0), **kw)
    return wl, plat, comp


CASES = ["nonadjacent_and_full_log", "stranded", "holds_frac", "resampled"]


def _numpy(wl, plat, comp):
    tr = des.simulate(wl, plat, scenario=comp)
    assert tr.att_start is not None
    return tr


def _same(a, b, what):
    np.testing.assert_array_equal(np.asarray(a, np.float64),
                                  np.asarray(b, np.float64), err_msg=what)


def _check_case_shape(name, t_np, R):
    """The case exercises what it names."""
    att = t_np.attempts
    assert R == batching.retry_width(_attempts(_workload()))
    if name == "nonadjacent_and_full_log":
        assert np.isfinite(t_np.att_start[BUSY]).sum() == att[BUSY].sum()
        assert int(np.maximum(att[BUSY] - 1, 0).sum()) == R
    if name == "stranded":
        assert not t_np.completed.all()
        req = _attempts(_workload())
        assert ((att > 0) & (att < req)).any()     # stopped mid-retry


@pytest.mark.parametrize("name", CASES)
def test_jax_one_replica_equals_numpy(name):
    wl, plat, comp = _case(name)
    t_np = _numpy(wl, plat, comp)
    t_jx = vdes.simulate_to_trace(wl, plat, scenario=comp)
    _check_case_shape(name, t_np, t_jx.attempt_log_slots)
    for f in ("start", "finish", "attempts", "att_start", "att_finish"):
        _same(getattr(t_jx, f), getattr(t_np, f), f)
    assert t_jx.waves == t_np.waves


def _ensemble(wl, plat, comp, rows, engine):
    """``rows`` copies of the case, the first with every pipeline and the
    others cut short (so padding rows ride along), through one batched
    call of ``engine``: the ensemble or the compaction driver."""
    wls = [wl] + [dataclasses.replace(
        wl, **{f.name: getattr(wl, f.name)[:N - 6 * r]
               for f in dataclasses.fields(M.Workload)
               if isinstance(getattr(wl, f.name), np.ndarray)})
        for r in range(1, rows)]
    comps = [dataclasses.replace(
        comp, attempts=comp.attempts[:w.n],
        attempt_service=None if comp.attempt_service is None
        else comp.attempt_service[:w.n]) for w in wls]
    cols = batching.pad_workloads(wls, plat)
    n_max = cols.pop("n_max")
    kw = batching.stack_scenarios(
        comps, n_max, 300.0,
        services=[w.service_time(plat.datastore) for w in wls])
    args = [cols[k] for k in ("arrival", "n_tasks", "task_res", "service",
                              "priority")]
    caps = np.tile(plat.capacities, (rows, 1)).astype(np.int32)
    if engine == "jax":
        out = vdes.simulate_ensemble(*args, caps, **kw)
    else:
        out = compaction.simulate_ensemble_compacted(
            *args, caps, segment_waves=16, drain_waves=16, min_rows=4,
            lookahead=4, admission_sort="dense", **kw)
    out = {k: np.asarray(v) for k, v in out.items()}
    return [(w, c, batching.batch_trace(
        out, i, w, plat.capacities,
        n_attempts=batching.attempt_width(c)))
        for i, (w, c) in enumerate(zip(wls, comps))]


@pytest.mark.parametrize("engine", ["jax", "jax-compact"])
@pytest.mark.parametrize("name", CASES)
def test_batched_rows_equal_numpy(name, engine):
    wl, plat, comp = _case(name)
    for w, c, tr in _ensemble(wl, plat, comp, 3, engine):
        t_np = _numpy(w, plat, c)
        assert tr.attempt_log_slots == batching.retry_width(comp.attempts)
        assert tr.attempt_slots == t_np.att_start.shape[2]
        for f in ("start", "finish", "attempts", "att_start", "att_finish"):
            _same(getattr(tr, f), getattr(t_np, f), f"{engine} row {w.n} {f}")


class _Blocks:
    """A pinned workload served as arrival-ordered blocks."""
    name = "attempt-log"

    def __init__(self, wl, block=7):
        self.wl, self.block = wl, block

    def blocks(self):
        for lo in range(0, self.wl.n, self.block):
            yield dataclasses.replace(self.wl, **{
                f.name: getattr(self.wl, f.name)[lo:lo + self.block]
                for f in dataclasses.fields(M.Workload)
                if isinstance(getattr(self.wl, f.name), np.ndarray)})


def _sorted(rec):
    o = np.lexsort((rec.task_pos, rec.pipeline))
    return {f: np.asarray(getattr(rec, f))[o]
            for f in ("pipeline", "task_pos", "start", "finish", "attempts",
                      "att_start", "att_finish")}


@pytest.mark.parametrize("name", CASES)
def test_stream_records_equal_numpy(name):
    wl, plat, comp = _case(name)
    t_np = _numpy(wl, plat, comp)
    want = _sorted(trace.flatten_trace(t_np, wl))
    sr = stream_simulate(_Blocks(wl), plat, scenario=comp, horizon_s=300.0,
                         window_s=60.0, min_rows=8)
    assert sr.n_windows > 1
    # the windows draw attempts block by block: the log takes the most
    # any pipeline can request
    assert sr.attempt_log_slots == (batching.attempt_width(comp) - 1) * T
    got = _sorted(sr.records)
    for f, v in want.items():
        _same(got[f], v, f)


def _spot_spec(engine):
    return ExperimentSpec(
        name="spot", platform=smoke_platform(), horizon_s=300.0,
        workload=smoke_workload(), engine=engine,
        reliability=ReliabilitySpec(
            topology=TopologySpec(zones=1, racks_per_zone=2),
            spot=SpotPoolSpec(frac=0.5, evict_mtbe_s=40.0, reclaim_s=20.0),
            time_quantum_s=1.0))


def test_spot_eviction_retries_equal_numpy():
    """Eviction retries fold into the attempts before the engine runs:
    one replica and a two-point batch, against numpy."""
    one = run_experiment(_spot_spec("jax")).trace
    ref = run_experiment(_spot_spec("numpy")).trace
    assert int(np.maximum(ref.attempts - 1, 0).sum()) > 0
    assert one.attempt_log_slots >= 1
    for f in ("start", "finish", "attempts", "att_start", "att_finish"):
        _same(getattr(one, f), getattr(ref, f), f)
    axes = {"capacity:a": [3, 4]}
    got = Sweep(_spot_spec("jax"), axes).run()
    want = Sweep(_spot_spec("numpy"), axes).run()
    for a, b in zip(got, want):
        for f in ("att_start", "att_finish"):
            _same(getattr(a.trace, f), getattr(b.trace, f), f)


# ------------------------------------------------- what the loop carries

def _inputs(attempts, attempt_service=None):
    wl = smoke_workload(n=12)
    plat = smoke_platform()
    comp = CompiledScenario(schedule=static_schedule(plat.capacities),
                            attempts=attempts, backoff=(4.0, 2.0, 16.0),
                            attempt_service=attempt_service)
    cols = batching.pad_workloads([wl, wl], plat)
    n_max = cols.pop("n_max")
    kw = batching.stack_scenarios([comp, comp], n_max, 300.0)
    args = [cols[k] for k in ("arrival", "n_tasks", "task_res", "service",
                              "priority")]
    return wl, args, np.tile(plat.capacities, (2, 1)).astype(np.int32), kw


def test_a_batch_without_retries_carries_no_log():
    """Resampled slots make the records two wide, yet nothing retries: the
    loop carries no log and no offset, and the logs come back empty."""
    wl = smoke_workload(n=12)
    svc = wl.service_time(smoke_platform().datastore)
    _, args, caps, kw = _inputs(np.ones((12, wl.max_tasks), np.int64),
                                np.repeat(svc[..., None], 2, -1))
    assert kw["n_retry_slots"] == 0
    out = vdes.simulate_ensemble(*args, caps, **kw, return_state=True)
    assert not {"att_log_start", "att_log_finish", "rbase"} & set(
        out["state"])
    assert out["att_log_start"].shape == (2, 12, 0)
    # and a batch with no per-attempt width asks for no record at all
    _, args, caps, kw = _inputs(np.ones((12, wl.max_tasks), np.int64))
    assert "n_retry_slots" not in kw
    out = vdes.simulate_ensemble(*args, caps, **kw, return_state=True)
    assert "att_log_start" not in out and "rbase" not in out["state"]


def test_a_retrying_batch_holds_no_per_attempt_array():
    wl = smoke_workload(n=12)
    att = np.where(wl.task_type >= 0, 1 + np.arange(12)[:, None] % 3, 1)
    _, args, caps, kw = _inputs(att)
    R = kw["n_retry_slots"]
    assert R == batching.retry_width(att) > 0
    shapes = jax.eval_shape(
        lambda *a: vdes.simulate_ensemble(*a, **kw, return_state=True),
        *[jnp.asarray(x) for x in args], jnp.asarray(caps))
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    assert not [p for p, s in leaves if len(s.shape) == 4], \
        "a [B, N, T, A] array is back in the outputs or the carry"
    assert shapes["att_log_start"].shape == (2, 12, R)
    assert shapes["state"]["rbase"].shape == (2, 12)
