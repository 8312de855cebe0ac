"""Windowed streaming simulation driver.

``stream_simulate`` runs an arrival-ordered task stream (any
:class:`repro.stream.sources.TraceSource`) through the batched JAX engine
in *horizon windows*: ingest every row arriving up to the next boundary,
run the wave loop with the boundary as the engine's ``time_budget`` (PR 8's
windowed-cut hook — the loop provably stops before any wave past the
guard), download the carry, retire DONE pipelines out of the working set,
append the next window's rows, and resume. The working set is therefore
sized by the *live* backlog, not the stream length: memory stays bounded
at millions of tasks while the queue/controller/fleet/probe state — every
scalar, tick cursor and recording buffer — rides the engine's resume carry
verbatim across each boundary.

Bit-parity argument (twin-tested in ``tests/test_stream.py`` and gated at
0.0 drift in ``benchmarks/stream_bench.py``):

  - a row absent from window ``k`` has ``float32(arrival) > boundary_k``
    (the ingestion buffer cuts on the same f32 cast as the engine clock),
    and the loop stops before any wave with ``t_star > boundary_k`` — so
    introducing the row in window ``k+1`` is invisible to every wave it
    could have touched;
  - retired rows are DONE (inert forever; their records are extracted at
    retirement);
  - the working layout is ``[retained exo rows | new exo rows | retraining
    pool | padding]`` with retained/new rows each in ascending global-id
    order and every new id greater than every retained id: all pairwise
    row orders match the one-shot layout, so the admission tie-break
    (a relative-order sort) decides identically, and the pool block stays
    contiguous at a per-window ``pool_base``;
  - fresh rows enter with exactly the engine's own initial per-row state
    (NOT_ARRIVED, ``t_next = f32(arrival)``, NaN time tensors), and
    padding rows carry ``arrival = inf``: they never arrive, never count
    as a pending event, and — exactly like latent retraining-pool rows —
    do not keep the wave loop alive, so the drain window exits at the
    same instant the one-shot run does (tail controller ticks included).

Synthesis for window ``k+1`` (block draws + per-block failure compiles +
host staging) overlaps window ``k``'s device step when ``overlap=True``;
the constant pool/pad blocks are device-resident from window 0.

``oneshot_reference`` materializes the SAME stream — identical per-block
RNG draws, identical pool/fleet/probe compiles — into one
``vdes.simulate_ensemble`` call: the parity oracle, and the fixed-horizon
baseline the benchmarks compare sustained tasks/s against.
"""
from __future__ import annotations

import dataclasses
import time
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

import jax

from repro.core import model as M
from repro.core import trace, vdes
from repro.core.batching import (batch_trace, stack_fleets, stack_probes,
                                 stack_scenarios)
from repro.core.compaction import ROW_STATE_KEYS, _bucket
from repro.core.des import (CTRL_INF, POLICY_FIFO, ctrl_tick_bound,
                            unpack_ctrl_actions, unpack_fleet_actions)
from repro.stream.sources import TraceSource, WorkloadManager

_DONE = 3            # vdes._DONE
_POSITIONAL = ("arrival", "n_tasks", "task_res", "service", "priority",
               "capacities")

#: host-side content columns kept per live row (what record extraction and
#: the next window's input tensors are assembled from)
_CONTENT = ("gid", "arrival", "n_tasks", "task_type", "task_res", "service",
            "read_bytes", "write_bytes", "framework", "priority", "attempts")


def _block_seed(seed: int, block_idx: int) -> int:
    """Per-block failure-draw seed — the streamed and one-shot paths MUST
    fold identically for attempts/attempt_service parity."""
    return int(seed) + 7919 * int(block_idx)

_POOL_SALT = 0x9E37    # pool rows compile as their own pseudo-block


@dataclasses.dataclass
class StreamResult:
    """What a streamed run produces. ``records`` is None when a ``sink``
    consumed them incrementally (unbounded runs); the operational
    timelines (controller actions, fleet tensors, probe matrix) come from
    the final carry — the recording buffers ride every boundary verbatim,
    so they are exactly the one-shot run's."""

    records: Optional[trace.TaskRecords]
    summary: Dict
    n_windows: int
    n_blocks: int
    n_pipelines: int            # exogenous pipelines ingested
    n_task_rows: int            # task records emitted (incl. retraining)
    waves: int
    peak_rows: int              # bounded working width (memory proxy)
    peak_live: int              # largest live (unretired) row count
    wall_s: float
    ingest_s: float             # host-side synthesis + failure-draw time
    ctrl_times: Optional[np.ndarray] = None
    ctrl_caps: Optional[np.ndarray] = None
    fleet_cols: Optional[Dict] = None
    probe_times: Optional[np.ndarray] = None
    probe_vals: Optional[np.ndarray] = None


class _StreamPlan:
    """Everything shared between the windowed driver and the one-shot
    reference: the schedule/controller/backoff resolution, the per-block
    failure compiles (same folded seeds), the fleet/pool/probe compiles,
    and the static engine arguments. One plan, two executions — the basis
    of the parity gate."""

    def __init__(self, platform, policy, scenario, fleet, trigger, probe,
                 horizon_s, seed, params, admission_sort):
        from repro.obs.probes import compile_probe
        from repro.ops.capacity import static_schedule
        from repro.ops.failures import RetryPolicy
        from repro.ops.scenario import CompiledScenario

        self.platform = platform or M.PlatformConfig()
        self.policy = int(policy)
        self.horizon_s = float(horizon_s)
        self.seed = int(seed)
        self.params = params
        self.admission_sort = admission_sort
        self.fleet_spec, self.trigger_spec = fleet, trigger
        self.caps = np.asarray(self.platform.capacities, np.int64)

        self.scenario = None            # ops.scenario.Scenario (or None)
        self.replay = None              # pre-compiled replay scenario
        if scenario is None:
            self.schedule = static_schedule(self.platform.capacities)
            self.controller = None
            self.backoff = RetryPolicy().backoff
            self.holds_frac = 1.0
            self.a_stat, self.has_asv = 1, False
        elif hasattr(scenario, "compile_schedule"):     # a Scenario spec
            self.scenario = scenario
            self.schedule = scenario.compile_schedule(
                self.platform, self.horizon_s, seed=self.seed,
                policy=self.policy)
            self.controller = (scenario.controller.compile(
                self.platform.capacities, self.horizon_s)
                if scenario.controller is not None else None)
            fm = scenario.failures
            self.backoff = (fm.retry.backoff if fm is not None
                            else RetryPolicy().backoff)
            self.holds_frac = (float(fm.fail_holds_frac)
                               if fm is not None else 1.0)
            self.a_stat = (fm.retry.max_retries + 1) if fm is not None else 1
            self.has_asv = bool(fm is not None and fm.resample_service)
        else:                                           # CompiledScenario
            self.replay = scenario
            self.schedule = scenario.schedule
            self.controller = scenario.controller
            self.backoff = scenario.backoff
            self.holds_frac = float(scenario.fail_holds_frac)
            asv = scenario.attempt_service
            self.a_stat = max(int(np.max(scenario.attempts)),
                              asv.shape[2] if asv is not None else 1)
            self.has_asv = asv is not None
            self._replay_off = 0
        self.n_attempt_slots = self.a_stat if self.a_stat > 1 else None
        self.n_ctrl_slots = (ctrl_tick_bound(self.controller) or None
                             if self.controller is not None else None)

        self.probe = None
        if probe is not None:
            n_models = fleet.n_models if fleet is not None else 0
            self.probe = compile_probe(probe, self.horizon_s,
                                       n_models=n_models)
        self.n_probe_slots = self.probe.n_ticks if self.probe else None
        self._CompiledScenario = CompiledScenario

    # -- per-block failure draws -------------------------------------------
    def block_attempts(self, wl: M.Workload, block_idx: int):
        """``(attempts [n, T] i64, attempt_service [n, T, A] | None)`` for
        one block — folded seeds, so any two consumers of the same source
        draw identically."""
        if self.scenario is not None:
            comp = self.scenario.compile(
                wl, self.platform, self.horizon_s,
                seed=_block_seed(self.seed, block_idx), policy=self.policy,
                schedule=self.schedule)
            return np.asarray(comp.attempts, np.int64), comp.attempt_service
        if self.replay is not None:
            off = self._replay_off
            self._replay_off = off + wl.n
            att = np.asarray(self.replay.attempts[off:off + wl.n], np.int64)
            asv = (self.replay.attempt_service[off:off + wl.n]
                   if self.has_asv else None)
            return att, asv
        return np.ones(wl.task_type.shape, np.int64), None

    def on_block(self, gid0: int):
        """The :class:`WorkloadManager` hook: raw columns + service +
        failure draws + global pipeline ids."""
        counter = [gid0]

        def hook(wl: M.Workload, block_idx: int) -> Dict[str, np.ndarray]:
            att, asv = self.block_attempts(wl, block_idx)
            cols = dict(
                gid=np.arange(counter[0], counter[0] + wl.n, dtype=np.int64),
                arrival=np.asarray(wl.arrival, np.float64),
                n_tasks=np.asarray(wl.n_tasks, np.int32),
                task_type=np.asarray(wl.task_type, np.int32),
                task_res=np.asarray(wl.task_res, np.int32),
                service=np.asarray(
                    wl.service_time(self.platform.datastore), np.float64),
                read_bytes=np.asarray(wl.read_bytes, np.float64),
                write_bytes=np.asarray(wl.write_bytes, np.float64),
                framework=np.asarray(wl.framework, np.int32),
                priority=np.asarray(wl.priority, np.float32),
                attempts=att)
            if self.has_asv:
                cols["att_svc"] = np.asarray(asv, np.float64)
            counter[0] += wl.n
            return cols
        return hook

    # -- fleet / retraining pool -------------------------------------------
    def compile_fleet(self, wl: M.Workload):
        """``(CompiledFleet, pool content columns)`` — pool draws depend
        only on (trigger, platform, horizon, seed, params), so compiling
        against any workload of the stream yields the same pool rows the
        one-shot reference appends."""
        from repro.core.runtime import TriggerSpec
        from repro.ops.scenario import compile_fleet
        trig = (self.trigger_spec if self.trigger_spec is not None
                else TriggerSpec())
        cf, ext = compile_fleet(self.fleet_spec, trig, wl, self.platform,
                                self.horizon_s, seed=self.seed,
                                params=self.params)
        n0, P = wl.n, cf.n_pool
        svc = np.asarray(ext.service_time(self.platform.datastore),
                         np.float64)[n0:]
        if self.scenario is not None:
            comp = self.scenario.compile(
                _rows_workload(ext, n0), self.platform, self.horizon_s,
                seed=_block_seed(self.seed, _POOL_SALT), policy=self.policy,
                schedule=self.schedule)
            att = np.asarray(comp.attempts, np.int64)
            asv = comp.attempt_service
        else:
            att = np.ones((P, ext.max_tasks), np.int64)
            asv = None
        pool = dict(
            arrival=np.asarray(ext.arrival, np.float64)[n0:],
            n_tasks=np.asarray(ext.n_tasks, np.int32)[n0:],
            task_type=np.asarray(ext.task_type, np.int32)[n0:],
            task_res=np.asarray(ext.task_res, np.int32)[n0:],
            service=svc,
            read_bytes=np.asarray(ext.read_bytes, np.float64)[n0:],
            write_bytes=np.asarray(ext.write_bytes, np.float64)[n0:],
            framework=np.asarray(ext.framework, np.int32)[n0:],
            priority=np.asarray(ext.priority, np.float32)[n0:],
            attempts=att)
        if self.has_asv:
            pool["att_svc"] = np.asarray(asv, np.float64)
        return cf, pool

    # -- engine kwargs ------------------------------------------------------
    def scenario_kwargs(self, attempts, att_svc, services, n_max):
        """The schedule/attempt/controller kwargs for one ensemble call,
        via the tested batching stacker — with the per-window attempt-slot
        and controller-slot statics REPLACED by the plan's global ones, so
        every window (and the reference) shares one compiled signature."""
        comp = self._CompiledScenario(
            schedule=self.schedule, attempts=attempts, backoff=self.backoff,
            attempt_service=att_svc, controller=self.controller,
            fail_holds_frac=self.holds_frac)
        kw = stack_scenarios([comp], n_max, self.horizon_s,
                             services=[services], record_attempts=True,
                             record_ctrl=True)
        kw.pop("n_attempt_slots", None)
        kw.pop("n_ctrl_slots", None)
        return kw

    def statics(self) -> Dict:
        return dict(n_attempt_slots=self.n_attempt_slots,
                    admission_sort=self.admission_sort,
                    n_ctrl_slots=self.n_ctrl_slots,
                    n_probe_slots=self.n_probe_slots)


def _rows_workload(wl: M.Workload, lo: int) -> M.Workload:
    """Row-slice a workload (dataclass fields only)."""
    cols = {f.name: (v[lo:] if isinstance(v := getattr(wl, f.name),
                                          np.ndarray) else v)
            for f in dataclasses.fields(M.Workload)}
    return M.Workload(**cols)


def _cat(parts: List[np.ndarray]) -> np.ndarray:
    return parts[0] if len(parts) == 1 else np.concatenate(parts)


def _merge(buf: Dict, segs: List[Dict]) -> Dict:
    if not segs:
        return buf
    return {k: _cat([buf[k]] + [s[k] for s in segs]) if buf[k].size
            else _cat([s[k] for s in segs]) for k in buf}


def _take(buf: Dict, idx: np.ndarray) -> Dict:
    return {k: v[idx] for k, v in buf.items()}


def _empty_buf(T: int, A: int, has_asv: bool) -> Dict[str, np.ndarray]:
    buf = dict(gid=np.zeros(0, np.int64), arrival=np.zeros(0, np.float64),
               n_tasks=np.zeros(0, np.int32),
               task_type=np.zeros((0, T), np.int32),
               task_res=np.zeros((0, T), np.int32),
               service=np.zeros((0, T), np.float64),
               read_bytes=np.zeros((0, T), np.float64),
               write_bytes=np.zeros((0, T), np.float64),
               framework=np.zeros(0, np.int32),
               priority=np.zeros(0, np.float32),
               attempts=np.ones((0, T), np.int64))
    if has_asv:
        buf["att_svc"] = np.zeros((0, T, A), np.float64)
    return buf


def _extract_records(content: Dict, st: Dict, row_idx: np.ndarray,
                     gids: np.ndarray, caps: np.ndarray,
                     arrival: Optional[np.ndarray] = None
                     ) -> trace.TaskRecords:
    """Records for the given working-set rows, straight through the ONE
    flattener every engine uses — pipeline ids remapped to global ids.
    ``arrival`` overrides the content arrivals (retraining-pool activation
    times; NaN rows are latent and drop out exactly like the one-shot
    path's)."""
    sl = lambda k: np.asarray(st[k][0][row_idx], np.float64)
    tr = M.SimTrace(
        start=sl("start"), finish=sl("finish"), ready=sl("ready"),
        n_tasks=content["n_tasks"].astype(np.int64),
        task_res=content["task_res"], task_type=content["task_type"],
        arrival=(arrival if arrival is not None else content["arrival"]),
        capacities=caps,
        attempts=np.asarray(st["att_out"][0][row_idx], np.int64),
        completed=np.asarray(st["phase"][0][row_idx] == _DONE),
        att_start=sl("att_start") if "att_start" in st else None,
        att_finish=sl("att_finish") if "att_finish" in st else None)
    wl_view = SimpleNamespace(read_bytes=content["read_bytes"],
                              write_bytes=content["write_bytes"],
                              framework=content["framework"])
    rec = trace.flatten_trace(tr, wl_view)
    rec.pipeline = np.asarray(gids, np.int64)[rec.pipeline]
    return rec


def _sort_records(rec: trace.TaskRecords) -> trace.TaskRecords:
    """Rows in (pipeline, task_pos) order — retirement order varies with
    the windowing, the one-shot flattener's doesn't."""
    order = np.lexsort((rec.task_pos, rec.pipeline))
    cols = {f.name: (v[order] if (v := getattr(rec, f.name)) is not None
                     else None)
            for f in dataclasses.fields(trace.TaskRecords)}
    return trace.TaskRecords(**cols)


def _fresh_rows(key: str, proto: np.ndarray, n: int, arr32: np.ndarray,
                done: bool = False) -> np.ndarray:
    """A fresh row's engine state, exactly as ``vdes`` initializes it.
    ``done=True`` builds *padding* rows: DONE with an inf event time, so
    they neither admit, nor fire events, nor keep the wave loop alive —
    indistinguishable from rows that finished long ago."""
    shape = (1, n) + proto.shape[2:]
    if key == "phase" and done:
        return np.full(shape, _DONE, proto.dtype)
    if key == "t_next":
        return arr32[None, :].astype(proto.dtype)
    if key in ("start", "finish", "ready", "att_start", "att_finish"):
        return np.full(shape, np.nan, proto.dtype)
    return np.zeros(shape, proto.dtype)     # phases, indices, counters


def stream_simulate(
        source: TraceSource,
        platform: Optional[M.PlatformConfig] = None,
        *,
        policy: int = POLICY_FIFO,
        scenario=None,
        fleet=None,
        trigger=None,
        probe=None,
        horizon_s: float = 7 * 86400.0,
        window_s: Optional[float] = None,
        seed: int = 0,
        params=None,
        max_blocks: Optional[int] = None,
        overlap: bool = True,
        min_rows: int = 64,
        admission_sort: str = "select",
        sink: Optional[Callable[[trace.TaskRecords], None]] = None,
        plan_out: Optional[list] = None) -> StreamResult:
    """Stream a :class:`TraceSource` through the batched engine in arrival
    windows of ``window_s`` (default ``horizon_s / 8``), bit-identical to
    materializing the whole stream into one ``simulate_ensemble`` call
    (:func:`oneshot_reference`).

    ``horizon_s`` bounds the *operational* grids (capacity schedule,
    controller / trigger / probe ticks), exactly as it does on the
    one-shot path — the task stream itself may run arbitrarily far past it
    (``max_blocks`` bounds an unbounded source; ``sink`` consumes each
    retired window's :class:`TaskRecords` so nothing accumulates).
    ``overlap=False`` disables the synthesis/transfer pipelining (the
    benchmark contrast). ``plan_out`` (a list) receives the internal plan
    for white-box tests."""
    t_wall = time.perf_counter()
    plan = _StreamPlan(platform, policy, scenario, fleet, trigger, probe,
                       horizon_s, seed, params, admission_sort)
    if plan_out is not None:
        plan_out.append(plan)
    window_s = float(window_s if window_s is not None else horizon_s / 8.0)
    if window_s <= 0:
        raise ValueError(f"window_s must be > 0, got {window_s}")

    ingest_s = [0.0]
    wm = WorkloadManager(source, on_block=plan.on_block(0))

    def take(bound):
        t0 = time.perf_counter()
        if max_blocks is not None and wm.n_blocks >= max_blocks:
            wm.stop()
        segs = wm.take_until(bound)
        ingest_s[0] += time.perf_counter() - t0
        return segs

    # ---- window 0 ingest (the fleet pool compiles off the first block)
    first = take(np.float32(window_s))
    cf, pool = None, None
    if fleet is not None:
        t0 = time.perf_counter()
        blocks_it = source.blocks()
        cf, pool = plan.compile_fleet(next(iter(blocks_it)))
        ingest_s[0] += time.perf_counter() - t0
    P = cf.n_pool if cf is not None else 0

    probe_kw = stack_probes([plan.probe], [cf]) if plan.probe else {}
    probe_kw.pop("n_probe_slots", None)
    fleet_kw = stack_fleets([cf], n_max=0) if cf is not None else {}
    statics = plan.statics()
    caps = plan.caps

    # content template dims from the first rows seen
    from repro.core.workload import MAX_TASKS
    T = (first[0]["task_type"].shape[1] if first
         else (pool["task_type"].shape[1] if pool is not None else MAX_TASKS))
    if not first and pool is None and wm.exhausted:
        raise ValueError(f"source {source.name!r} yielded no rows")
    buf = _merge(_empty_buf(T, plan.a_stat, plan.has_asv), first)

    recs: List[trace.TaskRecords] = []
    n_rows_emitted = [0]

    def emit(rec: trace.TaskRecords):
        n_rows_emitted[0] += int(rec.pipeline.shape[0])
        (sink if sink is not None else recs.append)(rec)

    W = 0
    k = 0
    peak_live = 0
    waves = 0
    st = None                    # downloaded carry from the last window
    keep_idx = None              # retained-row indices into the last layout
    prev_pool_off = 0
    pending_new = 0              # rows appended since the last layout
    final_exo_rows = final_gids = None
    capacities_row = np.asarray(plan.caps, np.int32)[None, :]

    while True:
        n_exo = int(buf["gid"].shape[0])
        peak_live = max(peak_live, n_exo)
        last = wm.exhausted
        need = n_exo + P
        # monotone power-of-two width: the jit signature changes only on
        # the (log-bounded) bucket growths, never window-to-window
        W = max(W, _bucket(need, min_rows))
        pads = W - need
        guard = (np.float32(CTRL_INF) if last
                 else np.float32((k + 1) * window_s))

        # ---- input tensors [1, W, ...]: [exo | pool | pad]
        def col(key, pad_val, dtype):
            parts = [buf[key]]
            if pool is not None:
                parts.append(pool[key])
            out = _cat(parts)
            if pads:
                pad_shape = (pads,) + out.shape[1:]
                out = np.concatenate(
                    [out, np.full(pad_shape, pad_val, out.dtype)])
            return out.astype(dtype)[None]

        # inert pads: arrival = inf rows never arrive and never keep the
        # loop alive (identical to latent pool rows), so every window —
        # the drain included — exits exactly where the one-shot loop does
        arrival32 = col("arrival", np.inf, np.float32)
        inputs = dict(
            arrival=arrival32,
            n_tasks=col("n_tasks", 1, np.int32),
            task_res=col("task_res", 0, np.int32),
            service=col("service", 0.0, np.float32),
            priority=col("priority", 0.0, np.float32),
            capacities=capacities_row)
        att = _cat([buf["attempts"]] + ([pool["attempts"]]
                                        if pool is not None else []))
        asv = (_cat([buf["att_svc"]] + ([pool["att_svc"]]
                                        if pool is not None else []))
               if plan.has_asv else None)
        svc = _cat([buf["service"]] + ([pool["service"]]
                                       if pool is not None else []))
        inputs.update(plan.scenario_kwargs(att, asv, svc, W))
        if cf is not None:
            inputs.update(fleet_kw)
            inputs["pool_base"] = np.asarray([n_exo], np.int32)
        inputs.update(probe_kw)

        # ---- resume carry: retained rows + fresh rows + pool + inert pads
        pad32 = np.full(pads, np.inf, np.float32)
        if st is None:
            # canonical init state via a zero-wave call (the compaction
            # pattern): after this, EVERY window — the first included —
            # resumes with one shared jit signature
            init = vdes.simulate_ensemble(
                *(inputs[k_] for k_ in _POSITIONAL), plan.policy,
                **{k_: v for k_, v in inputs.items()
                   if k_ not in _POSITIONAL},
                **statics, wave_budget=np.zeros(1, np.int32),
                return_state=True)
            resume = jax.device_get(init["state"])
            if pads:
                phase = np.array(resume["phase"])
                phase[:, need:] = _DONE
                resume["phase"] = phase
        else:
            n_new = pending_new
            new32 = arrival32[0, n_exo - n_new:n_exo] if n_new else None
            resume = {}
            for key, v in st.items():
                if key not in ROW_STATE_KEYS:
                    resume[key] = v
                    continue
                parts = [v[:, keep_idx]]
                if n_new:
                    parts.append(_fresh_rows(key, v, n_new, new32))
                parts.append(v[:, prev_pool_off:prev_pool_off + P])
                if pads:
                    parts.append(_fresh_rows(key, v, pads, pad32,
                                             done=True))
                resume[key] = np.concatenate(parts, axis=1)

        res = vdes.simulate_ensemble(
            *(inputs[k_] for k_ in _POSITIONAL), plan.policy,
            **{k_: v for k_, v in inputs.items() if k_ not in _POSITIONAL},
            **statics, resume=resume,
            time_budget=np.asarray([guard], np.float32), return_state=True)

        # ---- overlap: window k+1's synthesis + failure draws + staging
        segs = []
        if not last:
            if overlap:
                segs = take(np.float32((k + 2) * window_s))
        st = jax.device_get(res["state"])
        if not last and not overlap:
            segs = take(np.float32((k + 2) * window_s))

        k += 1
        waves = int(st["wave"][0])
        exo_done = np.asarray(st["phase"][0][:n_exo] == _DONE)
        if last:
            final_exo_rows = np.arange(n_exo)
            final_gids = buf["gid"]
            if n_exo:
                emit(_extract_records(buf, st, final_exo_rows, final_gids,
                                      plan.caps))
            if P:
                # pool pipeline ids follow ALL exogenous ids, exactly like
                # the one-shot extended workload's layout
                pool_gids = int(wm.n_rows) + np.arange(P)
                emit(_extract_records(
                    pool, st, n_exo + np.arange(P), pool_gids, plan.caps,
                    arrival=np.asarray(st["pool_arr"][0], np.float64)))
            break

        retired = np.flatnonzero(exo_done)
        if retired.size:
            emit(_extract_records(_take(buf, retired), st, retired,
                                 buf["gid"][retired], plan.caps))
        keep_idx = np.flatnonzero(~exo_done)
        prev_pool_off = n_exo
        buf = _take(buf, keep_idx)
        pending_new = sum(int(s["gid"].shape[0]) for s in segs)
        buf = _merge(buf, segs)

    # ---- result assembly --------------------------------------------------
    records = None
    summary: Dict = {}
    if sink is None and recs:
        records = _sort_records(trace.concat_records(recs))
        summary = trace.summarize(
            records, plan.caps, plan.horizon_s, schedule=plan.schedule,
            cost_rates=plan.platform.cost_rates,
            slo=plan.scenario.slo if plan.scenario is not None else None)
    ctrl_times = ctrl_caps = None
    if "ctrl_act" in st:
        ctrl_times, ctrl_caps = unpack_ctrl_actions(st["ctrl_act"][0],
                                                    st["ctrl_n"][0])
    fleet_cols = None
    if cf is not None and "fleet_perf" in st:
        ft, fk, fm = unpack_fleet_actions(st["fleet_act"][0],
                                          st["fleet_n"][0])
        fleet_cols = dict(
            fleet_perf=np.asarray(st["fleet_perf"][0], np.float64),
            fleet_stale=np.asarray(st["fleet_stale"][0], np.float64),
            fleet_ticks=np.asarray(cf.tick_times, np.float64),
            fleet_times=ft, fleet_kind=fk, fleet_model=fm,
            pool_arr=np.asarray(st["pool_arr"][0], np.float64),
            pool_model=np.asarray(st["pool_model"][0], np.int64))
    probe_times = probe_vals = None
    if plan.probe is not None and "probe_vals" in st:
        probe_times = np.asarray(plan.probe.times, np.float64)
        probe_vals = np.asarray(
            st["probe_vals"][0][:plan.probe.n_ticks], np.float64)

    wall = time.perf_counter() - t_wall
    summary.update(n_windows=k, n_blocks=wm.n_blocks, waves=waves,
                   peak_rows=W, wall_s=wall)
    return StreamResult(
        records=records, summary=summary, n_windows=k, n_blocks=wm.n_blocks,
        n_pipelines=wm.n_rows, n_task_rows=n_rows_emitted[0], waves=waves,
        peak_rows=W, peak_live=peak_live + P, wall_s=wall,
        ingest_s=ingest_s[0], ctrl_times=ctrl_times, ctrl_caps=ctrl_caps,
        fleet_cols=fleet_cols, probe_times=probe_times,
        probe_vals=probe_vals)


# ---------------------------------------------------------------------------
# one-shot reference (the parity oracle)
# ---------------------------------------------------------------------------

def oneshot_reference(
        source: TraceSource,
        platform: Optional[M.PlatformConfig] = None,
        *,
        policy: int = POLICY_FIFO,
        scenario=None, fleet=None, trigger=None, probe=None,
        horizon_s: float = 7 * 86400.0, seed: int = 0, params=None,
        max_blocks: Optional[int] = None,
        admission_sort: str = "select") -> Dict:
    """Materialize the ENTIRE stream — identical per-block draws to the
    windowed driver — into one ``vdes.simulate_ensemble`` call. Returns
    the sorted records plus the operational timelines, keyed like
    :class:`StreamResult` (plus ``wall_s`` for the fixed-horizon baseline
    wall and ``workload`` for inspection)."""
    from repro.core.runtime import _concat_workloads

    t0 = time.perf_counter()
    plan = _StreamPlan(platform, policy, scenario, fleet, trigger, probe,
                       horizon_s, seed, params, admission_sort)
    wls, atts, asvs = [], [], []
    for b, wl in enumerate(source.blocks()):
        if max_blocks is not None and b >= max_blocks:
            break
        att, asv = plan.block_attempts(wl, b)
        wls.append(wl)
        atts.append(att)
        if plan.has_asv:
            asvs.append(np.asarray(asv, np.float64))
    exo = wls[0]
    for w in wls[1:]:
        exo = _concat_workloads(exo, w)

    cf = None
    wl_ext = exo
    if fleet is not None:
        from repro.core.runtime import TriggerSpec
        from repro.ops.scenario import compile_fleet
        trig = trigger if trigger is not None else TriggerSpec()
        cf, wl_ext = compile_fleet(fleet, trig, exo, plan.platform,
                                   plan.horizon_s, seed=plan.seed,
                                   params=params)
        if plan.scenario is not None:
            comp = plan.scenario.compile(
                _rows_workload(wl_ext, exo.n), plan.platform, plan.horizon_s,
                seed=_block_seed(plan.seed, _POOL_SALT), policy=plan.policy,
                schedule=plan.schedule)
            atts.append(np.asarray(comp.attempts, np.int64))
            if plan.has_asv:
                asvs.append(np.asarray(comp.attempt_service, np.float64))
        else:
            atts.append(np.ones((wl_ext.n - exo.n, exo.max_tasks), np.int64))
            if plan.has_asv:
                asvs.append(np.repeat(np.asarray(
                    wl_ext.service_time(plan.platform.datastore),
                    np.float64)[exo.n:, :, None], plan.a_stat, -1))

    N = wl_ext.n
    svc = np.asarray(wl_ext.service_time(plan.platform.datastore),
                     np.float64)
    inputs = dict(
        arrival=np.asarray(wl_ext.arrival, np.float64
                           ).astype(np.float32)[None],
        n_tasks=np.asarray(wl_ext.n_tasks, np.int32)[None],
        task_res=np.asarray(wl_ext.task_res, np.int32)[None],
        service=svc.astype(np.float32)[None],
        priority=np.asarray(wl_ext.priority, np.float32)[None],
        capacities=np.asarray(plan.caps, np.int32)[None])
    inputs.update(plan.scenario_kwargs(
        np.concatenate(atts), np.concatenate(asvs) if plan.has_asv else None,
        svc, N))
    if cf is not None:
        inputs.update(stack_fleets([cf], n_max=N))
    if plan.probe is not None:
        pkw = stack_probes([plan.probe], [cf])
        pkw.pop("n_probe_slots", None)
        inputs.update(pkw)

    out = vdes.simulate_ensemble(
        *(inputs[k_] for k_ in _POSITIONAL), plan.policy,
        **{k_: v for k_, v in inputs.items() if k_ not in _POSITIONAL},
        **plan.statics())
    out = {k_: np.asarray(v) for k_, v in out.items()}
    tr = batch_trace(out, 0, wl_ext, plan.caps, with_scenario=True,
                     fleet=cf, probe=plan.probe)
    rec = trace.flatten_trace(tr, wl_ext)
    fleet_cols = None
    if cf is not None:
        fleet_cols = dict(
            fleet_perf=np.asarray(tr.fleet_perf, np.float64),
            fleet_stale=np.asarray(tr.fleet_stale, np.float64),
            fleet_ticks=np.asarray(cf.tick_times, np.float64),
            fleet_times=np.asarray(tr.fleet_times, np.float64),
            fleet_kind=np.asarray(tr.fleet_kind, np.int64),
            fleet_model=np.asarray(tr.fleet_model, np.int64),
            pool_arr=np.asarray(out["pool_arr"][0][:cf.n_pool], np.float64),
            pool_model=np.asarray(out["pool_model"][0][:cf.n_pool],
                                  np.int64))
    return dict(records=_sort_records(rec), trace=tr, workload=wl_ext,
                ctrl_times=tr.ctrl_times, ctrl_caps=tr.ctrl_caps,
                fleet_cols=fleet_cols,
                probe_times=(np.asarray(plan.probe.times, np.float64)
                             if plan.probe is not None else None),
                probe_vals=(np.asarray(tr.probe_vals, np.float64)
                            if plan.probe is not None else None),
                wall_s=time.perf_counter() - t0,
                summary=trace.summarize(
                    _sort_records(rec), plan.caps, plan.horizon_s,
                    schedule=plan.schedule,
                    cost_rates=plan.platform.cost_rates))


# ---------------------------------------------------------------------------
# parity metric
# ---------------------------------------------------------------------------

def _nan_drift(a, b) -> float:
    """Max |a - b| with NaN==NaN; shape mismatch or one-sided NaN = inf."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    if a.size == 0:
        return 0.0
    both_nan = np.isnan(a) & np.isnan(b)
    d = np.abs(a - b)
    d[both_nan] = 0.0
    if np.isnan(d).any():       # NaN on exactly one side
        return float("inf")
    return float(np.max(d))


def _pad_att(v: Optional[np.ndarray], width: int,
             n: int) -> Optional[np.ndarray]:
    if v is None:
        return np.full((n, width), np.nan)
    if v.shape[1] < width:
        v = np.pad(v, ((0, 0), (0, width - v.shape[1])),
                   constant_values=np.nan)
    return v


def parity_drift(sr: StreamResult, ref: Dict) -> float:
    """Max |streamed - oneshot| over every comparable tensor: the task
    records (timestamps, attempts, per-attempt windows), the realized
    controller timeline, the fleet drift/staleness/action tensors, and the
    probe matrix. 0.0 = bit parity. The wave counter is excluded by
    design (padding rows execute extra far-future waves in the drain
    window)."""
    a, b = sr.records, ref["records"]
    drift = 0.0
    if a.pipeline.shape != b.pipeline.shape:
        return float("inf")
    for f in ("pipeline", "task_pos", "task_type", "resource", "ready",
              "start", "finish", "read_bytes", "write_bytes", "framework",
              "attempts", "arrival", "pipeline_done"):
        drift = max(drift, _nan_drift(getattr(a, f), getattr(b, f)))
    wa = [v.shape[1] for v in (a.att_start, b.att_start) if v is not None]
    if wa:
        width, n = max(wa), a.pipeline.shape[0]
        for f in ("att_start", "att_finish"):
            drift = max(drift, _nan_drift(
                _pad_att(getattr(a, f), width, n),
                _pad_att(getattr(b, f), width, n)))
    for key in ("ctrl_times", "ctrl_caps", "probe_times", "probe_vals"):
        va, vb = getattr(sr, key), ref[key]
        if (va is None) != (vb is None):
            return float("inf")
        if va is not None:
            drift = max(drift, _nan_drift(va, vb))
    if (sr.fleet_cols is None) != (ref["fleet_cols"] is None):
        return float("inf")
    if sr.fleet_cols is not None:
        for key, va in sr.fleet_cols.items():
            drift = max(drift, _nan_drift(va, ref["fleet_cols"][key]))
    return drift
