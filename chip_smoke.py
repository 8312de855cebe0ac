#!/usr/bin/env python3
"""Bring-up smoke run: the JAX simulation engine end to end on one TPU.

    python chip_smoke.py

Drives the user entry points (``ExperimentSpec``, ``Sweep``,
``run_experiment``, ``get_engine``) with the committed fitted parameters
(``artifacts/pipesim_params.npz``, never refitted) on the paper's base
platform (48 compute + 32 learning slots). Phases, in order:

1. device     — JAX must report a TPU; otherwise exit 2 with no result.
2. sweep      — policy x learning capacity x load, 4 replicas each: 48
   rows over the 7-day horizon as ONE ``simulate_ensemble`` call and ONE
   compile. Cold wall, warm wall, compile time and completed tasks/s are
   printed as information, not gated.
3. full stack — controller + failure/retry + reliability + fleet/trigger +
   probe on one synthesized 1-day spec.
4. parity     — an integer-time 7-day workload (floored arrivals, ceiled
   service times: exact in f32) on ``numpy`` and ``jax``, FIFO and full
   stack: every SimTrace buffer bit-identical and equal wave counts.
5. engines    — ``jax-compact`` and ``jax-stream`` bit-identical to ``jax``.
6. kernel     — the default admission ranking (``"select"``) and
   ``admission_sort="pallas"``, which compiles to a Mosaic kernel
   (``tpu_custom_call`` in the program), are bit-identical to the 3-key
   sort ranking ``"fused"``.

Every check raises on failure, so the script exits non-zero and never
prints the result line. The numpy fallback of a sweep that cannot batch
is an error here. The compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``<checkout>/.jax_cache``.
The last line of stdout on success is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import dataclasses
import importlib.metadata
import json
import os
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import fitted_params  # noqa: E402
from repro.analysis.harness import capture_calls  # noqa: E402
from repro.core import batching, des, vdes  # noqa: E402
from repro.core import model as M  # noqa: E402
from repro.core.engines import JaxEngine, get_engine  # noqa: E402
from repro.core.experiment import (ExperimentSpec, Sweep,  # noqa: E402
                                   run_experiment)
from repro.core.runtime import FleetSpec, TriggerSpec, fleet_tensor  # noqa: E402
from repro.core.synthesizer import synthesize_workload  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.obs.probes import ProbeSpec  # noqa: E402
from repro.ops import (FailureModel, ReactiveController,  # noqa: E402
                       RetryPolicy, Scenario)
from repro.reliability import (DomainOutageModel, ReliabilitySpec,  # noqa: E402
                               RepairSpec, SpotPoolSpec, TopologySpec)

DAY = 86400.0
# the fleet tensor's seasonal-amplitude column: XLA's cos may differ from
# numpy's by an ulp, so parity runs switch the seasonal term off
FLEET_SEASONAL_AMP = 4


class SmokeFailure(AssertionError):
    """A phase's check failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@dataclasses.dataclass(frozen=True)
class Scale:
    """Run sizes. The defaults are the paper-scale run."""

    horizon_s: float = 7 * DAY      # sweep + oracle-parity horizon
    full_stack_s: float = DAY       # full-stack phase horizon
    side_s: float = DAY             # jax-compact / jax-stream / pallas points
    replicas: int = 4


class CompileClock:
    """Backend compile time per jitted function, from JAX's monitoring
    events (a persistent-cache hit is counted as a hit; its duration is
    the cache read)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.secs = {}
        self.count = {}
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def _dur(self, event, duration, **kw):
        if event == self.EVENT:
            name = kw.get("fun_name", "?")
            self.secs[name] = self.secs.get(name, 0.0) + duration
            self.count[name] = self.count.get(name, 0) + 1

    def _evt(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return dict(self.secs), dict(self.count), self.hits

    def since(self, snap, name: str):
        """(seconds, compiles) of ``name`` and (seconds, compiles) of all
        functions, plus persistent-cache hits, since ``snap``."""
        secs, count, hits = snap
        d_secs = {k: v - secs.get(k, 0.0) for k, v in self.secs.items()}
        d_count = {k: v - count.get(k, 0) for k, v in self.count.items()}
        return (d_secs.get(name, 0.0), d_count.get(name, 0),
                sum(d_secs.values()), sum(d_count.values()),
                self.hits - hits)


def say(phase: str, text: str) -> None:
    print(f"[{phase}] {text}", flush=True)


# ------------------------------------------------------------- comparisons

def _max_abs_diff(a, b) -> float:
    """max |a - b| with NaN == NaN; inf on a shape or one-sided-NaN
    mismatch."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    if a.size == 0:
        return 0.0
    both = np.isnan(a) & np.isnan(b)
    d = np.where(both, 0.0, np.abs(a - b))
    return float("inf") if np.isnan(d).any() else float(d.max())


def compare_fields(a, b, names) -> tuple:
    """(max drift, mismatching field names) over ``names`` of two
    dataclass instances; a field set on one side only is a mismatch."""
    drift, bad = 0.0, []
    for name in names:
        va, vb = getattr(a, name), getattr(b, name)
        if (va is None) != (vb is None):
            bad.append(name)
            continue
        if va is None:
            continue
        d = _max_abs_diff(va, vb)
        if d != 0.0:
            bad.append(name)
        drift = max(drift, d)
    return drift, bad


_DIAGNOSTICS = ("ops_waves", "fleet_waves", "retries", "attempt_slots",
                "attempt_log_slots", "rel_slot_s")


def compare_traces(a: M.SimTrace, b: M.SimTrace) -> tuple:
    """Every SimTrace buffer: schedules, attempts, controller, reliability,
    fleet and probe buffers, and the wave count. Not the batched engine's
    diagnostics, which the other engines do not keep: ``ops_waves``,
    ``fleet_waves`` (it depends on the rows batched together) and the
    failure and recovery counters of ``batching.batch_trace``."""
    return compare_fields(a, b, [f.name for f in dataclasses.fields(a)
                                 if f.name not in _DIAGNOSTICS])


def sorted_records(rec):
    """Records in (pipeline, task position) order, whatever order the
    engine emitted them in."""
    o = np.lexsort((rec.task_pos, rec.pipeline))
    return dataclasses.replace(rec, **{
        f.name: getattr(rec, f.name)[o]
        for f in dataclasses.fields(rec)
        if isinstance(getattr(rec, f.name), np.ndarray)})


def compare_records(a, b) -> tuple:
    return compare_fields(sorted_records(a), sorted_records(b),
                          [f.name for f in dataclasses.fields(a)])


def require_identical(phase: str, what: str, result: tuple,
                      waves=None) -> None:
    drift, bad = result
    check(not bad, f"{what}: buffers differ ({', '.join(bad)}), "
                   f"max drift {drift}")
    wave_txt = ""
    if waves is not None:
        check(waves[0] == waves[1], f"{what}: wave counts differ {waves}")
        wave_txt = f" waves={waves[0]}=={waves[1]}"
    say(phase, f"{what}: bit-identical, drift={drift}{wave_txt}")


# ------------------------------------------------------------------- specs

def integer_workload(params, platform, horizon_s: float,
                     seed: int) -> M.Workload:
    """A synthesized workload pinned to integer times: floored arrivals,
    service (execution + data transfer) ceiled into ``exec_time`` with the
    transfer bytes zeroed. Below 2**24 s every such time is exact in f32,
    so the f64 oracle and the f32 engine must agree bit for bit."""
    wl = synthesize_workload(params, jax.random.PRNGKey(seed), horizon_s,
                             platform)
    live = wl.task_type >= 0
    check(horizon_s < 2.0 ** 24, "integer-time horizon must stay below 2**24 s")
    return dataclasses.replace(
        wl, arrival=np.floor(wl.arrival),
        exec_time=np.ceil(wl.service_time(platform.datastore)) * live,
        read_bytes=np.zeros_like(wl.read_bytes),
        write_bytes=np.zeros_like(wl.write_bytes))


def ops_scenario() -> Scenario:
    """Closed-loop controller + failure/retry with integer backoffs."""
    return Scenario(
        name="full-stack",
        controller=ReactiveController(interval_s=1800.0, cooldown_s=3600.0),
        failures=FailureModel(retry=RetryPolicy(max_retries=2, base_s=60.0,
                                                mult=2.0, cap_s=600.0)))


def reliability_spec() -> ReliabilitySpec:
    """Correlated zone/rack outages, two repair crews and a spot slice,
    dense enough to fire within a day; event times on the integer grid."""
    return ReliabilitySpec(
        topology=TopologySpec(zones=2, racks_per_zone=4),
        outages=DomainOutageModel(zone_mtbf_s=2 * DAY, rack_mtbf_s=DAY,
                                  mttr_s=2 * 3600.0),
        repair=RepairSpec(crews=2),
        spot=SpotPoolSpec(frac=0.25, evict_mtbe_s=DAY, reclaim_s=1800.0),
        time_quantum_s=1.0)


def full_stack_spec(name: str, horizon_s: float, *, parity: bool,
                    workload=None, reliability: bool = True,
                    engine: str = "jax") -> ExperimentSpec:
    """Every stage lit: control (controller + retry), reliability, fleet +
    trigger, probe. ``parity`` pins the fleet's seasonal term to zero and
    the retrain durations to integers (the bit-parity recipe)."""
    if parity:
        fl = fleet_tensor(FleetSpec(n_models=20, drift_scale=20.0), seed=0)
        fl[:, FLEET_SEASONAL_AMP] = 0.0
        fleet = FleetSpec(params=fl)
        trigger = TriggerSpec(interval_s=3600.0, cooldown_s=6 * 3600.0,
                              retrain_durations=(3600.0, 600.0, 300.0))
    else:
        fleet = FleetSpec(n_models=20, drift_scale=20.0)
        trigger = TriggerSpec(interval_s=3600.0, cooldown_s=6 * 3600.0)
    return ExperimentSpec(
        name=name, horizon_s=horizon_s, workload=workload, engine=engine,
        scenario=ops_scenario(), fleet=fleet, trigger=trigger,
        probe=ProbeSpec(interval_s=900.0),
        reliability=reliability_spec() if reliability else None)


class BlockSource:
    """A pinned workload served as a TraceSource in arrival-ordered
    blocks (the streamed form of the same tensors)."""

    name = "pinned-blocks"

    def __init__(self, wl: M.Workload, block: int = 512):
        self.wl, self.block = wl, block

    def blocks(self):
        n = self.wl.n
        for lo in range(0, n, self.block):
            hi = min(lo + self.block, n)
            yield dataclasses.replace(self.wl, **{
                f.name: getattr(self.wl, f.name)[lo:hi]
                for f in dataclasses.fields(M.Workload)})


# ------------------------------------------------------------------ phases

def device_phase() -> dict:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        print(f"chip_smoke: JAX found platform {d.platform!r} "
              f"({d.device_kind!r}), not a TPU — nothing was run",
              file=sys.stderr)
        sys.exit(2)
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    say("device", f"device_kind={d.device_kind}")
    say("device", f"device_count={len(devs)}")
    say("device", f"jax={jax.__version__}")
    say("device", f"jaxlib={importlib.metadata.version('jaxlib')}")
    say("device", f"libtpu={libtpu}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def sweep_phase(params, scale: Scale, clock: CompileClock, kind: str):
    base = ExperimentSpec(name="paper", engine="jax",
                          horizon_s=scale.horizon_s,
                          n_replicas=scale.replicas)
    sweep = Sweep(base, {
        "policy": [des.POLICY_FIFO, des.POLICY_PRIORITY, des.POLICY_SJF],
        "capacity:learning_cluster": [16, 32],
        "interarrival_factor": [1.0, 0.5]})
    n_rows = len(sweep.points()) * scale.replicas
    snap = clock.snapshot()
    size0 = vdes.simulate_ensemble._cache_size()
    t0 = time.perf_counter()
    with capture_calls("simulate_ensemble") as calls:
        results = sweep.run(params)
    cold = time.perf_counter() - t0
    grew = vdes.simulate_ensemble._cache_size() - size0
    eng_s, eng_n, all_s, all_n, hits = clock.since(
        snap, "jit(simulate_ensemble)")
    check(len(calls) == 1,
          f"sweep made {len(calls)} simulate_ensemble calls, expected 1")
    check(grew == 1 and eng_n == 1,
          f"sweep compiled simulate_ensemble {eng_n} times "
          f"(jit cache grew by {grew}), expected 1")
    call = calls[0]
    rows, n_max, t_max = (int(x) for x in call.args[3].shape)
    check(rows == n_rows, f"batch has {rows} rows, expected {n_rows}")
    say("sweep", f"{len(results)} points x {scale.replicas} replicas = "
                 f"{rows} rows, N={n_max} T={t_max}: 1 simulate_ensemble "
                 f"call, 1 compile, admission_sort="
                 f"{call.kwargs.get('admission_sort')}")

    t0 = time.perf_counter()
    out = jax.block_until_ready(vdes.simulate_ensemble(*call.args,
                                                       **call.kwargs))
    warm = time.perf_counter() - t0
    real = np.asarray(call.args[0]) < batching.PAD_ARRIVAL   # [R, N]
    done = int((np.isfinite(np.asarray(out["finish"]))
                & real[:, :, None]).sum())
    recorded = sum(int(np.isfinite(r.records.finish).sum()) for r in results)
    check(done == recorded,
          f"warm re-run completed {done} tasks, cold run recorded {recorded}")
    check(done > 0, "sweep completed no tasks")
    pipelines = sum(s["n_pipelines"] for r in results
                    for s in r.replica_summaries)
    waves = np.asarray(out["waves"])
    say("sweep", f"pipelines={pipelines} tasks_completed={done} "
                 f"waves_max={int(waves.max())}")
    say("sweep", f"{kind}: cold_wall_s={cold:.3f} (synthesis + batching + "
                 f"compile + run + summaries)")
    say("sweep", f"{kind}: compile_s={eng_s:.3f} (simulate_ensemble); "
                 f"all_compiles_s={all_s:.3f} over {all_n} programs; "
                 f"persistent_cache_hits={hits}")
    say("sweep", f"{kind}: warm_wall_s={warm:.3f} "
                 f"(simulate_ensemble re-run, block_until_ready)")
    say("sweep", f"{kind}: tasks_per_s={done / warm:.1f} (warm)")


def full_stack_phase(params, scale: Scale, kind: str):
    spec = full_stack_spec("full-stack", scale.full_stack_s, parity=False)
    t0 = time.perf_counter()
    res = run_experiment(spec, params)
    wall = time.perf_counter() - t0
    tr = res.trace
    for buf in ("att_start", "ctrl_times", "rel_times", "fleet_perf",
                "probe_vals"):
        check(getattr(tr, buf) is not None,
              f"full-stack run recorded no {buf}: a stage is not lit")
    check(res.summary["n_tasks"] > 0, "full-stack run completed no tasks")
    say("full-stack", f"{res.summary['n_pipelines']} pipelines, "
                      f"{res.summary['n_tasks']} tasks, {tr.waves} waves; "
                      f"controller actions={len(tr.ctrl_times)} "
                      f"reliability events={len(tr.rel_times)} "
                      f"lifecycle actions={len(tr.fleet_times)} "
                      f"probe ticks={tr.probe_vals.shape[0]}")
    say("full-stack", f"{kind}: wall_s={wall:.3f} (cold, incl. compile)")


def parity_phase(params, scale: Scale, plat: M.PlatformConfig):
    wl = integer_workload(params, plat, scale.horizon_s, seed=11)
    say("parity", f"integer-time workload: {wl.n} pipelines, "
                  f"{int(wl.n_tasks.sum())} tasks, horizon "
                  f"{scale.horizon_s:.0f} s")
    fifo = ExperimentSpec(name="parity-fifo", horizon_s=scale.horizon_s,
                          workload=wl)
    full = full_stack_spec("parity-full", scale.horizon_s, parity=True,
                           workload=wl)
    for label, spec in (("fifo", fifo), ("full-stack", full)):
        ref = run_experiment(spec.with_(engine="numpy"), params).trace
        got = run_experiment(spec.with_(engine="jax"), params).trace
        require_identical("parity", f"{label} numpy vs jax",
                          compare_traces(ref, got), (ref.waves, got.waves))


def engines_phase(params, scale: Scale, plat: M.PlatformConfig):
    wl = integer_workload(params, plat, scale.side_s, seed=12)
    # jax-compact: every stage it supports (reliability is jax/numpy only)
    spec = full_stack_spec("compact", scale.side_s, parity=True,
                           workload=wl, reliability=False)
    ref = run_experiment(spec, params)
    got = run_experiment(spec.with_(engine="jax-compact"), params)
    require_identical("engines", "jax-compact vs jax",
                      compare_traces(ref.trace, got.trace),
                      (ref.trace.waves, got.trace.waves))
    # jax-stream: the same tensors served in blocks, controller + probe
    # (the engine draws failures and fleets per block, so those differ
    # from a one-shot run by construction)
    spec = ExperimentSpec(
        name="stream", horizon_s=scale.side_s, source=BlockSource(wl),
        scenario=Scenario(name="ctrl", controller=ReactiveController(
            interval_s=1800.0, cooldown_s=3600.0)),
        probe=ProbeSpec(interval_s=900.0))
    ref = run_experiment(spec.with_(engine="jax"), params)
    got = run_experiment(spec.with_(engine="jax-stream"), params)
    require_identical("engines", "jax-stream vs jax records",
                      compare_records(ref.records, got.records))
    sr = get_engine("jax-stream").last_result
    for name in ("ctrl_times", "ctrl_caps", "probe_times", "probe_vals"):
        check(_max_abs_diff(getattr(sr, name), getattr(ref.trace, name))
              == 0.0, f"jax-stream vs jax: {name} differs")
    say("engines", f"jax-stream vs jax: controller and probe buffers "
                   f"bit-identical over {sr.summary['n_windows']} windows")
    return wl


def kernel_phase(params, scale: Scale, wl: M.Workload, on_tpu: bool):
    spec = ExperimentSpec(name="kernel-fifo", horizon_s=scale.side_s,
                          workload=wl, engine="jax")
    ref = JaxEngine(admission_sort="fused").run(spec, params)
    default = run_experiment(spec, params)
    require_identical("kernel", "select (default) vs fused",
                      compare_traces(ref.trace, default.trace),
                      (ref.trace.waves, default.trace.waves))
    with capture_calls("simulate") as calls:
        got = JaxEngine(admission_sort="pallas").run(spec, params)
    check(len(calls) == 1 and
          calls[0].kwargs.get("admission_sort") == "pallas",
          "the pallas run did not reach vdes.simulate with the kernel")
    text = vdes.simulate.lower(*calls[0].args,
                               **calls[0].kwargs).compile().as_text()
    if on_tpu:
        check("tpu_custom_call" in text,
              "admission_sort='pallas' program holds no tpu_custom_call: "
              "the kernel did not compile for the chip")
        say("kernel", "admission_sort=pallas: tpu_custom_call in the "
                      "compiled program")
    require_identical("kernel", "pallas vs fused",
                      compare_traces(ref.trace, got.trace),
                      (ref.trace.waves, got.trace.waves))


def run_phases(device: dict, scale: Scale = Scale()) -> None:
    """Phases 2-6 (the device phase has run). Raises on any failure."""
    clock = CompileClock()
    kind = device["kind"]
    params = fitted_params()
    plat = M.PlatformConfig()
    t_all = t0 = time.perf_counter()

    def lap(name):
        nonlocal t0
        say(name, f"phase_wall_s={time.perf_counter() - t0:.3f}")
        t0 = time.perf_counter()

    sweep_phase(params, scale, clock, kind)
    lap("sweep")
    full_stack_phase(params, scale, kind)
    lap("full-stack")
    parity_phase(params, scale, plat)
    lap("parity")
    side_wl = engines_phase(params, scale, plat)
    lap("engines")
    kernel_phase(params, scale, side_wl, device["platform"] == "tpu")
    lap("kernel")
    say("total", f"wall_s={time.perf_counter() - t_all:.3f}")


def main() -> None:
    device = device_phase()
    say("cache", f"compile cache: {enable_compile_cache()}")
    # the smoke run must never pass through the numpy serial fallback
    warnings.filterwarnings("error", category=RuntimeWarning,
                            message="sweep grid cannot lower")
    run_phases(device)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
