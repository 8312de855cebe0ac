"""The unified Engine protocol: ``run(spec, params) -> ExperimentResult``.

Callers never branch on ``spec.engine`` — they ask the registry for an
engine and call it. Three implementations ship:

  - :class:`NumpyEngine` — the exact (f64, heap-based) reference engine.
    Replicas and sweep grids run as serial loops: the fallback for precise
    long-horizon runs where f32 clock ulp matters.
  - :class:`JaxEngine` — the vectorized engine. Replica ensembles AND whole
    sweep grids lower through :mod:`repro.core.batching` into ONE
    ``jit``+``vmap`` call of ``vdes.simulate_ensemble``: every grid point
    (its capacities, its admission policy, its compiled operational
    scenario) becomes a row of the batch, so a 24-point capacity x load x
    scenario grid costs one XLA compile and one device execution.
  - :class:`JaxCompactEngine` (``"jax-compact"``) — the batched engine with
    :mod:`repro.core.compaction`: the wave loop runs in segments, finished
    replicas and DONE pipelines drop out of the working set between
    segments (power-of-two buckets), so wave cost tracks the *live* width.
    Bit-identical results, different wall clock — the fast CPU path.

Both produce identical summaries on integer-time workloads (parity-tested);
results are :class:`repro.core.experiment.ExperimentResult` either way.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from typing import List, Protocol, Sequence, runtime_checkable

import jax
import numpy as np

from repro.core import batching, des, trace, vdes
from repro.core.synthesizer import synthesize_workload
from repro.obs import profile


@runtime_checkable
class Engine(Protocol):
    """One dispatch point for both simulation backends."""

    name: str

    def run(self, spec, params=None):
        """Run one :class:`ExperimentSpec` -> :class:`ExperimentResult`."""
        ...

    def run_sweep(self, specs: Sequence, params=None) -> List:
        """Run a grid of specs, one result per spec (order preserved)."""
        ...


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------

def _pad_platform(plat, nres: int):
    """Pad a platform to ``nres`` resources with inert pools (zero capacity,
    zero cost rate): nothing routes to them, nothing is provisioned on them,
    and they cost nothing — so a ragged platform grid can share one
    rectangular ``[B, nres]`` batch without changing any point's physics
    or accounting."""
    from repro.core import model as M
    pad = nres - len(plat.resources)
    if pad <= 0:
        return plat
    extra = tuple(
        M.ResourceConfig(name=f"__pad{len(plat.resources) + i}",
                         capacity=0, cost_per_node_hour=0.0)
        for i in range(pad))
    return dataclasses.replace(plat, resources=tuple(plat.resources) + extra)


def _workload_key(spec):
    """Grid points that differ only in capacities/policy/scenario draw the
    *same* workload; this key lets a sweep synthesize each distinct one
    once. Everything synthesize_workload reads is in here (capacity never
    enters synthesis — only routing and datastore parameters do)."""
    return (spec.horizon_s, spec.interarrival_factor, spec.seed,
            spec.n_replicas, tuple(sorted(spec.platform.routing.items())),
            dataclasses.astuple(spec.platform.datastore))


def _fold_reliability(comp, rel_c, w, plat):
    """Fold one replica's compiled reliability *task-level* effects into
    its compiled scenario: presampled spot-eviction retries add to the
    ``attempts`` tensor, and a CheckpointSpec scales every retry slot of
    ``attempt_service`` by ``1 - ckpt_frac`` (a checkpointed retrain only
    re-runs the lost fraction — the generalization of the failing-attempt
    ``fail_holds_frac`` hold). Scaled durations are computed in f32 so both
    engines see bit-identical values (the compile-time f32 convention).
    Capacity-level events ride the separate ``reliability=`` engine kwarg.
    Returns ``comp`` unchanged when the reliability has no task effects; a
    scenario-less spec gets the inert placeholder scenario first."""
    if rel_c is None:
        return comp
    ev, ck = rel_c.evict_attempts, rel_c.ckpt_frac
    if ev is None and ck is None:
        return comp
    if comp is None:
        from repro.ops.capacity import static_schedule
        from repro.ops.scenario import CompiledScenario
        comp = CompiledScenario(
            schedule=static_schedule(plat.capacities),
            attempts=np.ones(w.task_type.shape, np.int64),
            backoff=vdes._NO_RETRY_BACKOFF)
    att = np.asarray(comp.attempts, np.int64)
    if ev is not None:
        att = att + np.asarray(ev, np.int64)
    asv = getattr(comp, "attempt_service", None)
    if ck is not None:
        A = int(max(int(att.max()),
                    asv.shape[2] if asv is not None else 0))
        if A > 1:
            if asv is None:
                base = np.asarray(w.service_time(plat.datastore),
                                  np.float64)
                asv = np.repeat(base[..., None], A, -1)
            elif asv.shape[2] < A:
                # engines clip the attempt index at A-1: repeating the
                # last slot preserves the entry's semantics exactly
                asv = np.concatenate(
                    [asv, np.repeat(asv[..., -1:], A - asv.shape[2], -1)],
                    -1)
            asv = np.asarray(asv, np.float64).copy()
            asv[..., 1:] = (asv[..., 1:].astype(np.float32)
                            * np.float32(1.0 - ck)).astype(np.float64)
    return dataclasses.replace(comp, attempts=att, attempt_service=asv)


def _spec_workloads(spec, params, cache=None):
    """The spec's replica workloads + per-replica compiled scenarios and
    compiled fleets + the spec's compiled telemetry probe (None without a
    :class:`~repro.obs.probes.ProbeSpec`; probes are deterministic, so one
    compile covers every replica) + per-replica compiled reliability
    timelines (None without a
    :class:`~repro.reliability.ReliabilitySpec`).

    Seed conventions match the historical ``run_experiment`` exactly (single
    replica: PRNGKey(seed); ensembles: split(PRNGKey(seed), R); scenario /
    fleet / reliability replica r compiles with seed + 1000*r) so batched
    and serial execution see identical random draws. ``cache`` (dict)
    shares synthesis across grid points whose workload axes agree.

    With a :class:`~repro.core.runtime.FleetSpec` on the spec, each replica
    workload is *extended* with the latent retraining pool BEFORE the
    scenario compiles — failure/retry draws then cover retraining pipelines
    too, identically in both engines. Reliability compiles after the same
    extension (spot-eviction draws cover retraining pipelines), and its
    task-level effects (eviction retries, checkpointed retry scaling) fold
    into the compiled scenario via :func:`_fold_reliability` — composition
    with ``fail_holds_frac`` is rejected by
    :func:`repro.reliability.check_no_double_apply`.
    """
    if spec.workload is not None:
        wls = [spec.workload] * spec.n_replicas
    elif getattr(spec, "source", None) is not None:
        # non-stream engines treat a TraceSource as a pinned workload:
        # materialize the whole stream once (deterministic re-iteration,
        # so this equals what the stream engine consumes incrementally)
        from repro.stream import materialize
        wls = [materialize(spec.source)] * spec.n_replicas
    else:
        if params is None:
            raise ValueError("params required unless spec.workload is set")
        key = _workload_key(spec) if cache is not None else None
        if key is not None and key in cache:
            wls = cache[key]
        else:
            if spec.n_replicas == 1:
                keys = [jax.random.PRNGKey(spec.seed)]
            else:
                keys = jax.random.split(jax.random.PRNGKey(spec.seed),
                                        spec.n_replicas)
            wls = [synthesize_workload(params, k, spec.horizon_s,
                                       spec.platform,
                                       spec.interarrival_factor)
                   for k in keys]
            if key is not None:
                cache[key] = wls
    fleets = None
    if getattr(spec, "fleet", None) is not None:
        from repro.core.runtime import TriggerSpec
        from repro.ops.scenario import compile_fleet
        trig = spec.trigger if spec.trigger is not None else TriggerSpec()
        fleets, ext = [], []
        for r, w in enumerate(wls):
            cf, w2 = compile_fleet(spec.fleet, trig, w, spec.platform,
                                   spec.horizon_s,
                                   seed=spec.seed + 1000 * r, params=params)
            fleets.append(cf)
            ext.append(w2)
        wls = ext
    # failure sampling and the reliability timeline run inside a span of
    # their own, where the spec has either
    faults = (getattr(spec, "reliability", None) is not None
              or getattr(spec.scenario, "failures", None) is not None)
    with profile.span("faults") if faults else contextlib.nullcontext():
        rels = None
        if getattr(spec, "reliability", None) is not None:
            from repro.reliability import (check_no_double_apply,
                                           compile_reliability)
            check_no_double_apply(spec.reliability, spec.scenario)
            rels = [compile_reliability(spec.reliability, w, spec.platform,
                                        spec.horizon_s,
                                        seed=spec.seed + 1000 * r)
                    for r, w in enumerate(wls)]
        compiled = None
        if spec.scenario is not None:
            compiled = [spec.scenario.compile(w, spec.platform,
                                              spec.horizon_s,
                                              seed=spec.seed + 1000 * r,
                                              policy=spec.policy)
                        for r, w in enumerate(wls)]
        if rels is not None:
            compiled = [_fold_reliability(
                compiled[r] if compiled is not None else None, rels[r], w,
                spec.platform) for r, w in enumerate(wls)]
            if all(c is None for c in compiled):
                compiled = None
    probe = None
    if getattr(spec, "probe", None) is not None:
        from repro.obs.probes import compile_probe
        probe = compile_probe(
            spec.probe, spec.horizon_s,
            n_models=fleets[0].n_models if fleets is not None else 0)
    return wls, compiled, fleets, probe, rels


def _summarize(spec, rec, compiled, tr=None, rel=None):
    """Summary for one replica. ``tr`` (the SimTrace) carries the
    engine-recorded controller action timeline: under closed-loop control
    cost/utilization integrate the *realized* capacity schedule, not the
    planned one (identical — same object — when the controller never
    acted, so scenario-less and open-loop summaries are unchanged). It also
    carries the fleet-stage tensors, which fold in as the ``lifecycle``
    summary block. ``rel`` (the replica's
    :class:`~repro.reliability.CompiledReliability`) folds in as the
    ``availability`` block (downtime integrals, repair-queue stats, spot
    cost split)."""
    realized = None
    if compiled is not None and tr is not None:
        from repro.ops.accounting import realized_schedule
        realized = realized_schedule(tr, compiled)
        if realized is compiled.schedule:
            realized = None            # planned == realized: legacy path
    lifecycle = None
    if tr is not None and getattr(tr, "fleet_perf", None) is not None:
        from repro.ops.accounting import lifecycle_summary
        lifecycle = lifecycle_summary(tr)
    s = trace.summarize(
        rec, spec.platform.capacities, spec.horizon_s,
        schedule=compiled.schedule if compiled is not None else None,
        cost_rates=spec.platform.cost_rates if compiled is not None else None,
        slo=spec.scenario.slo if spec.scenario is not None else None,
        realized=realized, lifecycle=lifecycle)
    if rel is not None:
        from repro.ops.accounting import availability_summary
        s["availability"] = availability_summary(rel, spec.platform, tr=tr)
    return s


def _single_result(spec, wl, compiled, tr, wall, rel=None):
    from repro.core.experiment import ExperimentResult
    from repro.core.runtime import lifecycle_result
    rec = trace.flatten_trace(tr, wl)
    summary = _summarize(spec, rec, compiled, tr, rel=rel)
    summary["wall_s"] = wall
    # pipelines that actually entered the platform (latent, never-activated
    # retraining-pool rows are excluded by flatten_trace)
    summary["pipelines_per_s"] = summary["n_pipelines"] / max(wall, 1e-9)
    return ExperimentResult(spec, summary, rec, wall,
                            lifecycle=lifecycle_result(tr),
                            timeline=_probe_timeline(spec, tr), trace=tr)


def _probe_timeline(spec, tr):
    """The result's telemetry view (None for unprobed runs)."""
    if getattr(tr, "probe_vals", None) is None:
        return None
    from repro.obs.probes import ProbeTimeline
    return ProbeTimeline.from_trace(tr, spec.platform)


def _aggregate_replicas(spec, rep_sums, recs, wall):
    """Monte-Carlo summary across replicas (the old ``_run_ensemble`` tail)."""
    from repro.core.experiment import ExperimentResult
    summary = {
        "mean_wait_s": float(np.mean([s["mean_wait_s"] for s in rep_sums])),
        "p95_wait_s": float(np.mean([s["p95_wait_s"] for s in rep_sums])),
        "wait_ci95_halfwidth": float(1.96 * np.std(
            [s["mean_wait_s"] for s in rep_sums]) / np.sqrt(len(rep_sums))),
        "wall_s": wall,
        "n_replicas": len(rep_sums),
    }
    for k in ("total_cost", "deadline_miss_rate", "wait_slo_violation_rate",
              "mean_attempts", "planned_total_cost",
              "realized_vs_planned_cost_delta", "mean_staleness",
              "staleness_integral_s", "n_retrained", "n_triggered"):
        if all(k in s for s in rep_sums):
            summary[k] = float(np.mean([s[k] for s in rep_sums]))
    return ExperimentResult(spec, summary, trace.concat_records(recs), wall,
                            rep_sums)


# ---------------------------------------------------------------------------
# numpy: exact serial reference
# ---------------------------------------------------------------------------

class NumpyEngine:
    """Exact f64 heap engine; replicas and grids run serially."""

    name = "numpy"

    def run(self, spec, params=None, _cache=None):
        t0 = time.perf_counter()
        wls, compiled, fleets, probe, rels = _spec_workloads(spec, params,
                                                             cache=_cache)
        if spec.n_replicas == 1:
            comp = compiled[0] if compiled is not None else None
            tr = des.simulate(wls[0], spec.platform, spec.policy,
                              scenario=comp,
                              fleet=fleets[0] if fleets is not None else None,
                              probe=probe,
                              reliability=rels[0] if rels is not None
                              else None)
            return _single_result(spec, wls[0], comp, tr,
                                  time.perf_counter() - t0,
                                  rel=rels[0] if rels is not None else None)
        recs, sums = [], []
        for r, w in enumerate(wls):
            comp = compiled[r] if compiled is not None else None
            tr = des.simulate(w, spec.platform, spec.policy, scenario=comp,
                              fleet=fleets[r] if fleets is not None else None,
                              probe=probe,
                              reliability=rels[r] if rels is not None
                              else None)
            rec = trace.flatten_trace(tr, w)
            recs.append(rec)
            sums.append(_summarize(spec, rec, comp, tr,
                                   rel=rels[r] if rels is not None else None))
        return _aggregate_replicas(spec, sums, recs,
                                   time.perf_counter() - t0)

    def run_sweep(self, specs: Sequence, params=None) -> List:
        # one synthesis cache for the whole grid, matching the batched
        # path's dedup (grid points often share every workload axis)
        cache = {}
        return [self.run(s, params, _cache=cache) for s in specs]


# ---------------------------------------------------------------------------
# jax: everything lowers to one jit+vmap batch
# ---------------------------------------------------------------------------

class JaxEngine:
    """Vectorized engine; ensembles and sweep grids are one batched call.
    ``admission_sort`` picks the admission ranking (``"select"`` lex-min
    rounds, ``"fused"`` lax.sort, ``"pallas"`` kernel, ...; see
    :func:`repro.core.vdes.simulate`) — all variants are bit-identical."""

    name = "jax"

    def __init__(self, admission_sort: str = "select"):
        self.admission_sort = admission_sort

    def _ensemble(self, *args, **kwargs):
        """The one batched simulate call (overridden by
        :class:`JaxCompactEngine` to substitute the segmented compaction
        driver). Everything above this seam — padding, stacking, result
        slicing — is shared between the two engines."""
        kwargs.setdefault("admission_sort", self.admission_sort)
        return vdes.simulate_ensemble(*args, **kwargs)

    def run(self, spec, params=None):
        if spec.n_replicas <= 1:
            t0 = time.perf_counter()
            wls, compiled, fleets, probe, rels = _spec_workloads(spec,
                                                                 params)
            comp = compiled[0] if compiled is not None else None
            tr = vdes.simulate_to_trace(wls[0], spec.platform, spec.policy,
                                        scenario=comp,
                                        fleet=fleets[0]
                                        if fleets is not None else None,
                                        probe=probe,
                                        reliability=rels[0]
                                        if rels is not None else None,
                                        admission_sort=self.admission_sort)
            return _single_result(spec, wls[0], comp, tr,
                                  time.perf_counter() - t0,
                                  rel=rels[0] if rels is not None else None)
        return self.run_sweep([spec], params)[0]

    def run_sweep(self, specs: Sequence, params=None) -> List:
        """Compile the whole grid — every (point, replica) pair — into one
        ``vdes.simulate_ensemble`` call. Heterogeneous capacities ride the
        ``capacities [B, nres]`` tensor, heterogeneous schedulers the traced
        ``policies [B]`` tensor, heterogeneous scenarios/controllers the
        stacked schedule/attempt/ControllerParams tensors. A *ragged*
        platform grid (points with differing resource counts) is auto-padded
        to the common resource superset — padded pools have zero capacity
        and zero cost rate, so they are semantically inert (no task routes
        to them, nothing is provisioned or charged) and the grid stays on
        the batched path. Only genuinely incompatible grids (e.g. pinned
        workloads with differing ``max_tasks``) warn and fall back to the
        exact numpy serial loop."""
        t0 = time.perf_counter()
        # every statement below runs inside one of the layer spans
        # (repro.obs.profile.span), which nest under Sweep.run's "sweep"
        with profile.span("prep"):
            nres = {len(s.platform.resources) for s in specs}
            exec_specs = list(specs)
            if len(nres) != 1:
                # ragged platform grid: pad every point to the superset so
                # ONE rectangular batch still covers the grid (results and
                # summaries are computed against each point's own unpadded
                # platform)
                nres_max = max(nres)
                exec_specs = [
                    dataclasses.replace(
                        s, platform=_pad_platform(s.platform, nres_max))
                    for s in specs]

            entries = []  # (spec index, workload, compiled, fleet, probe, rel)
            wl_cache = {}   # distinct workloads synthesized once for the grid
            for g, spec in enumerate(exec_specs):
                wls, compiled, fleets, probe, rels = _spec_workloads(
                    spec, params, cache=wl_cache)
                for r, w in enumerate(wls):
                    entries.append(
                        (g, w, compiled[r] if compiled is not None else None,
                         fleets[r] if fleets is not None else None, probe,
                         rels[r] if rels is not None else None))

        with profile.span("batching"):
            plats = [exec_specs[g].platform for g, *_ in entries]
            try:
                cols = batching.pad_workloads([w for _, w, *_ in entries],
                                              plats)
            except ValueError as e:      # genuinely incompatible grid
                warnings.warn(
                    f"sweep grid cannot lower to one rectangular batch ({e}); "
                    "falling back to the exact numpy serial loop",
                    RuntimeWarning, stacklevel=2)
                cols = None
            if cols is not None:
                n_max = cols.pop("n_max")
                caps = np.stack([p.capacities for p in plats]).astype(
                    np.int32)
                pol = np.array([exec_specs[g].policy for g, *_ in entries],
                               np.int32)
                uniform_policy = bool((pol == pol[0]).all())
                stacked = self._stack(exec_specs, specs, entries, cols, n_max)
        if cols is None:
            return get_engine("numpy").run_sweep(specs, params)

        with profile.span("upload"):
            inputs = [jax.numpy.asarray(cols[k]) for k in
                      ("arrival", "n_tasks", "task_res", "service",
                       "priority")] + [jax.numpy.asarray(caps)]
        with profile.span("engine"):
            # wait here, so the fetch below times the copy alone
            out = jax.block_until_ready(self._ensemble(
                *inputs, int(pol[0]),
                policies=None if uniform_policy else pol, **stacked))
        with profile.span("fetch"):
            out = {k: np.asarray(v) for k, v in out.items()}
        wall = time.perf_counter() - t0

        results, i = [], 0
        for g, spec in enumerate(specs):
            with profile.span("summaries"):
                recs, sums = [], []
                last_tr = None
                for r in range(spec.n_replicas):
                    _, wl, comp, fl, pr, rl = entries[i + r]
                    tr = batching.batch_trace(
                        out, i + r, wl, spec.platform.capacities,
                        with_scenario=comp is not None, fleet=fl, probe=pr,
                        reliability=rl,
                        n_attempts=batching.attempt_width(comp)
                        if comp is not None else None)
                    last_tr = tr
                    rec = trace.flatten_trace(tr, wl)
                    recs.append(rec)
                    # summarize against the executed (possibly padded)
                    # platform so cost/schedule tensors line up; padded
                    # pools contribute zero everywhere
                    sums.append(_summarize(exec_specs[g], rec, comp, tr,
                                           rel=rl))
                i += spec.n_replicas
                if spec.n_replicas > 1:
                    res = _aggregate_replicas(spec, sums, recs, wall)
            with profile.span("results"):
                if spec.n_replicas == 1:
                    from repro.core.experiment import ExperimentResult
                    from repro.core.runtime import lifecycle_result
                    summary = sums[0]
                    summary["wall_s"] = wall   # the whole grid's wall clock
                    summary["pipelines_per_s"] = \
                        summary["n_pipelines"] / max(wall, 1e-9)
                    res = ExperimentResult(
                        spec, summary, recs[0], wall,
                        lifecycle=lifecycle_result(last_tr),
                        timeline=_probe_timeline(spec, last_tr),
                        trace=last_tr)
                results.append(res)
        return results

    @staticmethod
    def _stack(exec_specs, specs, entries, cols, n_max) -> dict:
        """The ensemble call's keyword tensors for every entry: compiled
        scenarios, fleets, probes and reliability timelines."""
        scen_kw = {}
        if any(c is not None for _, _, c, _, _, _ in entries):
            from repro.ops.scenario import CompiledScenario
            from repro.ops.capacity import static_schedule
            comps = []
            for g, w, c, _, _, _ in entries:
                if c is None:           # inert placeholder row
                    c = CompiledScenario(
                        schedule=static_schedule(
                            exec_specs[g].platform.capacities),
                        attempts=np.ones(w.task_type.shape, np.int64),
                        backoff=vdes._NO_RETRY_BACKOFF)
                comps.append(c)
            horizon = max(s.horizon_s for s in specs)
            services = [cols["service"][i][: w.n]
                        for i, (_, w, *_) in enumerate(entries)]
            scen_kw = batching.stack_scenarios(comps, n_max, horizon,
                                               services=services)
        # lifecycle (fleet/trigger) tensors batch per entry the same way —
        # a whole trigger-policy grid rides ONE jit+vmap call
        fleet_kw = batching.stack_fleets([f for _, _, _, f, _, _ in entries],
                                         n_max)
        # telemetry probes too: probed and unprobed points share one batch
        probe_kw = batching.stack_probes([p for _, _, _, _, p, _ in entries],
                                         [f for _, _, _, f, _, _ in entries])
        # reliability event timelines: padded rows never fire, so points
        # with and without reliability share the one batch
        rel_kw = batching.stack_reliability(
            [rl for _, _, _, _, _, rl in entries])
        return {**scen_kw, **fleet_kw, **probe_kw, **rel_kw}


class JaxCompactEngine(JaxEngine):
    """The batched engine with active-set compaction
    (:mod:`repro.core.compaction`): the wave loop runs in windowed
    segments, finished replicas drop off the batch axis, DONE pipelines
    are gathered out of the working set, and not-yet-arrived pipelines
    are deferred past a per-segment time guard (power-of-two buckets) —
    so the dominant O(N^2) admission term tracks the *active* width, not
    the allocated one. Results are bit-identical to :class:`JaxEngine`
    (twin-tested); only the wall clock differs. Uses the sort-free
    ``"dense"`` admission ranking — the fast CPU path the compaction is
    sized for."""

    name = "jax-compact"

    def __init__(self, segment_waves: int = 256, drain_waves: int = 256,
                 min_rows: int = 8, lookahead: int = 24,
                 admission_sort: str = "dense"):
        self.segment_waves = segment_waves
        self.drain_waves = drain_waves
        self.min_rows = min_rows
        self.lookahead = lookahead
        self.admission_sort = admission_sort
        self.last_log = None     # CompactionLog of the most recent sweep

    def _ensemble(self, *args, **kwargs):
        from repro.core.compaction import (CompactionLog,
                                           simulate_ensemble_compacted)
        if "rel_times" in kwargs:
            raise NotImplementedError(
                "reliability event timelines are not yet supported by the "
                "segmented compaction driver; run reliability specs on the "
                "'jax' (one-call batched) or 'numpy' engine")
        kwargs.setdefault("admission_sort", self.admission_sort)
        self.last_log = CompactionLog()
        return simulate_ensemble_compacted(
            *args, segment_waves=self.segment_waves,
            drain_waves=self.drain_waves, min_rows=self.min_rows,
            lookahead=self.lookahead, log=self.last_log, **kwargs)

    def run(self, spec, params=None):
        # single-replica runs go through the batched path too (B = 1):
        # compaction needs the segmented ensemble driver
        return self.run_sweep([spec], params)[0]

    def run_sweep(self, specs: Sequence, params=None) -> List:
        results = super().run_sweep(specs, params)
        if self.last_log is not None:
            for res in results:
                res.summary["n_compactions"] = self.last_log.n_compactions
                res.summary["compaction_segments"] = self.last_log.n_segments
        return results


class JaxStreamEngine:
    """Streaming engine (``"jax-stream"``): consumes ``spec.source`` (a
    :class:`~repro.stream.TraceSource`) through
    :func:`repro.stream.stream_simulate` — the batched wave loop runs in
    resumable arrival windows, retired pipelines leave the working set at
    window boundaries, and ingestion (synthesis / trace decode + failure
    draws) overlaps the device step. Results are bit-identical to
    materializing the stream and running ``"jax"`` (parity-gated by
    :func:`repro.stream.parity_drift`); memory is bounded by the live
    backlog instead of the stream length.

    Specs without a ``source`` stream their own synthetic workload: the
    engine wraps ``(params, seed, horizon)`` in a
    :class:`~repro.stream.SyntheticSource`. Blockwise synthesis keys
    differ from one-shot ``synthesize_workload`` (block ``b`` folds in its
    index), so set an explicit ``source`` when comparing engines — two
    engines reading the SAME source see identical tensors.
    """

    name = "jax-stream"

    def __init__(self, window_s=None, overlap: bool = True,
                 min_rows: int = 64, admission_sort: str = "select"):
        self.window_s = window_s
        self.overlap = overlap
        self.min_rows = min_rows
        self.admission_sort = admission_sort
        self.last_result = None       # StreamResult of the most recent run

    def _source(self, spec, params):
        if getattr(spec, "source", None) is not None:
            return spec.source
        if spec.workload is not None:
            raise ValueError(
                "jax-stream streams a TraceSource; wrap the pinned workload "
                "in a source (or use engine='jax' for pinned workloads)")
        if params is None:
            raise ValueError("params required unless spec.source is set")
        from repro.stream import SyntheticSource
        return SyntheticSource(params, platform=spec.platform,
                               seed=spec.seed, until_s=spec.horizon_s,
                               interarrival_factor=spec.interarrival_factor)

    def run(self, spec, params=None):
        if spec.n_replicas != 1:
            raise ValueError(
                "jax-stream is a single-replica engine (a stream has one "
                "realization); use n_replicas=1 or the 'jax' engine")
        if getattr(spec, "reliability", None) is not None:
            raise ValueError(
                "jax-stream does not support reliability specs yet (event "
                "timelines span windows); use the 'jax' or 'numpy' engine")
        from repro.core.experiment import ExperimentResult
        from repro.stream import stream_simulate
        sr = stream_simulate(
            self._source(spec, params), spec.platform, policy=spec.policy,
            scenario=spec.scenario, fleet=spec.fleet, trigger=spec.trigger,
            probe=spec.probe, horizon_s=spec.horizon_s,
            window_s=self.window_s, seed=spec.seed, params=params,
            overlap=self.overlap, min_rows=self.min_rows,
            admission_sort=self.admission_sort)
        self.last_result = sr
        summary = dict(sr.summary)
        summary["pipelines_per_s"] = sr.n_pipelines / max(sr.wall_s, 1e-9)
        return ExperimentResult(spec, summary, sr.records, sr.wall_s)

    def run_sweep(self, specs: Sequence, params=None) -> List:
        # streams are stateful and windowed; the grid runs serially (each
        # point still batches its own windows through one jit signature)
        return [self.run(s, params) for s in specs]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_ENGINES = {}


def register_engine(engine: Engine) -> None:
    _ENGINES[engine.name] = engine


def get_engine(name: str) -> Engine:
    try:
        return _ENGINES[name]
    except KeyError:
        raise KeyError(f"unknown engine {name!r}; "
                       f"registered: {sorted(_ENGINES)}") from None


register_engine(NumpyEngine())
register_engine(JaxEngine())
register_engine(JaxCompactEngine())
register_engine(JaxStreamEngine())
