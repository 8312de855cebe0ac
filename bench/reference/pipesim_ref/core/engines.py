"""The engine helpers the numpy reference path shares: per-spec workload,
scenario, fleet, reliability and probe preparation, fold of reliability
task effects, and per-replica summaries. Copied from the repro package's
core/engines.py; the jax engines and workload synthesis are left out (the
benchmark pins every workload)."""
from __future__ import annotations

import dataclasses

import numpy as np

from pipesim_ref.core import trace

# _NO_RETRY_BACKOFF: the no-retry backoff row of an inert scenario
_NO_RETRY_BACKOFF = (0.0, 2.0, 3600.0)


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------

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
        from pipesim_ref.ops.capacity import static_schedule
        from pipesim_ref.ops.scenario import CompiledScenario
        comp = CompiledScenario(
            schedule=static_schedule(plat.capacities),
            attempts=np.ones(w.task_type.shape, np.int64),
            backoff=_NO_RETRY_BACKOFF)
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
    :class:`~pipesim_ref.obs.probes.ProbeSpec`; probes are deterministic, so one
    compile covers every replica) + per-replica compiled reliability
    timelines (None without a
    :class:`~pipesim_ref.reliability.ReliabilitySpec`).

    Seed conventions match the historical ``run_experiment`` exactly (single
    replica: PRNGKey(seed); ensembles: split(PRNGKey(seed), R); scenario /
    fleet / reliability replica r compiles with seed + 1000*r) so batched
    and serial execution see identical random draws. ``cache`` (dict)
    shares synthesis across grid points whose workload axes agree.

    With a :class:`~pipesim_ref.core.runtime.FleetSpec` on the spec, each replica
    workload is *extended* with the latent retraining pool BEFORE the
    scenario compiles — failure/retry draws then cover retraining pipelines
    too, identically in both engines. Reliability compiles after the same
    extension (spot-eviction draws cover retraining pipelines), and its
    task-level effects (eviction retries, checkpointed retry scaling) fold
    into the compiled scenario via :func:`_fold_reliability` — composition
    with ``fail_holds_frac`` is rejected by
    :func:`pipesim_ref.reliability.check_no_double_apply`.
    """
    if spec.workload is None:
        raise ValueError("the reference runs pinned workloads only")
    wls = [spec.workload] * spec.n_replicas
    fleets = None
    if getattr(spec, "fleet", None) is not None:
        from pipesim_ref.core.runtime import TriggerSpec
        from pipesim_ref.ops.scenario import compile_fleet
        trig = spec.trigger if spec.trigger is not None else TriggerSpec()
        fleets, ext = [], []
        for r, w in enumerate(wls):
            cf, w2 = compile_fleet(spec.fleet, trig, w, spec.platform,
                                   spec.horizon_s,
                                   seed=spec.seed + 1000 * r, params=params)
            fleets.append(cf)
            ext.append(w2)
        wls = ext
    rels = None
    if getattr(spec, "reliability", None) is not None:
        from pipesim_ref.reliability import (check_no_double_apply,
                                       compile_reliability)
        check_no_double_apply(spec.reliability, spec.scenario)
        rels = [compile_reliability(spec.reliability, w, spec.platform,
                                    spec.horizon_s,
                                    seed=spec.seed + 1000 * r)
                for r, w in enumerate(wls)]
    compiled = None
    if spec.scenario is not None:
        compiled = [spec.scenario.compile(w, spec.platform, spec.horizon_s,
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
        from pipesim_ref.obs.probes import compile_probe
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
    :class:`~pipesim_ref.reliability.CompiledReliability`) folds in as the
    ``availability`` block (downtime integrals, repair-queue stats, spot
    cost split)."""
    realized = None
    if compiled is not None and tr is not None:
        from pipesim_ref.ops.accounting import realized_schedule
        realized = realized_schedule(tr, compiled)
        if realized is compiled.schedule:
            realized = None            # planned == realized: legacy path
    lifecycle = None
    if tr is not None and getattr(tr, "fleet_perf", None) is not None:
        from pipesim_ref.ops.accounting import lifecycle_summary
        lifecycle = lifecycle_summary(tr)
    s = trace.summarize(
        rec, spec.platform.capacities, spec.horizon_s,
        schedule=compiled.schedule if compiled is not None else None,
        cost_rates=spec.platform.cost_rates if compiled is not None else None,
        slo=spec.scenario.slo if spec.scenario is not None else None,
        realized=realized, lifecycle=lifecycle)
    if rel is not None:
        from pipesim_ref.ops.accounting import availability_summary
        s["availability"] = availability_summary(rel, spec.platform, tr=tr)
    return s


def _single_result(spec, wl, compiled, tr, wall, rel=None):
    from pipesim_ref.core.experiment import ExperimentResult
    from pipesim_ref.core.runtime import lifecycle_result
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
    from pipesim_ref.obs.probes import ProbeTimeline
    return ProbeTimeline.from_trace(tr, spec.platform)


def _aggregate_replicas(spec, rep_sums, recs, wall):
    """Monte-Carlo summary across replicas (the old ``_run_ensemble`` tail)."""
    from pipesim_ref.core.experiment import ExperimentResult
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
