"""Declarative experiment API (paper §IV: "The main entry point for users
is to define an experiment and its parameters, systematically mutating them
in an iterative, exploratory process").

:class:`ExperimentSpec` is the declarative description: a full
:class:`~repro.core.model.PlatformConfig` (arbitrarily many resources, each
with its own cost and routing), workload parameters, an admission policy, an
operational :class:`~repro.ops.scenario.Scenario`, and replication/seed
control. Specs are inert data — execution goes through the
:class:`~repro.core.engines.Engine` protocol (``get_engine(spec.engine)
.run(spec, params)``), so no caller ever branches on the backend.

:class:`Sweep` composes a spec with named axes (spec fields,
``"capacity:<resource>"`` shorthands, scenario families, closed-loop
``"controller"`` gains, policies) into a Cartesian grid. On the JAX engine
the *entire grid* lowers through :mod:`repro.core.batching` into one
``jit``+``vmap`` call; the numpy engine falls back to an exact serial loop
for long-horizon runs.

The legacy two-resource ``Experiment`` dataclass and the
``sweep(base, params, grid)`` helper (deprecated in the previous release)
have been removed — see the README migration guide.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import os
from typing import Dict, List, Mapping, Optional, Sequence

import numpy as np

from repro.core import des, trace
from repro.core import model as M
from repro.core.fitting import SimulationParams
from repro.core.runtime import FleetSpec, TriggerSpec
from repro.obs import profile
from repro.ops.scenario import Scenario

_UNSET = object()   # sentinel: "controller" axis absent vs explicitly None


@dataclasses.dataclass
class ExperimentSpec:
    """A declarative experiment over an arbitrary platform.

    ``platform`` replaces the legacy ``compute_capacity``/
    ``learning_capacity`` pair: any number of resources, each carrying its
    own capacity and cost rate, plus task-type routing and datastore
    parameters. ``workload`` optionally pins a pre-materialized
    :class:`~repro.core.model.Workload` (then no synthesis happens and
    ``interarrival_factor`` is ignored) — the hook deterministic parity
    tests and trace replays use. ``source`` (a
    :class:`~repro.stream.TraceSource`) is the *streamed* form of the same
    hook: the ``"jax-stream"`` engine pulls workload blocks from it
    incrementally and simulates in resumable windows with bounded memory,
    while every other engine materializes the source into a pinned
    workload once (deterministic re-iteration makes the two paths
    bit-identical).

    ``fleet`` + ``trigger`` declare the *run-time view* (Fig 7): a fleet of
    deployed models under drift and the execution trigger that retrains
    them. The lifecycle loop runs INSIDE the engines (the fifth kernel
    stage — see :mod:`repro.core.runtime`): drift evaluated as ``[M]``
    tensor ops at a compile-time tick grid, triggered retraining pipelines
    activated from a preallocated pool, redeploys resetting the drift
    state. ``trigger`` defaults to ``TriggerSpec()`` when a fleet is set;
    without a ``fleet`` it is ignored.

    ``probe`` (a :class:`~repro.obs.probes.ProbeSpec`) turns on in-loop
    telemetry: both engines sample live state (queue depth, busy slots,
    effective capacity, controller delta, fleet perf/staleness) at the
    probe's tick grid, surfaced as ``ExperimentResult.timeline``.
    """

    name: str
    platform: M.PlatformConfig = dataclasses.field(
        default_factory=M.PlatformConfig)
    horizon_s: float = 7 * 24 * 3600.0
    interarrival_factor: float = 1.0
    policy: int = des.POLICY_FIFO
    seed: int = 0
    n_replicas: int = 1
    engine: str = "numpy"  # "numpy" | "jax"
    scenario: Optional[Scenario] = None
    workload: Optional[M.Workload] = None
    fleet: Optional[FleetSpec] = None
    trigger: Optional[TriggerSpec] = None
    probe: Optional[object] = None   # repro.obs.probes.ProbeSpec
    # a repro.reliability.ReliabilitySpec: correlated failure domains,
    # finite repair crews, spot eviction, checkpointed retrains — compiled
    # per replica (seed + 1000*r) into the engines' control-stage event
    # timeline (see repro.reliability.compile)
    reliability: Optional[object] = None
    # a repro.stream.TraceSource: the streamed alternative to ``workload``.
    # The "jax-stream" engine consumes it incrementally (windowed, bounded
    # memory); every other engine materializes it into a pinned workload
    # once (bit-identical — TraceSource iteration is deterministic).
    source: Optional[object] = None

    def with_(self, **kw) -> "ExperimentSpec":
        """Functional update (``dataclasses.replace`` with axis shorthands):
        plain field names, ``**{"capacity:<resource>": n}`` to resize one
        pool of the platform, ``**{"trigger:<field>": v}`` /
        ``**{"fleet:<field>": v}`` / ``**{"probe:<field>": v}`` to update
        (or ``**{"reliability:<field>": v}``) to update
        one field of the lifecycle/telemetry/reliability specs (creating default
        ``TriggerSpec()`` / ``FleetSpec()`` / ``ProbeSpec()`` if the
        spec has none — the ``"trigger:drift_threshold"`` /
        ``"trigger:cooldown_s"`` / ``"probe:interval_s"`` Sweep axes), or
        ``controller=<ReactiveController>`` to set the closed-loop
        controller on the spec's scenario (creating an otherwise-empty
        scenario if the spec has none). ``controller`` is applied after
        every other key, so combining it with a ``scenario`` axis composes
        the same way regardless of kwarg order."""
        out = self
        ctrl = kw.pop("controller", _UNSET)
        for k, v in kw.items():
            if k.startswith("capacity:"):
                out = dataclasses.replace(
                    out, platform=out.platform.with_capacity(
                        k.split(":", 1)[1], v))
            elif k.startswith("trigger:"):
                trig = out.trigger if out.trigger is not None \
                    else TriggerSpec()
                out = dataclasses.replace(out, trigger=dataclasses.replace(
                    trig, **{k.split(":", 1)[1]: v}))
            elif k.startswith("fleet:"):
                fl = out.fleet if out.fleet is not None else FleetSpec()
                out = dataclasses.replace(out, fleet=dataclasses.replace(
                    fl, **{k.split(":", 1)[1]: v}))
            elif k.startswith("probe:"):
                from repro.obs.probes import ProbeSpec
                pr = out.probe if out.probe is not None else ProbeSpec()
                out = dataclasses.replace(out, probe=dataclasses.replace(
                    pr, **{k.split(":", 1)[1]: v}))
            elif k.startswith("reliability:"):
                from repro.reliability import ReliabilitySpec
                rl = out.reliability if out.reliability is not None \
                    else ReliabilitySpec()
                out = dataclasses.replace(
                    out, reliability=dataclasses.replace(
                        rl, **{k.split(":", 1)[1]: v}))
            else:
                out = dataclasses.replace(out, **{k: v})
        if ctrl is not _UNSET and not (ctrl is None and out.scenario is None):
            # (a None controller on a scenario-less spec stays pristine)
            sc = out.scenario if out.scenario is not None \
                else Scenario(name="controller")
            out = dataclasses.replace(
                out, scenario=dataclasses.replace(sc, controller=ctrl))
        return out

    def to_spec(self) -> "ExperimentSpec":
        return self


def as_spec(exp) -> "ExperimentSpec":
    """Normalize anything exposing ``to_spec`` to an :class:`ExperimentSpec`."""
    return exp.to_spec()


@dataclasses.dataclass
class ExperimentResult:
    experiment: ExperimentSpec
    summary: Dict
    records: trace.TaskRecords
    wall_s: float
    replica_summaries: Optional[List[Dict]] = None
    # model-lifecycle view (perf/staleness timelines at tick resolution,
    # trigger/redeploy events) — set for single-replica runs of specs with
    # a FleetSpec; replica ensembles aggregate lifecycle scalars into the
    # summary instead
    lifecycle: Optional[object] = None
    # in-loop telemetry view (a repro.obs.probes.ProbeTimeline: named
    # channel timelines at the probe's tick grid) — set for single-replica
    # runs of specs with a ProbeSpec
    timeline: Optional[object] = None
    # the engine's raw SimTrace (every engine-recorded buffer and the wave
    # count) — set for single-replica runs of the numpy and batched JAX
    # engines; what engine-parity checks compare
    trace: Optional[M.SimTrace] = None

    def save(self, directory: str) -> None:
        os.makedirs(directory, exist_ok=True)
        self.records.save(os.path.join(directory, "records.npz"))
        exp = self.experiment
        if getattr(exp, "workload", None) is not None:
            exp = dataclasses.replace(exp, workload=None)  # tensors -> npz
        if getattr(exp, "source", None) is not None:
            exp = dataclasses.replace(
                exp, source=getattr(exp.source, "name", "source"))
        meta = {"experiment": dataclasses.asdict(exp),
                "summary": self.summary, "wall_s": self.wall_s}
        with open(os.path.join(directory, "meta.json"), "w") as f:
            json.dump(meta, f, indent=2, default=_json_default)


def _json_default(x):
    if isinstance(x, np.ndarray):
        return x.tolist()
    try:
        return float(x)
    except (TypeError, ValueError):
        return str(x)


def run_experiment(exp, params: Optional[SimulationParams] = None
                   ) -> ExperimentResult:
    """Run one experiment spec on its declared engine."""
    from repro.core.engines import get_engine
    spec = as_spec(exp)
    res = get_engine(spec.engine).run(spec, params)
    res.experiment = exp            # hand back the caller's own object
    return res


# ---------------------------------------------------------------------------
# sweeps
# ---------------------------------------------------------------------------

def _fmt_axis_value(v):
    return getattr(v, "name", v)    # scenarios print by name, not repr


@dataclasses.dataclass
class Sweep:
    """A Cartesian grid of experiments, compiled as ONE batch when possible.

    ``axes`` maps axis names to value lists. An axis name is either a spec
    field (``interarrival_factor``, ``policy``, ``scenario``, ``seed``,
    ``platform``, ...), the shorthand ``"capacity:<resource name>"`` which
    resizes one pool of the platform (works for any resource count), or
    ``"controller"`` — a list of
    :class:`~repro.ops.capacity.ReactiveController` gains (or None) set on
    each point's scenario, so a closed-loop controller-gain grid lowers to
    one batched call.

    ``run`` dispatches through the Engine protocol: on the JAX engine the
    whole grid (heterogeneous capacities, interarrival factors, policies,
    controller gains, and per-point operational scenarios, times
    ``n_replicas`` Monte-Carlo replicas each) executes as a single
    ``jit``+``vmap`` ``simulate_ensemble`` call; the numpy engine runs an
    exact serial loop.

    A *ragged* platform grid (e.g. a ``"platform"`` axis mixing 2- and
    3-resource platforms) is auto-padded to the common resource superset —
    padded pools are inert (zero capacity, zero cost rate), so ragged grids
    stay on the batched jit+vmap path. Only genuinely incompatible grids
    (e.g. pinned workloads disagreeing on ``max_tasks``) warn and fall back
    to the exact numpy serial loop.

    Under a closed-loop ``"controller"`` axis, each point's summary charges
    the engine-recorded *realized* capacity timeline (see
    :func:`repro.ops.accounting.realized_schedule`) and reports the planned
    figures alongside (``planned_total_cost``,
    ``realized_vs_planned_cost_delta``).
    """

    base: ExperimentSpec
    axes: Mapping[str, Sequence]

    def points(self) -> List[ExperimentSpec]:
        base = as_spec(self.base)
        names = list(self.axes)
        pts = []
        for combo in itertools.product(*[self.axes[k] for k in names]):
            spec = base.with_(**dict(zip(names, combo)))
            label = ",".join(f"{k.split(':', 1)[-1]}={_fmt_axis_value(v)}"
                             for k, v in zip(names, combo))
            pts.append(dataclasses.replace(
                spec, name=f"{base.name}/{label}" if label else base.name))
        return pts

    def run(self, params: Optional[SimulationParams] = None
            ) -> List[ExperimentResult]:
        from repro.core.engines import get_engine
        with profile.span("sweep"):
            with profile.span("points"):
                specs = self.points()
            # an "engine" axis dispatches each point on its own backend
            # (each engine still batches its own group); order is preserved
            results: List[Optional[ExperimentResult]] = [None] * len(specs)
            for name in dict.fromkeys(s.engine for s in specs):
                idx = [i for i, s in enumerate(specs) if s.engine == name]
                for i, r in zip(idx, get_engine(name).run_sweep(
                        [specs[i] for i in idx], params)):
                    results[i] = r
        return results
