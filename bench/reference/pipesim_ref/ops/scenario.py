"""Operational scenario: capacity policy + failure/retry + outages + SLOs.

A :class:`Scenario` is the declarative description an experiment carries
(:class:`pipesim_ref.core.experiment.ExperimentSpec` has a ``scenario`` field, and
:class:`~pipesim_ref.core.experiment.Sweep` can grid over scenarios and over
closed-loop ``"controller"`` gains). ``compile`` materializes it against a
concrete workload/platform/horizon into a :class:`CompiledScenario` — plain
tensors (capacity schedule, pre-sampled attempt counts, backoff constants,
the flat ControllerParams vector) that both engines consume: the numpy
engine directly, the JAX engine as ``jit``/``vmap``-friendly device arrays.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from pipesim_ref.core import model as M
from pipesim_ref.core import metrics as MET
from pipesim_ref.ops.accounting import SLOConfig
from pipesim_ref.ops.capacity import (CapacitySchedule, StaticCapacity,
                                apply_capacity_deltas, static_schedule)
from pipesim_ref.ops.failures import FailureModel, OutageModel, RetryPolicy


@dataclasses.dataclass(frozen=True)
class CompiledScenario:
    """Scenario materialized for one workload: what the engines execute.

    ``schedule`` is the *planned* capacity timeline; under a closed-loop
    ``controller`` the engines additionally record the realized action
    timeline (``SimTrace.ctrl_times``/``ctrl_caps``), which
    :func:`pipesim_ref.ops.accounting.realized_schedule` splices back onto this
    schedule for exact provisioned cost/utilization accounting."""

    schedule: CapacitySchedule
    attempts: np.ndarray                      # [N, T] i64 attempts per task
    backoff: Tuple[float, float, float] = (30.0, 2.0, 1800.0)
    # [N, T, A] per-attempt service times (retry resampling); None = every
    # attempt re-runs with the task's base service time (seed behavior)
    attempt_service: Optional[np.ndarray] = None
    # flat [C] ControllerParams tensor (closed-loop in-engine control; see
    # pipesim_ref.ops.capacity.ReactiveController.compile); None = no controller
    controller: Optional[np.ndarray] = None
    # slot-holding fraction of a *failing* attempt (partial-progress
    # failures); 1.0 = hold for the full service time (historical semantics)
    fail_holds_frac: float = 1.0

    def __post_init__(self):
        if not 0.0 < self.fail_holds_frac <= 1.0:
            raise ValueError(f"fail_holds_frac must be in (0, 1], got "
                             f"{self.fail_holds_frac}")

    @property
    def cap_times(self) -> np.ndarray:
        return self.schedule.times

    @property
    def cap_vals(self) -> np.ndarray:
        return self.schedule.caps


@dataclasses.dataclass(frozen=True)
class Scenario:
    """Declarative operational scenario. All parts optional — an empty
    Scenario compiles to the static platform (engine-identical to no
    scenario at all)."""

    name: str = "static"
    capacity: Optional[object] = None         # a capacity policy (.build(...))
    failures: Optional[FailureModel] = None
    outages: Optional[OutageModel] = None
    slo: Optional[SLOConfig] = None
    # closed-loop in-engine controller (pipesim_ref.ops.capacity.ReactiveController)
    # — composes with `capacity` as a delta on top of the planned schedule
    controller: Optional[object] = None

    def compile_schedule(self, platform: M.PlatformConfig, horizon_s: float,
                         seed: int = 0, workload: Optional[M.Workload] = None,
                         policy: int = 0) -> CapacitySchedule:
        """Capacity schedule only (stable across co-simulation windows)."""
        base = platform.capacities
        pol = self.capacity or StaticCapacity()
        sched = pol.build(base, horizon_s, workload=workload,
                          platform=platform, policy=policy)
        if self.outages is not None:
            rng = np.random.default_rng(np.random.SeedSequence([seed, 0xD0]))
            sched = apply_capacity_deltas(
                sched, self.outages.sample_outages(rng, horizon_s, base))
        return sched

    def compile(self, workload: M.Workload, platform: M.PlatformConfig,
                horizon_s: float, seed: int = 0, policy: int = 0,
                schedule: Optional[CapacitySchedule] = None
                ) -> CompiledScenario:
        """Materialize against ``workload``. Pass a pre-built ``schedule`` to
        reuse one across windows while re-sampling failures per window."""
        if schedule is None:
            schedule = self.compile_schedule(platform, horizon_s, seed=seed,
                                             workload=workload, policy=policy)
        attempt_service = None
        fail_holds_frac = 1.0
        if self.failures is not None:
            rng = np.random.default_rng(np.random.SeedSequence([seed, 0xF0]))
            attempts = self.failures.sample_attempts(rng, workload)
            backoff = self.failures.retry.backoff
            fail_holds_frac = float(self.failures.fail_holds_frac)
            if self.failures.resample_service:
                rng_svc = np.random.default_rng(
                    np.random.SeedSequence([seed, 0xA5]))
                attempt_service = self.failures.sample_attempt_services(
                    rng_svc, workload.service_time(platform.datastore))
        else:
            attempts = np.ones(workload.task_type.shape, np.int64)
            backoff = RetryPolicy().backoff
        controller = None
        if self.controller is not None:
            controller = self.controller.compile(platform.capacities,
                                                 horizon_s)
        return CompiledScenario(schedule=schedule, attempts=attempts,
                                backoff=backoff,
                                attempt_service=attempt_service,
                                controller=controller,
                                fail_holds_frac=fail_holds_frac)


def compile_static(workload: M.Workload,
                   platform: M.PlatformConfig) -> CompiledScenario:
    """The no-op scenario (useful as an explicit baseline)."""
    return CompiledScenario(schedule=static_schedule(platform.capacities),
                            attempts=np.ones(workload.task_type.shape,
                                             np.int64))


# ---------------------------------------------------------------------------
# Model lifecycle (run-time view): FleetSpec/TriggerSpec -> flat tensors
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CompiledFleet:
    """Fleet + trigger materialized for one workload: what the engines'
    fifth kernel stage executes. All randomness is presampled here (exactly
    like the failure-attempt tensors), so the jitted loop stays pure:

    - ``fleet [M, FLEET_FIELDS]``: per-model drift-process parameters;
    - ``trig [TRIG_FIELDS]``: the trigger header (interval, cooldown,
      t_first, t_end, drift threshold, arrival delay) — the drift-evaluation
      tick grid uses the same f32 walk as the controller's;
    - ``obs_noise [E, M]``: per-tick observation noise;
    - ``drift_inc [E, M]``: presampled per-tick drift-loss increments —
      gradual drift ``rate * Δt`` PLUS the sudden-drift compound-Poisson
      draws for the interval. The engines *accumulate* these with plain f32
      adds (no runtime ``rate * dt`` product, which XLA would contract into
      an FMA and break bit-parity with numpy); drift therefore accrues per
      completed evaluation interval, and the partial interval behind a
      redeploy is dropped — a freshly redeployed model stays at its new
      ``perf0`` until its first full interval elapses;
    - ``pool_gain [P]``: per-pool-slot redeploy performance gains;
    - ``pool_base``: the extended workload's first latent retraining-pool
      row (``compile_fleet`` appends P train->evaluate->deploy pipelines
      with ``inf`` arrivals — the compile-time injection budget).
    """

    fleet: np.ndarray
    trig: np.ndarray
    obs_noise: np.ndarray
    drift_inc: np.ndarray
    pool_gain: np.ndarray
    pool_base: int
    tick_times: np.ndarray     # [E] f64 (values of the f32 tick grid)

    @property
    def n_models(self) -> int:
        return int(self.fleet.shape[0])

    @property
    def n_pool(self) -> int:
        return int(self.pool_gain.shape[0])

    @property
    def n_ticks(self) -> int:
        return int(self.tick_times.shape[0])


def compile_fleet(fleet_spec, trigger, workload: M.Workload,
                  platform: M.PlatformConfig, horizon_s: float,
                  seed: int = 0, params=None):
    """Materialize a :class:`~pipesim_ref.core.runtime.FleetSpec` +
    :class:`~pipesim_ref.core.runtime.TriggerSpec` against ``workload``: returns
    ``(CompiledFleet, extended_workload)`` where the extended workload is
    the exogenous pipelines followed by the latent retraining pool.

    Retrain durations come from ``trigger.retrain_durations`` when pinned
    (deterministic template — what integer-time parity tests use), else
    they are drawn per task type from the fitted ``params`` distributions.
    """
    import jax as _jax

    from pipesim_ref.core import runtime as RT
    from pipesim_ref.core.des import TRIG_FIELDS, fleet_tick_grid

    if trigger.interval_s <= 0:
        raise ValueError("TriggerSpec.interval_s must be > 0")
    fleet = RT.fleet_tensor(fleet_spec, seed)
    M_ = fleet.shape[0]
    t_first = float(np.float32(trigger.interval_s))
    ticks = fleet_tick_grid(trigger.interval_s, t_first, horizon_s)
    E = ticks.shape[0]
    if E == 0:
        raise ValueError(
            f"TriggerSpec.interval_s={trigger.interval_s} exceeds the "
            f"horizon {horizon_s}; no drift-evaluation tick would ever fire")
    trig = np.zeros(TRIG_FIELDS, np.float32)
    trig[:] = (trigger.interval_s, trigger.cooldown_s, t_first, horizon_s,
               trigger.drift_threshold, trigger.arrival_delay_s)

    rng = np.random.default_rng(np.random.SeedSequence([max(seed, 0), 0xF1]))
    obs = (rng.normal(0.0, trigger.obs_noise, (E, M_))
           if trigger.obs_noise > 0 else np.zeros((E, M_)))
    # drift-loss increment per tick: gradual rate * Δt plus the sudden-drift
    # compound Poisson — N ~ Poisson(rate * dt) jumps, each Exp(scale), so
    # the per-tick jump sum is Gamma(N, scale)
    widths = np.diff(np.concatenate([[0.0], ticks]))
    lam = (fleet[None, :, MET.FLEET_JUMP_RATE].astype(np.float64)
           * widths[:, None])
    n_jumps = rng.poisson(lam)
    drift_inc = (fleet[None, :, MET.FLEET_GRAD_RATE].astype(np.float64)
                 * widths[:, None]
                 + rng.gamma(n_jumps,
                             fleet[None, :, MET.FLEET_JUMP_SCALE]
                             .astype(np.float64)))

    # injection budget: at most one fire per model per cooldown window (and
    # never more than one per tick)
    if trigger.max_retrains is not None:
        P = int(trigger.max_retrains)
    else:
        eff_cd = max(trigger.cooldown_s, trigger.interval_s)
        per_model = int(np.floor(max(horizon_s - t_first, 0.0) / eff_cd)) + 1
        P = M_ * min(per_model, E)
    gains = rng.normal(trigger.perf_gain_mu, trigger.perf_gain_sigma, P)

    if trigger.retrain_durations is not None:
        exec3 = np.tile(np.asarray(trigger.retrain_durations,
                                   np.float64)[None, :], (P, 1))
        pool = RT._pool_workload(P, workload.max_tasks, platform, exec3)
    elif params is not None:
        pool = RT.synthesize_retrain_workload(
            params,
            _jax.random.PRNGKey((seed * 2654435761 + 0x5EED) % (1 << 31)),
            P, platform, workload.max_tasks)
    else:
        raise ValueError(
            "compile_fleet needs fitted params to draw retrain durations "
            "(or pin TriggerSpec.retrain_durations)")
    ext = RT._concat_workloads(workload, pool)
    compiled = CompiledFleet(
        fleet=fleet, trig=trig,
        obs_noise=obs.astype(np.float32),
        drift_inc=drift_inc.astype(np.float32),
        pool_gain=gains.astype(np.float32),
        pool_base=int(workload.n),
        tick_times=ticks)
    return compiled, ext
