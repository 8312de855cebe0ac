"""Vectorized discrete-event engine in pure JAX (DESIGN.md §3).

State is a struct-of-arrays over pipelines; a ``lax.while_loop`` advances the
global clock to the next event time and retires *all* events at that instant.
Each loop iteration (a **wave**) is composed of up to six named kernel stages:

  1. **event selection** (``_select_events``): the global next-event time
     ``t_star`` is the minimum over pending task events, the next scheduled
     capacity change, and the next controller evaluation tick;
  2. **completion/retry** (``_completion_stage``): finishes release slots,
     successful attempts advance the pipeline, failed attempts re-enter the
     arrival path after a deterministic bounded exponential backoff
     ``min(base * mult**k, cap)``; arrivals and successor tasks enqueue;
  3. **control** (``_control_stage``): the pending piecewise-constant
     capacity change applies, then the pending *reliability event* (if a
     compiled reliability timeline is given: correlated domain outages,
     repair-queue capacity returns, spot evictions — pre-sampled by
     :func:`repro.reliability.compile.compile_reliability`) applies its
     capacity delta and is recorded into a preallocated ``[RV, 1+nres]``
     event buffer, then the *closed-loop controller* (if configured)
     observes the live queue lengths and adjusts capacity — entirely inside
     the jitted loop, no Python-level replanning. Each integer-target move
     is appended to a preallocated ``[E, 1+nres]`` action buffer (the
     *realized capacity timeline*; ``E`` bounded by the compile-time
     evaluation-tick grid) so cost/utilization accounting can charge what
     was actually provisioned;
  4. **admission** (``_admission_stage``): one ranked admission round per
     resource via a single fused lexicographic ``lax.sort`` over
     ``(resource, policy key, enqueue wave)`` keys (``num_keys=3``) —
     replacing three chained stable argsorts (kept as the ``"chained"``
     reference path for equivalence tests and benchmarks);
  5. **fleet** (``_fleet_stage``, optional): the *model lifecycle* (run-time
     view, Fig 7). Retraining pipelines that completed this wave redeploy
     their model (drift state resets); at compile-time drift-evaluation
     ticks (the same f32 tick-grid machinery as the controller) the ``[M]``
     drift algebra from :mod:`repro.core.metrics` runs, drift triggers
     crossing their threshold activate latent pipelines from a preallocated
     retraining pool (compile-time injection budget), and trigger/redeploy
     actions append to the shared action timeline. All randomness
     (observation noise, sudden-drift increments, redeploy gains, retrain
     durations) is presampled outside the jitted loop;
  6. **probe** (``_probe_stage``, optional): *in-loop telemetry*. At
     compile-time probe ticks (the same f32 tick-grid machinery again) the
     settled post-wave state — per-resource queue depth, busy slots,
     effective capacity, controller delta, fleet min-perf/max-staleness —
     is sampled in f32 into a preallocated ``[E, K]`` buffer carried
     through the loop (see :mod:`repro.obs.probes`). Physics-invisible and
     parity-gated: the numpy engine mirrors the sampling op-for-op.

Semantics match ``repro.core.des`` exactly — same wave ordering, same
FIFO/PRIORITY/SJF keys — verified wave-for-wave by tests on integer-time
workloads, including under operational scenarios:

  - **capacity schedules**: a time-indexed ``[K, nres]`` tensor of
    piecewise-constant capacities; decreases never preempt — free goes
    negative and admission stalls until jobs drain;
  - **closed-loop controller**: a flat ``[C]`` ``ControllerParams`` tensor
    (see :func:`repro.ops.capacity.ReactiveController.compile`; layout
    ``[interval, cooldown, t_first, t_end]`` then per-resource
    ``[high, low, step, min_cap, max_cap, base]``). At every evaluation tick
    the controller compares the queued-jobs-per-effective-slot ratio against
    the per-resource watermarks and scales its continuous capacity state
    multiplicatively (clamped to ``[min_cap, max_cap]``); the rounded integer
    target composes with the schedule as a *delta*: effective capacity =
    schedule(t) + (target - base). Any movement of the continuous state
    starts the cooldown window, during which evaluations are suppressed.
    Controller arithmetic is float32 in BOTH engines, so decisions agree
    bit-for-bit. Evaluations stop after ``t_end``, which bounds the loop
    even when a scale-to-zero controller stalls the queue forever;
  - **failure/retry injection**: a pre-sampled ``attempts[N, T]`` tensor
    (every random draw happens outside the jitted function). A failing
    attempt holds its slot for ``fail_holds_frac * service`` (default 1.0:
    the full service time — partial-progress failures model a task that
    crashes part-way through).

Because the function stays pure jnp, it can be ``jax.vmap``-ed over a replica
axis and ``jax.jit``-ed: Monte-Carlo ensembles of *operational scenarios*
(per-replica capacity schedules, controller gains, failure draws, and
backoff constants) run as one batched program on one device (see
``benchmarks/controller_bench.py`` and
``examples/autoscaling_scenarios.py``).

Time is float32; recommended horizons <= ~30 days keep the clock ulp below
0.5 s (DESIGN.md §3 numerics note). FIFO ordering never depends on float
ties: ranking uses the integer enqueue-wave counter.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import model as M
from repro.core.des import (CTRL_FIELDS, CTRL_HEADER, CTRL_INF,
                            CTRL_INTERVAL, FLEET_ACT_REDEPLOY,
                            FLEET_ACT_TRIGGER, POLICY_FIFO, POLICY_PRIORITY,
                            POLICY_SJF, PROBE_INTERVAL, PROBE_N_MODELS,
                            PROBE_T_END, PROBE_T_FIRST, TRIG_FIELDS,
                            TRIG_INTERVAL, probe_channel_count,
                            unpack_controller)
from repro.core.metrics import (FLEET_PERF0, fleet_performance_acc,
                                fleet_staleness)

INF = jnp.float32(CTRL_INF)   # the ONE shared f32 "never" sentinel

# phases
_NOT_ARRIVED, _QUEUED, _RUNNING, _DONE = 0, 1, 2, 3

_NO_RETRY_BACKOFF = (0.0, 2.0, 3600.0)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class VWorkload:
    """Device-resident workload tensors (one replica). ``attempts`` is the
    pre-sampled service-attempt count per task for failure/retry scenarios
    (None = one attempt each)."""

    arrival: jnp.ndarray    # [N] f32
    n_tasks: jnp.ndarray    # [N] i32
    task_res: jnp.ndarray   # [N, T] i32
    service: jnp.ndarray    # [N, T] f32
    priority: jnp.ndarray   # [N] f32
    attempts: Optional[jnp.ndarray] = None   # [N, T] i32

    def tree_flatten(self):
        return ((self.arrival, self.n_tasks, self.task_res, self.service,
                 self.priority, self.attempts), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @staticmethod
    def from_workload(wl: M.Workload, platform: Optional[M.PlatformConfig] = None,
                      attempts: Optional[np.ndarray] = None) -> "VWorkload":
        platform = platform or M.PlatformConfig()
        return VWorkload(
            arrival=jnp.asarray(wl.arrival, jnp.float32),
            n_tasks=jnp.asarray(wl.n_tasks, jnp.int32),
            task_res=jnp.asarray(wl.task_res, jnp.int32),
            service=jnp.asarray(wl.service_time(platform.datastore), jnp.float32),
            priority=jnp.asarray(wl.priority, jnp.float32),
            attempts=None if attempts is None
            else jnp.asarray(attempts, jnp.int32),
        )


def _cummax(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.associative_scan(jnp.maximum, x)


def _onehot_cols(tcl: jnp.ndarray, T: int) -> jnp.ndarray:
    """``[N, T]`` one-hot of each pipeline's (clipped) current task column.

    The wave loop's ``[N, T]`` record updates and lookups all route through
    this mask instead of vector-index gather/scatter: on CPU a vmapped
    ``lax.scatter`` lowers to a serial per-row loop (~14 us/wave *each* at
    N=134) while the equivalent dense masked ``where`` fuses with its
    neighbours (<1 us/wave) — the difference between the batched engine
    losing and beating serial numpy. Values are bit-identical: exactly one
    column is hot per row."""
    return tcl[:, None] == jnp.arange(T, dtype=jnp.int32)[None, :]


def _take_cols(x: jnp.ndarray, oh: jnp.ndarray, fill) -> jnp.ndarray:
    """``x[i, tcl[i]]`` as a gather-free dense reduction: mask everything
    but the hot column to ``fill`` (strictly below any real value) and
    ``max`` over columns. Exactly one element survives per row, so the
    result is bit-identical to the gather and the reduction is
    order-independent (auditor-clean, unlike a float sum)."""
    return jnp.max(jnp.where(oh, x, fill), axis=1)


def _onehot_rows(buf: jnp.ndarray, idx: jnp.ndarray,
                 vals: jnp.ndarray) -> jnp.ndarray:
    """``buf[idx[p]] = vals[p]`` as a dense one-hot write (the scatter-free
    twin of ``.at[idx].set(vals, mode="drop")``: a traced-index scatter
    serializes per replica under vmap on CPU). Rows with
    ``idx == buf.shape[0]`` drop. Requirements, both guaranteed at the call
    sites: live indices are unique (each target row has exactly one
    writer, so the masked max selects *the* value bit-exactly) and values
    are nonnegative (strictly above the ``-INF`` fill)."""
    K = buf.shape[0]
    m = idx[:, None] == jnp.arange(K, dtype=jnp.int32)[None, :]   # [P, K]
    hit = jnp.any(m, axis=0)
    upd = jnp.max(jnp.where(m[:, :, None], vals[:, None, :], -INF), axis=0)
    return jnp.where(hit[:, None], upd, buf)


def admission_order(res_q: jnp.ndarray, pkey: jnp.ndarray,
                    enq_wave: jnp.ndarray) -> tuple:
    """Fused admission ranking: ONE stable lexicographic ``lax.sort`` over
    the stacked ``(resource, policy key, enqueue wave)`` keys
    (``num_keys=3``; pipeline-id ties resolved by sort stability). Returns
    ``(sorted resource column, permutation)``."""
    n = res_q.shape[0]
    r_s, _, _, o = jax.lax.sort(
        (res_q, pkey, enq_wave, jnp.arange(n, dtype=jnp.int32)),
        num_keys=3, is_stable=True)
    return r_s, o


def admission_order_chained(res_q: jnp.ndarray, pkey: jnp.ndarray,
                            enq_wave: jnp.ndarray) -> tuple:
    """Reference ranking: three chained stable argsorts (the pre-fusion
    implementation) — kept for equivalence tests and the
    ``benchmarks/controller_bench.py`` fused-vs-chained comparison."""
    o = jnp.argsort(enq_wave, stable=True)
    o = o[jnp.argsort(pkey[o], stable=True)]
    o = o[jnp.argsort(res_q[o], stable=True)]
    return res_q[o], o


def admission_mask_dense(res_q: jnp.ndarray, pkey: jnp.ndarray,
                         enq_wave: jnp.ndarray,
                         free: jnp.ndarray, *,
                         skip_pkey: bool = False) -> jnp.ndarray:
    """Sort-free admission decision: the ``[N]`` bool admitted mask, directly.

    A job's *seat* under the stable lexicographic ranking equals the count
    of same-resource jobs with strictly lex-smaller ``(pkey, enq_wave, id)``
    keys — full keys are unique because the pipeline id breaks every tie,
    so "stable sort position within the resource segment" and "number of
    lex-smaller keys in the segment" are the same integer, and

        admitted_i  =  seat_i < free[res_i]

    is bit-identical to the sorted seat test in :func:`admission_order`.
    The pairwise count is O(N^2) elementwise work, but it contains no sort
    and no scatter, so XLA CPU fuses the whole admission round into one
    pass (~20 us at N=134 vs ~40 us for the in-loop ``lax.sort`` *plus* the
    unsort scatter) — and the N^2 term collapses as compaction shrinks N.
    Comparisons are exact (int32 and f32 equality, no arithmetic), so the
    mask is a pure function of the same keys the sort consumes.

    ``skip_pkey`` (static) drops the two f32 pkey comparisons from the
    pairwise matrix. It is only valid when every pkey is identical (FIFO
    with a static policy: pkey == 0 everywhere), where ``pj < pi`` is
    identically False and ``pj == pi`` identically True — the mask is
    bit-identical, but the N^2 term sheds ~1/3 of its elementwise ops,
    which at N ~ 134 is the single largest cost of the whole wave."""
    n = res_q.shape[0]
    nres = free.shape[0]
    ids = jnp.arange(n, dtype=jnp.int32)
    # key_j <lex key_i over (pkey, enq_wave, id); axes are [i, j].
    # The integer (enq_wave, id) lex compare folds into one add + one
    # compare:  wj < wi + [idj < idi]  <=>  (wj < wi) | (wj == wi & idj <
    # idi)  — exact for int32 (enq_wave is a wave counter, far from
    # overflow), and the id matrix is loop-invariant so XLA hoists it.
    wj, wi = enq_wave[None, :], enq_wave[:, None]
    lt = wj < wi + (ids[None, :] < ids[:, None]).astype(jnp.int32)
    if not skip_pkey:
        pj, pi = pkey[None, :], pkey[:, None]
        lt = (pj < pi) | ((pj == pi) & lt)
    seat = jnp.sum((res_q[None, :] == res_q[:, None]) & lt, axis=1,
                   dtype=jnp.int32)
    # free[res] via a dense select over the (tiny, static) resource count —
    # sentinel rows (res_q == nres, i.e. not queued) keep 0 and never admit
    free_q = jnp.zeros((n,), jnp.int32)
    for r in range(nres):
        free_q = jnp.where(res_q == r, free[r], free_q)
    return (res_q < nres) & (seat < free_q)


def admission_mask_select(res_q: jnp.ndarray, pkey: jnp.ndarray,
                          enq_wave: jnp.ndarray, free: jnp.ndarray, *,
                          skip_pkey: bool = False) -> jnp.ndarray:
    """Sort-free admission by repeated lexicographic minimum: the ``[N]``
    bool admitted mask, directly.

    Resource ``r`` admits the ``min(free[r], queued on r)`` jobs with the
    smallest ``(pkey, enq_wave, id)`` keys — the same set as the sorted
    seat test of :func:`admission_order` (the id makes every key unique).
    Each round of the inner ``lax.while_loop`` picks, for every resource
    still owed a job, its lex-minimum not-yet-admitted job with three
    masked minimum reductions (pkey, then enqueue wave, then id). Only
    exact compares and min reductions, no arithmetic: the mask is
    bit-identical to the other rankings. The work is O(N) per admitted
    job instead of a sort of all N rows per wave — a wave typically
    admits the one or two jobs whose slots just freed — so this is the
    ranking for wide batches on the TPU, where a ``[R, N]`` multi-key sort
    and the scatter that undoes it cost milliseconds per wave.

    ``skip_pkey`` (static) drops the pkey round; valid only when every
    pkey is identical (static FIFO), see :func:`admission_mask_dense`."""
    n = res_q.shape[0]
    nres = free.shape[0]
    ids = jnp.arange(n, dtype=jnp.int32)
    of_r = res_q[None, :] == jnp.arange(nres, dtype=jnp.int32)[:, None]
    owed = jnp.minimum(free, jnp.sum(of_r, axis=1, dtype=jnp.int32))
    top = jnp.iinfo(jnp.int32).max

    def lex_min(cand, key, fill):
        k = jnp.where(cand, key[None, :], fill)
        return cand & (k == jnp.min(k, axis=1, keepdims=True))

    def pick(carry):
        k, admitted = carry
        cand = of_r & ~admitted[None, :] & (k < owed)[:, None]
        if not skip_pkey:
            cand = lex_min(cand, pkey, INF)
        cand = lex_min(lex_min(cand, enq_wave, top), ids, top)
        return k + 1, admitted | jnp.any(cand, axis=0)

    _, admitted = jax.lax.while_loop(lambda c: c[0] < jnp.max(owed), pick,
                                     (jnp.int32(0), jnp.zeros((n,), bool)))
    return admitted


@partial(jax.jit,
         static_argnames=("policy", "n_attempt_slots", "admission_sort",
                          "n_ctrl_slots", "n_probe_slots", "n_rel_slots",
                          "return_state"))
def simulate(vwl: VWorkload, capacities: jnp.ndarray, policy: int = POLICY_FIFO,
             cap_times: Optional[jnp.ndarray] = None,
             cap_vals: Optional[jnp.ndarray] = None,
             backoff=None,
             attempt_service: Optional[jnp.ndarray] = None,
             policy_dyn: Optional[jnp.ndarray] = None,
             n_attempt_slots: Optional[int] = None,
             controller: Optional[jnp.ndarray] = None,
             fail_holds_frac=None,
             admission_sort: str = "select",
             n_ctrl_slots: Optional[int] = None,
             fleet=None, trig=None, obs_noise=None, drift_inc=None,
             pool_gain=None, pool_base=None, n_pool_eff=None,
             probe=None, n_probe_slots: Optional[int] = None,
             rel_times=None, rel_deltas=None,
             n_rel_slots: Optional[int] = None,
             resume=None, wave_budget=None, time_budget=None,
             return_state: bool = False):
    """Run one replica. Returns dict with start/finish/ready [N, T] (f32;
    NaN where a task does not exist or never ran) and the wave count.

    ``cap_times [K]`` / ``cap_vals [K, nres]`` give a piecewise-constant
    capacity schedule (``cap_times[0]`` must be 0; ``capacities`` is ignored
    when given). ``backoff`` is the ``(base, mult, cap)`` retry-delay triple.

    ``attempt_service [N, T, A]`` gives per-attempt service times (attempt
    ``k`` of a task runs ``attempt_service[..., min(k, A-1)]``; overrides
    ``vwl.service``) — retry resampling stays pure: every draw happens
    outside the jitted function. ``policy_dyn`` is a *traced* i32 scalar that
    overrides the static ``policy`` so a vmapped batch can mix admission
    policies across its replica axis in one compiled program. With
    ``n_attempt_slots = A`` the engine also records per-attempt
    ``att_start``/``att_finish [N, T, A]`` tensors (NaN where the attempt
    never ran) for exact utilization/cost accounting under heavy retry.

    ``controller`` is a flat ``[C]`` ControllerParams tensor (see module
    docstring; ``C = CTRL_HEADER + CTRL_FIELDS * nres``) driving closed-loop
    queue-reactive scaling inside the loop. ``fail_holds_frac`` (traced
    scalar, default None = 1.0) makes a *failing* attempt hold its slot for
    only that fraction of its service time. ``admission_sort`` selects the
    admission ranking (static; every choice gives the same admitted mask):
    ``"select"`` lex-min rounds (default, :func:`admission_mask_select`),
    ``"fused"`` 3-key ``lax.sort``, ``"chained"`` 3-argsort reference,
    ``"dense"`` pairwise seat count, ``"pallas"`` kernel.

    ``n_ctrl_slots = E`` (static; use :func:`repro.core.des.ctrl_tick_bound`
    — actions only happen at evaluation ticks, so the compile-time tick grid
    bounds the buffer) turns on *realized capacity timeline* recording: each
    controller action (f32 time + integer per-resource target) is written
    into a preallocated ``[E, 1+nres]`` buffer carried through the
    ``lax.while_loop``, returned as ``ctrl_act`` with the action count
    ``ctrl_n`` — the engine-recorded ground truth that
    ``ops.accounting.realized_schedule`` splices onto the planned schedule
    for exact provisioned cost/utilization under closed-loop scaling.

    The **fleet stage** (model lifecycle, Fig 7) activates with the
    ``fleet``-group kwargs: ``fleet [M, FLEET_FIELDS]`` drift-process rows,
    ``trig [TRIG_FIELDS]`` header (interval, cooldown, t_first, t_end,
    drift threshold, arrival delay; ``interval <= 0`` disables the stage —
    the batched padding row), presampled ``obs_noise``/``drift_inc [E, M]``
    per-tick tensors, ``pool_gain [P]`` per-slot redeploy performance gains,
    and ``pool_base``/``n_pool_eff`` locating the latent retraining-pool
    rows inside the (extended) workload. Every random draw is presampled
    outside the jitted function, exactly like the failure-attempt tensors.

    The **probe stage** (in-loop telemetry) activates with ``probe`` — a
    ``[PROBE_FIELDS]`` f32 header ``[interval, t_first, t_end, n_models]``
    (``interval <= 0`` disables, the batched padding row) — plus the static
    ``n_probe_slots = E`` (the compile-time tick bound, same grid machinery
    as controller/trigger). At every probe tick (ticks join the next-event
    minimum and keep the loop alive until the grid exhausts) the settled
    post-wave state — per-resource queue depth, busy slots, effective
    capacity, controller delta, fleet min-perf / max-staleness (masked to
    the entry's own ``n_models`` rows; min/max so the reductions stay
    order-independent) — is written into a preallocated ``[E, K]`` f32
    buffer, returned as ``probe_vals`` with the tick count ``probe_n``. The
    numpy engine mirrors the sampling f32-op-for-op, so probe buffers are
    parity-gated like task timestamps. The stage is physics-invisible.

    The **reliability stage** activates with ``rel_times [RV]`` (f32,
    strictly increasing; padded tail rows at ``INF`` never fire) /
    ``rel_deltas [RV, nres]`` (integer capacity deltas) plus the static
    ``n_rel_slots = RV`` — the pre-sampled correlated outage / repair /
    eviction timeline from :func:`repro.reliability.compile.
    compile_reliability`. Each event joins the next-event minimum, applies
    its delta through the control stage's capacity machinery (drain
    semantics: a down event never preempts), and is recorded (f32 time +
    integer cumulative delta) into a ``[RV, 1+nres]`` buffer returned as
    ``rel_act``/``rel_n``. Like the capacity schedule — and unlike the
    controller/probe grids — pending reliability events do NOT keep the
    loop alive. The numpy engine mirrors the stage op-for-op.

    **Segment-restart hooks** (for the active-replica compaction driver,
    :mod:`repro.core.compaction`): ``resume`` is a prior carry pytree (the
    ``state`` returned by a ``return_state=True`` call, possibly permuted/
    compacted by the driver) adopted verbatim in place of the freshly built
    initial state; ``wave_budget`` is a *traced* i32 scalar capping how many
    waves this call may run (the loop also stops early when naturally
    finished); ``time_budget`` is a *traced* f32 time guard — the loop stops
    *before* processing any wave whose next-event time exceeds it, which
    lets the compaction driver defer not-yet-arrived rows (a row with
    ``phase == NOT_ARRIVED`` and ``t_next > guard`` is admission-inert and
    can never be the event minimum of a wave at or before the guard, so its
    absence is unobservable); ``return_state=True`` (static) additionally returns the raw
    final carry as ``state``, whether the loop would continue as
    ``running``, and the count of still-live non-padding pipelines as
    ``n_keep``. Stopping at a wave boundary and resuming from the carry is
    bit-exact: the carry *is* the loop's complete state.
    """
    n, T = vwl.task_res.shape
    if (cap_times is None) != (cap_vals is None):
        raise ValueError("cap_times and cap_vals must be given together")
    if admission_sort not in ("fused", "chained", "dense", "select",
                              "pallas"):
        raise ValueError(f"unknown admission_sort {admission_sort!r}")
    rank = (admission_order if admission_sort == "fused"
            else admission_order_chained)
    if cap_times is None:
        cap_times = jnp.zeros((1,), jnp.float32)
        cap_vals = jnp.asarray(capacities, jnp.int32)[None, :]
    cap_times = jnp.asarray(cap_times, jnp.float32)
    cap_vals = jnp.asarray(cap_vals, jnp.int32)
    K, nres = cap_vals.shape
    bo = jnp.asarray(backoff if backoff is not None else _NO_RETRY_BACKOFF,
                     jnp.float32)
    att_req = (jnp.ones((n, T), jnp.int32) if vwl.attempts is None
               else jnp.maximum(jnp.asarray(vwl.attempts, jnp.int32), 1))
    ids = jnp.arange(n, dtype=jnp.int32)

    has_fleet = trig is not None
    if has_fleet:
        trig_t = jnp.asarray(trig, jnp.float32)
        f_interval, f_cooldown, f_first, f_end, f_thr, f_delay = (
            trig_t[i] for i in range(TRIG_FIELDS))
        f_enabled = f_interval > 0.0
        fleet_t = jnp.asarray(fleet, jnp.float32)
        M_ = fleet_t.shape[0]
        obs_t = jnp.asarray(obs_noise, jnp.float32)      # [E, M]
        inc_t = jnp.asarray(drift_inc, jnp.float32)      # [E, M]
        gain_t = jnp.asarray(pool_gain, jnp.float32)     # [P]
        P = gain_t.shape[0]
        E_f = obs_t.shape[0]
        A_f = max(2 * P, 1)       # triggers + redeploys both bounded by P
        pbase = jnp.asarray(pool_base, jnp.int32)
        peff = jnp.asarray(P if n_pool_eff is None else n_pool_eff,
                           jnp.int32)

    has_probe = probe is not None and n_probe_slots is not None \
        and n_probe_slots > 0
    if has_probe:
        probe_t = jnp.asarray(probe, jnp.float32)
        p_interval = probe_t[PROBE_INTERVAL]
        p_first = probe_t[PROBE_T_FIRST]
        p_end = probe_t[PROBE_T_END]
        p_models = jnp.round(probe_t[PROBE_N_MODELS]).astype(jnp.int32)
        p_enabled = p_interval > 0.0
        E_p = n_probe_slots
        K_p = probe_channel_count(nres)

    has_ctrl = controller is not None
    if has_ctrl:
        ctrl = jnp.asarray(controller, jnp.float32)
        (c_interval, c_cooldown, c_first, c_end, c_high, c_low, c_step,
         c_min, c_max, c_base) = unpack_controller(ctrl)
        c_enabled = c_interval > 0.0
        base_i = jnp.round(c_base).astype(jnp.int32)

    has_rel = rel_times is not None and n_rel_slots is not None \
        and n_rel_slots > 0
    if has_rel:
        rel_t = jnp.asarray(rel_times, jnp.float32)      # [RV]
        rel_d = jnp.asarray(rel_deltas, jnp.int32)       # [RV, nres]
        RV = n_rel_slots

    state = dict(
        phase=jnp.full((n,), _NOT_ARRIVED, jnp.int32),
        task_idx=jnp.zeros((n,), jnp.int32),
        t_next=vwl.arrival,
        enq_wave=jnp.zeros((n,), jnp.int32),
        attempt=jnp.zeros((n,), jnp.int32),
        free=cap_vals[0],
        cap_idx=jnp.int32(1),
        wave=jnp.int32(0),
        start=jnp.full((n, T), jnp.nan, jnp.float32),
        finish=jnp.full((n, T), jnp.nan, jnp.float32),
        ready=jnp.full((n, T), jnp.nan, jnp.float32),
        att_out=jnp.zeros((n, T), jnp.int32),
    )
    if n_attempt_slots is not None:
        state["att_start"] = jnp.full((n, T, n_attempt_slots), jnp.nan,
                                      jnp.float32)
        state["att_finish"] = jnp.full((n, T, n_attempt_slots), jnp.nan,
                                       jnp.float32)
    rec_ctrl = has_ctrl and n_ctrl_slots is not None and n_ctrl_slots > 0
    if has_ctrl:
        state["ctrl_cap"] = c_base                       # continuous, f32
        state["ctrl_tgt"] = base_i                       # integer target
        state["t_eval"] = jnp.where(c_enabled & (c_first <= c_end),
                                    c_first, INF)
        state["t_act"] = -INF                            # last action time
    if rec_ctrl:
        # realized-timeline action buffer: [E, 1+nres] rows of
        # (f32 action time, integer per-resource target)
        state["ctrl_act"] = jnp.full((n_ctrl_slots, 1 + nres), jnp.nan,
                                     jnp.float32)
        state["ctrl_n"] = jnp.int32(0)
    if has_rel:
        state["rel_idx"] = jnp.int32(0)    # next pending compiled event
        state["rel_cum"] = jnp.zeros((nres,), jnp.int32)
        # fired-event buffer: [RV, 1+nres] rows of (f32 event time, integer
        # cumulative per-resource reliability delta) — same row layout as
        # the controller's realized-action buffer
        state["rel_act"] = jnp.full((n_rel_slots, 1 + nres), jnp.nan,
                                    jnp.float32)
        state["rel_n"] = jnp.int32(0)
    if has_fleet:
        state["fl_perf0"] = fleet_t[:, FLEET_PERF0]  # current post-deploy perf
        state["fl_dep"] = jnp.zeros((M_,), jnp.float32)   # deployed_at
        state["fl_acc"] = jnp.zeros((M_,), jnp.float32)   # drift-loss acc
        state["fl_dep_tick"] = jnp.full((M_,), -1, jnp.int32)
        state["fl_fire"] = jnp.full((M_,), -INF, jnp.float32)
        state["t_fleet"] = jnp.where(f_enabled & (f_first <= f_end),
                                     f_first, INF)
        state["f_tick"] = jnp.int32(0)
        state["pool_model"] = jnp.full((P,), -1, jnp.int32)
        state["pool_next"] = jnp.int32(0)
        state["pool_arr"] = jnp.full((P,), jnp.nan, jnp.float32)
        state["redeployed"] = jnp.zeros((P,), bool)
        state["fleet_perf"] = jnp.full((E_f, M_), jnp.nan, jnp.float32)
        state["fleet_stale"] = jnp.full((E_f, M_), jnp.nan, jnp.float32)
        # lifecycle action buffer: [A, 3] rows of (f32 time, kind, model id)
        state["fleet_act"] = jnp.full((A_f, 3), jnp.nan, jnp.float32)
        state["fleet_n"] = jnp.int32(0)
    if has_probe:
        state["t_probe"] = jnp.where(p_enabled & (p_first <= p_end),
                                     p_first, INF)
        state["p_tick"] = jnp.int32(0)
        state["probe_vals"] = jnp.full((E_p, K_p), jnp.nan, jnp.float32)

    if resume is not None:
        # segment restart: adopt the prior carry verbatim (the compaction
        # driver only permutes/pads rows between segments — same key set,
        # same dtypes, so the while-carry contract is unchanged)
        state = {k: resume[k] for k in state}

    def next_cap_time(cap_idx):
        return jnp.where(cap_idx < K, cap_times[jnp.clip(cap_idx, 0, K - 1)],
                         INF)

    # ------------------------------------------------------------ stages

    def _select_events(s):
        """Stage 1: the global next-event time. Task events, the next
        scheduled capacity change, the next reliability event, and the next
        controller tick all participate in the minimum."""
        t_cap = next_cap_time(s["cap_idx"])
        t_star = jnp.minimum(jnp.min(s["t_next"]), t_cap)
        if has_rel:
            ri = jnp.clip(s["rel_idx"], 0, RV - 1)
            t_rel = jnp.where(s["rel_idx"] < RV, rel_t[ri], INF)
            t_star = jnp.minimum(t_star, t_rel)
        if has_ctrl:
            t_star = jnp.minimum(t_star, s["t_eval"])
        if has_fleet:
            t_star = jnp.minimum(t_star, s["t_fleet"])
        if has_probe:
            t_star = jnp.minimum(t_star, s["t_probe"])
        return t_star, t_cap

    def _completion_stage(s, t_star):
        """Stage 2: finishes release slots; failed attempts re-enter the
        arrival path after their backoff delay; successful ones advance the
        pipeline; arrivals and successor tasks enqueue."""
        s = dict(s)
        phase, task_idx, t_next = s["phase"], s["task_idx"], s["t_next"]
        finishing = (phase == _RUNNING) & (t_next == t_star)
        arriving = (phase == _NOT_ARRIVED) & (t_next == t_star)

        tcl0 = jnp.clip(task_idx, 0, T - 1)
        oh0 = _onehot_cols(tcl0, T)
        res_now = _take_cols(vwl.task_res, oh0, -1)
        # per-resource count as a dense one-hot i32 sum: a vmapped
        # segment_sum lowers to a serial per-replica scatter-add on CPU;
        # the bool-mask sum vectorizes across the batch (and integer sums
        # are order-independent — exact under any reduction order)
        freed = jnp.sum(finishing[:, None]
                        & (res_now[:, None]
                           == jnp.arange(nres, dtype=jnp.int32)[None, :]),
                        axis=0, dtype=jnp.int32)
        s["free"] = s["free"] + freed

        att = s["attempt"]
        retrying = finishing & (att + 1 < _take_cols(att_req, oh0, 0))
        succeeding = finishing & ~retrying
        delay = jnp.minimum(bo[0] * bo[1] ** att.astype(jnp.float32), bo[2])

        task_idx = task_idx + succeeding.astype(jnp.int32)
        att = jnp.where(retrying, att + 1,
                        jnp.where(succeeding, 0, att))
        done_now = succeeding & (task_idx >= vwl.n_tasks)
        to_queue = (succeeding & ~done_now) | arriving
        s["phase"] = jnp.where(
            done_now, _DONE,
            jnp.where(to_queue, _QUEUED,
                      jnp.where(retrying, _NOT_ARRIVED, phase)))
        s["t_next"] = jnp.where(succeeding | arriving, INF,
                                jnp.where(retrying, t_star + delay, t_next))
        s["enq_wave"] = jnp.where(to_queue, s["wave"], s["enq_wave"])
        s["task_idx"], s["attempt"] = task_idx, att

        tcl = jnp.clip(task_idx, 0, T - 1)
        s["ready"] = jnp.where(_onehot_cols(tcl, T) & to_queue[:, None],
                               t_star, s["ready"])
        return s

    def _control_stage(s, t_star, t_cap):
        """Stage 3: the pending scheduled capacity change applies, then the
        pending reliability event (domain outage / repair return / spot
        eviction) applies its capacity delta and is recorded, then the
        closed-loop controller observes live queue lengths and adjusts
        capacity — all before the admission round."""
        s = dict(s)
        cap_changing = (t_cap == t_star) & (s["cap_idx"] < K)
        hi = jnp.clip(s["cap_idx"], 0, K - 1)
        lo = jnp.clip(s["cap_idx"] - 1, 0, K - 1)
        free = s["free"] + jnp.where(cap_changing, cap_vals[hi] - cap_vals[lo],
                                     0)
        cap_idx = s["cap_idx"] + cap_changing.astype(jnp.int32)
        if has_rel:
            # reliability capacity-delta event: same drain semantics as a
            # scheduled decrease, applied before the controller evaluates
            # so it reacts to post-outage capacity (numpy mirrors)
            ri = jnp.clip(s["rel_idx"], 0, RV - 1)
            rel_firing = (s["rel_idx"] < RV) & (rel_t[ri] == t_star)
            drow = jnp.where(rel_firing, rel_d[ri], 0)
            free = free + drow
            rel_cum = s["rel_cum"] + drow
            # record (t, cumulative delta) with the controller buffer's
            # dense one-hot row-write pattern (scatters serialize on CPU);
            # cumulative deltas can be negative, so a where-write, not
            # _onehot_rows
            ridx = jnp.minimum(s["rel_n"], n_rel_slots - 1)
            rrow = jnp.concatenate([jnp.reshape(t_star, (1,)),
                                    rel_cum.astype(jnp.float32)])
            oh_r = (jnp.arange(n_rel_slots, dtype=jnp.int32)
                    == ridx)[:, None]
            s["rel_act"] = jnp.where(oh_r & rel_firing, rrow[None, :],
                                     s["rel_act"])
            s["rel_n"] = jnp.minimum(
                s["rel_n"] + rel_firing.astype(jnp.int32), n_rel_slots)
            s["rel_cum"] = rel_cum
            s["rel_idx"] = s["rel_idx"] + rel_firing.astype(jnp.int32)
        if has_ctrl:
            firing = c_enabled & (s["t_eval"] == t_star)
            queued = s["phase"] == _QUEUED
            tcl = jnp.clip(s["task_idx"], 0, T - 1)
            res_q = jnp.where(
                queued, _take_cols(vwl.task_res, _onehot_cols(tcl, T), -1),
                nres)
            # dense one-hot count (see _completion_stage): the sentinel
            # res_q == nres never matches a real resource column
            qlen = jnp.sum(
                res_q[:, None] == jnp.arange(nres, dtype=jnp.int32)[None, :],
                axis=0, dtype=jnp.int32)
            sched_now = cap_vals[jnp.clip(cap_idx - 1, 0, K - 1)]
            cap_eff = sched_now + s["ctrl_tgt"] - base_i
            if has_rel:
                # the controller watches post-outage effective capacity
                cap_eff = cap_eff + s["rel_cum"]
            per_slot = (qlen.astype(jnp.float32)
                        / jnp.maximum(cap_eff, 1).astype(jnp.float32))
            can_act = firing & (t_star - s["t_act"] >= c_cooldown)
            cap_f = s["ctrl_cap"]
            new_cap = jnp.where(
                per_slot > c_high, cap_f * (jnp.float32(1.0) + c_step),
                jnp.where(per_slot < c_low,
                          cap_f * (jnp.float32(1.0) - c_step), cap_f))
            new_cap = jnp.where(can_act, jnp.clip(new_cap, c_min, c_max),
                                cap_f)
            new_tgt = jnp.round(new_cap).astype(jnp.int32)
            changed = can_act & jnp.any(new_cap != cap_f)
            if rec_ctrl:
                # an integer-target move is a provisioning action: append
                # (t, target) to the realized timeline (numpy mirrors). The
                # append is a dense one-hot row write — a traced-index
                # scatter would serialize under vmap on CPU
                tgt_changed = can_act & jnp.any(new_tgt != s["ctrl_tgt"])
                idx = jnp.minimum(s["ctrl_n"], n_ctrl_slots - 1)
                row = jnp.concatenate([jnp.reshape(t_star, (1,)),
                                       new_tgt.astype(jnp.float32)])
                oh_e = (jnp.arange(n_ctrl_slots, dtype=jnp.int32)
                        == idx)[:, None]
                s["ctrl_act"] = jnp.where(oh_e & tgt_changed, row[None, :],
                                          s["ctrl_act"])
                s["ctrl_n"] = jnp.minimum(
                    s["ctrl_n"] + tgt_changed.astype(jnp.int32), n_ctrl_slots)
            free = free + (new_tgt - s["ctrl_tgt"])
            s["ctrl_cap"], s["ctrl_tgt"] = new_cap, new_tgt
            s["t_act"] = jnp.where(changed, t_star, s["t_act"])
            # a tick that cannot advance past the f32 ulp would spin the
            # wave loop forever — exhaust the grid instead (numpy mirrors)
            t_nxt = s["t_eval"] + c_interval
            s["t_eval"] = jnp.where(
                firing,
                jnp.where((t_nxt > c_end) | (t_nxt <= s["t_eval"]),
                          INF, t_nxt),
                s["t_eval"])
        s["free"], s["cap_idx"] = free, cap_idx
        return s

    def _admission_stage(s, t_star):
        """Stage 4: one ranked admission round per resource, recording
        start/finish for admitted attempts. Five equivalent rankings select
        the same admitted mask (bit-identical, see
        :func:`admission_mask_dense`): ``"select"`` — repeated lex-min
        picks, O(N) per admitted job (the default); ``"fused"`` — one
        stable 3-key ``lax.sort``; ``"chained"`` — three stable argsorts;
        ``"dense"`` — sort-free pairwise seat count (the fast path at
        compacted widths on CPU); ``"pallas"`` —
        the fused VMEM kernel in :mod:`repro.kernels.queue_scan`
        (interpreted off-TPU)."""
        s = dict(s)
        att, task_idx = s["attempt"], s["task_idx"]
        tcl = jnp.clip(task_idx, 0, T - 1)
        oh = _onehot_cols(tcl, T)
        queued = s["phase"] == _QUEUED
        res_q = jnp.where(queued, _take_cols(vwl.task_res, oh, -1),
                          nres)                          # sentinel
        if attempt_service is None:
            svc = _take_cols(vwl.service, oh, -INF)
        else:
            A = attempt_service.shape[2]
            ka_s = jnp.clip(att, 0, A - 1)
            sel3 = oh[:, :, None] & (
                ka_s[:, None, None]
                == jnp.arange(A, dtype=jnp.int32)[None, None, :])
            svc = jnp.max(jnp.where(sel3, attempt_service, -INF), axis=(1, 2))
        if policy_dyn is not None:
            pkey = jnp.where(policy_dyn == POLICY_PRIORITY, -vwl.priority,
                             jnp.where(policy_dyn == POLICY_SJF, svc,
                                       jnp.zeros((n,), jnp.float32)))
        elif policy == POLICY_PRIORITY:
            pkey = -vwl.priority
        elif policy == POLICY_SJF:
            pkey = svc
        else:
            pkey = jnp.zeros((n,), jnp.float32)

        # lexicographic stable ranking: res -> pkey -> enq_wave -> pid
        if admission_sort in ("fused", "chained"):
            r_s, o = rank(res_q, pkey, s["enq_wave"])
            pos = jnp.arange(n, dtype=jnp.int32)
            is_start = jnp.concatenate([jnp.array([True]),
                                        r_s[1:] != r_s[:-1]])
            seg_start = _cummax(jnp.where(is_start, pos, -1))
            seat = pos - seg_start
            free_ext = jnp.concatenate([s["free"],
                                        jnp.zeros((1,), jnp.int32)])
            admit_sorted = seat < free_ext[r_s]
            admitted = jnp.zeros((n,), bool).at[o].set(admit_sorted) & queued
        elif admission_sort in ("dense", "select"):
            # statically-FIFO runs have pkey == 0 everywhere: skip the f32
            # pkey compares (bit-identical mask)
            fifo_static = policy_dyn is None and policy == POLICY_FIFO
            mask = (admission_mask_dense if admission_sort == "dense"
                    else admission_mask_select)
            admitted = mask(res_q, pkey, s["enq_wave"], s["free"],
                            skip_pkey=fifo_static) & queued
        else:  # "pallas": fused admission kernel (interpreted off-TPU)
            from repro.kernels.queue_scan import fused_admission
            admitted = fused_admission(res_q, pkey, s["enq_wave"],
                                       s["free"]) & queued

        # a failing attempt (known at admission from the pre-sampled attempt
        # tensor) may hold its slot for only a fraction of the service time
        if fail_holds_frac is None:
            dur = svc
        else:
            will_fail = (att + 1) < _take_cols(att_req, oh, 0)
            dur = jnp.where(will_fail,
                            jnp.asarray(fail_holds_frac, jnp.float32) * svc,
                            svc)
        t_fin = t_star + dur
        adm_col = oh & admitted[:, None]
        s["t_next"] = jnp.where(admitted, t_fin, s["t_next"])
        s["phase"] = jnp.where(admitted, _RUNNING, s["phase"])
        s["start"] = jnp.where(adm_col, t_star, s["start"])
        s["finish"] = jnp.where(adm_col, t_fin[:, None], s["finish"])
        # executed attempts (matches the numpy engine's attempts_out: a task
        # stranded mid-retry reports the admissions that actually happened)
        s["att_out"] = s["att_out"] + adm_col.astype(jnp.int32)
        # res_q of admitted jobs is < nres by construction (sentinel never
        # admits); dense one-hot count, see _completion_stage
        taken = jnp.sum(admitted[:, None]
                        & (res_q[:, None]
                           == jnp.arange(nres, dtype=jnp.int32)[None, :]),
                        axis=0, dtype=jnp.int32)
        s["free"] = s["free"] - taken
        if n_attempt_slots is not None:
            ka = jnp.clip(att, 0, n_attempt_slots - 1)
            adm_slot = adm_col[:, :, None] & (
                ka[:, None, None]
                == jnp.arange(n_attempt_slots, dtype=jnp.int32)[None, None, :])
            s["att_start"] = jnp.where(adm_slot, t_star, s["att_start"])
            s["att_finish"] = jnp.where(adm_slot, t_fin[:, None, None],
                                        s["att_finish"])
        return s

    def _fleet_stage(s, t_star):
        """Stage 5: model lifecycle (run-time view, Fig 7). Retraining-pool
        pipelines that completed this wave redeploy their model (drift
        state resets, presampled per-slot performance gain applies); at
        every drift-evaluation tick the [M] drift algebra runs, the
        performance/staleness timelines record, and triggers whose observed
        drift crosses the threshold (outside their cooldown) activate
        latent pool pipelines. Trigger and redeploy actions append to the
        shared lifecycle action buffer. Arithmetic is float32 — the numpy
        engine mirrors this stage operation-for-operation."""
        s = dict(s)
        slots = jnp.arange(P, dtype=jnp.int32)
        valid = slots < peff
        rows = jnp.clip(pbase + slots, 0, n - 1)
        # ---- redeploy-on-deploy-completion (any wave, not just ticks)
        p_done = ((s["phase"][rows] == _DONE) & (s["pool_model"] >= 0)
                  & ~s["redeployed"] & valid)
        mdl = jnp.clip(s["pool_model"], 0, max(M_ - 1, 0))
        # f32 sum over pool slots: the numpy mirror accumulates redeploy
        # gains in the identical slot order (parity-tested), so this
        # order-sensitive reduction is safe.  # parity: allow(loop-reduce)
        gain_m = jax.ops.segment_sum(jnp.where(p_done, gain_t, 0.0), mdl,
                                     num_segments=M_)
        hit = jnp.any(p_done[:, None]
                      & (mdl[:, None]
                         == jnp.arange(M_, dtype=jnp.int32)[None, :]), axis=0)
        s["fl_perf0"] = jnp.where(
            hit, jnp.clip(s["fl_perf0"] + gain_m, 0.4, 0.995), s["fl_perf0"])
        s["fl_dep"] = jnp.where(hit, t_star, s["fl_dep"])
        s["fl_acc"] = jnp.where(hit, 0.0, s["fl_acc"])
        s["fl_dep_tick"] = jnp.where(hit, s["f_tick"], s["fl_dep_tick"])
        s["redeployed"] = s["redeployed"] | p_done
        rk = jnp.cumsum(p_done.astype(jnp.int32)) - 1
        idx = jnp.where(p_done, s["fleet_n"] + rk, A_f)
        vals = jnp.stack(
            [jnp.full((P,), t_star),
             jnp.full((P,), jnp.float32(FLEET_ACT_REDEPLOY)),
             s["pool_model"].astype(jnp.float32)], 1)
        s["fleet_act"] = _onehot_rows(s["fleet_act"], idx, vals)
        # dtype pinned: jnp.sum would promote i32 to the platform int
        # (i64 under enable_x64) and break the carry contract
        s["fleet_n"] = s["fleet_n"] + jnp.sum(p_done, dtype=jnp.int32)
        # ---- drift-evaluation tick
        firing = f_enabled & (s["t_fleet"] == t_star)
        e = jnp.clip(s["f_tick"], 0, E_f - 1)
        dt = jnp.maximum(t_star - s["fl_dep"], 0.0)
        # drift accrues per COMPLETED interval: dep_tick gates the first
        # accrual after a redeploy (its partial interval is dropped)
        acc_new = jnp.where(e > s["fl_dep_tick"], s["fl_acc"] + inc_t[e],
                            s["fl_acc"])
        perf = fleet_performance_acc(s["fl_perf0"], acc_new, dt, fleet_t,
                                     xp=jnp)
        stale = fleet_staleness(s["fl_perf0"], perf, xp=jnp)
        # dense one-hot row writes (see _onehot_rows: scatters serialize
        # under vmap on CPU)
        oh_f = (jnp.arange(E_f, dtype=jnp.int32) == e)[:, None]
        s["fleet_perf"] = jnp.where(oh_f & firing, perf[None, :],
                                    s["fleet_perf"])
        s["fleet_stale"] = jnp.where(oh_f & firing, stale[None, :],
                                     s["fleet_stale"])
        obs = perf + obs_t[e]
        drift = s["fl_perf0"] - obs
        want = firing & (drift > f_thr) & ((t_star - s["fl_fire"])
                                           >= f_cooldown)
        rank = jnp.cumsum(want.astype(jnp.int32)) - 1
        slot = s["pool_next"] + rank
        fire = want & (slot < peff)        # injection budget exhausts
        s["fl_fire"] = jnp.where(fire, t_star, s["fl_fire"])
        arr_t = t_star + f_delay
        slot_idx = jnp.where(fire, slot, P)
        mids = jnp.arange(M_, dtype=jnp.int32)
        # dense one-hot writes into the [P] pool slots (fired slots are
        # unique: slot = pool_next + rank with distinct ranks)
        m_s = slot_idx[:, None] == jnp.arange(P, dtype=jnp.int32)[None, :]
        hit_s = jnp.any(m_s, axis=0)
        s["pool_model"] = jnp.where(
            hit_s, jnp.max(jnp.where(m_s, mids[:, None], -1), axis=0),
            s["pool_model"])
        s["pool_arr"] = jnp.where(hit_s, arr_t, s["pool_arr"])
        # activate the latent workload rows: they arrive at t_star + delay
        row_idx = jnp.where(fire, pbase + slot, n)
        hit_r = jnp.any(row_idx[:, None] == ids[None, :], axis=0)
        s["t_next"] = jnp.where(hit_r, arr_t, s["t_next"])
        aidx = jnp.where(fire, s["fleet_n"] + rank, A_f)
        avals = jnp.stack(
            [jnp.full((M_,), t_star),
             jnp.full((M_,), jnp.float32(FLEET_ACT_TRIGGER)),
             mids.astype(jnp.float32)], 1)
        s["fleet_act"] = _onehot_rows(s["fleet_act"], aidx, avals)
        # dtype pinned (see _fleet_stage completion above)
        s["fleet_n"] = s["fleet_n"] + jnp.sum(fire, dtype=jnp.int32)
        s["pool_next"] = s["pool_next"] + jnp.sum(fire, dtype=jnp.int32)
        s["fl_acc"] = jnp.where(firing, acc_new, s["fl_acc"])
        # advance the tick grid exactly as the controller's (f32 ulp guard)
        t_nxt = s["t_fleet"] + f_interval
        s["t_fleet"] = jnp.where(
            firing,
            jnp.where((t_nxt > f_end) | (t_nxt <= s["t_fleet"]), INF, t_nxt),
            s["t_fleet"])
        s["f_tick"] = s["f_tick"] + firing.astype(jnp.int32)
        return s

    def _probe_stage(s, t_star):
        """Stage 6 (optional): in-loop telemetry. Runs LAST in the wave so
        it samples the settled post-admission/post-fleet state at t_star —
        a probe tick that coincides with nothing else is a no-op wave for
        every other stage (the admission invariant guarantees no queued job
        has a free slot after any wave), so probes never perturb the
        physics. Arithmetic is float32 — the numpy engine mirrors this
        sampling operation-for-operation."""
        s = dict(s)
        firing = p_enabled & (s["t_probe"] == t_star)
        e = jnp.clip(s["p_tick"], 0, E_p - 1)
        queued = s["phase"] == _QUEUED
        tcl = jnp.clip(s["task_idx"], 0, T - 1)
        res_p = jnp.where(
            queued, _take_cols(vwl.task_res, _onehot_cols(tcl, T), -1),
            nres)
        # dense one-hot count (see _completion_stage); the sentinel
        # res_p == nres never matches a real resource column. An integer
        # bool-count is order-independent — exact under any reduction
        # order, so the numpy mirror agrees bit-for-bit.
        qlen = jnp.sum(  # parity: allow(probe-reduce)
            res_p[:, None] == jnp.arange(nres, dtype=jnp.int32)[None, :],
            axis=0, dtype=jnp.int32)
        sched_now = cap_vals[jnp.clip(s["cap_idx"] - 1, 0, K - 1)]
        if has_ctrl:
            delta = s["ctrl_tgt"] - base_i
        else:
            delta = jnp.zeros((nres,), jnp.int32)
        rdelta = s["rel_cum"] if has_rel \
            else jnp.zeros((nres,), jnp.int32)
        cap_eff = sched_now + delta + rdelta
        busy = cap_eff - s["free"]                       # running jobs
        if has_fleet:
            # fleet channels reduce with min/max (order-independent, so the
            # batched vmap and the numpy mirror agree bit-for-bit), masked
            # to the entry's own n_models rows (padded rows would corrupt
            # the min with their zero perf0)
            valid_m = jnp.arange(M_, dtype=jnp.int32) < p_models
            dtp = jnp.maximum(t_star - s["fl_dep"], 0.0)
            perf = fleet_performance_acc(s["fl_perf0"], s["fl_acc"], dtp,
                                         fleet_t, xp=jnp)
            stale = fleet_staleness(s["fl_perf0"], perf, xp=jnp)
            any_m = jnp.any(valid_m)
            f_perf = jnp.where(any_m,
                               jnp.min(jnp.where(valid_m, perf, INF)),
                               jnp.nan)[None]
            f_stale = jnp.where(any_m,
                                jnp.max(jnp.where(valid_m, stale, -INF)),
                                jnp.nan)[None]
        else:
            f_perf = f_stale = jnp.full((1,), jnp.nan, jnp.float32)
        # live-pipelines channel: queued + running pipelines — the
        # live-width timeline the compaction driver's wave-rate changes are
        # explained by (numpy mirrors: waiting heaps plus outstanding
        # finish events). A bool-count i32 sum is order-independent and
        # exact in f32.  # parity: allow(probe-reduce)
        live = jnp.sum((s["phase"] == _QUEUED) | (s["phase"] == _RUNNING),
                       dtype=jnp.int32)
        row = jnp.concatenate(
            [qlen.astype(jnp.float32), busy.astype(jnp.float32),
             cap_eff.astype(jnp.float32), delta.astype(jnp.float32),
             rdelta.astype(jnp.float32),
             f_perf.astype(jnp.float32), f_stale.astype(jnp.float32),
             live.astype(jnp.float32)[None]])
        # dense one-hot row write (a traced-index scatter would serialize
        # under vmap on CPU)
        oh_e = (jnp.arange(E_p, dtype=jnp.int32) == e)[:, None]
        s["probe_vals"] = jnp.where(oh_e & firing, row[None, :],
                                    s["probe_vals"])
        # advance the tick grid exactly as the controller's (f32 ulp guard)
        t_nxt = s["t_probe"] + p_interval
        s["t_probe"] = jnp.where(
            firing,
            jnp.where((t_nxt > p_end) | (t_nxt <= s["t_probe"]), INF, t_nxt),
            s["t_probe"])
        s["p_tick"] = s["p_tick"] + firing.astype(jnp.int32)
        return s

    # -------------------------------------------------------- wave loop

    def _running(s, t_star=None):
        if t_star is None:
            t_star, _ = _select_events(s)
        # exit when everything is done OR nothing can ever happen again
        # (e.g. capacity held at zero past the end of the schedule and the
        # controller's evaluation grid is exhausted). Remaining fleet ticks
        # keep the loop alive: models drift (and triggers may fire) even
        # after every pipeline drained.
        alive = jnp.any(s["phase"] != _DONE)
        if has_fleet:
            alive = alive | (s["t_fleet"] < INF)
        if has_probe:
            # remaining probe ticks keep the loop alive too: timelines must
            # cover the full grid even after every pipeline drained
            alive = alive | (s["t_probe"] < INF)
        return alive & (t_star < INF)

    def cond(s):
        t_star, _ = _select_events(s)
        go = _running(s, t_star)
        if wave_budget is not None:
            # segment cap: stop at the budget boundary — a wave boundary is
            # a consistent cut, so the compaction driver resumes bit-exactly
            go = go & (s["wave"] < jnp.asarray(wave_budget, jnp.int32))
        if time_budget is not None:
            # time-window cut: stop before processing any wave beyond the
            # driver's guard — rows deferred by the driver all satisfy
            # t_next > guard, so no wave at or before the guard can tell
            # they are missing (and if one of them *would* have been the
            # event minimum, the minimum over present rows is larger still,
            # and the cut fires either way)
            go = go & (t_star <= jnp.asarray(time_budget, jnp.float32))
        return go

    def body(s):
        t_star, t_cap = _select_events(s)
        s = _completion_stage(s, t_star)
        s = _control_stage(s, t_star, t_cap)
        s = _admission_stage(s, t_star)
        if has_fleet:
            s = _fleet_stage(s, t_star)
        if has_probe:
            s = _probe_stage(s, t_star)
        s["wave"] = s["wave"] + 1
        return s

    out = jax.lax.while_loop(cond, body, state)
    res = dict(start=out["start"], finish=out["finish"], ready=out["ready"],
               attempts=out["att_out"], done=out["phase"] == _DONE,
               waves=out["wave"])
    if n_attempt_slots is not None:
        res["att_start"] = out["att_start"]
        res["att_finish"] = out["att_finish"]
    if rec_ctrl:
        res["ctrl_act"] = out["ctrl_act"]
        res["ctrl_n"] = out["ctrl_n"]
    if has_rel:
        res["rel_act"] = out["rel_act"]
        res["rel_n"] = out["rel_n"]
    if has_fleet:
        for k in ("fleet_perf", "fleet_stale", "fleet_act", "fleet_n",
                  "pool_arr", "pool_model", "pool_next"):
            res[k] = out[k]
    if has_probe:
        res["probe_vals"] = out["probe_vals"]
        res["probe_n"] = out["p_tick"]
    if return_state:
        res["state"] = out
        # would the loop keep going without the budget cap?
        res["running"] = _running(out)
        # live pipelines: what the compaction driver must keep. Padding rows
        # (batching.pad_workloads, arrival = PAD_ARRIVAL) count as live
        # until their waves run at the padding timestamp — dropping them
        # early would change the wave counter vs the uncompacted run.
        res["n_keep"] = jnp.sum(out["phase"] != _DONE, dtype=jnp.int32)
    return res


def simulate_to_trace(wl: M.Workload, platform: Optional[M.PlatformConfig] = None,
                      policy: int = POLICY_FIFO, scenario=None,
                      fleet=None, probe=None, reliability=None,
                      admission_sort: str = "select") -> M.SimTrace:
    """Convenience: numpy Workload in, SimTrace out (single replica).
    ``scenario`` is a :class:`repro.ops.scenario.CompiledScenario`;
    ``fleet`` a :class:`repro.ops.scenario.CompiledFleet` (``wl`` must then
    be the extended workload carrying the latent retraining-pool rows);
    ``probe`` a :class:`repro.obs.probes.CompiledProbe` (in-loop telemetry
    sampling onto the trace's ``probe_times``/``probe_vals``);
    ``reliability`` a :class:`repro.reliability.compile.CompiledReliability`
    (correlated outage/repair/eviction capacity events recorded onto the
    trace's ``rel_times``/``rel_caps``). ``admission_sort`` selects the
    admission ranking as in :func:`simulate`."""
    platform = platform or M.PlatformConfig()
    att_start = att_finish = None
    ctrl_times = ctrl_caps = None
    fl = fleet
    if fl is not None and float(np.asarray(fl.trig)[TRIG_INTERVAL]) <= 0.0:
        fl = None
    fleet_kw = {}
    if fl is not None:
        fleet_kw = dict(
            fleet=jnp.asarray(fl.fleet, jnp.float32),
            trig=jnp.asarray(fl.trig, jnp.float32),
            obs_noise=jnp.asarray(fl.obs_noise, jnp.float32),
            drift_inc=jnp.asarray(fl.drift_inc, jnp.float32),
            pool_gain=jnp.asarray(fl.pool_gain, jnp.float32),
            pool_base=jnp.int32(fl.pool_base))
    pr = probe
    if pr is not None and \
            float(np.asarray(pr.header)[PROBE_INTERVAL]) <= 0.0:
        pr = None
    if pr is not None:
        hdr = np.asarray(pr.header, np.float32).copy()
        hdr[PROBE_N_MODELS] = np.float32(fl.n_models if fl is not None else 0)
        fleet_kw.update(probe=jnp.asarray(hdr),
                        n_probe_slots=int(pr.n_ticks))
    rel = reliability
    if rel is not None and int(np.asarray(rel.times).shape[0]) == 0:
        rel = None
    if rel is not None:
        fleet_kw.update(rel_times=jnp.asarray(rel.times, jnp.float32),
                        rel_deltas=jnp.asarray(rel.deltas, jnp.int32),
                        n_rel_slots=int(np.asarray(rel.times).shape[0]))
    if scenario is not None:
        from repro.core.des import ctrl_tick_bound, unpack_ctrl_actions
        vwl = VWorkload.from_workload(wl, platform, attempts=scenario.attempts)
        att_svc = getattr(scenario, "attempt_service", None)
        ctrl = getattr(scenario, "controller", None)
        frac = float(getattr(scenario, "fail_holds_frac", 1.0))
        slots = int(max(np.max(scenario.attempts), 1,
                        att_svc.shape[2] if att_svc is not None else 1))
        if slots == 1:   # no retries: single-attempt records already exact
            slots = None
        n_ctrl = ctrl_tick_bound(ctrl) if ctrl is not None else 0
        res = simulate(vwl, jnp.asarray(platform.capacities, jnp.int32), policy,
                       cap_times=jnp.asarray(scenario.cap_times, jnp.float32),
                       cap_vals=jnp.asarray(scenario.cap_vals, jnp.int32),
                       backoff=jnp.asarray(scenario.backoff, jnp.float32),
                       attempt_service=None if att_svc is None
                       else jnp.asarray(att_svc, jnp.float32),
                       n_attempt_slots=slots,
                       controller=None if ctrl is None
                       else jnp.asarray(ctrl, jnp.float32),
                       fail_holds_frac=None if frac >= 1.0 else frac,
                       n_ctrl_slots=n_ctrl if n_ctrl > 0 else None,
                       admission_sort=admission_sort, **fleet_kw)
        caps0 = np.asarray(scenario.cap_vals[0], np.int64)
        attempts = np.asarray(res["attempts"], np.int64)
        completed = np.asarray(res["done"])
        if slots is not None:
            att_start = np.asarray(res["att_start"], np.float64)
            att_finish = np.asarray(res["att_finish"], np.float64)
        if ctrl is not None and \
                float(np.asarray(ctrl)[CTRL_INTERVAL]) > 0.0:
            # enabled controller: realized timeline present (maybe empty),
            # exactly as the numpy engine reports it
            nres = int(scenario.cap_vals.shape[1])
            if n_ctrl > 0:
                ctrl_times, ctrl_caps = unpack_ctrl_actions(
                    res["ctrl_act"], res["ctrl_n"])
            else:
                ctrl_times = np.zeros(0, np.float64)
                ctrl_caps = np.zeros((0, nres), np.int64)
    else:
        vwl = VWorkload.from_workload(wl, platform)
        res = simulate(vwl, jnp.asarray(platform.capacities, jnp.int32),
                       policy, admission_sort=admission_sort, **fleet_kw)
        caps0 = platform.capacities
        attempts = None
        completed = np.asarray(res["done"]) if fl is not None else None
    arrival_out = np.asarray(wl.arrival, np.float64)
    fl_cols = {}
    if fl is not None:
        from repro.core.des import fleet_trace_columns
        arrival_out, fl_cols = fleet_trace_columns(
            fl, arrival_out, res["pool_arr"], res["fleet_act"],
            res["fleet_n"], res["fleet_perf"], res["fleet_stale"])
    if pr is not None:
        fl_cols.update(
            probe_times=np.asarray(pr.times, np.float64),
            probe_vals=np.asarray(res["probe_vals"], np.float64))
    if rel is not None:
        from repro.core.des import unpack_rel_actions
        rt, rc = unpack_rel_actions(res["rel_act"], res["rel_n"])
        fl_cols.update(rel_times=rt, rel_caps=rc)
    return M.SimTrace(
        start=np.asarray(res["start"], np.float64),
        finish=np.asarray(res["finish"], np.float64),
        ready=np.asarray(res["ready"], np.float64),
        n_tasks=wl.n_tasks.astype(np.int64),
        task_res=wl.task_res, task_type=wl.task_type,
        arrival=arrival_out,
        capacities=caps0,
        attempts=attempts,
        completed=completed,
        att_start=att_start,
        att_finish=att_finish,
        ctrl_times=ctrl_times,
        ctrl_caps=ctrl_caps,
        waves=int(res["waves"]),
        **fl_cols,
    )


# ---------------------------------------------------------------------------
# Monte-Carlo ensembles: vmap over a replica axis. Tensors must share shapes.
# ---------------------------------------------------------------------------

@partial(jax.jit,
         static_argnames=("policy", "n_attempt_slots", "admission_sort",
                          "n_ctrl_slots", "n_probe_slots", "n_rel_slots",
                          "return_state"))
def simulate_ensemble(arrival, n_tasks, task_res, service, priority,
                      capacities, policy: int = POLICY_FIFO,
                      attempts=None, cap_times=None, cap_vals=None,
                      backoff=None, policies=None, attempt_service=None,
                      n_attempt_slots: Optional[int] = None,
                      controllers=None, fail_holds_frac=None,
                      admission_sort: str = "select",
                      n_ctrl_slots: Optional[int] = None,
                      fleets=None, trig=None, obs_noise=None, drift_inc=None,
                      pool_gain=None, pool_base=None, n_pool_eff=None,
                      probes=None, n_probe_slots: Optional[int] = None,
                      rel_times=None, rel_deltas=None,
                      n_rel_slots: Optional[int] = None,
                      resume=None, wave_budget=None, time_budget=None,
                      return_state: bool = False):
    """arrival: [R, N]; task_res/service: [R, N, T]; capacities: [R, nres].

    Optional per-replica scenario tensors — ``attempts [R, N, T]``,
    ``cap_times [R, K]`` / ``cap_vals [R, K, nres]``, ``backoff [R, 3]``,
    ``attempt_service [R, N, T, A]`` (per-attempt resampled service times),
    ``controllers [R, C]`` (closed-loop ControllerParams rows; an all-zero
    row disables the controller for that replica), ``fail_holds_frac [R]``
    (slot-holding fraction of failing attempts) — let one batched call A/B
    capacity-planning *and* autoscaler/controller/failure scenarios across
    the replica axis. ``policies [R]`` (i32) assigns a (possibly different)
    admission policy per replica via the traced ``policy_dyn`` path, so a
    whole experiment grid — capacities, scenarios, controller gains, *and*
    schedulers — lowers to this one jit+vmap call. ``n_attempt_slots``
    (static) turns on per-attempt start/finish recording;
    ``admission_sort`` (static) selects the ranking as in :func:`simulate`;
    ``n_ctrl_slots`` (static; the max :func:`repro.core.des.ctrl_tick_bound`
    over the batch) turns on realized-capacity-timeline recording — the
    per-replica action buffers come back stacked ``ctrl_act [R, E, 1+nres]``
    with counts ``ctrl_n [R]``.

    The model-lifecycle stage batches the same way: ``fleets [R, M, 6]``,
    ``trig [R, TRIG_FIELDS]`` (an interval <= 0 row disables the stage for
    that replica), ``obs_noise``/``drift_inc [R, E, M]``, ``pool_gain
    [R, P]``, ``pool_base [R]`` and ``n_pool_eff [R]`` (entries padded to a
    common M/E/P; inert rows beyond each entry's own sizes). New
    ``"trigger:*"`` / ``"fleet:*"`` Sweep axes ride these tensors, so a
    whole lifecycle-policy grid lowers to this one jit+vmap call.

    The probe (telemetry) stage batches identically: ``probes
    [R, PROBE_FIELDS]`` headers (an interval <= 0 row disables the stage
    for that replica) plus the static ``n_probe_slots`` (the max tick bound
    over the batch) bring back stacked ``probe_vals [R, E, K]`` telemetry
    buffers, which ``batching.batch_trace`` slices per entry.

    The reliability stage batches the same way: ``rel_times [R, RV]`` /
    ``rel_deltas [R, RV, nres]`` (entries padded to a common RV with
    never-firing ``INF``-time zero-delta rows — a reliability-free replica
    is all padding) plus the static ``n_rel_slots`` bring back stacked
    ``rel_act [R, RV, 1+nres]`` event buffers with counts ``rel_n [R]``.
    ``"reliability:*"`` Sweep axes ride these tensors, so a whole
    availability-policy grid lowers to this one jit+vmap call.

    Segment-restart hooks batch per replica too: ``resume`` (a stacked
    carry pytree from a prior ``return_state=True`` call), ``wave_budget
    [R]`` i32 per-replica wave caps, ``time_budget [R]`` f32 per-replica
    time guards, and the static ``return_state`` — see :func:`simulate`
    and :mod:`repro.core.compaction`.
    """
    R = arrival.shape[0]
    if attempts is None:
        attempts = jnp.ones(task_res.shape, jnp.int32)
    if (cap_times is None) != (cap_vals is None):
        raise ValueError("cap_times and cap_vals must be given together")
    if cap_times is None:
        cap_times = jnp.zeros((R, 1), jnp.float32)
        cap_vals = jnp.asarray(capacities, jnp.int32)[:, None, :]
    if backoff is None:
        backoff = jnp.tile(jnp.asarray(_NO_RETRY_BACKOFF, jnp.float32)[None],
                           (R, 1))

    mapped = dict(arrival=arrival, n_tasks=n_tasks, task_res=task_res,
                  service=service, priority=priority,
                  attempts=jnp.asarray(attempts, jnp.int32),
                  capacities=capacities,
                  cap_times=jnp.asarray(cap_times, jnp.float32),
                  cap_vals=jnp.asarray(cap_vals, jnp.int32),
                  backoff=jnp.asarray(backoff, jnp.float32))
    if policies is not None:
        mapped["policy_dyn"] = jnp.asarray(policies, jnp.int32)
    if attempt_service is not None:
        mapped["attempt_service"] = jnp.asarray(attempt_service, jnp.float32)
    if controllers is not None:
        mapped["controllers"] = jnp.asarray(controllers, jnp.float32)
    if fail_holds_frac is not None:
        mapped["fail_holds_frac"] = jnp.asarray(fail_holds_frac, jnp.float32)
    if trig is not None:
        mapped["fleets"] = jnp.asarray(fleets, jnp.float32)
        mapped["trig"] = jnp.asarray(trig, jnp.float32)
        mapped["obs_noise"] = jnp.asarray(obs_noise, jnp.float32)
        mapped["drift_inc"] = jnp.asarray(drift_inc, jnp.float32)
        mapped["pool_gain"] = jnp.asarray(pool_gain, jnp.float32)
        mapped["pool_base"] = jnp.asarray(pool_base, jnp.int32)
        mapped["n_pool_eff"] = jnp.asarray(n_pool_eff, jnp.int32)
    if probes is not None:
        mapped["probes"] = jnp.asarray(probes, jnp.float32)
    if rel_times is not None:
        mapped["rel_times"] = jnp.asarray(rel_times, jnp.float32)
        mapped["rel_deltas"] = jnp.asarray(rel_deltas, jnp.int32)
    if resume is not None:
        mapped["resume"] = resume
    if wave_budget is not None:
        mapped["wave_budget"] = jnp.asarray(wave_budget, jnp.int32)
    if time_budget is not None:
        mapped["time_budget"] = jnp.asarray(time_budget, jnp.float32)

    def one(m):
        vwl = VWorkload(m["arrival"], m["n_tasks"], m["task_res"],
                        m["service"], m["priority"], m["attempts"])
        return simulate(vwl, m["capacities"], policy,
                        cap_times=m["cap_times"], cap_vals=m["cap_vals"],
                        backoff=m["backoff"],
                        attempt_service=m.get("attempt_service"),
                        policy_dyn=m.get("policy_dyn"),
                        n_attempt_slots=n_attempt_slots,
                        controller=m.get("controllers"),
                        fail_holds_frac=m.get("fail_holds_frac"),
                        admission_sort=admission_sort,
                        n_ctrl_slots=n_ctrl_slots,
                        fleet=m.get("fleets"), trig=m.get("trig"),
                        obs_noise=m.get("obs_noise"),
                        drift_inc=m.get("drift_inc"),
                        pool_gain=m.get("pool_gain"),
                        pool_base=m.get("pool_base"),
                        n_pool_eff=m.get("n_pool_eff"),
                        probe=m.get("probes"),
                        n_probe_slots=n_probe_slots,
                        rel_times=m.get("rel_times"),
                        rel_deltas=m.get("rel_deltas"),
                        n_rel_slots=n_rel_slots,
                        resume=m.get("resume"),
                        wave_budget=m.get("wave_budget"),
                        time_budget=m.get("time_budget"),
                        return_state=return_state)

    return jax.vmap(one)(mapped)
