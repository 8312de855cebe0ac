"""Active-set compaction: segmented wave loops over a windowed working set.

The batched engine's wave cost is dominated by terms that scale with the
*allocated* pipeline axis — above all the O(N^2) pairwise admission seat
count (``vdes.admission_mask_dense``), which at N ~ 134 is the single
largest op of the whole wave — while the number of pipelines that can
actually *do* anything at a given clock is far smaller: finished pipelines
are inert forever, and pipelines that have not arrived yet are inert until
their arrival. This driver runs ``vdes.simulate_ensemble`` in *segments*
(the engine's ``resume`` / ``wave_budget`` / ``time_budget`` /
``return_state`` hooks make both a wave boundary and a time boundary a
bit-exact cut) over a compact working set per segment:

  - **finished replicas retire** — replicas whose loop finished drop off
    the batch axis entirely, so a draining Monte-Carlo ensemble stops
    paying for its finished members;
  - **DONE rows drop** — a DONE row has ``t_next == INF`` and can never
    re-enter any stage;
  - **future arrivals defer** — a row with ``phase == NOT_ARRIVED`` and
    ``t_next > guard`` cannot affect any wave at clock <= ``guard``: it is
    the admission/queue/probe sentinel, and it cannot be the event minimum
    of such a wave (its ``t_next`` exceeds the guard). The driver picks a
    per-replica f32 ``guard``, defers every such row, and passes the guard
    as the engine's ``time_budget`` — the loop provably stops before any
    wave that could tell the difference. Deferred rows re-enter at a later
    segment once the window advances past their ``t_next`` (this also
    covers retry-backoff rows and ``batching.pad_workloads`` padding rows,
    which are plain ``NOT_ARRIVED`` rows with far-future times).

The working width is the power-of-two bucket of the *active* set (arrived
and unfinished, plus at least the next whole arrival-time group), floored
at ``min_rows``; spare bucket capacity is greedily filled with the nearest
future arrivals (whole time-groups only, so the guard cut never splits a
tie) purely to push the guard further out and spend fewer boundaries.
Bucketing both axes bounds the compiled-shape footprint to
O(log R x log N).

Each segment is ONE jitted call (``_segment_call``): the canonical
full-size state pytree lives on the device; the call gathers the working
set, traces straight into ``vdes.simulate_ensemble``, and scatters the
returned carry back into the full state. Between segments the host
downloads only ``phase`` / ``t_next`` / ``wave`` (a few KB) to choose the
next window, so per-boundary overhead is one dispatch plus three small
transfers rather than a full state round-trip.

Bit-parity argument (twin-tested against the uncompacted engine):

  - dropped rows are DONE (inert forever) or deferred (inert until after
    the guard, and the segment stops at the guard — if a deferred row
    *would* have been the event minimum, the minimum over present rows is
    larger still, so the cut fires either way);
  - gathers keep surviving rows in ascending original order, so every
    pairwise pipeline-id comparison (the admission tie-break) has the same
    outcome as in the full array; ``enq_wave`` rides in the carry;
  - padding slots (a bucket is not an exact fit) duplicate a dropped row;
    a DONE duplicate is inert, a deferred duplicate has ``t_next`` beyond
    the guard so its events never run — either way the slot comes back
    bit-identical and its scatter-back rewrites the source row with the
    values it already has;
  - fleet retraining-pool rows are *always* kept (the fleet stage
    addresses them as the contiguous block ``[pool_base, pool_base + P)``,
    live or not) and ``pool_base`` is remapped to the block's compacted
    position — the gather preserves contiguity because it preserves order;
  - the wave counter, controller/fleet/probe tick state, and every
    preallocated recording buffer ride the carry verbatim across segments;
    a replica whose budget expires while others continue is frozen by the
    batched ``while_loop``'s select semantics, another exact cut.

``simulate_ensemble_compacted`` returns the same result dict as
``vdes.simulate_ensemble`` (numpy, full original ``[R, N]`` shapes),
assembled from the final canonical state, so ``batching.batch_trace`` and
the engine layer consume it unchanged; the ``jax-compact`` engine
(:mod:`repro.core.engines`) is exactly the batched engine with this driver
substituted for the single ensemble call.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import vdes
from repro.core.des import POLICY_FIFO

_NOT_ARRIVED = 0  # vdes._NOT_ARRIVED (phase enum)
_DONE = 3         # vdes._DONE

#: carry keys indexed by the pipeline-row axis — everything else in the
#: carry is per-replica scalar/buffer state and passes through untouched
ROW_STATE_KEYS = ("phase", "task_idx", "t_next", "enq_wave", "attempt",
                  "start", "finish", "ready", "att_out",
                  "att_start", "att_finish")
#: ensemble input kwargs indexed by the pipeline-row axis (gather per row)
ROW_INPUT_KEYS = ("arrival", "n_tasks", "task_res", "service", "priority",
                  "attempts", "attempt_service")
#: static (non-array) ensemble kwargs passed through every segment
STATIC_KEYS = ("n_attempt_slots", "admission_sort", "n_ctrl_slots",
               "n_probe_slots")
_POSITIONAL = ("arrival", "n_tasks", "task_res", "service", "priority",
               "capacities")


def _bucket(n: int, floor: int = 1) -> int:
    """Smallest power of two >= max(n, floor, 1). (Half-step buckets
    3*2^k were measured and lost: the finer ladder shifts the guard
    cascade toward more, smaller segments, and per-boundary overhead eats
    the N^2 savings on CPU.)"""
    b = max(int(floor), 1)
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass
class CompactionLog:
    """What the driver did: segment count, gather events, and the
    (replicas, rows) working-shape timeline — the compiled-shape
    footprint."""

    n_compactions: int = 0                 # windowed-gather boundaries
    n_segments: int = 0                    # jitted segment calls
    shapes: List[Tuple[int, int]] = dataclasses.field(default_factory=list)
    live_rows: List[int] = dataclasses.field(default_factory=list)

    @property
    def distinct_shapes(self) -> int:
        return len(set(self.shapes))


@partial(jax.jit, static_argnames=("policy",) + STATIC_KEYS)
def _segment_call(dev_inputs, full_state, rep_idx, row_idx, pool_base_w,
                  wave_budget, time_budget, *, policy,
                  n_attempt_slots, admission_sort, n_ctrl_slots,
                  n_probe_slots):
    """One segment: gather the working set from the canonical full-size
    pytrees, run the wave loop under the wave/time budgets, scatter the
    carry back. The working shapes ``rep_idx [Rw]`` / ``row_idx [Rw, W]``
    key the compile cache; everything stays on the device."""
    def g(a):                         # per-replica gather
        return a[rep_idx]

    def gr(a):                        # per-row gather
        return a[rep_idx[:, None], row_idx]

    w_inputs = {k: (gr(v) if k in ROW_INPUT_KEYS else g(v))
                for k, v in dev_inputs.items()}
    if pool_base_w is not None:
        w_inputs["pool_base"] = pool_base_w
    w_state = {k: (gr(v) if k in ROW_STATE_KEYS else g(v))
               for k, v in full_state.items()}
    res = vdes.simulate_ensemble(
        *(w_inputs[k] for k in _POSITIONAL), policy,
        **{k: v for k, v in w_inputs.items() if k not in _POSITIONAL},
        n_attempt_slots=n_attempt_slots, admission_sort=admission_sort,
        n_ctrl_slots=n_ctrl_slots, n_probe_slots=n_probe_slots,
        resume=w_state, wave_budget=wave_budget, time_budget=time_budget,
        return_state=True)
    new = res["state"]
    # scatter the carry back; duplicate targets (padding slots/replicas)
    # carry values identical to what they gathered, so the scatter is
    # deterministic
    out_state = {k: (v.at[rep_idx[:, None], row_idx].set(new[k])
                     if k in ROW_STATE_KEYS else v.at[rep_idx].set(new[k]))
                 for k, v in full_state.items()}
    return out_state, res["running"]


def simulate_ensemble_compacted(
        arrival, n_tasks, task_res, service, priority, capacities,
        policy: int = POLICY_FIFO, *, segment_waves: int = 256,
        drain_waves: int = 256, min_rows: int = 8, lookahead: int = 24,
        log: Optional[CompactionLog] = None,
        **kw) -> Dict[str, np.ndarray]:
    """Drop-in for :func:`vdes.simulate_ensemble` (same tensor kwargs, same
    result keys/shapes, numpy values) that runs the wave loop in windowed,
    compacted segments. ``segment_waves`` caps the waves between
    boundaries while arrivals remain deferred (the time guard is the real
    cut there, so this is just a backstop); ``drain_waves`` is the
    per-segment budget once a replica's window holds everything left
    (guard = INF) — shorter segments in the drain phase let the working
    width shrink with the DONE rows; ``min_rows`` floors the bucketed
    working width; ``lookahead`` reserves window slots beyond the active
    set for future arrivals (a wider window runs more waves per boundary
    at a slightly wider, still-bucketed width — the knob trades per-wave
    cost against per-boundary overhead); ``log`` (optional
    :class:`CompactionLog`) records what the driver did."""
    if segment_waves < 1 or drain_waves < 1:
        raise ValueError("segment_waves and drain_waves must be >= 1, got "
                         f"{segment_waves}/{drain_waves}")
    log = log if log is not None else CompactionLog()
    statics = {k: kw.pop(k, None) for k in STATIC_KEYS}
    if statics["admission_sort"] is None:
        statics["admission_sort"] = "select"
    inputs = dict(arrival=arrival, n_tasks=n_tasks, task_res=task_res,
                  service=service, priority=priority, capacities=capacities)
    inputs.update({k: v for k, v in kw.items() if v is not None})
    dev_inputs = {k: jnp.asarray(v) for k, v in inputs.items()}
    has_fleet = "trig" in inputs
    P = int(dev_inputs["pool_gain"].shape[1]) if has_fleet else 0
    pool_base0 = (np.asarray(inputs["pool_base"]).astype(np.int64)
                  if has_fleet else None)

    R0, N0 = dev_inputs["arrival"].shape

    # materialize the canonical full-size carry with a zero-budget call:
    # the loop exits before its first wave, returning the exact initial
    # state (and the full-shape compile doubles as the uncompacted
    # engine's, so warmups share it)
    res0 = vdes.simulate_ensemble(
        *(dev_inputs[k] for k in _POSITIONAL), policy,
        **{k: v for k, v in dev_inputs.items() if k not in _POSITIONAL},
        **statics, wave_budget=np.zeros(R0, np.int32), return_state=True)
    full_state = res0["state"]
    log.n_segments += 1
    log.shapes.append((R0, N0))

    running, phase, t_next, wave = (a.copy() for a in jax.device_get(
        (res0["running"], full_state["phase"], full_state["t_next"],
         full_state["wave"])))

    while True:
        # a replica continues if its engine loop would (``running``) or if
        # a *deferred* row could still wake it: a NOT_ARRIVED row with
        # finite t_next that was absent from the last working set. (A
        # present row with finite t_next forces ``running`` True, so this
        # is exact — and a replica the engine halted over starved QUEUED
        # rows stays halted, matching the uncompacted loop.)
        live = running | ((phase == _NOT_ARRIVED)
                          & (t_next < np.inf)).any(axis=1)
        rep_live = np.flatnonzero(live)
        if not len(rep_live):
            break

        # ---- replica axis: live replicas, bucketed, padded with retired
        r_w = min(_bucket(len(rep_live)), R0)
        retired = np.flatnonzero(~live)
        rep_sel = np.concatenate([rep_live, retired[:r_w - len(rep_live)]])

        # ---- row axis (vectorized over the window's replica lanes):
        # forced = arrived-and-unfinished (plus the fleet pool block);
        # optional = NOT_ARRIVED rows, windowed by t_next
        nl = len(rep_live)
        forced = np.zeros((r_w, N0), bool)
        forced[:nl] = (phase[rep_live] != _DONE) \
            & (phase[rep_live] != _NOT_ARRIVED)
        cols = np.arange(N0)[None, :]
        if has_fleet:
            pb = pool_base0[rep_sel][:, None]
            forced |= (cols >= pb) & (cols < pb + P)
        opt = np.zeros((r_w, N0), bool)
        opt[:nl] = (phase[rep_live] == _NOT_ARRIVED) & ~forced[:nl]

        # per-lane optionals by ascending t_next (non-optionals pushed to
        # +inf; stable, so ties keep column order): one argsort serves the
        # width choice, the window fill and the guard
        ts = np.full((r_w, N0), np.inf, np.float32)
        ts[:nl] = np.where(opt[:nl], t_next[rep_live], np.inf)
        order = np.argsort(ts, axis=1, kind="stable")
        ts_s = np.take_along_axis(ts, order, axis=1)
        n_opt = opt.sum(axis=1)
        fc = forced.sum(axis=1)

        # width: bucket of the worst-case active set plus at least the
        # next whole arrival-time group (so every live replica can make
        # progress within its guard)
        first_group = np.minimum((ts_s == ts_s[:, :1]).sum(axis=1)
                                 * (n_opt > 0), n_opt)
        need = int(np.max(fc + np.maximum(first_group,
                                          np.minimum(lookahead, n_opt)),
                          initial=0))
        width = min(_bucket(need, min_rows), N0)

        # fill spare capacity with the nearest future groups (whole
        # groups only: the guard cut must not split a t_next tie)
        m = np.minimum(width - fc, n_opt)
        last_in = np.take_along_axis(
            ts_s, np.maximum(m - 1, 0)[:, None], axis=1)[:, 0]
        split = (m > 0) & (m < n_opt) & (np.take_along_axis(
            ts_s, np.minimum(m, N0 - 1)[:, None], axis=1)[:, 0] == last_in)
        # a tie at the cut excludes that whole group
        m = np.where(split, (ts_s < last_in[:, None]).sum(axis=1), m)
        last_in = np.take_along_axis(
            ts_s, np.maximum(m - 1, 0)[:, None], axis=1)[:, 0]
        # guard: the last included t_next; nothing included -> just before
        # the first excluded arrival; nothing excluded -> +inf
        guard = np.full(r_w, np.inf, np.float32)
        cut = m < n_opt
        guard[cut] = np.where(
            m[cut] > 0, last_in[cut],
            np.nextafter(ts_s[cut, 0], -np.inf)).astype(np.float32)

        keep = np.zeros((r_w, N0), bool)
        np.put_along_axis(keep, order, cols < m[:, None], axis=1)
        keep = forced | (keep & opt)

        # kept columns first (ascending), the first dropped column pads
        kidx = np.argsort(~keep, axis=1, kind="stable")
        n_kept = keep.sum(axis=1)
        pad = kidx[np.arange(r_w), np.minimum(n_kept, N0 - 1)]
        row_idx = np.where(cols[:, :width] < n_kept[:, None],
                           kidx[:, :width], pad[:, None])
        new_pb = ((keep & (cols < pool_base0[rep_sel][:, None]))
                  .sum(axis=1) if has_fleet else None)
        log.live_rows.append(int(fc[:nl].max()) if nl else 0)

        pool_base_w = (jnp.asarray(
            new_pb, dev_inputs["pool_base"].dtype) if has_fleet else None)
        # guard < INF: the time cut bounds the segment, the wave budget is
        # a backstop. guard == INF (drain phase): short segments, so the
        # width shrinks with the DONE rows
        seg_w = np.where(np.isfinite(guard), segment_waves, drain_waves)
        wb = jnp.asarray(wave[rep_sel] + seg_w, jnp.int32)
        tb = jnp.asarray(guard, jnp.float32)
        full_state, run_w = _segment_call(
            dev_inputs, full_state, jnp.asarray(rep_sel),
            jnp.asarray(row_idx), pool_base_w, wb, tb,
            policy=policy, **statics)
        log.n_segments += 1
        log.n_compactions += 1
        log.shapes.append((r_w, width))

        run_np, phase, t_next, wave = jax.device_get(
            (run_w, full_state["phase"], full_state["t_next"],
             full_state["wave"]))
        running[rep_sel] = run_np

    # ---- assemble the vdes.simulate_ensemble result dict from the final
    # canonical carry (the recording buffers ride the carry verbatim)
    st = jax.device_get(full_state)
    res = dict(start=st["start"], finish=st["finish"], ready=st["ready"],
               attempts=st["att_out"], done=st["phase"] == _DONE,
               waves=st["wave"])
    if "ops_waves" in st:
        res["ops_waves"] = st["ops_waves"]
    if "fleet_waves" in st:
        res["fleet_waves"] = st["fleet_waves"]
    if statics["n_attempt_slots"] is not None:
        res["att_start"] = st["att_start"]
        res["att_finish"] = st["att_finish"]
    if "controllers" in inputs and statics["n_ctrl_slots"]:
        res["ctrl_act"] = st["ctrl_act"]
        res["ctrl_n"] = st["ctrl_n"]
    if has_fleet:
        for k in ("fleet_perf", "fleet_stale", "fleet_act", "fleet_n",
                  "pool_arr", "pool_model", "pool_next"):
            res[k] = st[k]
    if "probes" in inputs and statics["n_probe_slots"]:
        res["probe_vals"] = st["probe_vals"]
        res["probe_n"] = st["p_tick"]
    return res
