"""What decides ``correct``: what the timed sweeps produced against the plain
reference, every number beside its limit.

Compared, per grid point and replica of every sweep in the window:

- ``trace_drift``: the widest gap over every buffer of the engine trace the
  sweep unpacked (``batching.batch_trace``): schedules, attempts, per-attempt
  times, controller, reliability, fleet and probe buffers, and the wave
  count. A buffer present on one side only, or of another shape, reads inf
  (but for the two paddings of a batch that carry no result, see
  :func:`buffer_drift`). A row with fewer pipelines than the widest row of its batch is padded
  (``batching.pad_workloads``), and its wave counter also counts the waves
  that retire the padding pipelines at the padding time, which the one-row
  reference does not have: there the wave count reads by how many waves the
  row ran fewer than the reference.
- ``records_drift``: the widest gap over the task records the user reads
  (``trace.flatten_trace``), in (pipeline, task) order.
- ``summary_drift``: the widest relative gap over every number of every
  point's summary and replica summaries, leaving out the wall-clock fields.
  A key on one side only reads inf.

And of the window as a whole:

- ``calls_off``: how far any sweep is from one ``simulate_ensemble`` call
  of its own, plus any results object a sweep handed back that an earlier
  sweep had already handed back.
- ``rows_missing``: engine traces the reference has and a sweep did not
  produce (or the other way round), over all sweeps.

On integer-time traffic the float32 engine is exact, so every limit is 0.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

LIMITS = {"trace_drift": 0.0, "records_drift": 0.0, "summary_drift": 0.0,
          "calls_off": 0.0, "rows_missing": 0.0}

# wall-clock readings in a summary: not results
TIMING_KEYS = {"wall_s", "pipelines_per_s"}


def max_abs_diff(a, b) -> float:
    """max |a - b| with NaN == NaN; inf on a shape or one-sided NaN
    mismatch."""
    try:
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
    except (TypeError, ValueError):
        return 0.0 if a == b else math.inf
    if a.shape != b.shape:
        return math.inf
    if a.size == 0:
        return 0.0
    both = np.isnan(a) & np.isnan(b)
    with np.errstate(invalid="ignore"):
        d = np.where(both, 0.0, np.abs(a - b))
    d = np.where(np.isinf(a) & np.isinf(b) & (a == b), 0.0, d)
    return math.inf if np.isnan(d).any() else float(d.max())


def _empty(v) -> bool:
    return v is None or (isinstance(v, np.ndarray) and v.size == 0)


def buffer_drift(got, ref) -> float:
    """Widest gap between one buffer of a batched row and the reference's.
    A batch pads a row's buffers to its widest row, and two such paddings
    carry no result: attempt slots past the row's own last axis, which
    must never have run (NaN), and an empty action timeline where the
    reference has none (a row whose controller is off)."""
    if _empty(got) and _empty(ref):
        return 0.0
    if (got is None) != (ref is None):
        return math.inf
    g, r = np.asarray(got), np.asarray(ref)
    if g.ndim == r.ndim and g.ndim > 0 and g.shape[:-1] == r.shape[:-1] \
            and g.shape[-1] > r.shape[-1]:
        extra = np.asarray(g[..., r.shape[-1]:], np.float64)
        if not np.isnan(extra).all():
            return math.inf
        g = g[..., :r.shape[-1]]
    return max_abs_diff(g, r)


def fields_drift(got, ref, names) -> float:
    """Widest gap over ``names`` of two dataclass instances; a field
    missing on ``got`` reads inf."""
    drift = 0.0
    for name in names:
        if not hasattr(got, name):
            return math.inf
        drift = max(drift, buffer_drift(getattr(got, name),
                                        getattr(ref, name)))
    return drift


def trace_drift(got, ref, padded: bool = False) -> float:
    names = [f.name for f in dataclasses.fields(ref)]
    if not padded:
        return fields_drift(got, ref, names)
    names.remove("waves")
    short = max(0, (ref.waves or 0) - (getattr(got, "waves", 0) or 0))
    return max(fields_drift(got, ref, names), float(short))


def sorted_records(rec):
    o = np.lexsort((rec.task_pos, rec.pipeline))
    return {f.name: getattr(rec, f.name)[o]
            for f in dataclasses.fields(rec)
            if isinstance(getattr(rec, f.name), np.ndarray)}


def records_drift(got, ref) -> float:
    a, b = sorted_records(got), sorted_records(ref)
    if set(a) != set(b):
        return math.inf
    return max([max_abs_diff(a[k], b[k]) for k in b] or [0.0])


def _leaves(d, prefix=""):
    if isinstance(d, dict):
        for k, v in d.items():
            if k not in TIMING_KEYS:
                yield from _leaves(v, f"{prefix}{k}.")
    elif isinstance(d, (list, tuple)) and d and isinstance(d[0], dict):
        for i, v in enumerate(d):
            yield from _leaves(v, f"{prefix}{i}.")
    else:
        yield prefix.rstrip("."), d


def summary_drift(got: dict, ref: dict) -> float:
    a, b = dict(_leaves(got)), dict(_leaves(ref))
    if set(a) != set(b):
        return math.inf
    drift = 0.0
    for k, vb in b.items():
        va = a[k]
        if isinstance(vb, str) or vb is None or isinstance(va, str) \
                or va is None:
            if va != vb:
                return math.inf
            continue
        va = np.asarray(va, np.float64)
        vb = np.asarray(vb, np.float64)
        scale = np.maximum(np.maximum(np.abs(va), np.abs(vb)), 1e-300)
        d = max_abs_diff(va / scale, vb / scale) if va.shape == vb.shape \
            else math.inf
        drift = max(drift, d)
    return drift


def compare(sweeps: list, reference: list) -> tuple:
    """``sweeps``: per window sweep ``{"results": [ExperimentResult per
    point], "traces": [SimTrace per row], "calls": int}``; ``reference``:
    :func:`reference.sweep.run`'s output. Returns every compared number
    over the window, and per sweep whether it matched the reference.
    Results a sweep hands back that an earlier sweep already handed back
    are counted in ``calls_off`` and not compared again."""
    ref_traces = [t for p in reference for t in p["traces"]]
    n_max = max(len(t.arrival) for t in ref_traces)
    total = dict(trace_drift=0.0, records_drift=0.0, summary_drift=0.0,
                 calls_off=0.0, rows_missing=0.0)
    seen, ok = set(), []
    for sw in sweeps:
        out = dict.fromkeys(total, 0.0)
        out["calls_off"] = abs(sw["calls"] - 1)
        fresh = []
        for res, ref in zip(sw["results"], reference):
            if id(res) in seen:
                out["calls_off"] += 1
            else:
                fresh.append((res, ref))
            seen.add(id(res))
        out["rows_missing"] = abs(len(sw["traces"]) - len(ref_traces)) \
            + abs(len(sw["results"]) - len(reference))
        for got, ref in zip(sw["traces"], ref_traces):
            out["trace_drift"] = max(out["trace_drift"], trace_drift(
                got, ref, padded=len(ref.arrival) < n_max))
        for res, ref in fresh:
            out["records_drift"] = max(out["records_drift"], records_drift(
                res.records, ref["records"]))
            out["summary_drift"] = max(out["summary_drift"], summary_drift(
                dict(summary=res.summary,
                     replicas=res.replica_summaries or []),
                dict(summary=ref["summary"],
                     replicas=ref["replica_summaries"] or [])))
        ok.append(verdict(out))
        for k in ("trace_drift", "records_drift", "summary_drift"):
            total[k] = max(total[k], out[k])
        total["calls_off"] = max(total["calls_off"], out["calls_off"])
        total["rows_missing"] += out["rows_missing"]
    return {k: float(v) for k, v in total.items()}, ok


def verdict(numbers: dict) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)


def checks_block(numbers: dict) -> dict:
    """The result line's last key: each number beside its limit."""
    return {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS}
