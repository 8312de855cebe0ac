"""Reduce a profiler trace to device busy time, idle gaps named by the host
span open in each, and the device operations that took most time.

Input is any object laid out as ``jax.profiler.ProfileData``: ``planes``,
each with ``name`` and ``lines``, each line with ``name`` and ``events``,
each event with ``name``, ``start_ns`` and ``duration_ns``. Device planes
are named ``/device:<KIND>:<n>``; operations are the events of their
``XLA Ops`` line, and whole programs those of ``XLA Modules``. Host spans
are the events of host planes whose names the caller gives (the
benchmark's own ``TraceAnnotation`` names).
"""
from __future__ import annotations

import glob
import os

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def latest_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _device_planes(trace) -> list:
    return [p for p in trace.planes if p.name.startswith("/device:")
            and "CPU" not in p.name]


def op_name(name: str) -> str:
    """An operation's short name: ``%fusion.61 = (...) fusion(...)``, as
    the TPU trace names it, becomes ``fusion.61``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            yield from line.events


def host_spans(trace, names) -> list:
    """``[(name, start_ns, end_ns)]`` of host events whose name is in
    ``names``, sorted by start."""
    names = set(names)
    out = []
    for plane in trace.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.append((ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns)))
    return sorted(out, key=lambda s: s[1])


def device_extent(trace, window):
    """``(first start, last end)`` in ns of the device operations that
    overlap ``window``, over every device plane; None where there are
    none."""
    t0, t1 = int(window[0]), int(window[1])
    lo = hi = None
    for plane in _device_planes(trace):
        for ev in _events(plane, OPS_LINE):
            a, b = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
            if b <= t0 or a >= t1:
                continue
            lo = a if lo is None else min(lo, a)
            hi = b if hi is None else max(hi, b)
    return None if lo is None else (lo, hi)


def covered_window(trace, window, engine, tol_ns: int = 250_000_000):
    """The part of ``window`` that the trace holds device events for.

    The profiler keeps a bounded number of device events, and a long loop
    with many operations per wave fills them: the trace then ends (or
    begins) inside the engine call ``engine = (start_ns, end_ns)``, during
    which the device runs throughout (the harness waits for the outputs
    inside that span). Returns ``(window, "whole")`` where the recorded
    operations reach both ends of the engine call to within ``tol_ns``;
    else ``(slice, "truncated")``, the window cut at the first or last
    recorded operation."""
    ext = device_extent(trace, window)
    if ext is None or engine is None:
        return (int(window[0]), int(window[1])), "whole"
    lo, hi = int(window[0]), int(window[1])
    cut = False
    if ext[1] < engine[1] - tol_ns:
        hi, cut = ext[1], True
    if ext[0] > engine[0] + tol_ns:
        lo, cut = ext[0], True
    return (lo, hi), ("truncated" if cut else "whole")


def union(intervals: np.ndarray) -> np.ndarray:
    """Merge ``[k, 2]`` (start, end) intervals into disjoint sorted ones."""
    if len(intervals) == 0:
        return np.zeros((0, 2), np.int64)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:] - 1, len(iv) - 1)
    return np.stack([starts, ends[last]], 1)


def reduce(trace, window, span_names=(), top: int = 10) -> dict:
    """Busy and idle inside ``window = (t0_ns, t1_ns)``, averaged over the
    device planes that ran an operation.

    ``busy_s``: the union of operation intervals, clipped to the window.
    ``idle_gaps``: ``[[host span name, seconds], ...]``, the device's idle
    time grouped by the innermost host span (of ``span_names``) open at the
    middle of each gap, ``"(no span)"`` where none is, longest first.
    ``device_ops``: ``[[op name, seconds], ...]`` summed over the window
    (an operation that holds others, such as a ``while``, counts their time
    too).
    ``modules``: seconds per program (``XLA Modules``) in the window.
    """
    t0, t1 = int(window[0]), int(window[1])
    spans = host_spans(trace, span_names)
    busy_total, gaps, ops, modules, n_planes = 0.0, {}, {}, {}, 0
    for plane in _device_planes(trace):
        iv = []
        for ev in _events(plane, OPS_LINE):
            a, b = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
            if b <= t0 or a >= t1:
                continue
            a, b = max(a, t0), min(b, t1)
            iv.append((a, b))
            op = op_name(ev.name)
            ops[op] = ops.get(op, 0.0) + (b - a) * 1e-9
        for ev in _events(plane, MODULES_LINE):
            a, b = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
            a, b = max(a, t0), min(b, t1)
            if b > a:
                modules[ev.name] = modules.get(ev.name, 0.0) + (b - a) * 1e-9
        if not iv:
            continue
        n_planes += 1
        merged = union(np.asarray(iv, np.int64))
        busy_total += float((merged[:, 1] - merged[:, 0]).sum()) * 1e-9
        edges = np.concatenate([[t0], merged.ravel(), [t1]]).reshape(-1, 2)
        edges = edges[edges[:, 1] > edges[:, 0]]
        secs = (edges[:, 1] - edges[:, 0]) * 1e-9
        mids = (edges[:, 0] + edges[:, 1]) // 2          # sorted
        # innermost span at each gap's middle: longer spans first, shorter
        # (inner) ones overwrite them
        owner = np.full(len(mids), -1)
        for k in sorted(range(len(spans)),
                        key=lambda k: spans[k][1] - spans[k][2]):
            lo, hi = np.searchsorted(mids, [spans[k][1], spans[k][2]])
            owner[lo:hi] = k
        for k in np.unique(owner):
            name = spans[k][0] if k >= 0 else "(no span)"
            gaps[name] = gaps.get(name, 0.0) + float(secs[owner == k].sum())
    n = max(n_planes, 1)

    def ranked(d):
        return [[k, v / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return dict(busy_s=busy_total / n, window_s=(t1 - t0) * 1e-9,
                device_planes=n_planes, idle_gaps=ranked(gaps),
                device_ops=ranked(ops),
                modules={k: v / n for k, v in modules.items()})
