"""Split a profiler trace's device time by wave-loop stage, and its device
idle time by the program's own host spans.

The program names both (``repro.core.vdes``, ``repro.obs.profile``):

- every stage of the wave loop runs under a ``jax.named_scope`` of its name
  (:data:`STAGES`), so each operation of the loop carries the stage in its
  name stack (``.../while/body/admission/eq``). The TPU trace's ``XLA Ops``
  events do not carry it; the HLO the trace file keeps does
  (``harness/hlo.py``). XLA fuses operations of several stages into one
  kernel, and under ``vmap`` the loop's carry update (a select the batched
  loop adds, named ``.../while``) is the root of most of them, so a fused
  operation's stage is the one most of the operations it fuses carry. A
  loop operation under no stage is the loop's own (``"other"``);
- each layer of a sweep is a host ``TraceAnnotation`` named
  ``pipesim/<layer>``.

A program that names neither gives no stage seconds and no span, and
:func:`split` then reports none. ``bench/stage_split.py`` runs this over one
traced sweep of a cell.
"""
from __future__ import annotations

import numpy as np

from harness import hlo, tracefile

#: the scope names of the wave loop's stages
STAGES = ("select", "completion", "control", "admission", "fleet", "probe")
SPAN_PREFIX = "pipesim/"
#: the sweep's own span: idle inside it but outside every layer span is
#: idle no layer accounts for
PARENT_SPAN = SPAN_PREFIX + "sweep"
NO_SPAN = "(no span)"
#: what an operation is charged to: a stage, the loop's own time, or
#: nothing (outside the loop)
KINDS = STAGES + ("other", None)


def stage_of(stack: str):
    """The stage a name stack runs through (its outermost stage scope),
    ``"other"`` for a loop operation under no stage, None outside the
    loop."""
    parts = stack.split("/")
    for p in parts:
        if p in STAGES:
            return p
    if any(p.startswith("while") or p.endswith("(while)") for p in parts):
        return "other"
    return None


def fused_stage(op_names) -> object:
    """The stage of a fused operation: the stage most of the operations it
    fuses carry (the earlier of :data:`STAGES` on a tie), leaving out the
    batched loop's per-row predicate (``.../while/body_pred/...``), which
    ``vmap`` recomputes at the end of the body and XLA fuses into every
    carry update. Where no other operation carries a stage: ``"other"``
    if some run in the loop (the loop's own bookkeeping), else the
    predicate's stage, else None."""
    kinds = [stage_of(n) for n in op_names]
    own = [k for n, k in zip(op_names, kinds) if "/body_pred/" not in n]
    counts = [own.count(st) for st in STAGES]
    if max(counts) > 0:
        return STAGES[counts.index(max(counts))]
    if "other" in own:
        return "other"
    counts = [kinds.count(st) for st in STAGES]
    if max(counts) > 0:
        return STAGES[counts.index(max(counts))]
    return "other" if "other" in kinds else None


def hlo_kinds(xplane: bytes) -> dict:
    """``{instruction name: kind}`` over every program whose HLO the trace
    file keeps, ``kind`` one of :data:`KINDS`: :func:`fused_stage` of a
    fusion's operations, else :func:`stage_of` of the instruction's own
    name stack. Where programs share a name, the largest program's wins:
    the traced sweep runs one program, the engine call."""
    modules = [hlo.module_ops(p)[2] for p in hlo.hlo_protos(xplane)]
    out = {}
    for ops in sorted(modules, key=len):
        out.update((i, fused_stage(names) if len(names) > 1
                    else stage_of(names[0])) for i, names in ops.items())
    return out


def exclusive(intervals: np.ndarray) -> np.ndarray:
    """Each interval's own time: its length less the part of it that the
    intervals starting inside it cover, for ``[k, 2]`` (start, end)
    intervals of one line that nest or follow one another (a ``while``
    holds its body's operations), so every covered instant counts once.
    Returns the times in the input's order."""
    iv = np.asarray(intervals, np.int64).reshape(-1, 2)
    own = iv[:, 1] - iv[:, 0]
    order = np.lexsort((-own, iv[:, 0]))   # by start, longest first
    s, e = iv[order, 0], iv[order, 1]
    if len(s) < 2 or (s[1:] >= np.maximum.accumulate(e)[:-1]).all():
        return own                          # nothing holds anything
    own_sorted = (e - s).tolist()
    starts, ends = s.tolist(), e.tolist()
    stack = []                      # positions of the open enclosing ops
    for i, (a, b) in enumerate(zip(starts, ends)):
        while stack and ends[stack[-1]] <= a:
            stack.pop()
        if stack:
            p = stack[-1]
            # the part of i inside its parent is the parent's no longer
            own_sorted[p] -= min(b, ends[p]) - a
        stack.append(i)
    out = np.empty_like(own)
    out[order] = own_sorted
    return out


def _host_spans(trace):
    out = []
    for plane in trace.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    out.append((ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns)))
    return out


def _busy_before(merged: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Busy nanoseconds before each time of ``t``, for disjoint sorted
    ``merged`` intervals."""
    if len(merged) == 0:
        return np.zeros(len(t), np.int64)
    lens = merged[:, 1] - merged[:, 0]
    cum = np.concatenate([[0], np.cumsum(lens)])
    k = np.searchsorted(merged[:, 0], t, side="right")  # intervals started
    last = np.clip(k - 1, 0, len(merged) - 1)
    partial = np.where(k > 0, np.clip(t - merged[last, 0], 0, lens[last]), 0)
    return cum[np.maximum(k - 1, 0)] * (k > 0) + partial


def idle_by_span(merged: np.ndarray, window, spans) -> dict:
    """Idle nanoseconds of ``window`` (the time outside the busy ``merged``
    intervals) by the innermost span of ``spans`` (``[(name, start_ns,
    end_ns)]``) open during it, by overlap: a gap that several spans share
    is split between them. ``NO_SPAN`` takes what no span covers."""
    t0, t1 = int(window[0]), int(window[1])
    cuts = {t0, t1}
    for _, a, b in spans:
        cuts.update(min(max(x, t0), t1) for x in (a, b))
    edges = np.array(sorted(cuts), np.int64)
    a, b = edges[:-1], edges[1:]
    idle = (b - a) - (_busy_before(merged, b) - _busy_before(merged, a))
    out = {}
    for lo, hi, ns in zip(a, b, idle):
        if ns <= 0:
            continue
        inside = [s for s in spans if s[1] <= lo and hi <= s[2]]
        # nested spans: the innermost opened last, or closes first
        name = max(inside, key=lambda s: (s[1], -s[2]))[0] if inside \
            else NO_SPAN
        out[name] = out.get(name, 0) + int(ns)
    return out


def reduce(trace, window, xplane: str = None) -> dict:
    """Stage and idle split of ``window = (t0_ns, t1_ns)``, averaged over
    the device planes that ran an operation there. ``xplane``: the trace's
    file, whose HLO names the stage of each operation (without it, no
    operation has one).

    ``stage_s``: seconds of device operations per stage of :data:`STAGES`
    that ran one, and ``"other"``, each operation charged its own time
    (:func:`exclusive`) clipped to the window. ``loop_s``: their sum, the
    loop's device time. ``idle_s``: the window less the union of operation
    intervals. ``idle_by_span``: ``{span name: seconds}`` of that idle time
    by the innermost ``pipesim/*`` span open in it (:func:`idle_by_span`).
    ``spans``: how many such spans the window holds. ``window_s``: the
    window's length.
    """
    t0, t1 = int(window[0]), int(window[1])
    spans = [sp for sp in _host_spans(trace) if sp[2] > t0 and sp[1] < t1]
    kinds = {}
    if xplane is not None:
        with open(xplane, "rb") as f:
            kinds = hlo_kinds(f.read())
    stage_ns, idle = {}, {}
    n_planes = 0
    for plane in tracefile._device_planes(trace):
        iv, codes = [], []
        code = {}                   # op name -> index into KINDS
        for line in plane.lines:
            if line.name != tracefile.OPS_LINE:
                continue
            for ev in line.events:
                a = int(ev.start_ns)
                b = a + int(ev.duration_ns)
                if b <= t0 or a >= t1:
                    continue
                c = code.get(ev.name)
                if c is None:
                    c = code[ev.name] = KINDS.index(
                        kinds.get(tracefile.op_name(ev.name)))
                iv.append((max(a, t0), min(b, t1)))
                codes.append(c)
        if not iv:
            continue
        n_planes += 1
        iv = np.asarray(iv, np.int64)
        per_kind = np.bincount(codes, weights=exclusive(iv),
                               minlength=len(KINDS))
        for kind, ns in zip(KINDS, per_kind):
            if kind is not None and ns > 0:
                stage_ns[kind] = stage_ns.get(kind, 0) + int(ns)
        merged = tracefile.union(iv)
        for name, ns in idle_by_span(merged, (t0, t1), spans).items():
            idle[name] = idle.get(name, 0) + ns
    n = max(n_planes, 1)
    stage_s = {k: v * 1e-9 / n for k, v in stage_ns.items()}
    return dict(stage_s=stage_s, loop_s=sum(stage_s.values()),
                window_s=(t1 - t0) * 1e-9,
                idle_s=sum(idle.values()) * 1e-9 / n,
                idle_by_span={k: v * 1e-9 / n for k, v in idle.items()},
                spans=len(spans), device_planes=n_planes)


def split(reduced: dict, engine_s: float, waves: int) -> dict:
    """What :func:`reduce`'s ``reduced`` says per wave, for a window whose
    engine call took ``engine_s`` seconds over ``waves`` loop iterations.

    ``wave_us``: the engine call's time per wave. ``stage_us``: each stage's
    share of the loop's device time, times ``wave_us`` (``"other"`` too);
    ``stages_share``: the stages of :data:`STAGES` together, in percent of
    the loop's device time. ``device_idle_share``: the window's idle time,
    in percent of the window. ``idle_named_share``: the percent of that
    idle time under a layer span (any ``pipesim/*`` span but the bare
    ``pipesim/sweep``). A number with nothing to read is None."""
    wave_us = engine_s / waves * 1e6 if engine_s and waves else None
    loop_s, idle_s = reduced["loop_s"], reduced["idle_s"]
    stage_us = {}
    if wave_us is not None and loop_s > 0:
        stage_us = {k: v / loop_s * wave_us
                    for k, v in reduced["stage_s"].items()}
    named = sum(v for k, v in reduced["idle_by_span"].items()
                if k.startswith(SPAN_PREFIX) and k != PARENT_SPAN)
    return dict(
        wave_us=wave_us, stage_us=stage_us,
        stages_share=(100.0 * sum(v for k, v in reduced["stage_s"].items()
                                  if k in STAGES) / loop_s
                      if loop_s > 0 else None),
        device_idle_share=(100.0 * idle_s / reduced["window_s"]
                           if reduced["device_planes"] else None),
        idle_named_share=(100.0 * named / idle_s
                          if reduced["spans"] and idle_s > 0 else None))
