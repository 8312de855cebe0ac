"""What the program records about itself, for the metric readers: its host
spans (``repro.obs.profile.spans``), and what the traced sweep's per-row
``SimTrace`` says of the wave loop: waves, admission instants and
``ops_waves``. Where the program records none of these, a reader returns
None."""
from __future__ import annotations

from collections import Counter, defaultdict

import numpy as np


def span_seconds(run, name: str):
    """Seconds per sweep inside the program's spans named ``name`` (summed
    where a sweep opens several, one per grid point), median over the
    window's sweeps; None where the program opened none, or where its
    bounded buffer may have dropped spans of the window."""
    try:
        from repro.obs import profile
    except ImportError:
        return None
    closed = getattr(profile, "spans", lambda: [])()
    mine = [(s.start_ns, s.end_ns) for s in closed if s.name == name]
    if not mine:
        return None
    # the buffer drops its oldest spans first: the window is whole when the
    # buffer never filled, or kept a span that closed before the window
    if (len(closed) >= getattr(profile, "SPAN_BUFFER", len(closed) + 1)
            and closed[0].end_ns >= run.sweeps[0]["start"]):
        return None
    return run.per_sweep(lambda sw: 1e-9 * sum(
        b - a for a, b in mine if sw["start"] <= a < sw["end"]))


def row_traces(run):
    """The ``SimTrace`` of every row of the traced sweep (the window's
    first), or None where a row's trace has no wave count."""
    rows = run.sweeps[0]["traces"]
    if not rows or any(t.waves is None for t in rows):
        return None
    return rows


def admission_rounds(trace) -> int:
    """The admission rounds of one row: for each instant at which its jobs
    started (every attempt, where the trace keeps them), the most jobs that
    started on one resource, summed over instants. Where one wave admits
    every job that starts at an instant, this is the number of rounds the
    ``"select"`` ranking's inner loop ran for the row's own pipelines; it
    leaves out the padding pipelines that a padded row runs at the padding
    time after its last pipeline."""
    starts = (trace.att_start if trace.att_start is not None
              else trace.start[..., None])
    res = np.broadcast_to(np.asarray(trace.task_res)[..., None], starts.shape)
    ran = np.isfinite(starts)
    most = defaultdict(int)
    for (t, _), n in Counter(zip(starts[ran].tolist(),
                                 res[ran].tolist())).items():
        most[t] = max(most[t], n)
    return sum(most.values())
