"""Pipelines simulated per second of the window: the pipelines of every grid
point and replica of every whole sweep (counted in the reference's
summaries, so the program's own arithmetic is not read), over the wall time
from the first sweep's start to the last one's end (host clock)."""


def read(run):
    per_sweep = 0
    for point in run.reference:
        sums = point["replica_summaries"] or [point["summary"]]
        per_sweep += sum(s["n_pipelines"] for s in sums)
    return len(run.sweeps) * per_sweep / run.window_s
