"""Percent of the traced sweep's wave-loop iterations in which the fleet
stage ran (``SimTrace.fleet_waves``): the most iterations any row counts,
over the most waves any row ran. The stage runs only in iterations where
some row has a drift tick or a retraining pipeline that finished, and every
row still running counts each of them, so the longest row counts them all.
Nothing where no row has the counter: a sweep without a fleet, or a program
that runs the stage in every wave."""
from harness.program import row_traces


def read(run):
    rows = row_traces(run)
    if rows is None:
        return None
    counts = [c for c in (getattr(t, "fleet_waves", None) for t in rows)
              if c is not None]
    if not counts:
        return None
    return 100.0 * max(counts) / max(max(t.waves for t in rows), 1)
