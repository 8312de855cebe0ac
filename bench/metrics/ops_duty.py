"""Percent of the traced sweep's row waves at which an operations event
was due (``SimTrace.ops_waves``: a capacity change, reliability event,
controller, drift or probe tick), over every row's waves. Nothing where
the program does not count such waves (it counts them only in a sweep with
a capacity schedule or an operations stage)."""
from harness.program import row_traces


def read(run):
    rows = row_traces(run)
    if rows is None or any(getattr(t, "ops_waves", None) is None
                           for t in rows):
        return None
    return 100.0 * sum(t.ops_waves for t in rows) / max(
        sum(t.waves for t in rows), 1)
