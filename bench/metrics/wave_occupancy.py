"""Percent of the batched wave loop's row-waves that did a row's own work
in the traced sweep: every row's waves over rows times the most waves of
any row (``SimTrace.waves``). A row that has finished rides along until the
longest one ends. Nothing where the rows' traces keep no wave count."""
from harness.program import row_traces


def read(run):
    rows = row_traces(run)
    if rows is None:
        return None
    most = max(t.waves for t in rows)
    return 100.0 * sum(t.waves for t in rows) / max(len(rows) * most, 1)
