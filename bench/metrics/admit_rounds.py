"""Admission rounds per wave in the traced sweep: for each instant at which
a row's jobs started, the most that started on one resource (the rounds the
``"select"`` ranking's inner loop runs for them), summed over every row,
over every row's waves (``SimTrace.start`` or ``att_start``, ``task_res``
and ``waves``). Nothing where the rows' traces keep no wave count."""
from harness.program import admission_rounds, row_traces


def read(run):
    rows = row_traces(run)
    if rows is None:
        return None
    return sum(admission_rounds(t) for t in rows) / max(
        sum(t.waves for t in rows), 1)
