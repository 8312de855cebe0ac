"""Percent of the traced sweep's executed task attempts that were retries:
every row's attempts beyond each task's first (``SimTrace.retries``) over
every row's executed attempts (``SimTrace.attempts``). Nothing where a row
has no such counter: a sweep without failures, or a program that does not
count them."""
from harness.program import row_traces


def read(run):
    rows = row_traces(run)
    if rows is None or any(getattr(t, "retries", None) is None
                           or t.attempts is None for t in rows):
        return None
    return 100.0 * sum(t.retries for t in rows) / max(
        sum(int(t.attempts.sum()) for t in rows), 1)
