"""The retry-log width the traced sweep's batched call compiled with
(``SimTrace.attempt_log_slots``: the R of its ``[N, R]`` retry logs, the
most non-final attempts any pipeline of the batch requests, which the
admission stage writes one slot of per admitted attempt that will fail).
Nothing where no row has the counter: a sweep without per-attempt records,
or a program that does not report it."""
from harness.program import row_traces


def read(run):
    rows = row_traces(run)
    if rows is None:
        return None
    slots = [r for r in (getattr(t, "attempt_log_slots", None) for t in rows)
             if r is not None]
    return max(slots) if slots else None
