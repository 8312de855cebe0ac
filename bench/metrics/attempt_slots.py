"""The per-attempt width the traced sweep's batched call compiled with
(``SimTrace.attempt_slots``, the A of its ``[N, T, A]`` per-attempt
buffers, which the admission stage rewrites in every wave). Nothing where
no row has the counter: a sweep without per-attempt records, or a program
that does not report it."""
from harness.program import row_traces


def read(run):
    rows = row_traces(run)
    if rows is None:
        return None
    slots = [a for a in (getattr(t, "attempt_slots", None) for t in rows)
             if a is not None]
    return max(slots) if slots else None
