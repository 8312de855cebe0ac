"""Percent of the traced sweep's slot-seconds that reliability events held
out of service: every row's ``SimTrace.rel_slot_s`` (the fired outage and
repair timeline's slot-seconds down, up to the horizon) over every row's
slots (``SimTrace.capacities``) times the horizon of the sweep's points.
Nothing where a row has no such counter: a sweep without reliability, or a
program that does not count it."""
from harness.program import row_traces


def read(run):
    rows = row_traces(run)
    if rows is None or any(getattr(t, "rel_slot_s", None) is None
                           for t in rows):
        return None
    horizon = max(r.experiment.horizon_s for r in run.sweeps[0]["results"])
    slot_s = sum(float(t.capacities.sum()) for t in rows) * horizon
    return 100.0 * sum(t.rel_slot_s for t in rows) / max(slot_s, 1e-9)
