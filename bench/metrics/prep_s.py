"""Host seconds per sweep inside the spans of ``bench/spans/prep.json``
(their union), median over the window's sweeps."""
from harness.spans import group_seconds


def read(run):
    return group_seconds(run, "prep")
