"""Host seconds per sweep inside the program's ``upload`` spans (``JaxEngine.run_sweep``: the batch inputs copied to the device), median over the window's
sweeps (``repro.obs.profile.spans()``). Nothing where the program opens no
such span."""
from harness.program import span_seconds


def read(run):
    return span_seconds(run, "upload")
