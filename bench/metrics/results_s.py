"""Host seconds per sweep inside the program's ``results`` spans (``JaxEngine.run_sweep``: lifecycle and probe views and the result objects, one span per grid point), median over the window's
sweeps (``repro.obs.profile.spans()``). Nothing where the program opens no
such span."""
from harness.program import span_seconds


def read(run):
    return span_seconds(run, "results")
