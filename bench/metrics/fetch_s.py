"""Host seconds per sweep inside the program's ``fetch`` spans (``JaxEngine.run_sweep``: the engine's outputs copied to the host, after the engine span waited for them), median over the window's
sweeps (``repro.obs.profile.spans()``). Nothing where the program opens no
such span."""
from harness.program import span_seconds


def read(run):
    return span_seconds(run, "fetch")
