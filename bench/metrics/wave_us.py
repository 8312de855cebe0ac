"""Microseconds per wave of the wave loop: the traced sweep's engine call,
from its dispatch until its outputs were ready (the harness waits for them
inside the call), on the host clock, over the iterations of its ``while``
loop (the most waves any row of the batch ran, from the output). The host
clock, not the trace: a long loop can fill the profiler's device events
before it ends. Nothing without a traced sweep."""


def read(run):
    if run.trace is None or not run.trace["waves"] \
            or not run.sweeps[0]["engine_s"]:
        return None
    return run.sweeps[0]["engine_s"] / run.trace["waves"] * 1e6
