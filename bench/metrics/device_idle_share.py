"""Percent of the traced window in which no operation ran on the device:
100 * (1 - busy / window), busy the union of the device's operation
intervals in the profiler trace, the window the traced sweep's host span,
or the part of it the trace holds device events for where the profiler's
buffers filled first (``harness/tracefile.covered_window``). Nothing
without a trace that holds a device."""


def read(run):
    if run.trace is None or not run.trace["device_planes"] \
            or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
