"""Seconds from process start to the first timed sweep: imports, device
start, traffic generation, sweep build and the warm-up sweep that compiles
(or loads from the persistent cache) every program of the window."""


def read(run):
    return run.setup_s
