"""Seconds of XLA backend compile per sweep (JAX's
``backend_compile_duration`` events that ended inside the sweep; a
persistent-cache hit counts its read time), median over the window's
sweeps. Zero when the warm-up compiled everything the window runs."""


def read(run):
    return run.per_sweep(lambda sw: sum(e[1] for e in sw["compiles"]))
