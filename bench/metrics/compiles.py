"""XLA backend compiles in the sweep of the window that had most (JAX's
``backend_compile_duration`` events). Zero when the warm-up compiled
everything the window runs."""


def read(run):
    return max(len(sw["compiles"]) for sw in run.sweeps)
