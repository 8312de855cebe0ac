"""The benchmark's plain reference: a frozen copy of the repro package's
float64 heap engine (``core/des.py``) and of everything it takes from the
spec (platform and workload types, scenario, capacity, failure, fleet,
reliability and probe compilers, traces, summaries and accounting).

The files are the package's own, copied with their imports renamed from
``repro`` to ``pipesim_ref``. Left out: the jax engines, batching, workload
synthesis and fitting (the benchmark pins every workload; ``engines.py``
keeps only the helpers the numpy path shares), the batching back-compat
wrapper of ``ops/scenario.py``, and all of ``core/workload.py`` but
``hour_of_week_weights``. It lives with the benchmark so that a change to
the program cannot change what the program is checked against.
"""
