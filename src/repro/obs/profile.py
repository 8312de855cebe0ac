"""Self-profiler: where does the *simulator's* wall time go?

The offline-profiling line of work (PAPERS.md) instruments the system being
modeled; this module instruments the model:

  - :func:`span` — the program's own host spans at the layer boundaries of
    a sweep (``Sweep.run``: ``sweep`` and the grid's ``points``;
    ``JaxEngine.run_sweep``: ``prep``, ``batching``, ``upload``,
    ``engine``, ``fetch``, ``summaries``, ``results``; inside ``prep``,
    ``engines._spec_workloads``'s ``faults`` around failure sampling and
    the reliability timeline, where a spec has either). Each is a
    ``jax.profiler.TraceAnnotation`` named ``pipesim/<name>``, so under the
    profiler it lands in the trace on the device's clock, and a record
    ``(name, parent, sweep, start_ns, end_ns)`` on the
    ``time.perf_counter_ns`` clock in a bounded buffer that :func:`spans`
    reads. Always on: a sweep opens a few dozen, and a sweep takes seconds.
    Inside the device loop the stages carry ``jax.named_scope`` names
    instead (:mod:`repro.core.vdes`), which split a profiler trace's device
    time by stage;
  - :func:`profile_compile_execute` — the JAX engine's compile-vs-execute
    wall split (cold first call = trace + XLA lower + compile + run; warm
    calls = run only), plus executed waves and **waves/s**;
  - :func:`profile_numpy` — the reference heap engine's wall and waves/s
    on the same program (the serial baseline every batched speedup is
    quoted against).

All timings take the best of ``repeats`` (minimum — the standard
noise-floor estimator for microbenchmarks).
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax

from repro.core import des, vdes

#: spans the buffer keeps; the oldest drop first
SPAN_BUFFER = 4096


class Span(NamedTuple):
    """One closed host span. ``parent`` is the name of the span it opened
    inside (None at the top); ``sweep`` the id of the ``sweep`` span it
    belongs to (None outside any)."""

    name: str
    parent: Optional[str]
    sweep: Optional[int]
    start_ns: int
    end_ns: int


_closed = collections.deque(maxlen=SPAN_BUFFER)
_sweep_ids = itertools.count(1)
_local = threading.local()          # this thread's open spans, innermost last


@contextlib.contextmanager
def span(name: str):
    """Time the block as span ``name``: a ``TraceAnnotation`` named
    ``pipesim/<name>`` and, once it closes, a :class:`Span` in the buffer.
    A span named ``sweep`` takes a new sweep id; any other takes that of
    the span it opened inside."""
    stack = _local.__dict__.setdefault("stack", [])
    parent = stack[-1] if stack else None
    sweep = next(_sweep_ids) if name == "sweep" else \
        (parent[1] if parent else None)
    stack.append((name, sweep))
    start = time.perf_counter_ns()
    try:
        with jax.profiler.TraceAnnotation(f"pipesim/{name}"):
            yield
    finally:
        end = time.perf_counter_ns()
        stack.pop()
        _closed.append(Span(name, parent[0] if parent else None, sweep,
                            start, end))


def spans() -> List[Span]:
    """The buffered spans, in the order they closed."""
    return list(_closed)


def _best_of(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def profile_numpy(wl, platform, policy: int = des.POLICY_FIFO,
                  scenario=None, fleet=None, probe=None,
                  repeats: int = 3) -> Dict[str, float]:
    """Wall + waves/s of the reference numpy engine on one program."""
    tr = des.simulate(wl, platform, policy, scenario=scenario, fleet=fleet,
                      probe=probe)
    wall = _best_of(lambda: des.simulate(wl, platform, policy,
                                         scenario=scenario, fleet=fleet,
                                         probe=probe), repeats)
    return {"wall_s": wall, "waves": int(tr.waves),
            "waves_per_s": tr.waves / max(wall, 1e-12)}


def profile_compile_execute(wl, platform, policy: int = des.POLICY_FIFO,
                            scenario=None, fleet=None, probe=None,
                            repeats: int = 3) -> Dict[str, float]:
    """The JAX engine's compile/execute split on one program.

    ``compile_s`` is the cold-call overhead (first call minus a warm call):
    trace + lowering + XLA compile. Cleared caches make the first call
    genuinely cold even when the surrounding process already ran the
    engine."""
    jax.clear_caches()

    def run():
        return vdes.simulate_to_trace(wl, platform, policy,
                                      scenario=scenario, fleet=fleet,
                                      probe=probe)

    t0 = time.perf_counter()
    tr = run()
    cold = time.perf_counter() - t0
    execute = _best_of(run, repeats)
    return {"cold_s": cold, "execute_s": execute,
            "compile_s": max(cold - execute, 0.0),
            "waves": int(tr.waves),
            "waves_per_s": tr.waves / max(execute, 1e-12)}
