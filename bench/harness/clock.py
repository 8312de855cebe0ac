"""Compile time and count per jitted function, from JAX's monitoring
events. A persistent-cache hit is counted as a compile whose duration is the
cache read."""
from __future__ import annotations

import time

import jax

EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Every backend compile as ``(end_ns, seconds, fun_name)``, on the
    ``time.perf_counter_ns`` clock."""

    def __init__(self):
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event, duration, **kw):
        if event == EVENT:
            self.events.append((time.perf_counter_ns(), float(duration),
                                kw.get("fun_name", "?")))

    def between(self, t0_ns: int, t1_ns: int) -> list:
        """Compiles that ended in ``[t0_ns, t1_ns)``."""
        return [e for e in self.events if t0_ns <= e[0] < t1_ns]
