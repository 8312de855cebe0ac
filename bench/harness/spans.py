"""Host spans around the program's layer boundaries, wrapped from outside.

Which functions are wrapped is data: every ``bench/spans/<group>.json``
names a layer and lists ``"module:function"`` targets. A metric reads the
spans of one group, by the file's name, and nothing else: a span file
added later, even one whose functions run inside an existing group's,
leaves that group's reading as it was.
The wrapper replaces the attribute for the run, records a span per call on
the ``time.perf_counter_ns`` clock, and in a traced run also opens a
``jax.profiler.TraceAnnotation`` named ``<layer>/<attribute>``, so the span
lands in the profiler's trace on the device trace's clock. The program
looks each of these names up at call time, so the wrapper sees every call.
"""
from __future__ import annotations

import contextlib
import functools
import glob
import importlib
import json
import os
import time

SPANS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "spans")


class Span:
    __slots__ = ("name", "layer", "group", "start", "end")

    def __init__(self, name, layer, group, start):
        self.name, self.layer, self.group = name, layer, group
        self.start, self.end = start, None


class Recorder:
    """Spans of one process, in the order they opened."""

    def __init__(self, annotate: bool = False):
        self.spans = []
        self._restore = []
        self.annotate = annotate

    @contextlib.contextmanager
    def span(self, name: str, layer: str, group: str = None):
        span = Span(name, layer, group or layer, time.perf_counter_ns())
        self.spans.append(span)
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(f"{layer}/{name}")
        try:
            with ann:
                yield
        finally:
            span.end = time.perf_counter_ns()

    def wrap(self, target: str, layer: str, group: str) -> None:
        """Wrap the function ``"pkg.module:name"`` for the run."""
        mod_name, name = target.split(":")
        owner = importlib.import_module(mod_name)
        fn = getattr(owner, name)

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with self.span(name, layer, group):
                return fn(*args, **kwargs)

        setattr(owner, name, wrapper)
        self._restore.append((owner, name, fn))

    def wrap_layers(self, spans_dir: str = SPANS_DIR) -> None:
        """Wrap every target of every span file."""
        for path in sorted(glob.glob(os.path.join(spans_dir, "*.json"))):
            with open(path) as f:
                spec = json.load(f)
            group = os.path.splitext(os.path.basename(path))[0]
            for target in spec["wrap"]:
                self.wrap(target, spec["layer"], group)

    def restore(self) -> None:
        while self._restore:
            owner, name, original = self._restore.pop()
            setattr(owner, name, original)


def covered_seconds(intervals) -> float:
    """Seconds in the union of ``(start_ns, end_ns)`` intervals."""
    covered, hi = 0, None
    for a, b in sorted(intervals):
        if hi is not None:
            a = max(a, hi)
        if b > a:
            covered += b - a
            hi = b
    return covered * 1e-9


def group_seconds(run, group: str):
    """Host seconds per sweep inside the spans of ``bench/spans/<group>
    .json``: the union of their intervals, so a nested call counts once and
    spans of other files change nothing. Median over the window's sweeps;
    None where no span of the group opened."""
    mine = [(s.start, s.end) for s in run.spans
            if s.group == group and s.end is not None]
    if not mine:
        return None
    return run.per_sweep(lambda sw: covered_seconds(
        iv for iv in mine if sw["start"] <= iv[0] < sw["end"]))
