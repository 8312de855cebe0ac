"""In-simulation telemetry plane (paper §V: the platform's observability
stack, rebuilt around the simulator itself).

Three parts, one import surface:

  - :mod:`repro.obs.probes` — parity-gated in-loop probes: a
    :class:`ProbeSpec` on an experiment samples live engine state (queue
    depth, busy slots, effective capacity, controller delta, fleet
    perf/staleness) at a compile-time f32 tick grid, bit-identically in
    both engines;
  - :mod:`repro.obs.spans` — OTel-style span export of task records and
    in-engine actions, with JSONL and Chrome-trace/Perfetto writers;
  - :mod:`repro.obs.profile` — the self-profiler: the program's host
    spans at a sweep's layer boundaries, compile-vs-execute split, waves/s
    for both engines.
"""
from repro.obs.probes import (CompiledProbe, ProbeSpec, ProbeTimeline,
                              compile_probe, probe_channel_names)
from repro.obs.spans import (attempt_intervals,
                             attempt_intervals_from_records, build_spans,
                             read_chrome_attempt_intervals,
                             read_spans_jsonl, write_chrome_trace,
                             write_spans_jsonl)
from repro.obs.profile import (profile_compile_execute, profile_numpy,
                               span, spans)

__all__ = [
    "ProbeSpec", "CompiledProbe", "ProbeTimeline", "compile_probe",
    "probe_channel_names",
    "build_spans", "write_spans_jsonl", "read_spans_jsonl",
    "write_chrome_trace", "attempt_intervals",
    "attempt_intervals_from_records", "read_chrome_attempt_intervals",
    "profile_numpy", "profile_compile_execute", "span", "spans",
]
