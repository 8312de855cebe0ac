#!/usr/bin/env python3
"""PipeSim benchmark: whole ``Sweep.run`` calls on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` once, on the machine it is started on:

1. set-up: JAX must find a TPU with as many chips as the cell asks for,
   else exit 2 with no result. The mix's pinned integer-time workloads are
   drawn from ``--seed`` (``bench/traffic``), the cell's ``Sweep`` is built
   from its configuration, and one warm-up sweep compiles every program the
   window runs: its engine call gets inputs of the same shapes and types
   whose pipelines all arrive at the padding time, so it compiles the same
   program and runs few waves.
2. window: whole ``Sweep.run`` calls back to back in this process, a closed
   loop with one client, until ``--seconds`` have passed; the window ends
   with the sweep during which they ran out. Every sweep repeats the same
   grid on the same workloads and must make its own ``simulate_ensemble``
   call. With ``--trace 1`` the first sweep of the window runs under the
   profiler, with host spans around the program's layer boundaries
   (``bench/spans``); stopping the TPU profiler takes minutes, so such a
   window seldom holds a second sweep.
3. check: once the window has closed and the device's peak memory has been
   read, the plain reference (``bench/reference``) runs the same grid on the
   same workloads, and every sweep's results are compared with it
   (``harness/check.py``).

The last line of stdout is one JSON object: ``correct``, ``attempted``
(sweeps run in the window), ``failed`` (sweeps whose results differ from
the reference), ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics, each read by ``bench/metrics/<name>.py``),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: every
number compared beside its limit. The same numbers end stderr.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (os.path.join(BENCH_DIR, "reference"), BENCH_DIR,
          os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import cells, check  # noqa: E402

METRICS_DIR = os.path.join(BENCH_DIR, "metrics")
# arrival of padding pipelines (core/batching.PAD_ARRIVAL): past any horizon
PAD_ARRIVAL = 3.0e37


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu and (d.platform != "tpu" or len(devs) < chips):
        raise NoChip(f"JAX found {len(devs)} {d.platform} device(s) "
                     f"({d.device_kind!r}); the cell needs {chips} TPU "
                     "chip(s). Nothing was run.")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def read_metric(name: str, run) -> object:
    """``bench/metrics/<name>.py``'s ``read(run)``: a number, or None when
    the run holds nothing for it to read."""
    path = os.path.join(METRICS_DIR, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


class Run:
    """What the metric readers read: ``setup_s``; ``sweeps`` (per window
    sweep: ``start``/``end`` on the ``perf_counter_ns`` clock, ``calls``,
    ``traces``, ``results``, ``compiles``); ``window_s``; ``spans`` (every
    host span, ``harness/spans.py``); ``reference``; ``trace`` (the reduced
    profiler trace of the traced sweep, or None); ``device``."""

    def __init__(self):
        self.setup_s = None
        self.sweeps = []
        self.spans = []
        self.reference = None
        self.trace = None
        self.device = {}

    @property
    def window_s(self) -> float:
        return (self.sweeps[-1]["end"] - self.sweeps[0]["start"]) * 1e-9

    def per_sweep(self, fn) -> float:
        """Median over the window's sweeps of ``fn(sweep)``."""
        return statistics.median(fn(s) for s in self.sweeps)


def _no_work(args):
    """The engine call's inputs with every pipeline arriving at the
    padding time: same shapes, types and static arguments, few waves."""
    import jax.numpy as jnp
    import numpy as np
    arrival = np.full(args[0].shape, PAD_ARRIVAL, np.float32)
    return (jnp.asarray(arrival),) + tuple(args[1:])


def run_cell(argv, require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result line's object."""
    a = parse(argv)
    bench = cells.load_benchmark()
    found = cells.find_cell(a.workload, bench)
    cfg, mix = found["config"], found["mix"]

    device = device_info(found["cell"]["chips"], require_tpu)
    import jax
    import numpy as np
    from harness.clock import CompileClock
    from harness.spans import Recorder
    from repro.core import batching, vdes
    from repro.launch.compile_cache import enable_compile_cache
    from traffic import generator

    enable_compile_cache()
    clock = CompileClock()
    run = Run()
    run.device = dict(device)

    cols = cells.traffic(mix, cfg, a.seed, generator)
    ref_cols = [{k: v.copy() for k, v in c.items()} for c in cols]
    api = cells.load_api("repro")
    sweep = cells.sweep(api, cfg, mix, a.workload,
                        [cells.workload(api, c) for c in cols])

    # capture what every sweep produces, and count its engine calls; in
    # the traced sweep, also wait for the engine's outputs inside a span of
    # its own, so the trace shows where the device loop ends
    state = {"calls": 0, "traces": [], "warm": False, "wait": False,
             "engine_s": None}
    rec = Recorder(annotate=bool(a.trace))
    real_ensemble, real_batch_trace = vdes.simulate_ensemble, \
        batching.batch_trace

    def ensemble(*args, **kwargs):
        state["calls"] += 1
        if state["warm"]:
            args = _no_work(args)
        if not state["wait"]:
            return real_ensemble(*args, **kwargs)
        t0 = time.perf_counter_ns()
        with rec.span("engine_call", "harness"):
            out = jax.block_until_ready(real_ensemble(*args, **kwargs))
        state["engine_s"] = (time.perf_counter_ns() - t0) * 1e-9
        return out

    def batch_trace(*args, **kwargs):
        tr = real_batch_trace(*args, **kwargs)
        state["traces"].append(tr)
        return tr

    vdes.simulate_ensemble, batching.batch_trace = ensemble, batch_trace
    try:
        if a.trace:
            rec.wrap_layers()
        state["warm"] = True
        with np.errstate(all="ignore"):
            sweep.run(None)
        state["warm"] = False
        log_dir = tempfile.mkdtemp(prefix="pipesim-trace-") if a.trace \
            else None
        run.setup_s = time.perf_counter() - T_START
        t_end = time.perf_counter_ns() + int(a.seconds * 1e9)
        while not run.sweeps or time.perf_counter_ns() < t_end:
            traced = bool(a.trace) and not run.sweeps
            state["calls"], state["traces"], state["wait"] = 0, [], traced
            if traced:
                jax.profiler.start_trace(log_dir)
            t0 = time.perf_counter_ns()
            with rec.span("sweep", "harness"):
                results = sweep.run(None)
            t1 = time.perf_counter_ns()
            if traced:
                jax.profiler.stop_trace()
            state["wait"] = False
            run.sweeps.append(dict(start=t0, end=t1, calls=state["calls"],
                                   traces=state["traces"], results=results,
                                   compiles=clock.between(t0, t1),
                                   engine_s=state["engine_s"] if traced
                                   else None))
    finally:
        rec.restore()
        vdes.simulate_ensemble, batching.batch_trace = real_ensemble, \
            real_batch_trace
    run.spans = rec.spans

    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        run.device["memory_peak_bytes"] = int(stats["peak_bytes_in_use"])
    if a.trace:
        from harness import tracefile
        pd = jax.profiler.ProfileData.from_file(
            tracefile.latest_xplane(log_dir))
        first = run.sweeps[0]
        names = {f"{s.layer}/{s.name}" for s in rec.spans}
        marks = {n: (t0, t1) for n, t0, t1 in tracefile.host_spans(
            pd, ["harness/sweep", "harness/engine_call"])}
        window, extent = tracefile.covered_window(
            pd, marks.get("harness/sweep", (first["start"], first["end"])),
            marks.get("harness/engine_call"))
        run.trace = tracefile.reduce(pd, window, names)
        run.trace["extent"] = extent
        run.trace["waves"] = max(
            (t.waves or 0 for t in first["traces"]), default=0)
        del pd
        shutil.rmtree(log_dir, ignore_errors=True)
        if run.trace["device_planes"]:
            run.device["busy_s"] = run.trace["busy_s"]
            run.device["window_s"] = run.trace["window_s"]

    # the plain reference, once the window has closed
    t_ref = time.perf_counter()
    from reference import sweep as ref_sweep
    api_ref = cells.load_api("pipesim_ref")
    run.reference = ref_sweep.run(cells.sweep(
        api_ref, cfg, mix, a.workload,
        [cells.workload(api_ref, c) for c in ref_cols]))
    numbers, ok = check.compare(run.sweeps, run.reference)
    failed = ok.count(False)
    ref_s = time.perf_counter() - t_ref

    kind = "per_layer" if a.trace else "end_to_end"
    metrics = {}
    for m in found[kind]:
        v = read_metric(m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    line = {"correct": check.verdict(numbers),
            "attempted": len(run.sweeps), "failed": failed,
            "metrics": metrics, "device": run.device}
    if run.trace is not None:
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
    line["checks"] = check.checks_block(numbers)
    if run.trace is not None:
        print(f"trace={run.trace['extent']} "
              f"traced_window_s={run.trace['window_s']:.3f} "
              f"busy_s={run.trace['busy_s']:.3f}", file=sys.stderr)
    print(f"sweeps={len(run.sweeps)} window_s={run.window_s:.3f} "
          f"setup_s={run.setup_s:.3f} reference_s={ref_s:.3f} "
          f"compiles_in_window="
          f"{sum(len(s['compiles']) for s in run.sweeps)}", file=sys.stderr)
    for k, v in line["checks"].items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    return line


def main(argv=None) -> int:
    try:
        line = run_cell(sys.argv[1:] if argv is None else argv)
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
