#!/usr/bin/env python3
"""Split one traced sweep of a benchmark cell by wave-loop stage, and its
device idle time by the program's layer spans.

    python3 bench/stage_split.py --workload <cell> --seed <n>

Builds the cell's sweep as ``bench/run.py`` does, runs it once to compile
every program, then runs one sweep under the profiler and reduces the trace
with ``harness/stages.py`` over the program's own ``pipesim/sweep`` span.
The engine call is the program's ``pipesim/engine`` span (dispatch until
the outputs are ready), its waves the most of any row. The last line of
stdout is one JSON object: ``trace`` (``whole``, or ``truncated`` where the
profiler's device buffers filled before the engine call ended; the split
is then over the slice it holds), ``window_s``, ``engine_s``, ``waves``,
``stage_s`` and ``loop_s`` (device seconds), ``idle_by_span`` (idle
seconds), and :func:`harness.stages.split`'s per-wave numbers. Nothing
here checks results against the reference: ``bench/run.py`` does.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import cells, stages, tracefile  # noqa: E402


def split_cell(workload: str, seed: int) -> dict:
    import jax
    import numpy as np
    from repro.core import batching
    from repro.launch.compile_cache import enable_compile_cache
    from traffic import generator

    found = cells.find_cell(workload, cells.load_benchmark())
    cfg, mix = found["config"], found["mix"]
    enable_compile_cache()
    api = cells.load_api("repro")
    sweep = cells.sweep(api, cfg, mix, workload,
                        [cells.workload(api, c) for c in
                         cells.traffic(mix, cfg, seed, generator)])
    rows = []
    real_batch_trace = batching.batch_trace

    def batch_trace(*args, **kwargs):
        tr = real_batch_trace(*args, **kwargs)
        rows.append(tr)
        return tr

    log_dir = tempfile.mkdtemp(prefix="pipesim-stages-")
    batching.batch_trace = batch_trace
    try:
        with np.errstate(all="ignore"):
            sweep.run(None)
            rows.clear()
            jax.profiler.start_trace(log_dir)
            try:
                sweep.run(None)
            finally:
                jax.profiler.stop_trace()
        xplane = tracefile.latest_xplane(log_dir)
        pd = jax.profiler.ProfileData.from_file(xplane)
        marks = {n: (a, b) for n, a, b in tracefile.host_spans(
            pd, ["pipesim/sweep", "pipesim/engine"])}
        if "pipesim/sweep" not in marks:
            raise SystemExit("the trace holds no pipesim/sweep span: the "
                             "program opens none")
        engine = marks.get("pipesim/engine")
        window, extent = tracefile.covered_window(pd, marks["pipesim/sweep"],
                                                  engine)
        reduced = stages.reduce(pd, window, xplane)
    finally:
        batching.batch_trace = real_batch_trace
        shutil.rmtree(log_dir, ignore_errors=True)
    engine_s = (engine[1] - engine[0]) * 1e-9 if engine else None
    waves = max((t.waves or 0 for t in rows), default=0)
    return dict(workload=workload, seed=seed, trace=extent,
                window_s=reduced["window_s"], engine_s=engine_s,
                waves=waves, stage_s=reduced["stage_s"],
                loop_s=reduced["loop_s"], idle_s=reduced["idle_s"],
                idle_by_span=reduced["idle_by_span"],
                **stages.split(reduced, engine_s, waves))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    a = ap.parse_args(argv)
    print(json.dumps(split_cell(a.workload, a.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
