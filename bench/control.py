#!/usr/bin/env python3
"""The control of ``correct``: the plain reference put in the program's
place, computed on a time grid of the next precision below the program's.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...]

The program keeps its clock in float32 on an integer-second grid, where
float32 is exact. The control rounds every arrival and service time of the
cell's traffic to bfloat16 (8 significant bits), runs the reference on
that, and hands its traces, records and summaries to the same comparison
that judges the program's sweeps (``harness/check.py``), against the
reference on the exact traffic. It must come out not correct. For each
seed it prints one JSON line of the compared numbers; the benchmark's own
runs never run it. Needs no chip: the reference and the control both run
on the host.
"""
import argparse
import json
import os
import sys
import time
import types

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.join(BENCH_DIR, "reference"), BENCH_DIR,
          os.path.join(os.path.dirname(BENCH_DIR), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from harness import cells, check  # noqa: E402


def bf16(x: np.ndarray) -> np.ndarray:
    """Round to the nearest bfloat16 (round half to even), back in f64."""
    import ml_dtypes
    return np.asarray(x, ml_dtypes.bfloat16).astype(np.float64)


def lower_precision(cols: dict) -> dict:
    out = dict(cols)
    out["arrival"] = bf16(cols["arrival"])
    out["exec_time"] = bf16(cols["exec_time"])
    return out


def as_sweep(points: list) -> dict:
    """Reference output laid out as one program sweep for the comparison."""
    results = [types.SimpleNamespace(
        records=p["records"], summary=p["summary"],
        replica_summaries=p["replica_summaries"]) for p in points]
    return dict(results=results, calls=1,
                traces=[t for p in points for t in p["traces"]])


def readings(name: str, seed: int) -> dict:
    """The compared numbers of the control on ``seed``."""
    from reference import sweep as ref_sweep
    from traffic import generator
    found = cells.find_cell(name, cells.load_benchmark())
    cfg, mix = found["config"], found["mix"]
    api = cells.load_api("pipesim_ref")
    cols = cells.traffic(mix, cfg, seed, generator)

    def run(columns):
        return ref_sweep.run(cells.sweep(
            api, cfg, mix, name, [cells.workload(api, c) for c in columns]))

    reference = run(cols)
    control = run([lower_precision(c) for c in cols])
    return check.compare([as_sweep(control)], reference)[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args(argv)
    for seed in a.seeds:
        t0 = time.perf_counter()
        numbers = readings(a.workload, seed)
        print(json.dumps({"workload": a.workload, "seed": seed,
                          "correct": check.verdict(numbers),
                          "checks": check.checks_block(numbers),
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
