"""Benchmark harness — one module per paper table/figure + the roofline.

Prints ``name,us_per_call,derived`` CSV rows (one per measured quantity).

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig13      # one suite
  PYTHONPATH=src python -m benchmarks.run --smoke    # fast CI subset

A suite that raises prints a ``<suite>_FAILED`` row and the run goes on to
the next suite; the process then exits 1.
"""
from __future__ import annotations

import os
import sys
import traceback

SUITES = [
    ("table1", "benchmarks.table1_compression"),
    ("fig9", "benchmarks.fig9_task_durations"),
    ("fig10", "benchmarks.fig10_arrivals"),
    ("fig11", "benchmarks.fig11_saturation"),
    ("fig12", "benchmarks.fig12_accuracy"),
    ("fig13", "benchmarks.fig13_scalability"),
    ("kernels", "benchmarks.kernels_bench"),
    ("roofline", "benchmarks.roofline"),
    ("scenarios", "benchmarks.scenario_bench"),
    ("sweep", "benchmarks.sweep_bench"),
    ("controller", "benchmarks.controller_bench"),
    ("feedback", "benchmarks.feedback_bench"),
    ("obs", "benchmarks.obs_bench"),
    ("stream", "benchmarks.stream_bench"),
    ("reliability", "benchmarks.reliability_bench"),
]

# fast subset for CI: shrunken sizes via REPRO_BENCH_SMOKE ("kernels"
# rides along for artifacts/BENCH_kernels.json — in smoke mode it skips
# the heavy reference-kernel rows and runs only the admission/compaction
# parity section)
SMOKE_SUITES = ("scenarios", "sweep", "controller", "feedback", "obs",
                "kernels", "stream", "reliability")


def main() -> None:
    import importlib

    args = sys.argv[1:]
    smoke = "--smoke" in args
    unknown = [a for a in args if a.startswith("-") and a != "--smoke"]
    if unknown:
        print(f"unknown option(s): {' '.join(unknown)}", file=sys.stderr)
        sys.exit(2)
    names = [a for a in args if not a.startswith("-")]
    which = names[0] if names else None
    suites = SUITES
    if smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
        if which is None:        # bare --smoke: the fast CI subset
            suites = [(t, m) for t, m in SUITES if t in SMOKE_SUITES]
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for tag, modname in suites:
        if which and which != tag:
            continue
        try:
            mod = importlib.import_module(modname)
            for row in mod.rows():
                print(",".join(str(x) for x in row), flush=True)
        except Exception as e:
            traceback.print_exc()
            print(f"{tag}_FAILED,0,{type(e).__name__}", flush=True)
            failed.append(tag)
    if failed:
        print(f"failed suites: {' '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
