"""Run one cell of a benchmark copy on the CPU, with the chip check off and
one fault planted in the timed path, and print the result line.

    python drive.py <bench dir> <fault> --workload ... --seed ... --seconds ... --trace ...

Faults: ``none``; ``frozen``, an engine call that returns its state as it
was before any wave; ``half_batch``, the second half of the batch's rows
left out and filled with the first half's; ``altered``, one finish time
moved by one second where the engine produces it; ``reused``, a sweep that
hands back the previous sweep's results instead of running. (Nothing of a
cell crosses chips, so there is no exchange between chips to leave out.)
"""
import json
import sys

bench_dir, fault, *argv = sys.argv[1:]
sys.path.insert(0, bench_dir)

import jax.numpy as jnp  # noqa: E402

import run  # noqa: E402
from repro.core import experiment, vdes  # noqa: E402

real = vdes.simulate_ensemble


def frozen(*args, **kwargs):
    kwargs["wave_budget"] = jnp.zeros(args[0].shape[0], jnp.int32)
    return real(*args, **kwargs)


def half_batch(*args, **kwargs):
    out = dict(real(*args, **kwargs))
    rows = args[0].shape[0]
    half = rows // 2
    for k, v in out.items():
        if getattr(v, "ndim", 0) and v.shape[0] == rows:
            out[k] = v.at[half:].set(v[:rows - half])
    return out


def altered(*args, **kwargs):
    out = dict(real(*args, **kwargs))
    out["finish"] = out["finish"].at[0, 0, 0].add(1.0)
    return out


if fault in ("frozen", "half_batch", "altered"):
    vdes.simulate_ensemble = globals()[fault]
elif fault == "reused":
    real_run, kept = experiment.Sweep.run, []

    def reused(self, params=None):
        kept.append(real_run(self, params) if len(kept) < 2 else kept[-1])
        return kept[-1]
    experiment.Sweep.run = reused
elif fault != "none":
    raise SystemExit(f"unknown fault {fault!r}")

print(json.dumps(run.run_cell(argv, require_tpu=False)))
