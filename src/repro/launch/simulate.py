"""PipeSim experiment launcher (the paper's CLI entry point).

Loads the fitted simulation parameters (``artifacts/pipesim_params.npz``,
or fits them from generated empirical traces when the file is missing),
runs one experiment, prints the analytics summary. On ``--engine jax``
(the default) the summary names the device the engine ran on.

  PYTHONPATH=src python -m repro.launch.simulate --horizon-days 1 \
      --learning-capacity 8 --policy sjf
"""
from __future__ import annotations

import argparse
import json
import os

import jax

from repro.core import (ExperimentSpec, PlatformConfig, ResourceConfig,
                        fit_simulation_params, generate_empirical_workload,
                        run_experiment)
from repro.core.des import POLICY_NAMES
from repro.launch.compile_cache import enable_compile_cache

PARAMS_PATH = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "artifacts",
    "pipesim_params.npz"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--days", type=float, default=2.0,
                    help="days of empirical traces to fit on")
    ap.add_argument("--horizon-days", type=float, default=1.0)
    ap.add_argument("--interarrival-factor", type=float, default=1.0)
    ap.add_argument("--compute-capacity", type=int, default=48)
    ap.add_argument("--learning-capacity", type=int, default=32)
    ap.add_argument("--policy", default="fifo", choices=POLICY_NAMES)
    ap.add_argument("--engine", default="jax", choices=["numpy", "jax"])
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--params-cache", default=PARAMS_PATH)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    from repro.core.fitting import SimulationParams
    if os.path.exists(args.params_cache):
        params = SimulationParams.load(args.params_cache)
        print(f"[params] loaded {args.params_cache}")
    else:
        print(f"[fit] generating {args.days} days of empirical traces ...")
        wl = generate_empirical_workload(seed=123,
                                         horizon_s=args.days * 86400.0)
        print(f"[fit] fitting on {wl.n} pipelines ...")
        params = fit_simulation_params(wl)
        params.save(args.params_cache)

    exp = ExperimentSpec(
        name="cli",
        platform=PlatformConfig(resources=(
            ResourceConfig("compute_cluster", args.compute_capacity),
            ResourceConfig("learning_cluster", args.learning_capacity, 3.0),
        )),
        horizon_s=args.horizon_days * 86400.0,
        interarrival_factor=args.interarrival_factor,
        policy=POLICY_NAMES.index(args.policy),
        seed=args.seed,
        n_replicas=args.replicas,
        engine=args.engine,
    )
    res = run_experiment(exp, params)
    summary = dict(res.summary)
    if args.engine == "jax":
        dev = jax.devices()[0]
        summary["device"] = {"platform": dev.platform,
                             "kind": dev.device_kind,
                             "count": len(jax.devices())}
    print(json.dumps(summary, indent=2, default=float))
    if args.out:
        res.save(args.out)
        print(f"[saved] {args.out}")


if __name__ == "__main__":
    main()
