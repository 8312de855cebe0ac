"""Find a cell by name and build its sweep, for the program and for the
reference alike.

``BENCHMARK.json`` names each cell (``workloads``), its configuration (a
file under ``bench/configs/``) and its traffic mix (``bench/cells/<traffic>
.json``). Nothing here knows any cell by name: a new cell is a new mix file,
a new configuration is a new config file, each with its entry in
``BENCHMARK.json``.

A configuration is a deployment: the platform (resource pools, routing,
data store) and the operations stack it runs (controller, failure and
retry, reliability, model fleet and retrain trigger, probe). A mix is the
what-if query an operator sends: horizon, arrival rates, the grid axes and
the replica count.

The same JSON builds two sweeps: one from the program's classes
(``repro``) and one from the reference's frozen copies (``pipesim_ref``).
Both modules lay out the same names, so one function builds both.
"""
from __future__ import annotations

import importlib
import json
import os
import types

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
BENCHMARK_JSON = os.path.join(ROOT, "BENCHMARK.json")
# the experiment seed of every sweep: the program's per-replica draws come
# from it, and the traffic (pinned workloads) from the generator
SPEC_SEED = 0


def load_benchmark(path: str = BENCHMARK_JSON) -> dict:
    with open(path) as f:
        return json.load(f)


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str, bench: dict) -> dict:
    """``{"cell": <workloads entry>, "config": <config file>, "mix": <mix
    file>, "per_layer": [<metric entries that read this cell>],
    "end_to_end": [...]}``. Raises KeyError for an unknown cell."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read(os.path.join(ROOT, configs[cell["config"]]["file"]))
    mix = _read(os.path.join(BENCH_DIR, "cells", f"{cell['traffic']}.json"))

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return dict(cell=cell, config=config, mix=mix,
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


# ------------------------------------------------------------------- builds

def load_api(pkg: str) -> types.SimpleNamespace:
    """The classes a sweep is built from, out of ``repro`` (the program) or
    ``pipesim_ref`` (the reference's copy)."""
    def mod(m):
        return importlib.import_module(f"{pkg}.{m}")

    exp = mod("core.experiment")
    rel = mod("reliability")
    return types.SimpleNamespace(
        pkg=pkg, ExperimentSpec=exp.ExperimentSpec, Sweep=exp.Sweep,
        M=mod("core.model"), des=mod("core.des"), runtime=mod("core.runtime"),
        metrics=mod("core.metrics"), capacity=mod("ops.capacity"),
        failures=mod("ops.failures"), scenario=mod("ops.scenario"),
        probes=mod("obs.probes"), ReliabilitySpec=rel.ReliabilitySpec,
        TopologySpec=rel.TopologySpec, DomainOutageModel=rel.DomainOutageModel,
        RepairSpec=rel.RepairSpec, SpotPoolSpec=rel.SpotPoolSpec)


def platform(api, cfg: dict):
    p = cfg["platform"]
    M = api.M
    return M.PlatformConfig(
        resources=tuple(M.ResourceConfig(**r) for r in p["resources"]),
        routing={int(k): int(v) for k, v in p["routing"].items()},
        datastore=M.DataStoreConfig(**p["datastore"]))


def workload(api, cols: dict):
    """A program or reference ``Workload`` from the generator's columns."""
    fields = ("arrival", "n_tasks", "task_type", "task_res", "exec_time",
              "read_bytes", "write_bytes", "framework", "priority",
              "model_perf", "model_size", "model_clever")
    return api.M.Workload(**{k: cols[k] for k in fields})


def _fleet(api, f: dict):
    fl = api.runtime.fleet_tensor(
        api.runtime.FleetSpec(n_models=f["n_models"],
                              drift_scale=f["drift_scale"]),
        seed=f["fleet_seed"])
    fl[:, api.metrics.FLEET_SEAS_AMP] = np.float32(f["seasonal_amp"])
    return api.runtime.FleetSpec(params=fl)


def _reliability(api, r: dict):
    return api.ReliabilitySpec(
        topology=api.TopologySpec(**r["topology"]),
        outages=api.DomainOutageModel(**r["outages"]),
        repair=api.RepairSpec(**r["repair"]),
        spot=api.SpotPoolSpec(**r["spot"]) if r.get("spot") else None,
        time_quantum_s=r["time_quantum_s"])


def _controller(api, c: dict):
    return api.capacity.ReactiveController(**c)


def sweep(api, cfg: dict, mix: dict, name: str, wls: list):
    """The cell's ``Sweep`` over pinned workloads ``wls`` (one per arrival
    rate of the mix, in the mix's order)."""
    fm = None
    if "failures" in cfg:
        f = cfg["failures"]
        fm = api.failures.FailureModel(
            p_fail_by_type=tuple(f["p_fail_by_type"]),
            framework_mult=tuple(f["framework_mult"]),
            retry=api.failures.RetryPolicy(**f["retry"]))
    axes = {}
    for key, values in mix["axes"].items():
        if key == "policy":
            values = [api.des.POLICY_NAMES.index(v) for v in values]
        elif key == "controller":
            values = [None if v == "none"
                      else _controller(api, cfg["controller"])
                      for v in values]
        axes[key] = list(values)
    scen = None
    if fm is not None or ("controller" in cfg and "controller" not in axes):
        scen = api.scenario.Scenario(
            name=cfg["name"], failures=fm,
            controller=None if "controller" in axes or "controller" not in cfg
            else _controller(api, cfg["controller"]))
    trig = cfg.get("trigger")
    base = api.ExperimentSpec(
        name=name, platform=platform(api, cfg), horizon_s=mix["horizon_s"],
        seed=SPEC_SEED, n_replicas=mix["n_replicas"],
        engine=cfg["engine"], scenario=scen,
        fleet=_fleet(api, cfg["fleet"]) if "fleet" in cfg else None,
        trigger=api.runtime.TriggerSpec(**dict(
            trig, retrain_durations=tuple(trig["retrain_durations"])))
        if trig else None,
        probe=api.probes.ProbeSpec(**cfg["probe"]) if "probe" in cfg
        else None,
        reliability=_reliability(api, cfg["reliability"])
        if "reliability" in cfg else None,
        workload=wls[0] if len(wls) == 1 else None)
    if len(wls) > 1:
        axes["workload"] = list(wls)
    return api.Sweep(base, axes)


def traffic(mix: dict, cfg: dict, seed: int, generator) -> list:
    """The mix's pinned workloads for ``seed``, as generator columns."""
    p = cfg["platform"]
    params = generator.load_params(
        os.path.join(BENCH_DIR, cfg["params"]))
    return [generator.make_workload(
        params, horizon_s=mix["horizon_s"], interarrival_factor=f,
        base_seed=mix["base_seed"], seed=seed, level=i,
        routing={int(k): int(v) for k, v in p["routing"].items()},
        datastore=p["datastore"])
        for i, f in enumerate(mix["interarrival_factors"])]
