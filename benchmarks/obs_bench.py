"""Telemetry-plane acceptance bench (``artifacts/BENCH_obs.json``).

Three measurements, one report:

  1. **Probe parity** (``probe_parity_drift``, gated at exactly 0.0 by
     ``check_drift.py``): a fully-loaded program — closed-loop controller +
     model-lifecycle fleet + in-loop probe — on an integer-time workload
     must fill *bit-identical* probe buffers in the numpy reference engine
     and the vmapped JAX engine, wave counts included.
  2. **Span export round-trip** (``span_roundtrip_drift``, gated too): the
     probed run's Chrome-trace export must reconstruct every attempt
     interval bit-exactly against ``TaskRecords`` (the acceptance
     criterion), and the JSONL export must parse back equal.
  3. **Self-profile**: compile-vs-execute wall split of the JAX engine and
     waves/s for BOTH engines on the same program.

``REPRO_BENCH_SMOKE=1`` (or ``--smoke``) shrinks the horizon for CI.

  PYTHONPATH=src python -m benchmarks.run obs
  PYTHONPATH=src python benchmarks/obs_bench.py --smoke
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..")))

import jax

from benchmarks.common import ART, fitted_params
from repro.core import des, trace, vdes
from repro.core.metrics import FLEET_FIELDS
from repro.core.runtime import FleetSpec, TriggerSpec
from repro.core.synthesizer import synthesize_workload
from repro.obs import (ProbeSpec, attempt_intervals_from_records,
                       build_spans, compile_probe, profile_compile_execute,
                       profile_numpy, read_chrome_attempt_intervals,
                       read_spans_jsonl, write_chrome_trace,
                       write_spans_jsonl)
from repro.ops import ReactiveController, Scenario
from repro.ops.scenario import compile_fleet

OUT_PATH = os.path.abspath(os.path.join(ART, "BENCH_obs.json"))


def _integer_workload(horizon_s: float):
    """Integer-time synthesized workload (arrival floor, exec ceil, no IO)
    so the f32 probe arithmetic has no representation error to hide behind:
    any nonzero drift is a real parity break."""
    params = fitted_params()
    wl = synthesize_workload(params, jax.random.PRNGKey(29), horizon_s)
    wl.arrival = np.floor(wl.arrival)
    wl.exec_time = np.ceil(wl.exec_time)
    wl.read_bytes[:] = 0.0
    wl.write_bytes[:] = 0.0
    return wl


def _fleet_tensor():
    fl = np.zeros((4, FLEET_FIELDS), np.float32)
    fl[:, 0] = [0.9, 0.8, 0.95, 0.7]
    fl[:, 1] = [2e-3, 1e-3, 5e-4, 3e-3]
    fl[:, 5] = 7 * 24 * 3600.0
    return fl


def rows():
    smoke = os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"
    horizon = (0.125 if smoke else 0.5) * 86400.0
    wl = _integer_workload(horizon)
    from repro.core.experiment import ExperimentSpec
    base = ExperimentSpec(name="obsbench", horizon_s=horizon,
                          workload=wl).with_(
        **{"capacity:compute_cluster": 6, "capacity:learning_cluster": 4})
    plat = base.platform

    trig = TriggerSpec(drift_threshold=0.05, cooldown_s=600.0,
                       obs_noise=0.01, interval_s=300.0,
                       retrain_durations=(400.0, 50.0, 150.0))
    ctrl_sc = Scenario(name="ctrl", controller=ReactiveController(
        high_watermark=0.3, low_watermark=0.05, step=0.5, min_scale=0.5,
        max_scale=3.0, interval_s=1800.0))
    cf, ext = compile_fleet(FleetSpec(params=_fleet_tensor()), trig, wl,
                            plat, horizon, seed=11)
    comp = ctrl_sc.compile(ext, plat, horizon, seed=11)
    probe = compile_probe(ProbeSpec(interval_s=900.0), horizon,
                          n_models=cf.n_models)

    # --- 1. probe parity: the fully-loaded program, both engines
    t0 = time.perf_counter()
    t_np = des.simulate(ext, plat, scenario=comp, fleet=cf, probe=probe)
    wall_np = time.perf_counter() - t0
    t_jx = vdes.simulate_to_trace(ext, plat, scenario=comp, fleet=cf,
                                  probe=probe)
    waves_agree = bool(t_np.waves == t_jx.waves)
    probe_parity_drift = float(np.max(np.abs(
        np.nan_to_num(t_np.probe_vals) - np.nan_to_num(t_jx.probe_vals))))
    nan_masks_agree = bool(np.array_equal(np.isnan(t_np.probe_vals),
                                          np.isnan(t_jx.probe_vals)))
    if not (waves_agree and nan_masks_agree):
        probe_parity_drift = max(probe_parity_drift, 1.0)

    # --- 2. span export round-trip (the acceptance criterion)
    rec = trace.flatten_trace(t_np, ext)
    spans = build_spans(rec, t_np, name="obsbench")
    with tempfile.TemporaryDirectory() as tmp:
        jsonl = os.path.join(tmp, "spans.jsonl")
        chrome = os.path.join(tmp, "trace.json")
        write_spans_jsonl(spans, jsonl)
        write_chrome_trace(spans, chrome)
        jsonl_ok = read_spans_jsonl(jsonl) == spans
        want = attempt_intervals_from_records(rec)
        got = read_chrome_attempt_intervals(chrome)
    span_roundtrip_drift = 0.0 if (jsonl_ok and got == want) else 1.0
    n_spans = len(spans)

    # --- 3. self-profile: compile/execute split + waves/s, both engines
    prof_np = profile_numpy(ext, plat, scenario=comp, fleet=cf, probe=probe,
                            repeats=1 if smoke else 3)
    prof_jx = profile_compile_execute(ext, plat, scenario=comp, fleet=cf,
                                      probe=probe,
                                      repeats=1 if smoke else 3)

    report = {
        "pipelines": wl.n,
        "horizon_s": horizon,
        "probe_ticks": probe.n_ticks,
        "probe_parity_drift": probe_parity_drift,
        "waves_agree": waves_agree,
        "span_roundtrip_drift": span_roundtrip_drift,
        "n_spans": n_spans,
        "n_attempt_intervals": len(want),
        "numpy_wall_s": prof_np["wall_s"],
        "numpy_waves_per_s": prof_np["waves_per_s"],
        "jax_compile_s": prof_jx["compile_s"],
        "jax_execute_s": prof_jx["execute_s"],
        "jax_waves_per_s": prof_jx["waves_per_s"],
        "waves": prof_jx["waves"],
        "smoke": smoke,
    }
    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as f:
        json.dump(report, f, indent=2)

    return [
        ("obs_probe_parity", wall_np * 1e6,
         f"drift={probe_parity_drift}_waves_agree={waves_agree}"),
        ("obs_span_roundtrip", span_roundtrip_drift * 1e6,
         f"{len(want)}intervals_{n_spans}spans"),
        ("obs_numpy_engine", prof_np["wall_s"] * 1e6,
         f"{prof_np['waves_per_s']:.0f}waves/s"),
        ("obs_jax_engine", prof_jx["execute_s"] * 1e6,
         f"{prof_jx['waves_per_s']:.0f}waves/s_compile"
         f"{prof_jx['compile_s']:.1f}s"),
    ]


def main():
    if "--smoke" in sys.argv[1:]:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    for r in rows():
        print(",".join(str(x) for x in r))
    print(f"# wrote {OUT_PATH}")


if __name__ == "__main__":
    main()
