"""Self-profiler: where does the *simulator's* wall time go?

The offline-profiling line of work (PAPERS.md) instruments the system being
modeled; this module instruments the model. Three measurements every
benchmark and the ``BENCH_obs.json`` artifact report through:

  - :func:`profile_compile_execute` — the JAX engine's compile-vs-execute
    wall split (cold first call = trace + XLA lower + compile + run; warm
    calls = run only), plus executed waves and **waves/s**;
  - :func:`profile_numpy` — the reference heap engine's wall and waves/s
    on the same program (the serial baseline every batched speedup is
    quoted against);
  - :func:`stage_attribution` — per-stage cost attribution across the wave
    loop's kernel stages by *differential ablation*: the same workload runs
    with the optional stages toggled (base = select + completion +
    admission; then + control, + fleet, + probe), and each stage's
    per-wave cost is the delta over its baseline. Ablation is the honest
    way to attribute a fused ``lax.while_loop`` — XLA compiles the wave
    body as one program, so there is no per-op timeline to read; deltas of
    measured per-wave costs are what toggling the stage actually buys or
    costs.

All timings take the best of ``repeats`` (minimum — the standard
noise-floor estimator for microbenchmarks).
"""
from __future__ import annotations

import time
from typing import Dict, Optional

import jax
import numpy as np

from repro.core import des, vdes


def _best_of(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def profile_numpy(wl, platform, policy: int = des.POLICY_FIFO,
                  scenario=None, fleet=None, probe=None,
                  repeats: int = 3) -> Dict[str, float]:
    """Wall + waves/s of the reference numpy engine on one program."""
    tr = des.simulate(wl, platform, policy, scenario=scenario, fleet=fleet,
                      probe=probe)
    wall = _best_of(lambda: des.simulate(wl, platform, policy,
                                         scenario=scenario, fleet=fleet,
                                         probe=probe), repeats)
    return {"wall_s": wall, "waves": int(tr.waves),
            "waves_per_s": tr.waves / max(wall, 1e-12)}


def profile_compile_execute(wl, platform, policy: int = des.POLICY_FIFO,
                            scenario=None, fleet=None, probe=None,
                            repeats: int = 3) -> Dict[str, float]:
    """The JAX engine's compile/execute split on one program.

    ``compile_s`` is the cold-call overhead (first call minus a warm call):
    trace + lowering + XLA compile. Cleared caches make the first call
    genuinely cold even when the surrounding process already ran the
    engine."""
    jax.clear_caches()

    def run():
        return vdes.simulate_to_trace(wl, platform, policy,
                                      scenario=scenario, fleet=fleet,
                                      probe=probe)

    t0 = time.perf_counter()
    tr = run()
    cold = time.perf_counter() - t0
    execute = _best_of(run, repeats)
    return {"cold_s": cold, "execute_s": execute,
            "compile_s": max(cold - execute, 0.0),
            "waves": int(tr.waves),
            "waves_per_s": tr.waves / max(execute, 1e-12)}


def stage_attribution(wl, platform, scenario=None, fleet=None, probe=None,
                      policy: int = des.POLICY_FIFO,
                      repeats: int = 3) -> Dict[str, Dict[str, float]]:
    """Per-stage wall attribution by differential ablation.

    Returns ``{stage: {per_wave_us, waves, wall_s}}`` for the always-on
    core (``select+completion+admission`` — the base config's whole wave)
    and a delta entry per optional stage that was supplied (``control`` /
    ``fleet`` / ``probe`` — that stage's config minus the base, per wave;
    clipped at 0 when the delta drowns in noise). Stages the caller didn't
    supply (no scenario/fleet/probe) are omitted, not estimated."""
    configs = {"base": {}}
    if scenario is not None:
        configs["control"] = {"scenario": scenario}
    if fleet is not None:
        configs["fleet"] = {"fleet": fleet}
    if probe is not None:
        configs["probe"] = {"probe": probe}

    measured = {}
    for name, kw in configs.items():
        prof = profile_compile_execute(wl, platform, policy, repeats=repeats,
                                       **kw)
        measured[name] = {"wall_s": prof["execute_s"],
                          "waves": prof["waves"],
                          "per_wave_us": 1e6 * prof["execute_s"]
                          / max(prof["waves"], 1)}
    base_pw = measured["base"]["per_wave_us"]
    out = {"select+completion+admission": {
        "per_wave_us": base_pw,
        "waves": measured["base"]["waves"],
        "wall_s": measured["base"]["wall_s"],
    }}
    for name in ("control", "fleet", "probe"):
        if name not in measured:
            continue
        m = measured[name]
        out[name] = {"per_wave_us": max(m["per_wave_us"] - base_pw, 0.0),
                     "waves": m["waves"], "wall_s": m["wall_s"]}
    return out
