"""The program's own instrumentation of a sweep.

  - host spans at the layer boundaries (``repro.obs.profile.span``): one
    ``sweep`` per ``Sweep.run`` whose children nest under its id in layer
    order and tile ``JaxEngine.run_sweep``;
  - each span is a ``pipesim/<name>`` ``TraceAnnotation`` in a profiler
    trace, as long there as in the recorder;
  - the wave loop's per-row count of waves at which an operations event
    was due (``SimTrace.ops_waves``) on hand-built workloads whose every
    wave is known;
  - the persistent compilation cache (``repro.launch.compile_cache``) hands
    a program back with its own scope names, never a cached program's that
    differs only in them, and hits from a checkout at another path.

That the named scopes leave every output bit-identical is what the
engine-parity tests already check.
"""
import glob
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batching, des, vdes
from repro.core import model as M
from repro.core.experiment import ExperimentSpec, Sweep
from repro.obs import profile
from test_des_engines import make_workload, platform

CHILDREN = ("points", "prep", "batching", "upload", "engine", "fetch")
PER_POINT = ("summaries", "results")


@pytest.fixture(scope="module")
def sweep():
    """A four-point grid on one pinned integer-time workload, warmed up
    (compiled) once."""
    rng = np.random.default_rng(1313)
    wl = make_workload(rng, 1500, integer_time=True, horizon=15000.0)
    base = ExperimentSpec(name="spans", platform=platform(), horizon_s=15000.0,
                          workload=wl, engine="jax")
    sw = Sweep(base, {"policy": [des.POLICY_FIFO, des.POLICY_SJF],
                      "capacity:a": [3, 4]})
    sw.run()
    return sw


def _last_sweep():
    closed = profile.spans()
    sid = max(s.sweep for s in closed if s.name == "sweep")
    return sorted((s for s in closed if s.sweep == sid),
                  key=lambda s: s.start_ns)


def test_spans_nest_in_layer_order_and_tile_the_sweep(sweep):
    results = sweep.run()
    spans = _last_sweep()
    parent, children = spans[0], spans[1:]
    assert parent.name == "sweep" and parent.parent is None
    assert [s.name for s in children] == \
        list(CHILDREN) + list(PER_POINT) * len(results)
    assert all(s.parent == "sweep" for s in children)
    # children lie inside the parent, one after another
    assert parent.start_ns <= children[0].start_ns
    assert children[-1].end_ns <= parent.end_ns
    for a, b in zip(children, children[1:]):
        assert a.end_ns <= b.start_ns
    whole = parent.end_ns - parent.start_ns
    assert whole >= 0.5e9, "the sweep is too short to test the tiling"
    uncovered = whole - sum(s.end_ns - s.start_ns for s in children)
    assert 0 <= uncovered < 0.01 * whole


def test_spans_land_in_the_profiler_trace(sweep, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        sweep.run()
    finally:
        jax.profiler.stop_trace()
    spans = _last_sweep()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    traced = [(ev.name, ev.duration_ns)
              for plane in jax.profiler.ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith("pipesim/")]
    assert sorted(n for n, _ in traced) == \
        sorted(f"pipesim/{s.name}" for s in spans)
    for s in spans:
        durs = [d for n, d in traced if n == f"pipesim/{s.name}"]
        assert min(abs(d - (s.end_ns - s.start_ns)) for d in durs) < 1e6


def test_span_records_parent_and_sweep_id():
    with profile.span("sweep"):
        with profile.span("prep"):
            pass
    with profile.span("fetch"):
        pass
    prep, sweep_, fetch = profile.spans()[-3:]
    assert (prep.name, prep.parent, prep.sweep) == \
        ("prep", "sweep", sweep_.sweep)
    assert (fetch.parent, fetch.sweep) == (None, None)
    assert sweep_.start_ns <= prep.start_ns <= prep.end_ns <= sweep_.end_ns


def _one_resource_workload(arrival, exec_time):
    n = len(arrival)
    return M.Workload(
        arrival=np.asarray(arrival, np.float64),
        n_tasks=np.ones(n, np.int32), task_type=np.zeros((n, 1), np.int32),
        task_res=np.zeros((n, 1), np.int32),
        exec_time=np.asarray(exec_time, np.float64)[:, None],
        read_bytes=np.zeros((n, 1)), write_bytes=np.zeros((n, 1)),
        framework=np.zeros(n, np.int32), priority=np.zeros(n, np.float32),
        model_perf=np.zeros(n, np.float32),
        model_size=np.zeros(n, np.float32),
        model_clever=np.zeros(n, np.float32))


def test_counters_one_slot_two_pipelines():
    """Two pipelines of one 10 s task each arrive at 0 on one slot, a no-op
    capacity change applies at 5, a probe ticks at 10, 20 and 30. Waves:
    t=0 (arrivals; p0 admitted), 5 (the capacity change), 10 (p0 ends; p1
    admitted; probe), 20 (p1 ends; probe), 30 (probe)."""
    wl = _one_resource_workload([0.0, 0.0], [10.0, 10.0])
    plat = M.PlatformConfig(resources=(M.ResourceConfig("a", 1),))
    cols = batching.pad_workloads([wl], plat)
    hdr = np.zeros(des.PROBE_FIELDS, np.float32)
    hdr[des.PROBE_INTERVAL], hdr[des.PROBE_T_FIRST] = 10.0, 10.0
    hdr[des.PROBE_T_END] = 30.0
    out = vdes.simulate_ensemble(
        *[jnp.asarray(cols[k]) for k in ("arrival", "n_tasks", "task_res",
                                         "service", "priority")],
        jnp.asarray([[1]], jnp.int32),
        cap_times=jnp.asarray([[0.0, 5.0]], jnp.float32),
        cap_vals=jnp.asarray([[[1], [1]]], jnp.int32),
        probes=jnp.asarray(hdr[None]), n_probe_slots=3)
    out = {k: np.asarray(v) for k, v in out.items()}
    tr = batching.batch_trace(out, 0, wl, plat.capacities,
                              with_scenario=False)
    assert tr.waves == 5
    np.testing.assert_array_equal(tr.start[:, 0], [0.0, 10.0])
    # a capacity change (t=5) or a probe tick (10, 20, 30) was due at four
    assert tr.ops_waves == 4


def test_counters_two_slots_through_a_sweep():
    """Both pipelines are admitted in one wave on two slots. With no
    capacity schedule and no operations stage the loop counts no
    ``ops_waves``, and the numpy engine never does."""
    wl = _one_resource_workload([0.0, 0.0], [10.0, 10.0])
    plat = M.PlatformConfig(resources=(M.ResourceConfig("a", 2),))
    base = ExperimentSpec(name="c", platform=plat, horizon_s=20.0,
                          workload=wl, engine="jax")
    sw = Sweep(base, {"policy": [des.POLICY_FIFO, des.POLICY_SJF]})
    for res in sw.run():
        assert res.trace.waves == 2
        np.testing.assert_array_equal(res.trace.start[:, 0], [0.0, 0.0])
        assert res.trace.ops_waves is None
        assert "ops_waves" not in res.summary
    np_res = Sweep(base.with_(engine="numpy"), {"policy": [0]}).run()
    assert np_res[0].trace.ops_waves is None


COMPILE_CACHE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src", "repro", "launch", "compile_cache.py")
# compiles, in a checkout holding only the cache module, a function whose
# ops sit under the scope named by argv[1]; prints the scopes its compiled
# program names
_SCOPED = """
import sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
enable_compile_cache()
def f(x):
    with jax.named_scope(sys.argv[1]):
        return jnp.sin(x) * 2 + jnp.cos(x)
txt = jax.jit(f).lower(jnp.ones(8)).compile().as_text()
print(" ".join(n for n in ("alpha", "beta") if f"/{n}/" in txt))
"""


def _checkout(root):
    """A checkout at ``root`` with the cache module and the script."""
    os.makedirs(os.path.join(root, "src", "repro", "launch"))
    shutil.copy(COMPILE_CACHE, os.path.join(root, "src", "repro", "launch"))
    with open(os.path.join(root, "src", "scoped.py"), "w") as f:
        f.write(_SCOPED)
    return root


def _compile_scoped(checkout, cache, scope):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(checkout, "src"),
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    run = subprocess.run(
        [sys.executable, os.path.join(checkout, "src", "scoped.py"), scope],
        env=env, capture_output=True, text=True, timeout=300)
    assert run.returncode == 0, run.stderr[-2000:]
    return run.stdout.split()


def test_compile_cache_keeps_each_programs_scope_names(tmp_path):
    """Two programs with the same ops under other scope names: the second
    must not load the first's cached executable and its names."""
    co = _checkout(str(tmp_path / "co"))
    cache = tmp_path / "cache"
    assert _compile_scoped(co, cache, "alpha") == ["alpha"]
    assert _compile_scoped(co, cache, "beta") == ["beta"]
    assert _compile_scoped(co, cache, "alpha") == ["alpha"]


def test_compile_cache_hits_from_another_checkout(tmp_path):
    """The key takes source paths relative to the checkout: the same
    program compiled from a checkout elsewhere adds no cache entry."""
    cache = tmp_path / "cache"
    assert _compile_scoped(_checkout(str(tmp_path / "a")), cache,
                           "alpha") == ["alpha"]
    entries = sorted(os.listdir(cache))
    assert entries
    assert _compile_scoped(_checkout(str(tmp_path / "b" / "deeper")), cache,
                           "alpha") == ["alpha"]
    assert sorted(os.listdir(cache)) == entries
