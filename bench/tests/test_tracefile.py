"""The reduction from a profiler trace to busy time, idle gaps named by the
host span open in each, and the top device operations."""
import json
import os
import sys
import types

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import tracefile  # noqa: E402


def _trace(planes):
    """A ProfileData-shaped object from ``{plane: {line: [(name, start,
    duration)]}}``."""
    def ev(name, start, dur):
        return types.SimpleNamespace(name=name, start_ns=start,
                                     duration_ns=dur)
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=p, lines=[
            types.SimpleNamespace(name=ln, events=[ev(*e) for e in evs])
            for ln, evs in lines.items()])
        for p, lines in planes.items()])


SMALL = _trace({
    "/device:TPU:0": {
        "XLA Modules": [("jit_simulate_ensemble(1)", 100, 500)],
        "XLA Ops": [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 100, 200),
                    ("while", 150, 100),
                    ("fusion.2", 400, 200), ("copy", 800, 50)],
    },
    "/host:CPU": {
        "python": [("harness/sweep", 0, 1000),
                   ("batching/pad_workloads", 10, 80),
                   ("summaries/batch_trace", 650, 100),
                   ("jax other", 650, 300)],
    },
})
NAMES = ["harness/sweep", "batching/pad_workloads", "summaries/batch_trace"]


def test_union_merges_overlaps():
    iv = np.array([[5, 10], [0, 3], [2, 4], [10, 12], [20, 21]])
    np.testing.assert_array_equal(tracefile.union(iv),
                                  [[0, 4], [5, 12], [20, 21]])


def test_busy_idle_and_named_gaps():
    out = tracefile.reduce(SMALL, (0, 1000), NAMES)
    # busy: [100, 300) + [400, 600) + [800, 850)
    assert abs(out["busy_s"] - 450e-9) < 1e-15
    assert abs(out["window_s"] - 1000e-9) < 1e-15
    gaps = dict(out["idle_gaps"])
    # [0,100): mid 50 inside pad_workloads [10, 90); [300,400) and
    # [850,1000): only the sweep; [600,800): mid 700 in batch_trace
    assert abs(gaps["batching/pad_workloads"] - 100e-9) < 1e-15
    assert abs(gaps["summaries/batch_trace"] - 200e-9) < 1e-15
    assert abs(gaps["harness/sweep"] - 250e-9) < 1e-15
    ops = dict(out["device_ops"])
    assert abs(ops["fusion.1"] - 200e-9) < 1e-15
    assert list(ops)[0] in ("fusion.1", "fusion.2")
    assert abs(out["modules"]["jit_simulate_ensemble(1)"] - 500e-9) < 1e-15


def test_window_clips():
    out = tracefile.reduce(SMALL, (200, 500), NAMES)
    assert abs(out["busy_s"] - 200e-9) < 1e-15      # [200,300) + [400,500)
    assert abs(out["window_s"] - 300e-9) < 1e-15


def test_op_name():
    assert tracefile.op_name("%while.225 = (s32[8]) while(%t), body=%b") \
        == "while.225"
    assert tracefile.op_name("copy") == "copy"


def test_no_device_plane():
    host_only = _trace({"/host:CPU": {"python": [("harness/sweep", 0, 10)]}})
    out = tracefile.reduce(host_only, (0, 10), NAMES)
    assert out["device_planes"] == 0 and out["busy_s"] == 0.0


def _recorded():
    with open(os.path.join(HERE, "data", "trace_slice_grid8.json")) as f:
        d = json.load(f)
    return d, _trace({p: {ln: [tuple(e) for e in evs]
                          for ln, evs in lines.items()}
                      for p, lines in d["planes"].items()})


def test_recorded_trace_busy_matches_a_brute_force_union():
    """On a slice recorded on the chip: busy is the union of the operation
    intervals, counted nanosecond by nanosecond; idle is the rest of the
    window, every gap named by a benchmark span or none."""
    d, trace = _recorded()
    names = [e[0] for e in d["planes"]["/host:CPU"]["python3"]]
    out = tracefile.reduce(trace, d["window"], names)
    busy = 0
    ops = [(int(a), int(a) + int(dur))
           for _, a, dur in d["planes"]["/device:TPU:0"]["XLA Ops"]]
    w0, w1 = (int(t) for t in d["window"])
    for c0, c1 in d["cuts"]:
        near = [(max(a, w0), min(b, w1)) for a, b in ops
                if a < c1 and b > c0 and min(b, w1) > max(a, w0)]
        lo = min(a for a, _ in near)
        mask = np.zeros(max(b for _, b in near) - lo, bool)
        for a, b in near:
            mask[a - lo:b - lo] = True
        busy += int(mask.sum())
    assert abs(out["busy_s"] - busy * 1e-9) < 1e-12
    window = (d["window"][1] - d["window"][0]) * 1e-9
    idle = sum(v for _, v in out["idle_gaps"])
    assert abs(idle - (window - out["busy_s"])) < 1e-9
    assert {n for n, _ in out["idle_gaps"]} <= set(names) | {"(no span)"}
    assert out["device_planes"] == 1
    mod = d["planes"]["/device:TPU:0"]["XLA Modules"][0]
    assert abs(out["modules"][mod[0]] - mod[2] * 1e-9) < 1e-12


def test_covered_window_whole_and_truncated():
    """A trace whose device operations reach both ends of the engine call
    covers the whole sweep; one whose operations stop (or start) inside
    the call, as when the profiler's buffers fill, covers only the slice
    it holds, and busy and idle are read over that slice."""
    ops = [("fusion.%d" % i, 1000 + 100 * i, 90) for i in range(50)]
    full = _trace({"/device:TPU:0": {"XLA Ops": ops}})
    sweep, engine = (0, 8000), (900, 6100)
    assert tracefile.covered_window(full, sweep, engine, tol_ns=200) == \
        ((0, 8000), "whole")
    head = _trace({"/device:TPU:0": {"XLA Ops": ops[:20]}})   # ends at 2990
    window, extent = tracefile.covered_window(head, sweep, engine,
                                              tol_ns=200)
    assert (window, extent) == ((0, 2990), "truncated")
    out = tracefile.reduce(head, window)
    assert abs(out["busy_s"] - 20 * 90e-9) < 1e-15
    assert abs(out["window_s"] - 2990e-9) < 1e-15
    tail = _trace({"/device:TPU:0": {"XLA Ops": ops[30:]}})   # from 4000
    assert tracefile.covered_window(tail, sweep, engine, tol_ns=200) == \
        ((4000, 8000), "truncated")
    # no device plane (a CPU run) or no engine span: the sweep as it is
    assert tracefile.covered_window(_trace({}), sweep, engine) == \
        (sweep, "whole")
    assert tracefile.covered_window(head, sweep, None) == (sweep, "whole")
