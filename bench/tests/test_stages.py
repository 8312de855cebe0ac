"""The reduction from a profiler trace to seconds per wave-loop stage and to
device idle time by the program's own host spans (``harness/stages.py``),
and the readers of what the program records about itself."""
import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import stages, tracefile  # noqa: E402
from test_tracefile import _recorded, _trace  # noqa: E402


def _ev(name, start, dur, stats=()):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats))


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=evs)
        for ln, evs in lines.items()])


LOOP = "jit(simulate_ensemble)/vmap(jit(simulate))/while"
#: each operation's name stack, as the HLO the trace file keeps gives it
STACKS = {
    "copy.1": "jit(simulate_ensemble)/copy",
    "fusion.1": LOOP + "/body/completion/eq",
    "while.2": LOOP + "/body/admission/while",
    "reduce.3": LOOP + "/body/admission/while/body/reduce_min",
    "fusion.4": LOOP + "/body/admission/and",
    "add.5": LOOP + "/body/add",
    "reduce.6": LOOP + "/body_pred/select/min",
}
SMALL = types.SimpleNamespace(planes=[
    _plane("/device:TPU:0", {"XLA Ops": [
        # the TPU names an event by its HLO instruction's text
        _ev("%copy.1 = f32[8]{0} copy(f32[8]{0} %p)", 100, 50),
        _ev("fusion.1", 200, 100),
        # a while that holds two operations: [400, 700) less [420, 500)
        # and [550, 650) is its own
        _ev("while.2", 400, 300),
        _ev("reduce.3", 420, 80),
        _ev("fusion.4", 550, 100),
        _ev("add.5", 700, 20),
        _ev("reduce.6", 750, 30),
    ]}),
    _plane("/host:CPU", {"python": [
        _ev("pipesim/sweep", 0, 1000),
        _ev("pipesim/upload", 10, 140),
        _ev("pipesim/engine", 150, 700),
        _ev("pipesim/fetch", 850, 100),
        _ev("harness/sweep", 0, 1000),
    ]}),
])


@pytest.fixture()
def with_hlo(monkeypatch, tmp_path):
    """``stages.reduce``'s ``xplane`` argument: a file whose HLO gives
    ``kinds`` (``{instruction name: kind}``)."""
    def make(kinds):
        monkeypatch.setattr(stages, "hlo_kinds", lambda raw: dict(kinds))
        path = tmp_path / "trace.xplane.pb"
        path.write_bytes(b"")
        return str(path)
    return make


def test_stage_of():
    assert stages.stage_of("jit(f)/vmap(while)/body/control/eq") == "control"
    assert stages.stage_of("while/body_pred/select/reduce_min") == "select"
    # the jax primitive select_n is no stage; a loop op under no stage is
    # the loop's own; an op outside the loop is nobody's
    assert stages.stage_of("jit(f)/while/body/select_n") == "other"
    assert stages.stage_of("jit(f)/copy") is None
    assert stages.stage_of("") is None


def test_exclusive_counts_each_instant_once():
    iv = np.array([[400, 700], [420, 500], [550, 650], [100, 150],
                   [700, 720]])
    np.testing.assert_array_equal(stages.exclusive(iv),
                                  [120, 80, 100, 50, 20])
    # disjoint intervals keep their lengths
    np.testing.assert_array_equal(stages.exclusive(np.array([[0, 5],
                                                             [5, 9]])), [5, 4])


def test_reduce_partitions_the_loop_and_names_the_idle(with_hlo):
    xplane = with_hlo({k: stages.stage_of(v) for k, v in STACKS.items()})
    out = stages.reduce(SMALL, (0, 1000), xplane)
    ns = {k: round(v * 1e9) for k, v in out["stage_s"].items()}
    assert ns == {"completion": 100, "admission": 300, "other": 20,
                  "select": 30}
    assert abs(out["loop_s"] - 450e-9) < 1e-15
    # busy: [100,150) [200,300) [400,720) [750,780) = 500 of 1000
    assert abs(out["idle_s"] - 500e-9) < 1e-15
    idle = {k: round(v * 1e9) for k, v in out["idle_by_span"].items()}
    # [0,10) sweep; [10,100) upload; [150,200) [300,400) [720,750)
    # [780,850) engine; [850,950) fetch; [950,1000) sweep
    assert idle == {"pipesim/sweep": 60, "pipesim/upload": 90,
                    "pipesim/engine": 250, "pipesim/fetch": 100}
    assert out["spans"] == 4
    # without the HLO no operation has a stage
    assert stages.reduce(SMALL, (0, 1000))["stage_s"] == {}
    per_wave = stages.split(out, 1e-3, 10)
    # 100 us per wave; admission holds 300 of the loop's 450 ns
    assert per_wave["wave_us"] == pytest.approx(100.0)
    assert per_wave["stage_us"]["admission"] == pytest.approx(
        100 * 300 / 450)
    assert "fleet" not in per_wave["stage_us"]
    assert sum(per_wave["stage_us"].values()) == pytest.approx(100.0)
    assert per_wave["stages_share"] == pytest.approx(100 * 430 / 450)
    assert per_wave["device_idle_share"] == pytest.approx(50.0)
    assert per_wave["idle_named_share"] == pytest.approx(88.0)


def test_idle_split_by_overlap_not_midpoint():
    """One gap that spans the end of one span and the start of the next
    goes to both, in proportion."""
    busy = np.array([[0, 100], [400, 500]], np.int64)
    spans = [("pipesim/sweep", 0, 500), ("pipesim/batching", 0, 150),
             ("pipesim/upload", 150, 380)]
    got = stages.idle_by_span(busy, (0, 500), spans)
    assert got == {"pipesim/batching": 50, "pipesim/upload": 230,
                   "pipesim/sweep": 20}
    assert stages.idle_by_span(busy, (0, 500), []) == {stages.NO_SPAN: 300}


def test_a_program_that_names_nothing_reads_nothing():
    """The committed slice of a trace taken before the program named its
    stages and spans: no operation carries a stage and no span is the
    program's, so no stage has a time and no idle time a layer span."""
    d, trace = _recorded()
    out = stages.reduce(trace, d["window"])
    assert set(out["stage_s"]) <= {"other"} and out["spans"] == 0
    per_wave = stages.split(out, 1.0, 10)
    assert not set(per_wave["stage_us"]) & set(stages.STAGES)
    assert per_wave["idle_named_share"] is None
    assert per_wave["device_idle_share"] > 0


def test_recorded_trace_reduce_unchanged():
    """``tracefile.reduce`` of the committed slice reads as it did before
    the stage reduction was added beside it."""
    d, trace = _recorded()
    names = [e[0] for e in d["planes"]["/host:CPU"]["python3"]]
    out = tracefile.reduce(trace, d["window"], names)
    assert out["device_planes"] == 1
    assert out["busy_s"] == pytest.approx(0.000597641, abs=1e-15)
    assert out["window_s"] == pytest.approx(6.796676329, abs=1e-15)
    assert [n for n, _ in out["idle_gaps"]] == ["harness/sweep",
                                                "batching/pad_workloads"]
    assert out["idle_gaps"][0][1] == pytest.approx(6.728307391, abs=1e-12)
    assert out["idle_gaps"][1][1] == pytest.approx(0.067771297, abs=1e-12)
    assert out["device_ops"][0] == ["while.225",
                                    pytest.approx(0.000102538, abs=1e-15)]
    assert [n for n, _ in out["device_ops"]] == [
        "while.225", "select_select_fusion.14", "select_select_fusion.13",
        "fusion.67", "fusion.61", "broadcast_select_fusion.5",
        "broadcast_select_fusion.6", "reduce_min.33", "fusion.63",
        "add_select_fusion.9"]
    assert out["modules"] == {"jit_simulate_ensemble":
                              pytest.approx(6.339032297, abs=1e-12)}


def _row(waves, start, task_res, att_start=None, ops_waves=None):
    return types.SimpleNamespace(
        waves=waves, start=np.asarray(start, float),
        task_res=np.asarray(task_res), ops_waves=ops_waves,
        att_start=None if att_start is None else np.asarray(att_start, float))


def _readers():
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "metrics"))
    import admit_rounds
    import ops_duty
    import wave_occupancy
    return admit_rounds, ops_duty, wave_occupancy


def test_program_readers_silent_without_counters_or_spans(monkeypatch):
    from harness import program
    run = types.SimpleNamespace(sweeps=[{"traces": [
        _row(None, [[0.0]], [[0]])]}])
    assert program.row_traces(run) is None
    assert all(m.read(run) is None for m in _readers())
    import repro.obs.profile as prof
    monkeypatch.delattr(prof, "spans")
    assert program.span_seconds(run, "fetch") is None


def test_program_readers():
    nan = float("nan")
    rows = [
        # two jobs start on resource 0 at 0 (two rounds), one on
        # resource 1 at 5 (one round)
        _row(10, [[0.0, 5.0], [0.0, nan]], [[0, 1], [0, 0]], ops_waves=2),
        # attempts at 1 and 3 on resource 0: one round each
        _row(5, [[3.0]], [[0]], att_start=[[[1.0, 3.0]]], ops_waves=1)]
    run = types.SimpleNamespace(sweeps=[{"traces": rows}])
    admit_rounds, ops_duty, wave_occupancy = _readers()
    assert admit_rounds.read(run) == pytest.approx(5 / 15)
    assert ops_duty.read(run) == pytest.approx(100 * 3 / 15)
    assert wave_occupancy.read(run) == pytest.approx(100 * 15 / 20)


def test_ops_duty_silent_without_ops_counter():
    """A sweep with no capacity schedule and no operations stage counts no
    ``ops_waves``: ``ops_duty`` reads nothing, the others read as usual."""
    rows = [_row(10, [[0.0], [0.0], [4.0]], [[0], [1], [0]])]
    run = types.SimpleNamespace(sweeps=[{"traces": rows}])
    admit_rounds, ops_duty, wave_occupancy = _readers()
    assert ops_duty.read(run) is None
    assert admit_rounds.read(run) == pytest.approx(0.2)
    assert wave_occupancy.read(run) == pytest.approx(100.0)


def test_span_seconds_refuses_a_window_the_buffer_dropped(monkeypatch):
    """Once the bounded span buffer has dropped spans that closed inside
    the window, ``span_seconds`` reads nothing rather than a short sum."""
    from harness import program
    from repro.obs import profile
    monkeypatch.setattr(profile, "_closed",
                        __import__("collections").deque(maxlen=4))
    monkeypatch.setattr(profile, "SPAN_BUFFER", 4)
    run = types.SimpleNamespace(
        sweeps=[{"start": 100, "end": 200}, {"start": 200, "end": 300}],
        per_sweep=lambda fn: sorted(fn(sw) for sw in run.sweeps)[0])
    Span = profile.Span
    before = Span("fetch", None, None, 10, 20)
    inside = [Span("fetch", "sweep", 1, 110, 130),
              Span("fetch", "sweep", 2, 210, 250)]
    for sp in [before] + inside:
        profile._closed.append(sp)
    # not full: every span is there
    assert program.span_seconds(run, "fetch") == pytest.approx(20e-9)
    profile._closed.append(Span("results", "sweep", 2, 250, 260))
    # full, and the oldest kept span closed before the window: whole
    assert program.span_seconds(run, "fetch") == pytest.approx(20e-9)
    profile._closed.append(Span("results", "sweep", 2, 260, 270))
    # ``before`` dropped: so may spans of the window have been
    assert program.span_seconds(run, "fetch") is None


def test_fused_stage_by_majority_without_the_batched_predicate():
    L = "jit(f)/vmap(jit(g))/while"
    pred = [f"{L}/body_pred/select/reduce_min"] * 6
    # a carry update: the batched select (the loop's) over fleet work
    assert stages.fused_stage([L, "", f"{L}/body/fleet/add",
                               f"{L}/body/fleet/mul"] + pred) == "fleet"
    assert stages.fused_stage([L, f"{L}/body/control/eq",
                               f"{L}/body/admission/lt",
                               f"{L}/body/admission/and"]) == "admission"
    # ties go to the earlier stage of STAGES
    assert stages.fused_stage([f"{L}/body/probe/x",
                               f"{L}/body/completion/y"]) == "completion"
    # the wave counter's update: the loop's own, not the predicate's
    assert stages.fused_stage([L, f"{L}/body/add"] + pred) == "other"
    assert stages.fused_stage([L] + pred) == "other"
    # the predicate alone: the condition's work, event selection
    assert stages.fused_stage(pred) == "select"
    assert stages.fused_stage(["", "jit(f)/copy"]) is None


def test_protobuf_fields():
    from harness import hlo
    # field 1 varint 300, field 2 "hi", field 3 packed [1, 150]
    msg = bytes([0x08, 0xAC, 0x02, 0x12, 0x02]) + b"hi" + \
        bytes([0x1A, 0x03, 0x01, 0x96, 0x01])
    got = list(hlo.fields(msg))
    assert got[0] == (1, 300) and bytes(got[1][1]) == b"hi"
    assert hlo._packed(got[2][1]) == [1, 150]


def test_hlo_kinds_of_a_profiled_program(tmp_path):
    """The trace file of a profiled run keeps its program's HLO: every
    fusion that holds a stage's operations is charged to a stage."""
    import glob

    import jax
    import jax.numpy as jnp

    from harness import hlo

    def body(c):
        i, v = c
        with jax.named_scope("completion"):
            v = jnp.sin(v) * 2.0 + 1.0
        with jax.named_scope("admission"):
            v = jnp.where(v > 1.5, v * 0.5, v)
        return i + 1, v

    @jax.jit
    def prog(x):
        return jax.vmap(lambda r: jax.lax.while_loop(
            lambda c: c[0] < 20 + c[1][0].astype(jnp.int32), body,
            (0, r))[1])(x)

    x = jnp.ones((4, 256))
    prog(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        prog(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    with open(path, "rb") as f:
        raw = f.read()
    mine = stages.hlo_kinds(raw)
    assert {"completion", "admission"} <= set(mine.values())
    for proto in hlo.hlo_protos(raw):
        name, _, ops = hlo.module_ops(proto)
        if name != "jit_prog":
            continue
        for ins, names in ops.items():
            if len(names) > 1 and any(stages.stage_of(n) in
                                      ("completion", "admission")
                                      for n in names):
                assert mine[ins] in ("completion", "admission"), ins


def _tpu_slice():
    with open(os.path.join(HERE, "data", "trace_slice_stages_grid8.json")) as f:
        d = json.load(f)
    return d, _trace({p: {ln: [tuple(e) for e in evs]
                          for ln, evs in lines.items()}
                      for p, lines in d["planes"].items()})


def test_recorded_tpu_slice(with_hlo):
    """Two cuts of the grid cell's traced sweep recorded on a TPU v5e, with
    the HLO name stacks of their operations: the engine call's first device
    operations after the upload, and the last ones the trace kept. The
    loop's time is the union of its operations' intervals, split among the
    stages; idle time goes, nanosecond by nanosecond, to the innermost
    program span open."""
    d, trace = _tpu_slice()
    kinds = {i: stages.fused_stage(n) if len(n) > 1 else stages.stage_of(n[0])
             for i, n in d["op_names"].items()}
    xplane = with_hlo(kinds)
    ops = [(n, int(a), int(a) + int(dur))
           for n, a, dur in d["planes"]["/device:TPU:0"]["XLA Ops"]]
    spans = sorted(((n, int(a), int(a) + int(dur))
                    for n, a, dur in d["planes"]["/host:CPU"]["python3"]
                    if n.startswith(stages.SPAN_PREFIX)),
                   key=lambda s: (s[1], -s[2]))
    seen = set()
    for c0, c1 in d["cuts"]:
        out = stages.reduce(trace, (c0, c1), xplane)
        busy = np.zeros(c1 - c0, bool)
        loop = np.zeros(c1 - c0, bool)
        for name, a, b in ops:
            a, b = max(a, c0) - c0, min(b, c1) - c0
            if b > a:
                busy[a:b] = True
                loop[a:b] |= kinds.get(name) is not None
        assert out["loop_s"] == pytest.approx(loop.sum() * 1e-9, abs=1e-12)
        assert out["idle_s"] == pytest.approx((~busy).sum() * 1e-9,
                                              abs=1e-12)
        owner = np.full(c1 - c0, -1)
        for k, (_, a, b) in enumerate(spans):   # outer first, inner over it
            a, b = max(a, c0) - c0, min(b, c1) - c0
            if b > a:
                owner[a:b] = k
        want = {}
        for k in np.unique(owner[~busy]):
            name = spans[k][0] if k >= 0 else stages.NO_SPAN
            want[name] = want.get(name, 0) + int(((owner == k) & ~busy).sum())
        got = {k: round(v * 1e9) for k, v in out["idle_by_span"].items()}
        assert got == want
        seen |= set(out["stage_s"])
    assert {"select", "completion", "admission", "control"} <= seen


def test_stage_split_runs_a_cell(small_bench, cache_dir):
    """``bench/stage_split.py`` on a one-hour copy of a cell, on the CPU:
    it finds the program's sweep and engine spans in the trace and the
    loop's waves. The CPU trace has no device plane, so no stage or idle
    time is read."""
    import subprocess

    from conftest import env
    p = subprocess.run(
        [sys.executable, os.path.join(small_bench, "stage_split.py"),
         "--workload", "paper-week-grid8", "--seed", "3000000019"],
        capture_output=True, text=True, env=env(cache_dir), timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["trace"] == "whole" and out["waves"] > 0
    assert 0 < out["engine_s"] < out["window_s"]
    assert out["wave_us"] == pytest.approx(out["engine_s"] / out["waves"]
                                           * 1e6)
    assert out["stage_us"] == {} and out["device_idle_share"] is None
