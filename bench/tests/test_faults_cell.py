"""The failure-and-recovery cell ``faults-week-grid8`` on the CPU at a
one-hour horizon: it resolves to its configuration and mix, its result
line is correct and carries its three per-layer metrics, the planted
faults and the control read not correct on it, and the metric readers
(``retry_share``, ``attempt_slots``, ``capacity_down_share``) read what
the rows count and nothing where they count nothing."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from conftest import BENCH_DIR, env

sys.path[:0] = [BENCH_DIR, os.path.join(BENCH_DIR, "metrics")]
import attempt_slots  # noqa: E402
import capacity_down_share  # noqa: E402
import retry_share  # noqa: E402
from harness import cells, stages  # noqa: E402

CELL = "faults-week-grid8"
METRICS = ("retry_share", "attempt_slots", "capacity_down_share")


def test_cell_resolves_to_its_config_and_mix():
    found = cells.find_cell(CELL, cells.load_benchmark())
    assert found["cell"]["chips"] == 1
    assert found["config"]["name"] == "pipesim-faults"
    assert {"failures", "reliability"} <= set(found["config"])
    grid = cells.find_cell("paper-week-grid8", cells.load_benchmark())
    # the paper grid's traffic, platform and axes, with failures on
    for key in ("horizon_s", "interarrival_factors", "base_seed",
                "n_replicas", "axes"):
        assert found["mix"][key] == grid["mix"][key]
    assert found["config"]["platform"] == grid["config"]["platform"]
    assert [m["name"] for m in found["per_layer"]] == list(METRICS)
    assert not {m["name"] for m in grid["per_layer"]} & set(METRICS)


def test_result_line_is_correct_and_reads_the_counters(small_bench, drive):
    line = drive(small_bench, "none", "--workload", CELL, "--seed",
                 "3000000019", "--seconds", "0.5", "--trace", "1")
    assert line["correct"] is True and line["failed"] == 0
    for v in line["checks"].values():
        assert v == {"value": 0.0, "limit": 0.0}
    assert set(line["metrics"]) == set(METRICS)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m["retry_share"] < 100
    assert m["attempt_slots"] >= 2
    assert 0 <= m["capacity_down_share"] < 100


@pytest.mark.parametrize("fault", ["frozen", "half_batch", "altered",
                                   "reused"])
def test_fault_is_not_correct(small_bench, drive, fault):
    line = drive(small_bench, fault, "--workload", CELL, "--seed", "23",
                 "--seconds", "0.5", "--trace", "0")
    assert line["correct"] is False
    assert any(v["value"] > v["limit"] for v in line["checks"].values())


def test_control_is_not_correct(small_bench, cache_dir):
    """The reference on a bfloat16 time grid, in the program's place."""
    p = subprocess.run(
        [sys.executable, os.path.join(small_bench, "control.py"),
         "--workload", CELL, "--seeds", "1", "2", "3"],
        capture_output=True, text=True, env=env(cache_dir), timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    for row in map(json.loads, p.stdout.strip().splitlines()):
        assert row["correct"] is False
        assert row["checks"]["trace_drift"]["value"] > 0


@pytest.mark.parametrize("scope,stage", [("completion/retry", "completion"),
                                         ("control/reliability", "control"),
                                         ("admission/attempts", "admission")])
def test_nested_scopes_count_to_their_stage(scope, stage):
    """The program's nested scopes leave the stage split as it was."""
    name = f"jit(simulate_ensemble)/while/body/{scope}/select_n"
    assert stages.stage_of(name) == stage
    assert stages.fused_stage([name, f"jit(x)/while/body/{stage}/add"]) \
        == stage


# ------------------------------------------------------- the readers

def _run(rows, horizon_s=100.0):
    results = [types.SimpleNamespace(
        experiment=types.SimpleNamespace(horizon_s=horizon_s))]
    return types.SimpleNamespace(sweeps=[{"traces": rows,
                                          "results": results}])


def _row(retries=None, slots=None, rel_s=None, attempts=None):
    counters = {k: v for k, v in (("retries", retries),
                                  ("attempt_slots", slots),
                                  ("rel_slot_s", rel_s)) if v is not None}
    return types.SimpleNamespace(
        waves=10, capacities=np.array([3, 1]),
        attempts=None if attempts is None else np.asarray(attempts),
        **counters)


def test_readers_on_a_synthetic_run():
    run = _run([_row(1, 3, 40.0, [[2, 1], [1, 0]]),
                _row(3, 3, 0.0, [[4, 1], [1, 1]])])
    # 4 retries of 11 executed attempts
    assert retry_share.read(run) == pytest.approx(100 * 4 / 11)
    assert attempt_slots.read(run) == 3
    # 40 slot-seconds of 2 rows x 4 slots x 100 s
    assert capacity_down_share.read(run) == pytest.approx(100 * 40 / 800)


@pytest.mark.parametrize("row", [
    # the paper grid's rows: no scenario, no reliability
    dict(),
    # a program version that does not keep these counters
    dict(attempts=[[1, 1]]),
], ids=["grid_cell", "no_counters"])
def test_readers_are_silent_without_the_counters(row):
    run = _run([_row(**row), _row(**row)])
    for mod in (retry_share, attempt_slots, capacity_down_share):
        assert mod.read(run) is None
