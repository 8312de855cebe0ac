"""The harness end to end on the CPU at a one-hour horizon: the result
line, the refusal without a chip, cells and metrics added as files only,
the faults that must come out not correct, and the control."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT, copy_benchmark, env

CELLS = ("paper-week-grid8", "ops-week-stack8")
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
CHECKS = ["trace_drift", "records_drift", "summary_drift", "calls_off",
          "rows_missing"]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", CELLS)
def test_result_line_untraced(small_bench, drive, cell):
    line = drive(small_bench, "none", "--workload", cell, "--seed",
                 "3000000019", "--seconds", "0.5", "--trace", "0")
    assert list(line)[:5] == RESULT_KEYS and list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert list(line["checks"]) == CHECKS
    for v in line["checks"].values():
        assert v == {"value": 0.0, "limit": 0.0}
    # the CPU reports no device memory, so device_peak_mb is left out
    want = {m["name"] for m in _bench()["end_to_end"]} - {"device_peak_mb"}
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert line["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", CELLS)
def test_result_line_traced(small_bench, drive, cell):
    line = drive(small_bench, "none", "--workload", cell, "--seed", "7",
                 "--seconds", "0.5", "--trace", "1")
    assert line["correct"] is True
    assert list(line)[-2:] == ["breakdown", "checks"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in line["breakdown"].values())
    # host spans and compile counts; the device metrics need a device plane
    # in the trace, which a CPU run does not have
    assert {"prep_s", "batching_s", "summarize_s", "compile_s",
            "compiles"} <= set(line["metrics"])
    assert "device_idle_share" not in line["metrics"]
    assert line["metrics"]["compiles"]["value"] == 0
    for name in ("prep_s", "batching_s", "summarize_s"):
        assert line["metrics"][name]["value"] > 0


def test_refuses_without_a_tpu(cache_dir):
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True,
        env=env(cache_dir), timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    assert "TPU" in p.stderr


def test_cell_config_and_metric_added_as_files(tmp_path, drive):
    """A new configuration, mix, span layer and metric reader, and their
    BENCHMARK.json entries, are all a new cell needs. The configuration
    runs the stages no committed one does (failure and retry, outages,
    repair and spot), so they stay one file away."""
    bench = copy_benchmark(str(tmp_path))
    with open(os.path.join(bench, "configs", "pipesim-paper.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "pipesim-small"
    cfg["platform"]["resources"][0]["capacity"] = 24
    cfg["failures"] = {
        "p_fail_by_type": [0.05, 0.1, 0.05, 0.1, 0.1, 0.05],
        "framework_mult": [1.0] * 5,
        "retry": {"max_retries": 2, "base_s": 60.0, "mult": 2.0,
                  "cap_s": 600.0}}
    cfg["reliability"] = {
        "topology": {"zones": 2, "racks_per_zone": 2},
        "outages": {"zone_mtbf_s": 3600.0, "rack_mtbf_s": 1200.0,
                    "mttr_s": 300.0},
        "repair": {"crews": 1},
        "spot": {"frac": 0.25, "evict_mtbe_s": 900.0, "reclaim_s": 120.0},
        "time_quantum_s": 1.0}
    with open(os.path.join(bench, "configs", "pipesim-small.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "cells", "hour-fifo2.json"), "w") as f:
        json.dump({"horizon_s": 1800.0, "interarrival_factors": [1.0],
                   "base_seed": 5, "n_replicas": 2,
                   "axes": {"policy": ["fifo"]}}, f)
    with open(os.path.join(bench, "spans", "records.json"), "w") as f:
        json.dump({"layer": "records", "wrap":
                   ["repro.core.trace:concat_records"]}, f)
    with open(os.path.join(bench, "metrics", "waves_per_sweep.py"),
              "w") as f:
        f.write("def read(run):\n"
                "    return max(t.waves for t in run.sweeps[0]['traces'])\n")
    with open(os.path.join(bench, "metrics", "records_s.py"), "w") as f:
        f.write("from harness.spans import group_seconds\n\n\n"
                "def read(run):\n"
                "    return group_seconds(run, 'records')\n")
    path = os.path.join(str(tmp_path), "BENCHMARK.json")
    with open(path) as f:
        b = json.load(f)
    b["configs"].append({"name": "pipesim-small", "source": "test",
                         "file": "bench/configs/pipesim-small.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "small-hour", "config": "pipesim-small",
                           "traffic": "hour-fifo2", "chips": 1,
                           "why": "test"})
    for name in ("waves_per_sweep", "records_s"):
        b["per_layer"].append({"name": name, "unit": "count",
                               "better": "lower", "source": "program_span",
                               "layer": "records", "moves": "pipelines_per_s",
                               "workloads": ["small-hour"]})
    with open(path, "w") as f:
        json.dump(b, f)
    line = drive(bench, "none", "--workload", "small-hour", "--seed", "11",
                 "--seconds", "0.2", "--trace", "1")
    assert line["correct"] is True
    assert line["checks"]["trace_drift"] == {"value": 0.0, "limit": 0.0}
    assert line["metrics"]["waves_per_sweep"]["value"] > 0
    assert line["metrics"]["records_s"]["value"] > 0
    # the existing cells do not list the new metrics
    line = drive(bench, "none", "--workload", CELLS[0], "--seed", "11",
                 "--seconds", "0.2", "--trace", "1")
    assert "waves_per_sweep" not in line["metrics"]


@pytest.mark.parametrize("fault", ["frozen", "half_batch", "altered",
                                   "reused"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(small_bench, drive, cell, fault):
    line = drive(small_bench, fault, "--workload", cell, "--seed", "23",
                 "--seconds", "0.5", "--trace", "0")
    assert line["correct"] is False
    assert any(v["value"] > v["limit"] for v in line["checks"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(small_bench, cache_dir, cell):
    """The reference on a bfloat16 time grid, in the program's place."""
    p = subprocess.run(
        [sys.executable, os.path.join(small_bench, "control.py"),
         "--workload", cell, "--seeds", "1", "2", "3"],
        capture_output=True, text=True, env=env(cache_dir), timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    for row in map(json.loads, p.stdout.strip().splitlines()):
        assert row["correct"] is False
        assert row["checks"]["trace_drift"]["value"] > 0
