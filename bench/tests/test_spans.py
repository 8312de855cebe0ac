"""Span readings: a group's seconds come from its own span file alone, so a
span file added later leaves every existing reading as it was."""
import json
import os
import statistics
import sys
import time

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

from harness.spans import Recorder, covered_seconds, group_seconds  # noqa: E402

TOY = '''
import time
import toy_layers


def outer():
    time.sleep(0.02)
    toy_layers.inner()
    toy_layers.inner()


def inner():
    time.sleep(0.01)
'''


class _Run:
    def __init__(self, spans, sweeps):
        self.spans, self.sweeps = spans, sweeps

    def per_sweep(self, fn):
        return statistics.median(fn(s) for s in self.sweeps)


@pytest.fixture
def toy(tmp_path, monkeypatch):
    (tmp_path / "toy_layers.py").write_text(TOY)
    monkeypatch.syspath_prepend(str(tmp_path))
    import toy_layers
    return toy_layers


def _record(toy, spans_dir):
    rec = Recorder()
    rec.wrap_layers(str(spans_dir))
    t0 = time.perf_counter_ns()
    try:
        toy.outer()
    finally:
        rec.restore()
    return _Run(rec.spans, [dict(start=t0, end=time.perf_counter_ns())])


def test_added_span_file_leaves_existing_group(tmp_path, toy):
    """A later file wrapping a function that runs inside an existing
    group's span (as ``compile_fleet`` runs inside ``_spec_workloads``)
    neither lowers nor raises that group's reading."""
    old, new = tmp_path / "old", tmp_path / "new"
    for d in (old, new):
        d.mkdir()
        (d / "prep.json").write_text(json.dumps(
            {"layer": "experiment prep", "wrap": ["toy_layers:outer"]}))
    (new / "fleet.json").write_text(json.dumps(
        {"layer": "fleet compile", "wrap": ["toy_layers:inner"]}))

    before = _record(toy, old)
    after = _record(toy, new)
    assert [s.group for s in before.spans] == ["prep"]
    assert [s.group for s in after.spans] == ["prep", "fleet", "fleet"]
    outer = after.spans[0]
    prep = group_seconds(after, "prep")
    # the whole outer span, the nested spans of the new file not taken off
    assert prep == pytest.approx((outer.end - outer.start) * 1e-9, abs=0)
    # the same reading as with the new file's spans left out
    only_prep = _Run([s for s in after.spans if s.group == "prep"],
                     after.sweeps)
    assert group_seconds(only_prep, "prep") == prep
    assert group_seconds(after, "fleet") == pytest.approx(0.02, rel=0.5)
    assert group_seconds(before, "fleet") is None


def test_group_counts_nested_calls_once():
    assert covered_seconds([(0, 10), (2, 5), (8, 12), (20, 21)]) == \
        pytest.approx(13e-9)
    assert covered_seconds([]) == 0.0


def test_span_files_name_distinct_groups():
    """Every reader in ``bench/metrics`` that reads host spans names one
    file of ``bench/spans`` by its name."""
    spans = {os.path.splitext(f)[0]
             for f in os.listdir(os.path.join(BENCH_DIR, "spans"))}
    for name in os.listdir(os.path.join(BENCH_DIR, "metrics")):
        with open(os.path.join(BENCH_DIR, "metrics", name)) as f:
            text = f.read()
        if "group_seconds(run," in text:
            group = text.split("group_seconds(run, ")[1].split(")")[0]
            assert group.strip("\"'") in spans, name
