"""``attempt_log_slots``: the retry-log width of the traced sweep's rows,
the most any row reports; silent where no row reports one (a sweep that
records no attempts, or a program without the log)."""
import os
import sys
import types

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH_DIR, os.path.join(BENCH_DIR, "metrics")]
import attempt_log_slots  # noqa: E402


def _run(*rows, waves=10):
    return types.SimpleNamespace(sweeps=[{"traces": [
        types.SimpleNamespace(waves=waves, **({} if r is None else
                                              {"attempt_log_slots": r}))
        for r in rows]}])


def test_the_widest_row():
    assert attempt_log_slots.read(_run(3, 2, None)) == 3
    # a batch that records attempts yet retries nothing logs no column
    assert attempt_log_slots.read(_run(0, 0)) == 0


@pytest.mark.parametrize("run", [_run(None, None), _run(3, waves=None)],
                         ids=["no_counter", "no_wave_count"])
def test_silent_without_the_counter(run):
    assert attempt_log_slots.read(run) is None
