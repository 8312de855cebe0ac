"""``fleet_engaged``: the share of wave-loop iterations in which the fleet
stage ran, read from the traced sweep's rows; silent where no row counts
them."""
import os
import sys
import types

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH_DIR, os.path.join(BENCH_DIR, "metrics")]
import fleet_engaged  # noqa: E402


def _run(*rows):
    return types.SimpleNamespace(sweeps=[{"traces": [
        types.SimpleNamespace(waves=w, **({} if f is None
                                          else {"fleet_waves": f}))
        for w, f in rows]}])


def test_most_counted_iterations_over_the_longest_row():
    # the row that ended at wave 300 counted the iterations before it did
    assert fleet_engaged.read(_run((400, 6), (300, 5))) == \
        pytest.approx(100 * 6 / 400)


@pytest.mark.parametrize("rows", [((400, None), (300, None)),
                                  ((None, None),)],
                         ids=["no_counter", "no_wave_count"])
def test_silent_without_the_counter(rows):
    assert fleet_engaged.read(_run(*rows)) is None
