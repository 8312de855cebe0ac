"""Fixtures of the benchmark's own tests (``python -m pytest bench/tests``,
on the CPU): a copy of the benchmark with every mix cut to a one-hour
horizon, and a helper that runs one cell of such a copy in a fresh process
with the chip check off."""
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
DRIVE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "drive.py")
SMALL_HORIZON_S = 3600.0


def copy_benchmark(dest: str) -> str:
    """``dest`` with ``BENCHMARK.json`` and ``bench/``, mixes cut to
    :data:`SMALL_HORIZON_S` and the controller's and trigger's tick
    intervals cut to fit in it (whole seconds still); returns the copy's
    ``bench`` directory."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    bench = os.path.join(dest, "bench")
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    cells = os.path.join(bench, "cells")
    for name in os.listdir(cells):
        path = os.path.join(cells, name)
        with open(path) as f:
            mix = json.load(f)
        mix["horizon_s"] = SMALL_HORIZON_S
        with open(path, "w") as f:
            json.dump(mix, f)
    configs = os.path.join(bench, "configs")
    for name in os.listdir(configs):
        path = os.path.join(configs, name)
        with open(path) as f:
            cfg = json.load(f)
        for stage in ("controller", "trigger"):
            if stage in cfg:
                ticks = cfg[stage]
                ticks["interval_s"] = min(ticks["interval_s"],
                                          SMALL_HORIZON_S / 4)
                ticks["cooldown_s"] = min(ticks["cooldown_s"],
                                          SMALL_HORIZON_S / 2)
        with open(path, "w") as f:
            json.dump(cfg, f)
    return bench


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("jax_cache"))


@pytest.fixture(scope="module")
def small_bench(tmp_path_factory):
    return copy_benchmark(str(tmp_path_factory.mktemp("bench_copy")))


def env(cache_dir: str) -> dict:
    e = dict(os.environ)
    e.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=cache_dir,
             PYTHONPATH=os.path.join(ROOT, "src"))
    return e


@pytest.fixture(scope="module")
def drive(cache_dir):
    """``drive(bench_dir, fault, *args)`` -> the result line's object."""
    def run(bench_dir, fault, *args):
        p = subprocess.run(
            [sys.executable, DRIVE, bench_dir, fault, *args],
            capture_output=True, text=True, env=env(cache_dir), timeout=600)
        assert p.returncode == 0, p.stderr[-4000:]
        return json.loads(p.stdout.strip().splitlines()[-1])
    return run
