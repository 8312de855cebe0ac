"""Mesh-agnostic checkpointing with atomic writes, keep-last-k, async save,
and restore-with-resharding (elastic scaling / fault tolerance).

Format: one ``.npz`` per step, leaves keyed by their pytree path. Restore
takes *target shardings* — a checkpoint written on a 16x16 mesh restores onto
2x16x16 (or a single device) unchanged: arrays are host-gathered on save and
``device_put`` with the new NamedSharding on load.

The training loop in ``launch/train.py`` wraps this with crash-restart:
failures (including injected ones) roll back to the latest checkpoint, and
the deterministic data pipeline replays from the restored step.
"""
from __future__ import annotations

import json
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np


def _flatten_with_names(tree) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(_key_str(k) for k in path)
        flat[name] = np.asarray(leaf)
    return flat


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


class CheckpointManager:
    def __init__(self, directory: str, keep_last: int = 3,
                 async_save: bool = True):
        self.dir = directory
        self.keep_last = keep_last
        self.async_save = async_save
        self._thread: Optional[threading.Thread] = None
        os.makedirs(directory, exist_ok=True)

    # ------------------------------------------------------------- save
    def save(self, step: int, state_tree, block: bool = False) -> str:
        flat = _flatten_with_names(state_tree)  # host-gather happens here
        path = os.path.join(self.dir, f"ckpt_{step:08d}.npz")

        def write():
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                np.savez(f, **flat)   # file handle: no suffix appended
            os.replace(tmp, path)
            self._gc()

        self.wait()  # never let two writers race on the same tmp path
        if self.async_save and not block:
            self._thread = threading.Thread(target=write, daemon=True)
            self._thread.start()
        else:
            write()
        return path

    def wait(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            self._thread.join()

    def _gc(self) -> None:
        steps = self.all_steps()
        for s in steps[:-self.keep_last]:
            try:
                os.remove(os.path.join(self.dir, f"ckpt_{s:08d}.npz"))
            except OSError:
                pass

    # ---------------------------------------------------------- restore
    def all_steps(self) -> List[int]:
        out = []
        for f in os.listdir(self.dir):
            m = re.match(r"ckpt_(\d+)\.npz$", f)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, step: int, target_tree, shardings=None):
        """Restore into the structure of ``target_tree`` (shapes/dtypes used
        for validation), placing leaves with ``shardings`` if given —
        resharding onto any mesh."""
        self.wait()
        path = os.path.join(self.dir, f"ckpt_{step:08d}.npz")
        z = np.load(path)
        names = list(z.files)
        flat_target, treedef = jax.tree_util.tree_flatten_with_path(target_tree)
        sh_flat = (jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda x: x is None or hasattr(x, "spec"))
            if shardings is not None else [None] * len(flat_target))
        out = []
        for (path_k, leaf), sh in zip(flat_target, sh_flat):
            name = "/".join(_key_str(k) for k in path_k)
            if name not in z:
                raise KeyError(f"checkpoint missing leaf {name}")
            arr = z[name]
            if tuple(arr.shape) != tuple(leaf.shape):
                raise ValueError(
                    f"shape mismatch for {name}: ckpt {arr.shape} vs "
                    f"target {leaf.shape}")
            arr = arr.astype(leaf.dtype)
            if sh is not None:
                out.append(jax.device_put(arr, sh))
            else:
                out.append(jax.numpy.asarray(arr))
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(target_tree), out)


class FaultInjector:
    """Deterministic failure schedule for fault-tolerance tests: raises
    RuntimeError at configured steps (once each).

    Wired into the simulator's reliability subsystem:
    :meth:`pipesim_ref.reliability.CheckpointSpec.injector` maps a compiled
    reliability timeline's outage start times onto training steps and
    returns one of these — the same schedule that drains simulated
    capacity crashes the real training loop (``launch/train.py``), so
    fault-tolerance tests and simulation share one failure source."""

    def __init__(self, fail_at: List[int]):
        self.fail_at = set(fail_at)
        self.fired: set = set()

    def maybe_fail(self, step: int) -> None:
        if step in self.fail_at and step not in self.fired:
            self.fired.add(step)
            raise RuntimeError(f"injected node failure at step {step}")


class StragglerMonitor:
    """Step-time watchdog: flags steps slower than ``threshold x`` the
    trailing median (the straggler-mitigation signal; on a real pod this
    triggers re-slicing / hot-spare swap, here it feeds logs + PipeSim).

    Also the simulator's repair watchdog:
    :func:`pipesim_ref.reliability.compile_reliability` streams repair-crew
    service durations through one of these, so pathologically slow repairs
    surface in ``availability_summary`` (``n_stragglers``) through the
    same statistic that flags slow training steps."""

    def __init__(self, window: int = 20, threshold: float = 2.5):
        self.times: List[float] = []
        self.window = window
        self.threshold = threshold
        self.flagged: List[int] = []

    def record(self, step: int, seconds: float) -> bool:
        self.times.append(seconds)
        hist = self.times[-self.window:]
        if len(hist) >= 5:
            med = float(np.median(hist))
            if seconds > self.threshold * med:
                self.flagged.append(step)
                return True
        return False
