"""Compile the main path's kernels for a described TPU v5e (no chip needed).

The TPU compiler ships with jaxlib, so these tests lower and compile for a
chip that is described, not attached: they catch what interpret mode
cannot (block shapes off the (8, 128) tiling, VMEM overruns, primitives
Mosaic cannot lower). Nothing runs. The topology is described inside a
module fixture, never at import, so every test worker collects the same
tests and only the one given this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import vdes
from repro.kernels.queue_scan import _fused_admission_call, _queue_scan_call


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_fused_admission_compiles_vmapped(one_chip):
    """The admission kernel at paper-scale width, vmapped over a grid."""
    R, N, nres = 64, 14336, 2

    def rounds(res_q, pkey, wave, free):
        return jax.vmap(lambda r, p, w, f: _fused_admission_call(
            r, p, w, f, nres=nres, interpret=False))(res_q, pkey, wave, free)

    compiled = jax.jit(rounds).lower(
        _spec((R, N), jnp.int32, one_chip), _spec((R, N), jnp.float32,
                                                  one_chip),
        _spec((R, N), jnp.int32, one_chip),
        _spec((R, nres), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_wave_loop_with_pallas_admission_compiles(one_chip, monkeypatch):
    """The whole batched wave loop with the kernel selected. The engine
    picks interpret mode from the default backend, which is the CPU here:
    steer it to the compiled kernel, as it runs on a TPU."""
    from repro.kernels import queue_scan
    monkeypatch.setattr(queue_scan, "_auto_interpret", lambda: False)
    vdes.simulate_ensemble.clear_cache()
    R, N, T, nres = 8, 2048, 6, 2
    f32, i32 = jnp.float32, jnp.int32
    compiled = vdes.simulate_ensemble.lower(
        _spec((R, N), f32, one_chip), _spec((R, N), i32, one_chip),
        _spec((R, N, T), i32, one_chip), _spec((R, N, T), f32, one_chip),
        _spec((R, N), f32, one_chip), _spec((R, nres), i32, one_chip),
        admission_sort="pallas").compile()
    vdes.simulate_ensemble.clear_cache()
    assert "tpu_custom_call" in compiled.as_text()


def test_queue_scan_compiles(one_chip):
    R, N = 8, 1024
    compiled = jax.jit(lambda r, s: _queue_scan_call(
        r, s, capacity=4, interpret=False)).lower(
        _spec((R, N), jnp.float32, one_chip),
        _spec((R, N), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sweep_wave_loop_compiles_sort_free(one_chip):
    """The paper-scale sweep program (48 rows, 7-day width, traced
    policies) with the default admission ranking: it compiles in seconds
    and holds no sort, which costs minutes of compile at this width."""
    vdes.simulate_ensemble.clear_cache()
    R, N, T, nres = 48, 28727, 6, 2
    f32, i32 = jnp.float32, jnp.int32
    compiled = vdes.simulate_ensemble.lower(
        _spec((R, N), f32, one_chip), _spec((R, N), i32, one_chip),
        _spec((R, N, T), i32, one_chip), _spec((R, N, T), f32, one_chip),
        _spec((R, N), f32, one_chip), _spec((R, nres), i32, one_chip),
        policies=_spec((R,), i32, one_chip)).compile()
    vdes.simulate_ensemble.clear_cache()
    assert " sort(" not in compiled.as_text()
