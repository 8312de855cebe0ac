import os
import sys

# make the top-level benchmarks/ package importable regardless of cwd
sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..")))

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(1234)


@pytest.fixture(autouse=True, scope="module")
def _release_jax_executables():
    """Free compiled executables between modules — the full suite compiles
    hundreds of graphs and LLVM OOMs if they all stay resident."""
    yield
    import jax
    jax.clear_caches()
    import gc
    gc.collect()
