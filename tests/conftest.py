"""Shared test fixtures. NOTE: no XLA_FLAGS here — smoke tests must see
the single real CPU device; only launch/dryrun.py forces 512."""
import warnings

import numpy as np
import pytest

warnings.filterwarnings("ignore", message=".*dtype int64.*")
warnings.filterwarnings("ignore", message=".*x64.*")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="module", autouse=True)
def _bound_jit_cache():
    """Clear XLA caches between modules: 90+ accumulated compilations make
    later compiles pathologically slow on this single-core container."""
    yield
    import jax
    jax.clear_caches()
