"""Shared fixtures. NOTE: no XLA_FLAGS here on purpose — smoke tests must
see the real single CPU device; a test that needs more devices starts its
own process."""
import jax
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture()
def np_rng():
    return np.random.default_rng(0)
