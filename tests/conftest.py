"""Shared fixtures. NOTE: no XLA_FLAGS here — tier-1 tests see 1 CPU device;
the multi-device tests force their device count in a subprocess."""

import jax
import pytest


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
