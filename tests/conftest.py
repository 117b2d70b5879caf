"""Shared fixtures.  NOTE: no XLA device-count override here — smoke tests
and benches see the real single CPU device; only dryrun/sweep force 512."""
import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def make_mesh11():
    from repro.launch.mesh import make_mesh
    return make_mesh((1, 1), ("data", "model"))
