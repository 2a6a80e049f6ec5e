"""Shared fixtures. NOTE: no XLA_FLAGS here — tests run on 1 device by
design (the 512-device forcing is exclusively dryrun.py's, per task spec).
"""

import jax
import pytest
from repro.launch.mesh import make_test_mesh


@pytest.fixture(scope="session")
def test_mesh():
    """(1,1) mesh with production axis names (shard_map code paths need
    the named axes to exist)."""
    return make_test_mesh()


@pytest.fixture(autouse=True)
def _under_mesh(test_mesh):
    with jax.set_mesh(test_mesh):
        yield
