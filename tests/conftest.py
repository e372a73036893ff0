import os
import sys

# tests run on the real (1-device) CPU backend — the 512-device flag lives
# ONLY in launch/dryrun.py.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import pytest


@pytest.fixture(scope="session")
def mesh1():
    import jax
    from jax.sharding import AxisType

    return jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)


@pytest.fixture(scope="session")
def rules():
    from repro.models.sharding import DEFAULT_RULES

    return DEFAULT_RULES
