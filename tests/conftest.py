import os

# Tests exercising shard_map need a few host devices; smoke tests see the
# same count (cheap).  Do NOT set 512 here — that is dryrun.py's job only.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro.launch.mesh import make_mesh  # noqa: E402


@pytest.fixture(scope="session")
def mesh11():
    return make_mesh((1, 1), ("data", "model"))


@pytest.fixture(scope="session")
def mesh22():
    return make_mesh((2, 2), ("data", "model"))


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
