import os

# NOTE: do NOT set --xla_force_host_platform_device_count here — smoke
# tests and benches must see the real single device; only launch/dryrun.py
# (and subprocess tests that re-exec python) use fake device counts.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from hypothesis import HealthCheck, settings  # noqa: E402

settings.register_profile(
    "ci", max_examples=20, deadline=None,
    suppress_health_check=[HealthCheck.too_slow,
                           HealthCheck.data_too_large])
settings.load_profile("ci")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)
