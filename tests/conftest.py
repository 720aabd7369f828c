"""Test harness config.

Runs everything on a virtual 8-device CPU mesh (SURVEY.md §4: the reference
tests all parallelism single-host; we use XLA's forced host device count the
way the reference uses its `custom_cpu` fake device plugin).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# holds even where a pytest plugin imported jax before this file ran
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 window (ROADMAP.md runs "
        "pytest -m 'not slow'); covered by the tools/ gates instead")


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu as paddle

    paddle.seed(2024)
    np.random.seed(2024)
    yield
