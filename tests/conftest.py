"""Test harness config: force a virtual 8-device CPU platform BEFORE any
backend initializes, so multi-chip sharding tests run without TPU hardware."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# hermetic: neither this process nor the children tests start may read
# programs an earlier run left in the checkout's compile cache
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

from pathway_tpu.utils import jaxcfg  # noqa: E402

jaxcfg.guard_cpu_platform(force_device_count=8)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-second suites (supervised-restart integration etc.) "
        "excluded from tier-1 runs via -m 'not slow'",
    )


@pytest.fixture(autouse=True)
def clear_parse_graph():
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    yield
    G.clear()
