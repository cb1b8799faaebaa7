"""Test configuration: the suite runs on the CPU with 8 virtual devices.

Tests (and every child they spawn, through the inherited environment) never
want a chip; shardings are validated over ``xla_force_host_platform_device_count``
CPU devices. The chip has its own check: ``chip_smoke.py``.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (-m 'not slow'); covered by "
        "the analysis gate or a dedicated stage instead")


@pytest.fixture(autouse=True)
def _reset_config_singleton():
    """Each test sees a fresh Config.from_env() so monkeypatched env vars apply;
    poller/pool singletons die with the test that used them."""
    from tpurpc.utils import config as config_mod

    config_mod.set_config(None)
    yield
    from tpurpc.core.poller import PairPool, Poller

    Poller.reset()
    PairPool.reset()
    config_mod.set_config(None)


#: shared skip marker for suites that need the native core built
#: (tests/test_native_client.py, test_native_server.py, test_aio.py,
#: test_scalability.py import it instead of hand-rolling the path check)
requires_native_lib = pytest.mark.skipif(
    not os.path.exists(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "native", "build", "libtpurpc.so")),
    reason="native lib not built")
