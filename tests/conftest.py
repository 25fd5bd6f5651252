import jax
import pytest

# NOTE: do NOT set xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device; only launch/dryrun.py uses 512 placeholders.

jax.config.update("jax_platform_name", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tier2: sim<->serving parity / property suites, run as a separate "
        "non-blocking CI job (select with -m tier2)")


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.key(0)
