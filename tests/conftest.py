"""Test harness: force CPU JAX with 8 virtual devices.

The TPU-native analogue of the reference's "multi-node simulation without a
cluster" (SURVEY.md §4): multi-chip sharding tests run on a virtual
8-device CPU mesh via ``--xla_force_host_platform_device_count``.

The platform is forced here, not left to the caller's ``JAX_PLATFORMS``,
so the suite is safe on a machine that has a chip: a chip belongs to one
process at a time, and the suite must neither take it nor fight a running
job for it.  pytest imports this conftest before any test module, so no
backend exists yet when the config is updated.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# The suite stays OFF the persistent compilation cache.  run_experiments
# turns it on at <checkout>/.jax_cache with zero thresholds; under the
# suite that would write thousands of CPU executables into the checkout,
# and the chip tool copies the tree as it stands on disk.
jax.config.update("jax_enable_compilation_cache", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: tier-2 tests (multi-device shard_map compiles, large-model "
        "CPU compiles) excluded from the tier-1 `-m 'not slow'` budget",
    )
