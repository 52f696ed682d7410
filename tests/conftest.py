"""Test config: the tests run on the CPU backend with 8 virtual devices
(``JAX_PLATFORMS=cpu`` plus ``XLA_FLAGS=--xla_force_host_platform_
device_count=8``), so the multi-chip sharding paths (jax.sharding.Mesh /
shard_map) are exercised without hardware.  Both are applied here, before
the first backend use, so a bare ``pytest`` on a machine with a chip
never takes it: a chip belongs to one process, and what runs on it is
``chip_smoke.py``.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running live-cluster / subprocess / fuzz tests "
        "(`-m 'not slow'` = the ~4-minute medium tier)")
    config.addinivalue_line(
        "markers",
        "quick: fast broad-coverage smoke modules — `pytest -m quick` "
        "is the sub-minute iteration tier; the full suite (CI, "
        "pre-merge) runs everything")
