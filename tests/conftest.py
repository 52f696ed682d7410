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


import pytest  # noqa: E402


@pytest.fixture
def small_tiles(monkeypatch):
    """``small_tiles(lanes)``: from that call to the end of the test a loop
    of more than ``lanes`` lookups runs its rounds in LANE TILES
    (``core.search.ROUND_TILE_LANES``, 131,072 on the chip) — the constant,
    and builders that never traced under the other one: a jit's cache does
    not know the constant it was traced with."""
    def patch(lanes: int = 1024) -> None:
        from opendht_tpu.core import search
        from opendht_tpu.parallel import sharded
        engine = search._simulate_lookups_jit.__wrapped__

        def _simulate_lookups_jit(*args, **kw):
            # a function of its own: jax keys its traces by the function
            return engine(*args, **kw)

        monkeypatch.setattr(search, "ROUND_TILE_LANES", lanes)
        monkeypatch.setattr(search, "_simulate_lookups_jit", jax.jit(
            _simulate_lookups_jit,
            static_argnames=("k", "alpha", "search_nodes", "max_hops",
                             "state_limbs", "block_mode")))
        monkeypatch.setattr(sharded, "build_tp_lookup",
                            sharded.build_tp_lookup.__wrapped__)
    return patch
