"""Batched hot-cache membership probe (ISSUE-11).

The serving half of the keyspace observatory (``opendht_tpu/hotcache.py``)
keeps a bounded device table of the hot keys' canonical 20-byte ids.
Before an ingest wave launches its ``[Q]`` closest-node resolve
(``runtime/wave_builder.py _launch``), this kernel answers "which of the
wave's targets are cached?" in ONE XOR-compare launch over the whole
wave — a hit peels the carried get off the wave entirely (it is served
from the cache's host-side value payloads), the miss set falls through
to the unchanged lookup launch.

Design mirrors :mod:`opendht_tpu.ops.sketch`:

- ids are the uint32 ``[.., 5]`` limb vectors of :mod:`opendht_tpu.ops.ids`
  — a probe is 5 limb compares per (target, slot) pair, reduced with
  ``jnp.all``; match == XOR distance exactly zero, hence "XOR-compare".
- the cache table is TINY (``[C, 5]`` with C <= a few hundred), so the
  ``[Q, C]`` compare is noise next to the ``[Q, N]`` lookup it spares.
- a bit-exact numpy mirror (:func:`probe_host`) is the tests' oracle,
  and the batching-off escape hatch's per-op membership test — the two
  paths must take the SAME hit/miss decision (pinned in
  tests/test_hotcache.py).

The kernel never carries payloads: values live host-side on the
:class:`~opendht_tpu.hotcache.HotValueCache` keyed by the same canonical
bytes, so the device answers membership + slot and the host serves the
payload.  The tp twin is ``sharded_cache_probe`` in
``parallel/sharded.py``.
"""

from __future__ import annotations

import functools

import numpy as np

from .ids import N_LIMBS

#: default bounded cache table capacity (slots of 20-byte ids); the
#: [Q, C] probe stays tiny against the [Q, N] lookup it replaces
CACHE_CAPACITY = 64


@functools.lru_cache(maxsize=8)
def _build_probe(capacity: int):
    import jax
    import jax.numpy as jnp

    def fn(cache_ids, valid, targets):
        t = targets.reshape(-1, N_LIMBS).astype(jnp.uint32)
        c = cache_ids.reshape(-1, N_LIMBS).astype(jnp.uint32)
        # [Q, C]: all-limb equality == XOR distance exactly zero
        eq = jnp.all(t[:, None, :] == c[None, :, :], axis=-1) & valid[None, :]
        hit = jnp.any(eq, axis=1)
        # lowest matching slot (slots hold distinct ids, so at most one
        # matches; argmax of the mask is deterministic either way)
        slot = jnp.where(hit, jnp.argmax(eq, axis=1).astype(jnp.int32),
                         jnp.int32(-1))
        return hit, slot
    return jax.jit(fn)


def cache_probe(cache_ids, valid, targets):
    """ONE batched XOR-compare launch: ``(hit [Q] bool, slot [Q] int32)``
    for a wave's targets against the cache table.

    ``cache_ids``: uint32 ``[C, 5]`` (device or host), ``valid``: bool
    ``[C]`` (False rows never match), ``targets``: uint32 ``[Q, 5]``.
    ``slot[i]`` is the matching cache row, -1 on miss.  Dispatch is one
    fused compare-reduce; nothing here blocks until the caller reads
    the result."""
    return _build_probe(int(cache_ids.shape[0]))(cache_ids, valid, targets)


def probe_host(cache_ids, valid, targets) -> tuple:
    """Bit-exact numpy mirror of :func:`cache_probe` — the tests'
    oracle and the batching-off path's per-op membership test (the two
    serving paths must take the same decision)."""
    c = np.asarray(cache_ids, np.uint32).reshape(-1, N_LIMBS)
    v = np.asarray(valid, bool).reshape(-1)
    t = np.asarray(targets, np.uint32).reshape(-1, N_LIMBS)
    eq = np.all(t[:, None, :] == c[None, :, :], axis=-1) & v[None, :]
    hit = eq.any(axis=1)
    slot = np.where(hit, eq.argmax(axis=1).astype(np.int32),
                    np.int32(-1))
    return hit, slot
