"""Batched listener-table membership match (ISSUE-20).

The reference's proxy layer exists largely to fan stored values out to
subscribers (``DhtProxyServer`` push, ``Dht::storageChanged``), and
until round 24 that fan-out started with a host-side dict probe per
put: every ``storage_store`` walked Python listener records one value
at a time.  This kernel turns the membership question — "which of this
ingest wave's stored-put keys have listeners?" — into ONE XOR-equality
launch over the whole wave, the same Orca-style amortization move the
churn table (PR-7) and the hot-cache probe (PR-11) made: a million
idle-but-subscribed keys cost one batched compare per wave, not a
million dict probes.

Design mirrors :mod:`opendht_tpu.ops.cache_probe` (deliberately — the
all-limb-compare shape is shared):

- ids are the uint32 ``[.., 5]`` limb vectors of :mod:`opendht_tpu.ops.ids`
  — a match is 5 limb compares per (stored key, table slot) pair,
  reduced with ``jnp.all``; match == XOR distance exactly zero.
- the listener table is ``[L, 5]`` with L up to the configured
  capacity (tombstoned rows carry ``valid=False`` and never match —
  the append+tombstone+compact discipline of ``ops/sorted_table.py``'s
  churn path, host-managed in :mod:`opendht_tpu.listeners`).
- a bit-exact numpy mirror (:func:`match_host`) is the tests' oracle
  and the ``listen_batching="off"`` path's membership decision — the
  two delivery paths must reach the SAME hit set (pinned in
  tests/test_listener.py).

The kernel never carries listener records or payloads: per-key listener
sets (local callbacks, remote ``(node, sid)`` sockets, proxy push
subscriptions) live host-side on the :class:`~opendht_tpu.runtime.dht.Dht`
storage, so the device answers membership + slot and the host performs
one coalesced delivery dispatch per wave per listener.  The tp twin is
``sharded_listener_match`` in ``parallel/sharded.py``.
"""

from __future__ import annotations

import functools

import numpy as np

from .ids import N_LIMBS

#: default bounded listener table capacity (slots of 20-byte key ids);
#: the [S, L] compare is one fused reduce — at the canonical wave
#: S=64 even L=1e6 is a single ~300M-lane elementwise pass, which is
#: the whole point (no chip has timed it yet: PERF.md §7)
LISTENER_CAPACITY = 1024


@functools.lru_cache(maxsize=8)
def _build_match(capacity: int):
    import jax
    import jax.numpy as jnp

    def fn(table_ids, valid, stored):
        s = stored.reshape(-1, N_LIMBS).astype(jnp.uint32)
        t = table_ids.reshape(-1, N_LIMBS).astype(jnp.uint32)
        # [S, L]: all-limb equality == XOR distance exactly zero;
        # tombstoned/never-filled rows are masked by valid
        eq = jnp.all(s[:, None, :] == t[None, :, :], axis=-1) & valid[None, :]
        hit = jnp.any(eq, axis=1)
        # lowest matching slot (live slots hold distinct ids, so at
        # most one matches; argmax of the mask is deterministic)
        slot = jnp.where(hit, jnp.argmax(eq, axis=1).astype(jnp.int32),
                         jnp.int32(-1))
        return hit, slot
    return jax.jit(fn)


def listener_match(table_ids, valid, stored):
    """ONE batched XOR-equality launch: ``(hit [S] bool, slot [S] int32)``
    for a wave's stored-put keys against the listener table.

    ``table_ids``: uint32 ``[L, 5]`` (device or host), ``valid``: bool
    ``[L]`` (tombstoned rows never match), ``stored``: uint32
    ``[S, 5]``.  ``slot[i]`` is the matching table row, -1 on miss.
    Dispatch is one fused compare-reduce; nothing here blocks until the
    caller reads the result."""
    return _build_match(int(table_ids.shape[0]))(table_ids, valid, stored)


def match_host(table_ids, valid, stored) -> tuple:
    """Bit-exact numpy mirror of :func:`listener_match` — the tests'
    oracle and the ``listen_batching="off"`` path's membership decision
    (the two delivery paths must reach the same hit set)."""
    t = np.asarray(table_ids, np.uint32).reshape(-1, N_LIMBS)
    v = np.asarray(valid, bool).reshape(-1)
    s = np.asarray(stored, np.uint32).reshape(-1, N_LIMBS)
    eq = np.all(s[:, None, :] == t[None, :, :], axis=-1) & v[None, :]
    hit = eq.any(axis=1)
    slot = np.where(hit, eq.argmax(axis=1).astype(np.int32),
                    np.int32(-1))
    return hit, slot
