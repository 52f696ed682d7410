"""The node table: a growable slab of known peers with k-bucket admission
and device-snapshot queries.

This replaces three reference structures with one:

- ``RoutingTable``/``Bucket`` (include/opendht/routing_table.h:26-97,
  src/routing_table.cpp) — k=8 buckets split around the own id.  Here
  buckets are *implicit*: bucket(peer) = commonBits(self, peer) (see
  ops/radix.py); admission keeps ≤ k non-expired peers per bucket, which
  is the steady state the reference's split rule converges to.
- ``NodeCache`` (src/node_cache.cpp) — the interning map of every peer
  ever heard of; here the slab itself, with a host dict for O(1) id→row.
- ``Node`` liveness state (include/opendht/node.h:73-158) — the
  good/dubious/expired timers become per-row columns.

Host/device split (the architectural core of the TPU build): per-packet
mutations are O(1) host-side numpy/dict updates; *all* closest-node
queries go through an immutable device ``Snapshot`` (sorted id matrix +
permutation) built lazily and reused until the table changes.  That
turns the reference's per-search scalar scans
(``findClosestNodes`` src/routing_table.cpp:109-150,
``getCachedNodes`` src/node_cache.cpp:41-74) into one batched
sorted-window top-k (ops/sorted_table.py) over thousands of concurrent
targets.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp

from .. import telemetry
from ..infohash import InfoHash
from ..ops import ids as IK
from ..ops import radix
from ..ops.churn_table import (ChurnTable, churn_apply, churn_compact,
                               churn_table, stale_limit, tomb_words)
from ..ops.sorted_table import (_resolve_merge_pack, sort_table, lookup_topk,
                                expand_table, churn_lookup_topk,
                                default_lut_bits)

# liveness windows (reference include/opendht/node.h:148-158)
NODE_GOOD_TIME = 120 * 60.0       # replied within 2 h → good
NODE_EXPIRE_TIME = 10 * 60.0      # silent for 10 min → expirable
MAX_RESPONSE_TIME = 1.0           # per-attempt RPC timeout
MAX_AUTH_ERRORS = 3               # 3 strikes → expired (node.h:73-77)

TARGET_NODES = 8                  # k (routing_table.h:26)
SEARCH_NODES = 14                 # search candidate set (dht.h:308)

DELTA_CAP = 4096                  # churn side-slab capacity (inserts
                                  # absorbed without re-sorting)
TOMB_MIN = 1024                   # compact when tombstones exceed
TOMB_FRAC = 16                    # max(TOMB_MIN, n_base // TOMB_FRAC)
MAX_STALE_SHARE = 1 / TOMB_FRAC   # the same rule as a share of the base

# compactions are a first-class perf signal (every full re-sort+re-expand
# stalls behind a device sort): counted per-process alongside each
# NodeTable's own ``compactions`` attribute
_M_COMPACTIONS = telemetry.get_registry().counter(
    "dht_table_compactions_total")

# Below these sizes closest-node queries run as an exact numpy scan on
# the host slab instead of a device kernel: a live protocol node's
# table is tens-to-hundreds of rows, where one XLA compile (~10 s on a
# CPU backend) or even one device round-trip dwarfs the O(Q·N) scan.
# The device path (snapshot/churn kernels) is for simulation-scale
# tables and query waves, where it is the headline win.
HOST_SCAN_MAX_ROWS = 4096
HOST_SCAN_MAX_QUERIES = 64


@dataclasses.dataclass
class NodeView:
    """Host-side view of one table row (≈ reference Node, node.h)."""

    row: int
    id: InfoHash
    addr: Any
    time_reply: float
    time_seen: float
    expired: bool

    def is_good(self, now: float) -> bool:
        return (not self.expired) and self.time_reply > 0 and \
            now - self.time_reply < NODE_GOOD_TIME


class PendingLookup:
    """Handle for an in-flight (dispatched, not yet consumed) batched
    closest-node resolve — the round-20 async seam.

    JAX dispatch is asynchronous: the device kernel is launched when
    ``lookup_launch``/``find_closest_launch`` returns, but the blocking
    ``np.asarray`` transfer (and the host-side row mapping behind it)
    is deferred into :meth:`consume`.  ``ready()`` is a non-blocking
    probe (``jax.Array.is_ready``) so a caller — the wave-builder
    pipeline — can fill and launch wave N+1 while wave N still runs on
    device, and only pay the wait where the results are actually used.

    The finalize closure must capture every piece of mutable host
    state it maps through (churn-view ``delta_rows``/``_d_perm``, the
    launch-time ``now``) AT LAUNCH TIME: the table may mutate between
    launch and consume, and depth-1 equivalence requires the mapping
    the synchronous path would have used.  Row→id/addr materialization
    above this seam (``ids_of_rows``/``addr_of``) still reads the live
    slab at consume; the one-pump window is sub-millisecond and an
    eviction+row-reuse inside it resolves against the row's current
    occupant — same class of benign race the synchronous path has
    between resolve and RPC send.

    ``consume()`` is idempotent (caches its result and drops the device
    refs) so ``lookup(...) = lookup_launch(...).consume()`` is the ONE
    codepath for both the synchronous and pipelined forms."""

    __slots__ = ("_finalize", "_probe", "_done", "_result")

    def __init__(self, finalize, probe=None):
        self._finalize = finalize         # () -> result tuple
        self._probe = probe               # device array or None (=ready)
        self._done = False
        self._result = None

    @classmethod
    def resolved(cls, *result):
        """An already-materialized result (host-scan fast path)."""
        pl = cls(None)
        pl._done = True
        pl._result = result if len(result) != 1 else result[0]
        return pl

    def ready(self) -> bool:
        """Non-blocking: True when consume() will not wait on device."""
        if self._done or self._probe is None:
            return True
        try:
            return bool(self._probe.is_ready())
        except AttributeError:            # numpy / stub result
            return True

    def consume(self):
        """Block until the device work finishes, materialize, cache."""
        if not self._done:
            self._result = self._finalize()
            self._done = True
            self._finalize = None
            self._probe = None
        return self._result


class Snapshot:
    """Immutable device view: lexicographically sorted ids + row map."""

    def __init__(self, sorted_ids, perm, n_valid, version: int, mask_key):
        self.sorted_ids = sorted_ids      # uint32 [cap, 5] device
        self.perm = perm                  # int32 [cap] sorted→row (-1 pad)
        self.n_valid = n_valid            # int32 scalar
        self.version = version
        self.mask_key = mask_key
        self._expanded = None             # lazy expand_table
        self._tp_state = None             # lazy (mesh, placed dict)

    def lookup(self, queries, *, k: int = TARGET_NODES, window: int = 128,
               mesh=None, layout=None):
        """Batched exact k-closest.  queries: uint32 [Q,5] (device or np).
        Returns (rows [Q,k] int32 numpy, dist [Q,k,5] numpy) with -1 padding.

        Uses the expanded row-gather fast path (built lazily per
        snapshot — the table is immutable until the next version) with
        the default fast3 select, which carries all five distance limbs.
        ``window`` is accepted for API symmetry with the non-expanded
        path but IGNORED here: the candidate window is fixed at
        EXPAND_LEN=192 rows, and uncertified queries fall back to the
        exact full scan on device inside lookup_topk.  No prefix LUT:
        routing-table ids cluster around self_id by design, so LUT
        buckets degenerate — the plain log2(cap)-step positioning
        search is both exact and cheap at routing-table sizes.

        ``mesh`` (round 13, ``config.resolve_mesh_t``): a (q=1, t)
        device mesh row-shards the resolve — per-shard windowed top-k
        over each shard's contiguous slice of the sorted slab, ONE
        cross-shard merge collective (parallel/sharded.py
        ``sharded_window_lookup``) — so the resolve table scales past
        one device's HBM.  Exact either way; results identical (the
        window kernel's certificate decertifies into the shard-local
        full scan).

        ``layout`` (ISSUE-17, load-aware resharding): an installed
        :class:`~opendht_tpu.reshard.ReshardLayout` moves the shard
        boundaries to traffic-weighted row splits of THIS snapshot —
        same merge kernel, same results, different ownership."""
        return self.lookup_launch(queries, k=k, window=window,
                                  mesh=mesh, layout=layout).consume()

    def lookup_launch(self, queries, *, k: int = TARGET_NODES,
                      window: int = 128, mesh=None,
                      layout=None) -> PendingLookup:
        """Async form of :meth:`lookup` (round-20 wave pipeline): the
        device kernel is dispatched before this returns; the blocking
        transfer + perm row-mapping are deferred into the handle's
        ``consume()``.  The per-wave query buffer is donated to the
        kernel when it is this call's own upload (non-CPU backends
        only — see ops/sorted_table._donating_lookup_topk)."""
        q = jnp.asarray(queries, jnp.uint32)
        if mesh is not None and mesh.shape.get("t", 1) > 1:
            return self._lookup_sharded_launch(mesh, q, k, window, layout)
        if self._expanded is None:
            self._expanded = expand_table(self.sorted_ids)
        dist, idx, _ = lookup_topk(self.sorted_ids, self.n_valid, q, k=k,
                                   expanded=self._expanded,
                                   donate_queries=q is not queries)
        perm = self.perm

        def finalize(idx=idx, dist=dist, perm=perm):
            idx = np.asarray(idx)         # blocks on the device call
            rows = np.where(idx >= 0,
                            np.asarray(perm)[np.clip(idx, 0, None)], -1)
            return rows.astype(np.int32), np.asarray(dist)

        return PendingLookup(finalize, probe=idx)

    def reshard_boundary_rows(self, layout, n_t: int):
        """Traffic-weighted interior row boundaries of THIS snapshot
        for an installed reshard layout — re-derived per snapshot (raw
        row offsets go stale across rebuilds; the layout carries bin
        loads, not rows), cached by ``(layout.gen, t)``.

        Returns ``n_t - 1`` nondecreasing row indices into the valid
        prefix of the sorted order (parallel/partition.py
        ``solve_shard_boundaries``): the snapshot's per-bin row counts
        come from one searchsorted over the sorted top limb."""
        key = (int(layout.gen), int(n_t))
        cached = getattr(self, "_reshard_rows", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        from ..parallel.partition import solve_shard_boundaries
        n = int(self.n_valid)
        top = np.asarray(self.sorted_ids[:, 0]).astype(np.int64)
        edges_v = np.arange(1, 256, dtype=np.int64) << 24
        counts = np.searchsorted(top[:n], edges_v, side="left")
        bin_rows = np.diff(np.concatenate([[0], counts, [n]]))
        rows = solve_shard_boundaries(
            bin_rows, layout.bin_loads, n_t,
            load_weight=layout.load_weight)
        self._reshard_rows = (key, rows)
        return rows

    def _shard_state(self, mesh, layout=None):
        """Row-shard this snapshot's sorted slab over the mesh ``t``
        axis ONCE (declarative placement — parallel/partition.py) and
        cache the placed operands; subsequent waves reuse them with
        zero copies (the shard fns are placement-idempotent).

        With a reshard ``layout`` (ISSUE-17) the split is the
        traffic-weighted one: shard ``i`` owns rows
        ``[b_i, b_{i+1})`` of the sorted order, physically realized as
        equal-capacity slabs (rearranged rows + per-shard widths) so
        ``P('t', None)`` placement still sees equal chunks.  The cache
        key includes ``layout.gen`` — a hot swap is one attribute
        write on the DHT loop; the NEXT wave rebuilds here (row
        movement + placement, never a re-sort) while any wave already
        in flight keeps the operands and perm map its launch captured.

        Returns ``(placed, perm_host)``: ``perm_host`` is None for the
        uniform split (global sorted positions map through
        ``self.perm``) or the rearranged position→slab-row map for the
        weighted one."""
        st = self._tp_state
        key = (None if layout is None
               else (int(layout.gen), int(mesh.shape["t"])))
        if st is not None and st[0] is mesh and st[1] == key:
            return st[2], st[3]
        from ..parallel import partition
        from ..parallel.sharded import pad_to_multiple
        n_t = mesh.shape["t"]
        n = int(self.n_valid)
        if layout is not None:
            bnd = self.reshard_boundary_rows(layout, n_t)
            bounds = np.maximum.accumulate(
                np.concatenate([[0], np.clip(bnd, 0, n), [n]]))
            widths = np.diff(bounds)
            shard_cap = int(-(-max(int(widths.max()), 1)
                              // partition.RESHARD_ALIGN)
                            * partition.RESHARD_ALIGN)
            ids_np = np.asarray(self.sorted_ids, np.uint32)
            perm_np = np.asarray(self.perm)
            ids_re = np.zeros((n_t * shard_cap, ids_np.shape[1]), np.uint32)
            perm_host = np.full(n_t * shard_cap, -1, np.int32)
            for i in range(n_t):
                w = int(widths[i])
                lo, hi = int(bounds[i]), int(bounds[i + 1])
                ids_re[i * shard_cap:i * shard_cap + w] = ids_np[lo:hi]
                perm_host[i * shard_cap:i * shard_cap + w] = perm_np[lo:hi]
            nv = widths.astype(np.int32)
            perm_local = np.tile(np.arange(shard_cap, dtype=np.int32), n_t)
            placed = partition.shard_put(
                mesh, {"sorted_ids": ids_re, "perm": perm_local,
                       "n_valid": nv},
                partition.TABLE_AXIS_RULES)
            self._tp_state = (mesh, key, placed, perm_host)
            return placed, perm_host
        cap = self.sorted_ids.shape[0]
        ids = self.sorted_ids
        if cap % n_t:
            # append-pad on host; pad rows land past the valid prefix
            # (the last shard) and every shard excludes rows beyond its
            # local n_valid, so their content never participates
            ids, _ = pad_to_multiple(np.asarray(ids), n_t)
        shard_n = ids.shape[0] // n_t
        nv = np.clip(n - np.arange(n_t) * shard_n, 0,
                     shard_n).astype(np.int32)
        # per-shard LOCAL sorted positions: the sharded kernel offsets
        # them by the shard base, yielding global sorted positions that
        # this snapshot's perm then maps to slab rows host-side
        perm_local = np.tile(np.arange(shard_n, dtype=np.int32), n_t)
        placed = partition.shard_put(
            mesh, {"sorted_ids": ids, "perm": perm_local, "n_valid": nv},
            partition.TABLE_AXIS_RULES)
        self._tp_state = (mesh, key, placed, None)
        return placed, None

    def _lookup_sharded_launch(self, mesh, q, k: int, window: int,
                               layout=None) -> PendingLookup:
        from ..parallel.sharded import sharded_window_lookup
        placed, perm_host = self._shard_state(mesh, layout)
        dist, gpos = sharded_window_lookup(
            mesh, q, placed["sorted_ids"], placed["perm"],
            placed["n_valid"], k=k, window=window)
        # captured AT LAUNCH: a reshard swap between launch and consume
        # must not remap this wave's positions through the new layout
        perm = self.perm if perm_host is None else perm_host

        def finalize(gpos=gpos, dist=dist, perm=perm):
            gpos = np.asarray(gpos)       # blocks on the collective
            rows = np.where(gpos >= 0,
                            np.asarray(perm)[np.clip(gpos, 0, None)], -1)
            return rows.astype(np.int32), np.asarray(dist)

        return PendingLookup(finalize, probe=gpos)


class ChurnView:
    """Append+tombstone view over an immutable base :class:`Snapshot`
    (SURVEY §7 "incremental updates"; reference mutation path
    src/routing_table.cpp:204-262).

    Mutations since the base was built are absorbed host-side in O(1):
    evictions set one bit in a packed tombstone mask over *sorted
    positions* (dead rows stay in the device array as mere sort keys);
    inserts land in a small delta slab.  ``lookup`` runs
    ops/sorted_table.churn_lookup_topk — tombstone-masked window top-k
    over the base, window top-k over the delta (kept as its own mini
    sorted+expanded table, re-sorted lazily per mutation batch), one
    lane-packed merge (on TPU, 128//k queries share each 128-lane
    physical row, ops/sorted_table.packed_churn_merge — the round-7
    amortizer for the [Q, k] padding tax) — in a single device call,
    bit-identical to a full re-sort of the mutated id set.  Device
    state is refreshed lazily:
    tombstone words re-upload whole (1.25 MB per 10M rows — noise), the
    delta re-sorts on device (one small sort+expand per dirty batch).

    Correctness never depends on churn volume (a heavily-tombstoned
    window decertifies into the kernel's exact fallback), so compaction
    — dropping this view and rebuilding the base — is purely a
    performance policy, owned by :class:`NodeTable`.
    """

    def __init__(self, base: Snapshot, cap_rows: int,
                 delta_cap: int = DELTA_CAP):
        self.base = base
        n = base.sorted_ids.shape[0]
        perm = np.asarray(base.perm)
        self.n_base = int((perm >= 0).sum())
        self._perm = perm
        # slab row -> sorted position AT BASE-BUILD TIME.  Never re-read
        # after the row is freed+reused: inserts always go to the delta,
        # and note_evict checks delta membership first, so a stale
        # mapping is only ever used to tombstone the id that actually
        # occupied the position.
        self.inv_perm = np.full(cap_rows, -1, dtype=np.int64)
        pos = np.nonzero(perm >= 0)[0]
        self.inv_perm[perm[pos]] = pos
        self.tomb_np = np.zeros(tomb_words(n), dtype=np.uint32)
        self.tomb_count = 0
        self.delta_ids_np = np.zeros((delta_cap, IK.N_LIMBS), dtype=np.uint32)
        self.delta_rows = np.full(delta_cap, -1, dtype=np.int64)
        self._delta_pos: dict[int, int] = {}
        self.n_delta = 0
        self._dev_tomb = None
        self._dev_delta = None            # (d_sorted, d_expanded, d_n_valid)
        self._d_perm = None               # delta sorted pos -> slot
        self._dirty_tomb = True
        self._dirty_delta = True

    @property
    def pending(self) -> int:
        return self.tomb_count + self.n_delta

    def grow_delta(self) -> None:
        """Double the delta slab in place (churn kernels recompile once
        per slab size — shapes recur, so a steady state is reached).
        Lets an overflowing delta keep absorbing inserts while a
        background compaction builds the next base (NodeTable
        ``_start_compaction``) instead of stalling a lookup behind a
        synchronous full rebuild."""
        dcap = self.delta_ids_np.shape[0]
        self.delta_ids_np = np.concatenate(
            [self.delta_ids_np, np.zeros_like(self.delta_ids_np)])
        self.delta_rows = np.concatenate(
            [self.delta_rows, np.full(dcap, -1, dtype=np.int64)])
        self._dirty_delta = True

    def note_insert(self, row: int, limbs) -> bool:
        """Absorb a newly-live slab row.  False = delta slab full (the
        caller must compact).  The row must NOT be live in the base:
        NodeTable only routes here rows that are new, revived after an
        expiry (whose base position the expiry tombstoned), or absent
        from the base mask at build time — so live ids stay unique
        across base and delta and merge order stays exact."""
        if row in self._delta_pos:
            return True
        if self.n_delta >= self.delta_ids_np.shape[0]:
            return False
        s = self.n_delta
        self.delta_ids_np[s] = limbs
        self.delta_rows[s] = row
        self._delta_pos[row] = s
        self.n_delta = s + 1
        self._dirty_delta = True
        return True

    def note_evict(self, row: int) -> None:
        """Absorb a row leaving the live set (evicted or expired).
        Delta membership is checked before the base mapping so a reused
        slab row never tombstones another id's position."""
        s = self._delta_pos.pop(row, None)
        if s is not None:
            last = self.n_delta - 1
            if s != last:
                self.delta_ids_np[s] = self.delta_ids_np[last]
                lrow = int(self.delta_rows[last])
                self.delta_rows[s] = lrow
                self._delta_pos[lrow] = s
            self.delta_rows[last] = -1
            self.n_delta = last
            self._dirty_delta = True
            return
        if 0 <= row < len(self.inv_perm):
            p = int(self.inv_perm[row])
            if p >= 0 and not (int(self.tomb_np[p >> 5]) >> (p & 31)) & 1:
                self.tomb_np[p >> 5] |= np.uint32(1) << (p & 31)
                self.tomb_count += 1
                self._dirty_tomb = True

    def lookup(self, queries, *, k: int = TARGET_NODES, window: int = 128):
        """Batched exact k-closest over (live base ∪ delta) — same
        contract as :meth:`Snapshot.lookup` (``window`` ignored).

        Host-side telemetry (ISSUE-3; the kernel itself is untouched):
        ``dht_churn_lookup_seconds`` spans the whole device call;
        ``dht_churn_lookups_total{pack=}`` records which merge
        pack path the backend resolves ("auto" → 128//k on TPU, 1
        elsewhere); tombstone/delta gauges expose the view's churn
        debt."""
        return self.lookup_launch(queries, k=k, window=window).consume()

    def lookup_launch(self, queries, *, k: int = TARGET_NODES,
                      window: int = 128) -> PendingLookup:
        """Async form of :meth:`lookup` (round-20 wave pipeline).
        Telemetry and the lazy tombstone/delta device refresh happen at
        launch; the ``dht_churn_lookup_seconds`` histogram observes
        dispatch + blocking-wait at consume (same device interval the
        synchronous span covered).  The finalize closure captures
        ``delta_rows``/``_d_perm``/``_perm`` AT LAUNCH: ``note_evict``
        swap-removes delta slots in place and a delta re-sort replaces
        ``_d_perm`` wholesale, so mapping through the live view at
        consume could diverge from what this launch's kernel saw."""
        reg = telemetry.get_registry()
        reg.counter("dht_churn_lookups_total",
                    pack=_resolve_merge_pack("auto", k)).inc()
        reg.gauge("dht_churn_tombstones").set(self.tomb_count)
        reg.gauge("dht_churn_delta_rows").set(self.n_delta)
        q = jnp.asarray(queries, jnp.uint32)
        base = self.base
        if base._expanded is None:
            base._expanded = expand_table(base.sorted_ids)
        if self._dirty_tomb or self._dev_tomb is None:
            self._dev_tomb = jnp.asarray(self.tomb_np)
            self._dirty_tomb = False
        if self._dirty_delta or self._dev_delta is None:
            dcap = self.delta_ids_np.shape[0]
            dvalid = np.zeros(dcap, bool)
            dvalid[:self.n_delta] = True      # slots are prefix-dense
            ds, dp, dnv = sort_table(jnp.asarray(self.delta_ids_np),
                                     jnp.asarray(dvalid))
            self._dev_delta = (ds, expand_table(ds, stride=32), dnv)
            self._d_perm = np.asarray(dp)
            self._dirty_delta = False
        ds, de, dnv = self._dev_delta
        t0 = time.perf_counter()
        dist, enc, _ = churn_lookup_topk(
            base.sorted_ids, base._expanded, base.n_valid,
            self._dev_tomb, ds, de, dnv, q, k=k)
        dispatch_s = time.perf_counter() - t0
        n = base.sorted_ids.shape[0]
        d_perm = self._d_perm
        base_perm = self._perm
        delta_rows = self.delta_rows.copy()
        hist = reg.histogram("dht_churn_lookup_seconds")

        def finalize(dist=dist, enc=enc):
            t1 = time.perf_counter()
            enc = np.asarray(enc)           # blocks on the device call
            hist.observe(dispatch_s + (time.perf_counter() - t1))
            # enc in [n, n+D) is a *delta sorted position* → slot → slab row
            dslot = d_perm[np.clip(enc - n, 0, len(d_perm) - 1)]
            rows = np.where(
                enc < 0, -1,
                np.where(enc < n, base_perm[np.clip(enc, 0, n - 1)],
                         delta_rows[np.clip(dslot, 0, None)]))
            return rows.astype(np.int32), np.asarray(dist)

        return PendingLookup(finalize, probe=enc)


class DeviceChurnTable:
    """A sorted id table that departs, joins and compacts ON THE DEVICE
    — the lookup simulator's table under membership churn
    (``simulate_lookups(table.view, None, targets, ...)``), where
    :class:`ChurnView` is the served node's.

    ``ChurnView`` keeps its tombstones and its delta in numpy and takes
    one Python call a mutation; a simulated network of 10M nodes turns
    over 33,334 members a second (OpenDHT ``NODE_EXPIRE_TIME``: N/600
    departures and as many arrivals), which cannot go that way.  Here a
    tick is one device program over a batch (``ops.churn_table
    .churn_apply``) and a compaction another (``churn_compact``); the
    host holds five integers.  Shared with the churn view: the liveness
    word layout (``ops.sorted_table.unpack_tomb_bits``) and the rule for
    when departed rows force a compaction (``stale_limit``,
    :data:`MAX_STALE_SHARE`).

    The table compacts BY ITSELF, before a tick that would take the
    departed share of the base past :data:`MAX_STALE_SHARE` or the delta
    past its capacity — sooner than the rule at most by one batch,
    never later.  Nothing here selects a path: what ``simulate_lookups``
    does with a table it reads off the table.  ``view`` is the table as
    it is NOW: a tick and a compaction consume the one before (its
    buffers are donated), so read ``table.view`` anew after either.

    A table that outgrows one chip is the sibling
    ``parallel.churn.ShardedChurnTable``: the same object over a table
    row-sharded over a mesh, one ``ChurnTable`` a shard, searched by
    ``parallel.tp_simulate_lookups(mesh, state=table.view)``.  It is
    this class with the two device programs replaced, so the rule, the
    spans and the series below are one spelling: the host keeps its
    integers ONE A SHARD (here: one), and whichever shard would pass a
    limit first makes all compact.

    Telemetry: span ``dht_table_apply_seconds`` around a tick's ingest
    and ``dht_table_compact_seconds`` around a compaction (each waits
    for its result); counters ``dht_table_compactions_total``,
    ``dht_table_rows_departed_total``, ``dht_table_rows_joined_total``;
    gauges ``dht_churn_tombstones`` and ``dht_churn_delta_rows`` (the
    sum over the shards) and ``dht_churn_delta_rows_max`` (the fullest
    shard's, the one that triggers).
    """

    def __init__(self, sorted_ids, n_valid, *, delta_capacity: int):
        rows = sorted_ids.shape[0]
        # room for a full delta, in whole liveness words
        capacity = 32 * tomb_words(rows + delta_capacity)
        self.view: ChurnTable = churn_table(
            sorted_ids, n_valid, capacity=capacity,
            delta_capacity=delta_capacity,
            stale_rows=stale_limit(capacity, MAX_STALE_SHARE),
            lut_bits=default_lut_bits(rows))
        self._capacity, self._delta_capacity = capacity, delta_capacity
        self.compactions = 0
        self._rebased([int(self.view.n_base)])

    # -- the host's integers, one of each a shard (each read back with a
    # result the span waits for anyway) ---------------------------------
    def _rebased(self, n_base) -> None:
        """The counts right after a build or a compaction."""
        self._n_base = np.asarray(n_base, np.int64)
        self._n_tomb = np.zeros_like(self._n_base)
        self._n_delta = np.zeros_like(self._n_base)
        self._n_delta_gone = np.zeros_like(self._n_base)

    n_base = property(lambda self: int(self._n_base.sum()))
    n_tomb = property(lambda self: int(self._n_tomb.sum()))
    n_delta = property(lambda self: int(self._n_delta.sum()))
    n_delta_gone = property(lambda self: int(self._n_delta_gone.sum()))

    def _live(self) -> np.ndarray:
        return (self._n_base - self._n_tomb
                + self._n_delta - self._n_delta_gone)

    @property
    def n_live(self) -> int:
        return int(self._live().sum())

    def _stale_max(self) -> np.ndarray:
        return np.array([stale_limit(int(n), MAX_STALE_SHARE)
                         for n in self._n_base])

    @property
    def stale_rows_max(self) -> int:
        """Departed base rows a base may hold (the tightest shard's)."""
        return int(self._stale_max().min())

    # -- the two device programs (parallel.churn replaces them) ---------
    def _run_tick(self, leave_ids, join_ids):
        """One tick on the device: ``(left_base, left_delta, joined)``,
        one of each a shard."""
        self.view, left = churn_apply(self.view, leave_ids, join_ids)
        left_base, left_delta = (int(x) for x in jax.device_get(left))
        return [left_base], [left_delta], [join_ids.shape[0]]

    def _run_compact(self):
        """One compaction on the device: the new bases' row counts."""
        self.view = churn_compact(self.view)
        return [int(self.view.n_base)]

    def _make_room(self, E: int, J: int) -> None:
        """Before a tick that hands a shard up to ``E`` departures and
        ``J`` arrivals: compact if any shard's departed rows or delta
        would pass their limit, and refuse what no compaction makes
        room for."""
        D = self._delta_capacity
        if ((self._n_tomb + E > self._stale_max()).any()
                or (self._n_delta + J > D).any()):
            self.compact()
            if (E > self._stale_max()).any() or J > D:
                raise ValueError(
                    f"a tick of {E} departures and {J} arrivals does not "
                    f"fit a base of {self._n_base.tolist()} rows (at most "
                    f"{self._stale_max().tolist()} departed) and a delta "
                    f"of {D}")

    def _tick(self, leave_ids, join_ids, **how) -> None:
        """The tick proper: span, program (``how``: what the sibling's
        takes besides), counts, series."""
        reg = telemetry.get_registry()
        with reg.span("dht_table_apply_seconds"):
            left_base, left_delta, joined = self._run_tick(
                leave_ids, join_ids, **how)
        self._n_tomb += left_base
        self._n_delta_gone += left_delta
        self._n_delta += joined
        reg.counter("dht_table_rows_departed_total").inc(
            int(np.sum(left_base) + np.sum(left_delta)))
        reg.counter("dht_table_rows_joined_total").inc(int(np.sum(joined)))
        self._gauges(reg)

    def apply(self, leave_ids, join_ids) -> None:
        """One tick: ``leave_ids`` [E,5] depart (found by id; an id that
        is no live member leaves nothing), then ``join_ids`` [J,5]
        arrive.  Both are device arrays; each distinct (E, J) is an
        executable of its own."""
        self._make_room(leave_ids.shape[0], join_ids.shape[0])
        self._tick(leave_ids, join_ids)

    def compact(self) -> None:
        """Merge the live rows of base and delta into a new sorted base
        with its LUT, departed rows dropped."""
        if (self._live() > self._capacity).any():
            raise ValueError(
                f"{self._live().tolist()} live rows would pass the "
                f"capacity of {self._capacity} rows (a shard): nothing is "
                "compacted and no row dropped, build a larger table")
        reg = telemetry.get_registry()
        with reg.span("dht_table_compact_seconds"):
            n_base = self._run_compact()
        self._rebased(n_base)
        self.compactions += 1
        _M_COMPACTIONS.inc()
        self._gauges(reg)

    def _gauges(self, reg) -> None:
        reg.gauge("dht_churn_tombstones").set(self.n_tomb)
        reg.gauge("dht_churn_delta_rows").set(self.n_delta)
        reg.gauge("dht_churn_delta_rows_max").set(int(self._n_delta.max()))


class NodeTable:
    """Growable peer slab with k-bucket admission (one per address family,
    like the reference's buckets4/buckets6, dht.h:370-381)."""

    def __init__(self, self_id: InfoHash, *, k: int = TARGET_NODES,
                 capacity: int = 1024, delta_cap: int = DELTA_CAP):
        self.self_id = self_id
        self.self_limbs = IK.ids_from_bytes(bytes(self_id)).reshape(-1)
        self.k = k
        self._cap = capacity
        self._delta_cap = delta_cap
        self._churn: Optional[ChurnView] = None
        self.compactions = 0              # full re-sort+re-expand count
        self._ids = np.zeros((capacity, IK.N_LIMBS), dtype=np.uint32)
        self._valid = np.zeros(capacity, dtype=bool)
        self._expired = np.zeros(capacity, dtype=bool)
        self._time_reply = np.zeros(capacity, dtype=np.float64)
        self._time_seen = np.zeros(capacity, dtype=np.float64)
        self._auth_err = np.zeros(capacity, dtype=np.int8)
        self._bucket = np.zeros(capacity, dtype=np.int16)
        self._addrs: list = [None] * capacity
        self._row_of: dict[bytes, int] = {}
        self._free: list[int] = list(range(capacity - 1, -1, -1))
        self._bucket_count = np.zeros(radix.ID_BITS, dtype=np.int32)
        # one cached replacement candidate per bucket (↔ Bucket::cached,
        # routing_table.h:31-45)
        self._cached: dict[int, tuple[bytes, Any]] = {}
        self._version = 0
        self._maint_key = None            # reusable refresh-target PRNG
                                          # key (lazy; split per use)
        self._snap: Optional[Snapshot] = None
        #: whether the most recent find_closest ran the t-sharded
        #: resolve (round 13) — host scans and churn views reset it
        self.last_resolve_sharded = False
        # in-flight background compaction: dispatched device arrays +
        # the mutation log to replay at swap (see _start_compaction)
        self._pending_base: Optional[dict] = None

    # ------------------------------------------------------------------ size
    def __len__(self) -> int:
        return len(self._row_of)

    @property
    def capacity(self) -> int:
        return self._cap

    def _grow(self) -> None:
        old = self._cap
        new = old * 2
        for name in ("_ids", "_valid", "_expired", "_time_reply", "_time_seen",
                     "_auth_err", "_bucket"):
            arr = getattr(self, name)
            grown = np.zeros((new,) + arr.shape[1:], dtype=arr.dtype)
            grown[:old] = arr
            setattr(self, name, grown)
        self._addrs.extend([None] * old)
        self._free.extend(range(new - 1, old - 1, -1))
        self._cap = new

    # ------------------------------------------------------------ liveness
    def good_mask(self, now: float) -> np.ndarray:
        return (
            self._valid
            & ~self._expired
            & (self._time_reply > 0)
            & (now - self._time_reply < NODE_GOOD_TIME)
        )

    def reachable_mask(self, now: float) -> np.ndarray:
        """Valid, non-expired nodes (good or dubious) — what lookups may
        contact (the reference inserts dubious nodes into searches too)."""
        return self._valid & ~self._expired

    def is_good(self, row: int, now: float) -> bool:
        return bool(self.good_mask(now)[row])

    # ------------------------------------------------------------- mutation
    def _touch(self, count_compaction: bool = True) -> None:
        """Structural change the churn view cannot absorb: drop both the
        base snapshot and the churn state (next view rebuilds).  A view
        carrying pending churn counts as a compaction — the rebuild it
        forces folds that churn into the next base.  ``count_compaction
        =False`` suppresses that increment for callers that already
        counted the same event (the replay-overflow path of
        :meth:`_maybe_swap`, which books its compaction before
        replaying — ADVICE r5 finding 2's double count)."""
        if count_compaction and self._churn is not None \
                and self._churn.pending:
            self.compactions += 1
            _M_COMPACTIONS.inc()
        self._version += 1
        self._snap = None
        self._churn = None
        self._pending_base = None        # dispatched from a stale state

    # -------------------------------------------- non-blocking compaction
    def _start_compaction(self) -> None:
        """Dispatch the next base build (full re-sort of the CURRENT
        host state) WITHOUT blocking: the device computes while the old
        snapshot + churn view keep serving every lookup exactly, and
        :meth:`_maybe_swap` installs the result once it is ready.
        Mutations that land between dispatch and swap are logged and
        replayed into the fresh view's churn state (host-side O(1)
        each), so no lookup ever waits behind the rebuild — the
        round-4 verdict's "overflow stalls a lookup" fix."""
        if self._pending_base is not None or self._snap is None:
            return
        m = self.reachable_mask(time.monotonic())
        sorted_ids, perm, n_valid = sort_table(
            jnp.asarray(self._ids), jnp.asarray(m))
        self._pending_base = {
            "sorted": sorted_ids, "perm": perm, "n_valid": n_valid,
            "mutlog": [],
        }

    def _maybe_swap(self, force: bool = False) -> bool:
        """Install a finished background compaction; with ``force`` wait
        for it.  Replays the post-dispatch mutation log into the new
        churn view so the swap is exact."""
        pb = self._pending_base
        if pb is None:
            return False
        nv = pb["n_valid"]
        if not force:
            ready = getattr(nv, "is_ready", None)
            if ready is not None and not ready():
                return False
        snap = Snapshot(pb["sorted"], pb["perm"], nv, self._version,
                        ("reachable", 0))
        self._snap = snap
        self._churn = ChurnView(snap, self._cap, self._delta_cap)
        self._pending_base = None
        self.compactions += 1
        _M_COMPACTIONS.inc()
        # flight recorder (ISSUE-4): churn swaps / compactions are
        # postmortem-grade events — when a lookup traces slow, the ring
        # shows whether a base swap landed mid-wave
        from .. import tracing
        _tr = tracing.get_tracer()
        if _tr.enabled:
            _tr.event("table_churn_swap", replayed=len(pb["mutlog"]),
                      compactions=self.compactions)
        for op, row in pb["mutlog"]:
            if op == "i":
                if not self._churn.note_insert(row, self._ids[row]):
                    # replay overflow (log larger than a fresh slab) —
                    # correctness over latency: full rebuild.  The swap
                    # was already counted above; without the flag the
                    # partially-replayed view's pending entries made
                    # _touch book the SAME event a second time
                    # (ADVICE r5 finding 2).
                    self._touch(count_compaction=False)
                    return True
            else:
                self._churn.note_evict(row)
        return True

    def _tomb_limit(self) -> int:
        ch = self._churn
        n = ch.n_base if ch is not None else 0
        return max(TOMB_MIN, stale_limit(n, MAX_STALE_SHARE))

    def _delta_growth_limit(self) -> int:
        """Overflow headroom: the delta may double up to 8× its
        configured capacity while a background compaction is pending."""
        return 8 * self._delta_cap

    def _absorb_insert(self, row: int) -> None:
        """A slab row became live.  Absorbed into the churn delta when a
        'reachable' base view is active (``_version`` untouched — the
        change is *in* the view); otherwise full invalidation.  A full
        delta no longer stalls anything: the slab doubles (bounded) and
        a background compaction starts, with the old view serving every
        lookup exactly until the new base is ready."""
        ch = self._churn
        if ch is not None and self._snap is not None:
            if self._pending_base is not None:
                self._pending_base["mutlog"].append(("i", row))
            if ch.note_insert(row, self._ids[row]):
                return
            if ch.delta_ids_np.shape[0] < self._delta_growth_limit():
                ch.grow_delta()
                self._start_compaction()
                if ch.note_insert(row, self._ids[row]):
                    return
        self._touch()                   # growth exhausted / no churn view

    def _absorb_evict(self, row: int) -> None:
        """A slab row left the live set (evicted or expired)."""
        ch = self._churn
        if ch is not None and self._snap is not None:
            if self._pending_base is not None:
                self._pending_base["mutlog"].append(("e", row))
            ch.note_evict(row)
            if ch.tomb_count > self._tomb_limit():
                # compaction due (perf policy) — built in the background
                self._start_compaction()
            return
        self._touch()

    def insert(self, node_id: InfoHash, addr: Any, now: Optional[float] = None,
               *, confirm: int = 0) -> Optional[int]:
        """Learn about a peer (↔ Dht::onNewNode/RoutingTable::onNewNode,
        src/routing_table.cpp:204-262).

        confirm: 0 = hearsay (from another node's reply blob),
                 1 = sent us a query, 2 = replied to us.
        Returns the row, or None if the bucket is full of live nodes (the
        peer is kept as the bucket's cached candidate instead).
        """
        if now is None:
            now = time.monotonic()
        key = bytes(node_id)
        if key == bytes(self.self_id):
            return None
        row = self._row_of.get(key)
        if row is not None:
            self._time_seen[row] = now
            if confirm >= 2:
                if self._expired[row]:
                    # revival: the row is dead in every view (its base
                    # copy, if any, was tombstoned when it expired) —
                    # re-enters as a delta insert
                    self._expired[row] = False
                    self._absorb_insert(row)
                elif self._time_reply[row] == 0:
                    # first reply: 'reachable' membership is unchanged
                    # (the row was already in that view), but a cached
                    # 'good'-mask snapshot goes stale
                    if self._snap is not None \
                            and self._snap.mask_key[0] == "good":
                        self._touch()
                self._time_reply[row] = now
                self._auth_err[row] = 0
            if addr is not None:
                self._addrs[row] = addr
            return row

        b = min(InfoHash.common_bits(self.self_id, node_id), radix.MAX_BUCKET)
        if self._bucket_count[b] >= self.k:
            # replace an expired node in this bucket if any
            rows = np.nonzero(self._valid & (self._bucket == b) & self._expired)[0]
            if len(rows) == 0:
                # bucket full of live nodes: keep as replacement candidate
                self._cached[b] = (key, addr)
                return None
            self._evict_row(int(rows[0]))

        if not self._free:
            self._grow()
        row = self._free.pop()
        self._ids[row] = IK.ids_from_bytes(key)
        self._valid[row] = True
        self._expired[row] = False
        self._auth_err[row] = 0
        self._time_seen[row] = now
        self._time_reply[row] = now if confirm >= 2 else 0.0
        self._bucket[row] = b
        self._addrs[row] = addr
        self._row_of[key] = row
        self._bucket_count[b] += 1
        self._absorb_insert(row)
        return row

    def _evict_row(self, row: int) -> None:
        key = self._ids[row:row + 1]
        kb = IK.ids_to_bytes(key).tobytes()
        self._row_of.pop(kb, None)
        self._bucket_count[self._bucket[row]] -= 1
        self._valid[row] = False
        self._addrs[row] = None
        self._free.append(row)
        self._absorb_evict(row)

    def remove(self, node_id: InfoHash) -> None:
        row = self._row_of.get(bytes(node_id))
        if row is not None:
            self._evict_row(row)
            # promote the bucket's cached candidate, if one is waiting
            b = min(InfoHash.common_bits(self.self_id, node_id), radix.MAX_BUCKET)
            cand = self._cached.pop(b, None)
            if cand is not None:
                self.insert(InfoHash(cand[0]), cand[1])

    def on_reply(self, node_id: InfoHash, now: Optional[float] = None) -> None:
        """Peer answered a request (↔ Node::received)."""
        self.insert(node_id, None, now, confirm=2)

    def on_expired(self, node_id: InfoHash) -> None:
        """Request to the peer timed out 3× (↔ Node::setExpired via
        NetworkEngine timeouts, src/request.h:108-112)."""
        row = self._row_of.get(bytes(node_id))
        if row is not None and not self._expired[row]:
            self._expired[row] = True
            self._absorb_evict(row)

    def on_auth_error(self, node_id: InfoHash) -> None:
        """Crypto failure from this peer; 3 strikes expire it (node.h:73-77)."""
        row = self._row_of.get(bytes(node_id))
        if row is not None:
            self._auth_err[row] += 1
            if self._auth_err[row] >= MAX_AUTH_ERRORS \
                    and not self._expired[row]:
                self._expired[row] = True
                self._absorb_evict(row)

    def clear_bad(self) -> None:
        """Drop expired nodes (↔ NodeCache::clearBadNodes on connectivity
        change, src/node_cache.cpp:76-85)."""
        for row in np.nonzero(self._valid & self._expired)[0]:
            self._evict_row(int(row))

    def bulk_load(self, ids_u32: np.ndarray, now: float = 0.0,
                  *, replied: bool = True, addrs=None,
                  buckets=None) -> None:
        """Fill the slab from an [N,5] uint32 id matrix (simulation-scale
        path: no per-row dict bookkeeping, buckets computed on device).
        ``addrs``: optional per-row address (sequence aligned to rows, or
        one address shared by all) so loaded rows are servable in
        closest-node replies (tests/test_live_node_scale.py).
        ``buckets``: optional precomputed ``common_bits(self, id)`` per
        row — callers loading many small tables (the converged-cluster
        seeder, testing/virtual_net.py) pass it to skip the per-call
        device dispatch of ``radix.bucket_of``.

        Ids already LIVE in the table and batch-internal duplicates are
        dropped: live ids must stay unique across base and delta
        (note_insert's precondition — a duplicate would otherwise appear
        twice in a top-k result through the churn merge).  Known ids
        that have EXPIRED are not dropped: with ``replied=True`` (the
        default) they revive exactly as ``insert(confirm=2)`` would —
        address, reply clock, auth strikes and all (``_row_of`` also
        holds expired rows, so the old skip left a re-seeded peer
        permanently dead — ADVICE r5 finding 3); with
        ``replied=False`` the re-sighting is hearsay and, as in
        ``insert(confirm=0)``, refreshes only ``time_seen`` and the
        address."""
        ids_u32 = np.asarray(ids_u32, dtype=np.uint32)
        raw = IK.ids_to_bytes(ids_u32)
        per_row_addrs = isinstance(addrs, (list, tuple, np.ndarray))
        seen: set = set()
        keep: list = []
        for i in range(ids_u32.shape[0]):
            kb = raw[i].tobytes()
            if kb in seen:
                continue
            row = self._row_of.get(kb)
            if row is not None:
                # known id: refresh it the way insert() would — clocks
                # and address — and revive it if expired
                self._time_seen[row] = now
                if addrs is not None:
                    self._addrs[row] = addrs[i] if per_row_addrs else addrs
                if replied:
                    if self._expired[row]:
                        # revival (↔ insert confirm=2): dead in every
                        # view, re-enters as a delta insert
                        self._expired[row] = False
                        self._absorb_insert(row)
                    elif self._time_reply[row] == 0 \
                            and self._snap is not None \
                            and self._snap.mask_key[0] == "good":
                        # first reply: a cached 'good'-mask snapshot
                        # goes stale (same rule as insert())
                        self._touch()
                    self._time_reply[row] = now
                    self._auth_err[row] = 0
                continue
            seen.add(kb)
            keep.append(i)
        if len(keep) != ids_u32.shape[0]:
            if per_row_addrs:
                addrs = [addrs[i] for i in keep]
            if buckets is not None:
                buckets = np.asarray(buckets)[keep]
            ids_u32 = ids_u32[keep]
            raw = raw[keep]
        n = ids_u32.shape[0]
        if n == 0:
            return
        while self._cap < len(self) + n:
            self._grow()
        rows = np.array([self._free.pop() for _ in range(n)], dtype=np.int64)
        self._ids[rows] = ids_u32
        self._valid[rows] = True
        self._expired[rows] = False
        self._auth_err[rows] = 0
        self._time_seen[rows] = now
        self._time_reply[rows] = now if replied else 0.0
        if buckets is not None:
            b = np.minimum(np.asarray(buckets), radix.MAX_BUCKET)
        else:
            b = np.asarray(radix.bucket_of(jnp.asarray(self.self_limbs),
                                           jnp.asarray(ids_u32)))
        self._bucket[rows] = b.astype(np.int16)
        np.add.at(self._bucket_count, b, 1)
        for i, row in enumerate(rows):
            self._row_of[raw[i].tobytes()] = int(row)
            if addrs is not None:
                self._addrs[int(row)] = addrs[i] if per_row_addrs else addrs
        if self._churn is not None and self._snap is not None \
                and self._churn.n_delta + n <= self.delta_capacity:
            # through _absorb_insert, NOT note_insert directly: a
            # pending background compaction must see these rows in its
            # mutation log or they would vanish from the serving view
            # at swap (found by review; pinned in test_table_churn.py)
            for row in rows:
                self._absorb_insert(int(row))
        else:
            self._touch()

    # --------------------------------------------------------------- reads
    def get_view(self, row: int) -> NodeView:
        return NodeView(
            row=row,
            id=InfoHash(IK.ids_to_bytes(self._ids[row]).tobytes()),
            addr=self._addrs[row],
            time_reply=float(self._time_reply[row]),
            time_seen=float(self._time_seen[row]),
            expired=bool(self._expired[row]),
        )

    def row_of(self, node_id: InfoHash) -> Optional[int]:
        return self._row_of.get(bytes(node_id))

    def addr_of(self, row: int):
        return self._addrs[row]

    def id_of(self, row: int) -> InfoHash:
        return InfoHash(IK.ids_to_bytes(self._ids[row]).tobytes())

    def ids_of_rows(self, rows: np.ndarray) -> list:
        """Vectorized :meth:`id_of` over an int array (-1 → None): ONE
        ids_to_bytes pass instead of a numpy round-trip per row, which
        made materializing a 4096×8 batched-resolve result take a
        minute of host time."""
        rows = np.asarray(rows).reshape(-1)
        raw = IK.ids_to_bytes(self._ids[np.clip(rows, 0, None)])
        return [InfoHash(raw[i].tobytes()) if r >= 0 else None
                for i, r in enumerate(rows)]

    @property
    def delta_capacity(self) -> int:
        return self._delta_cap

    @property
    def churn_pending(self) -> int:
        """Mutations absorbed by the churn view since the last base
        build (tombstones + delta inserts).  0 ⇒ the base snapshot is
        complete."""
        return self._churn.pending if self._churn is not None else 0

    def snapshot(self, now: Optional[float] = None, *,
                 mask: str = "reachable") -> Snapshot:
        """Full device snapshot for batched queries.  mask: 'reachable'
        (valid & not expired), 'good', or 'valid'.  Cached until the
        table mutates (liveness masks additionally keyed by a 10 s time
        bucket).  Pending churn (delta inserts / tombstones) forces a
        rebuild here — this is the compaction point; lookups that can
        use the incremental view go through :meth:`view` instead."""
        if now is None:
            now = time.monotonic()
        if mask == "reachable":
            self._maybe_swap(force=True)
        tkey = int(now // 10) if mask == "good" else 0
        mk = (mask, tkey)
        if self._snap is not None and self._snap.version == self._version \
                and self._snap.mask_key == mk and self.churn_pending == 0:
            return self._snap
        if mask == "good":
            m = self.good_mask(now)
        elif mask == "valid":
            m = self._valid
        else:
            m = self.reachable_mask(now)
        # count a *compaction* only when this rebuild folds pending
        # churn (delta inserts / tombstones) back into the base — plain
        # first builds and mask-flavor rebuilds are not compactions
        if self.churn_pending > 0:
            self.compactions += 1
            _M_COMPACTIONS.inc()
        sorted_ids, perm, n_valid = sort_table(
            jnp.asarray(self._ids), jnp.asarray(m)
        )
        self._snap = Snapshot(sorted_ids, perm, n_valid, self._version, mk)
        # churn absorption only tracks the 'reachable' mask — the one
        # every routing lookup uses.  'good'/'valid' snapshots rebuild
        # on mutation as before.
        self._churn = ChurnView(self._snap, self._cap, self._delta_cap) \
            if mask == "reachable" else None
        return self._snap

    def view(self, now: Optional[float] = None, *, mask: str = "reachable"):
        """Lookup view: the O(1)-mutation churn view while deltas or
        tombstones are pending, else the plain snapshot.  Both expose
        ``lookup(queries, k=, window=)`` with identical (exact)
        results; the churn view skips the full re-sort + re-expand a
        mutation would otherwise cost (SURVEY §7 incremental updates)."""
        if mask == "reachable":
            self._maybe_swap()           # install a finished compaction
        ch = self._churn
        if ch is not None and self._snap is not None and ch.pending \
                and self._snap.mask_key == (mask, 0):
            return ch
        return self.snapshot(now, mask=mask)

    def find_closest(self, targets, *, k: int = TARGET_NODES,
                     now: Optional[float] = None, mask: str = "reachable",
                     window: int = 128, mesh=None, layout=None):
        """k closest known peers for each target id
        (↔ RoutingTable::findClosestNodes, src/routing_table.cpp:109-150 —
        but batched over Q targets in one device call).

        targets: [Q,5] uint32, [Q,20] uint8, bytes, or list of InfoHash.
        Returns (rows [Q,k] int32, dist [Q,k,5] uint32) numpy, -1 padded.

        Small tables × small batches (the live protocol regime) take an
        exact host scan over the slab — no snapshot, no device call, no
        compile; results are bit-identical to the device path (live ids
        are unique, so XOR distances never tie and the order is fully
        determined).  Large tables or big query waves go through
        :meth:`view` (device snapshot / churn kernels); a ``mesh``
        (``config.resolve_mesh_t``) row-shards the snapshot resolve
        over its ``t`` axis (:meth:`Snapshot.lookup`) — the churn view
        and the host scan ignore it (identical results either way).
        A reshard ``layout`` (ISSUE-17) moves the sharded split to
        traffic-weighted boundaries — same results, rebalanced load.
        """
        return self.find_closest_launch(targets, k=k, now=now, mask=mask,
                                        window=window, mesh=mesh,
                                        layout=layout).consume()

    def find_closest_launch(self, targets, *, k: int = TARGET_NODES,
                            now: Optional[float] = None,
                            mask: str = "reachable", window: int = 128,
                            mesh=None, layout=None) -> PendingLookup:
        """Async form of :meth:`find_closest` (round-20 wave pipeline):
        returns a :class:`PendingLookup` whose device kernel is already
        in flight; ``consume()`` blocks and maps rows.  The host-scan
        fast path returns an already-resolved handle (``ready()`` is
        immediately True — the live-protocol regime never defers)."""
        q = _as_limbs(targets)
        q = q.reshape(-1, IK.N_LIMBS)
        # truth flag for the spans/counters upstream: whether THIS
        # resolve actually ran the t-sharded kernel (the host scan and
        # the churn view ignore mesh) — read by
        # Dht.find_closest_nodes_launch right after the call, same
        # thread (the DHT loop is single-threaded)
        self.last_resolve_sharded = False
        if len(self) <= HOST_SCAN_MAX_ROWS \
                and q.shape[0] <= HOST_SCAN_MAX_QUERIES:
            return PendingLookup.resolved(
                *self._find_closest_host(q, k, now, mask))
        view = self.view(now, mask=mask)
        if mesh is not None and mesh.shape.get("t", 1) > 1 \
                and isinstance(view, Snapshot):
            self.last_resolve_sharded = True
            return view.lookup_launch(q, k=k, window=window, mesh=mesh,
                                      layout=layout)
        return view.lookup_launch(q, k=k, window=window)

    def _find_closest_host(self, q: np.ndarray, k: int,
                           now: Optional[float], mask: str):
        """Exact numpy top-k over the live slab rows (host fast path)."""
        if now is None:
            now = time.monotonic()
        if mask == "good":
            m = self.good_mask(now)
        elif mask == "valid":
            m = self._valid
        else:
            m = self.reachable_mask(now)
        rows = np.nonzero(m)[0]
        Qn = q.shape[0]
        out_rows = np.full((Qn, k), -1, dtype=np.int32)
        out_dist = np.full((Qn, k, IK.N_LIMBS), 0xFFFFFFFF, dtype=np.uint32)
        if len(rows):
            d = self._ids[rows][None, :, :] ^ q[:, None, :]    # [Q, n, 5]
            for i in range(Qn):
                # lexicographic 160-bit ordering: np.lexsort's LAST key
                # is primary (limb 0), matching InfoHash::xorCmp
                order = np.lexsort(
                    (d[i, :, 4], d[i, :, 3], d[i, :, 2],
                     d[i, :, 1], d[i, :, 0]))[:k]
                out_rows[i, :len(order)] = rows[order]
                out_dist[i, :len(order)] = d[i, order]
        return out_rows, out_dist

    # --------------------------------------------------------- maintenance
    def bucket_occupancy(self) -> np.ndarray:
        return self._bucket_count.copy()

    def stale_buckets(self, now: float, age: float = NODE_EXPIRE_TIME) -> np.ndarray:
        """Occupied buckets with no *reply* within `age` seconds — incl.
        never-replied buckets, which the reference marks stale from birth
        (Bucket::time = time_point::min(); bucketMaintenance's 10-min
        rule, src/dht.cpp:1780-1838, src/routing_table.cpp:210-211).
        Computed by the device compare-and-reduce (ops/radix.py
        bucket_last_seen, which owns the never-replied semantics — the
        host ``np.maximum.at`` duplicate this replaced diverged from it)."""
        last = np.asarray(radix.bucket_last_seen(
            jnp.asarray(self.self_limbs), jnp.asarray(self._ids),
            jnp.asarray(self._valid), jnp.asarray(self._time_reply)))
        occupied = self._bucket_count > 0
        return np.nonzero(occupied & (last < now - age))[0]

    def _next_maint_key(self):
        """Thread the table's reusable maintenance PRNG key (minted once
        at construction; split per use — no fresh PRNGKey per tick)."""
        if self._maint_key is None:
            self._maint_key = jax.random.PRNGKey(
                int.from_bytes(os.urandom(4), "big"))
        self._maint_key, sub = jax.random.split(self._maint_key)
        return sub

    def maintenance_sweep(self, now: float, age: float = NODE_EXPIRE_TIME,
                          key=None):
        """ONE fused device pass over the slab: occupancy, per-bucket
        last-reply staleness (never-replied ⇒ stale from birth), and a
        refresh target inside every stale bucket
        (↔ Dht::bucketMaintenance, src/dht.cpp:1780-1838 +
        RoutingTable::randomId) — replaces the stale_buckets +
        refresh_targets pair with a single launch.

        Returns ``(stale, targets)``: stale bucket indices [B] int64 and
        their refresh ids [B, 5] uint32."""
        counts, _last, stale, targets = radix.maintenance_sweep(
            jnp.asarray(self.self_limbs), jnp.asarray(self._ids),
            jnp.asarray(self._valid), jnp.asarray(self._time_reply),
            now, age, key if key is not None else self._next_maint_key())
        stale = np.nonzero(np.asarray(stale))[0]
        return stale, np.asarray(targets)[stale]

    def refresh_targets(self, buckets, key=None) -> np.ndarray:
        """Random lookup target inside each given bucket (↔
        RoutingTable::randomId, src/routing_table.cpp:67-85).  → [B,5].
        With ``key=None`` the table's reusable maintenance key is
        threaded (split per call) instead of minting a fresh PRNGKey."""
        out = radix.random_id_in_bucket(
            jnp.asarray(self.self_limbs), jnp.asarray(np.asarray(buckets)),
            key if key is not None else self._next_maint_key()
        )
        return np.asarray(out)

    def network_size_estimate(self) -> int:
        return int(radix.estimate_network_size(
            jnp.asarray(self.self_limbs), jnp.asarray(self._ids),
            jnp.asarray(self._valid), k=self.k,
        ))

    def export_nodes(self, now: Optional[float] = None) -> list:
        """Good nodes for persistence/bootstrap (↔ Dht::exportNodes,
        src/dht.cpp:2029-2059)."""
        if now is None:
            now = time.monotonic()
        rows = np.nonzero(self.good_mask(now))[0]
        return [(self.id_of(int(r)), self._addrs[int(r)]) for r in rows]


def _as_limbs(targets) -> np.ndarray:
    if isinstance(targets, (bytes, bytearray)):
        return IK.ids_from_bytes(targets)
    if isinstance(targets, (list, tuple)):
        return IK.ids_from_hashes(targets)
    arr = np.asarray(targets)
    if arr.dtype == np.uint8:
        return IK.ids_from_bytes(arr)
    return arr.astype(np.uint32)
