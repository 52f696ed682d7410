"""The mutable table ROW-SHARDED over a mesh: a table that outgrows one
chip departs, joins and compacts on the devices, shard by shard.

``core.table.DeviceChurnTable`` is one ``ops.churn_table.ChurnTable`` on
one device; ``partition.TableState`` is a table sharded over ``t`` that
is built once.  :class:`ShardedChurnTable` is both: ONE ``ChurnTable`` A
SHARD — each shard its own sorted base, liveness words, ``dead_pos``,
delta slab and delta LUT, the leaves stacked along the shard axis — and
beside them the layout the sharded lookup reads (``shard_rows``, the
replicated block LUT, the row count), searched through
``tp_simulate_lookups(mesh, state=table.view)``.

OWNERSHIP IS BY FIXED KEY RANGE: shard ``d`` owns the ids whose top
bits fall in the ``d``-th of ``t`` equal ranges of the key space
(``global_sort.dest_shard``, the build's splitters), whatever the rows
it holds at the moment.  So no row ever crosses a shard: a departure is
found on its owner, an arrival lands in its owner's delta, a compaction
is local; what moves is each shard's row COUNT, which the weighted
layout carries as data.  Sound for ids that are uniform hashes (a
shard's count random-walks by the square root of its turnover); a
clustered id set would need boundaries that move, which this does not
bring.

The device programs are ``ops/churn_table.py``'s own, run per shard
under ``shard_map``:

- a TICK (stages ``table_route`` + ``table_apply``): the batch arrives
  as global arrays, not routed.  Every shard takes the ids it owns into
  a buffer of FIXED width (mean + six deviations of a uniform batch:
  :func:`routed_rows`), padding behind them, and runs ``_apply`` on it
  with the counts of real rows.  A batch that piles more on one shard
  than the width holds is not truncated: the first pass sees it, leaves
  every shard untouched, and the host applies the batch a width at a
  time — the departures, then the arrivals, by the same executable.
- a COMPACTION (stages ``table_compact`` + ``table_relayout``): every
  shard merges its live rows (``_compact``), then the shards learn
  their new ``(base, width)`` from one ``all_gather`` of the widths and
  the replicated block LUT is the ``psum`` of the shards' own LUTs.
  One program; the lookup's executable reads all of it as data, so
  nothing recompiles.

Node identity, as the lookup engine sees it: a base row is its GLOBAL
row (``shard_rows`` base + local row); a delta row is ``t ·
capacity`` + its place in the global order of the shards' deltas
(shard 0's rows, then shard 1's...), which is the one-device table's
``capacity + slot`` with another capacity.
"""

from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..core.table import MAX_STALE_SHARE, DeviceChurnTable
from ..ops.churn_table import (ChurnTable, _apply, _compact, _running_sum,
                               churn_table, stale_limit, tomb_words)
from ..ops.ids import N_LIMBS
from ..ops.sorted_table import build_prefix_lut, default_lut_bits
from ..telemetry import device_stage
from .global_sort import SEGMENT_ALIGN, dest_shard
from .partition import TableState
from .sharded import shard_offset

_U32 = jnp.uint32
_ONES = 0xFFFFFFFF

#: how each leaf of the stacked table lies over the mesh
SHARD_SPECS = ChurnTable(
    base=P("t", None), n_base=P("t"), lut=P("t", None), lut_ok=P("t"),
    tomb_bits=P("t"), dead_pos=P("t"), n_tomb=P("t"), delta=P("t", None),
    delta_pos=P("t"), n_delta=P("t"), delta_lut=P("t", None))
#: the leaves that are one value (or one LUT) a shard: stacked they gain
#: a leading shard axis, which a shard sees as length 1
_PER_SHARD = ("n_base", "lut", "lut_ok", "n_tomb", "n_delta", "delta_lut")
#: a tick's counts, one row a shard: departed base rows, departed delta
#: rows, arrivals merged, and the departures and arrivals of the WHOLE
#: batch that the shard owns
_LEFT_BASE, _LEFT_DELTA, _JOINED, _OWN_LEAVE, _OWN_JOIN = range(5)


def one_shard(stacked: ChurnTable) -> ChurnTable:
    """A shard's own ``ChurnTable`` out of its piece of the stack."""
    return stacked._replace(**{f: getattr(stacked, f)[0] for f in _PER_SHARD})


def _stack(tbl: ChurnTable) -> ChurnTable:
    return tbl._replace(**{f: getattr(tbl, f)[None] for f in _PER_SHARD})


def routed_rows(batch_rows: int, n_t: int) -> int:
    """The fixed width at which a shard is handed its part of a batch of
    ``batch_rows`` uniform ids: the mean ``batch_rows / t`` plus six
    standard deviations and one alignment unit, in whole lanes
    (``global_sort.default_segment_rows``' rule), never more than the
    batch.  A batch that puts more on one shard takes more passes
    (:meth:`ShardedChurnTable.apply`), it is never cut."""
    mean = batch_rows / n_t
    rows = math.ceil(mean + 6.0 * math.sqrt(mean)) + SEGMENT_ALIGN
    return min(batch_rows, -(-rows // SEGMENT_ALIGN) * SEGMENT_ALIGN)


def _layout(tbl: ChurnTable, n_t: int, block_bits: int):
    """Stage ``table_relayout``: what the sharded lookup reads besides
    the shards themselves — every shard's ``(base, width)`` in the
    global order, the replicated block LUT (the sum of the shards'
    prefix LUTs: entry p of a shard's is its count of rows with prefix
    < p, and the ranges partition the rows) and the row count.  ONE
    ``psum``, the shards' widths riding behind the LUT: two collectives
    are merged by the compiler into one that carries neither's name,
    and a trace then charges the stage nothing (PERF.md section 6,
    PR 34)."""
    bits = (tbl.lut.shape[0] - 1).bit_length() - 1
    part = (tbl.lut if bits == block_bits else
            build_prefix_lut(tbl.base, tbl.n_base, bits=block_bits))
    width = jnp.zeros((n_t,), jnp.int32).at[lax.axis_index("t")].set(
        tbl.n_base)
    summed = lax.psum(jnp.concatenate([part, width]), "t")
    base, n_valid = shard_offset(summed[-n_t:], n_t)
    return (jnp.stack([base, tbl.n_base])[None].astype(jnp.int32),
            summed[:-n_t], n_valid.astype(jnp.int32))


_LAYOUT_SPECS = (P("t", None), P(), P())


@functools.lru_cache(maxsize=8)
def _build_from_state(mesh: Mesh, capacity: int, delta_capacity: int,
                      stale_rows: int, lut_bits: int, block_bits: int):
    n_t = mesh.shape["t"]

    def local(sorted_shard, shard_rows):
        width = shard_rows[0, 1]
        tbl = churn_table(sorted_shard, width, capacity=capacity,
                          delta_capacity=delta_capacity,
                          stale_rows=stale_rows, lut_bits=lut_bits)
        # ownership by key range: in a sorted shard, the first and the
        # last row say it for all
        ends = jnp.stack([sorted_shard[0, 0],
                          sorted_shard[jnp.maximum(width - 1, 0), 0]])
        owned = (width == 0) | jnp.all(
            dest_shard(ends, n_t) == lax.axis_index("t"))
        return (_stack(tbl), *_layout(tbl, n_t, block_bits), owned[None])

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("t", None), P("t", None)),
        out_specs=(SHARD_SPECS, *_LAYOUT_SPECS, P("t")), check_vma=False))


@functools.lru_cache(maxsize=8)
def _build_compact(mesh: Mesh, block_bits: int):
    n_t = mesh.shape["t"]

    def local(stacked):
        tbl = device_stage("table_compact")(_compact)(one_shard(stacked))
        return (_stack(tbl), *device_stage("table_relayout")(
            functools.partial(_layout, n_t=n_t, block_bits=block_bits))(tbl))

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(SHARD_SPECS,),
        out_specs=(SHARD_SPECS, *_LAYOUT_SPECS), check_vma=False),
        donate_argnums=(0,))


@functools.lru_cache(maxsize=8)
def _build_apply(mesh: Mesh, leave_width: int, join_width: int):
    n_t = mesh.shape["t"]

    def take_owned(ids, start, width: int, pad: int):
        """This shard's ids of a batch, ``width`` of them from its
        ``start``-th on, in the batch's order, ``pad`` rows behind
        them; how many of them are ids; how many the shard owns in
        all."""
        n = ids.shape[0]
        if not n:
            zero = jnp.int32(0)
            return jnp.zeros((0, N_LIMBS), _U32), zero, zero
        mine = dest_shard(ids[:, 0], n_t) == lax.axis_index("t")
        rank = _running_sum(mine) - 1
        owned = rank[-1] + 1
        take = mine & (rank >= start) & (rank < start + width)
        src = jnp.full((width,), n, jnp.int32).at[
            jnp.where(take, rank - start, width)].set(
            jnp.arange(n, dtype=jnp.int32), mode="drop")
        rows = jnp.where((src < n)[:, None],
                         jnp.take(ids, jnp.minimum(src, n - 1), axis=0),
                         _U32(pad))
        return rows, jnp.clip(owned - start, 0, width), owned

    def local(stacked, leave_ids, join_ids, start, all_or_nothing):
        tbl = one_shard(stacked)

        @device_stage("table_route")
        def route(leave_ids, join_ids, start, all_or_nothing):
            leave, n_leave, own_leave = take_owned(
                leave_ids, start[0], leave_width, 0)
            join, n_join, own_join = take_owned(
                join_ids, start[1], join_width, _ONES)
            # a first pass is all or nothing: where one shard's part of
            # the batch does not fit the width, every shard does a tick
            # of no rows, and the host comes again a width at a time
            over = (own_leave > leave_width) | (own_join > join_width)
            go = ~all_or_nothing | (lax.psum(over.astype(jnp.int32), "t")
                                    == 0)
            return (leave, jnp.where(go, join, _U32(_ONES)),
                    jnp.where(go, n_leave, 0), jnp.where(go, n_join, 0),
                    own_leave, own_join)

        leave, join, n_leave, n_join, own_leave, own_join = route(
            leave_ids, join_ids, start, all_or_nothing)
        tbl, left = device_stage("table_apply")(_apply)(
            tbl, leave, join, (n_leave, n_join))
        return _stack(tbl), jnp.stack(
            [left[0], left[1], n_join, own_leave, own_join])[None]

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(SHARD_SPECS, P(), P(), P(), P()),
        out_specs=(SHARD_SPECS, P("t", None)), check_vma=False),
        donate_argnums=(0,))


class ShardedChurnTable(DeviceChurnTable):
    """``DeviceChurnTable`` over a table row-sharded over ``mesh``'s
    ``t`` axis (module docstring): the same ``apply(leave_ids,
    join_ids)``, ``compact()``, ``view``, ``n_live``, ``compactions``,
    the same rule for when it compacts — held per shard: the first
    shard whose departed share or delta would pass its limit makes all
    compact, in one program — and the same spans, counters and gauges.

    Built over the :class:`~opendht_tpu.parallel.partition.TableState`
    of ``sharded_global_sort`` (whose range partition IS the ownership
    rule; a state cut anywhere else is refused), which it replaces: the
    rows move into shards of ``state.shard_n + delta_capacity`` rows of
    capacity, ``delta_capacity`` the delta slab of EACH shard.

    ``view`` is a ``TableState`` with the table's churn leaves beside
    the frozen ones; what ``tp_simulate_lookups`` does with it it reads
    off it.  Like the one-device table's, it is the table as it is NOW:
    a tick and a compaction consume the arrays of the one before.
    """

    def __init__(self, mesh: Mesh, state: TableState, *,
                 delta_capacity: int):
        if "shard_rows" not in state.arrays:
            raise ValueError("a table cut by key range is asked for: build "
                             "the state with sharded_global_sort")
        self.mesh = mesh
        capacity = 32 * tomb_words(state.shard_n + delta_capacity)
        self._capacity, self._delta_capacity = capacity, delta_capacity
        self._lut_bits = default_lut_bits(state.shard_n)
        self._block_bits = state.block_bits
        a = state.arrays
        *built, owned = _build_from_state(
            mesh, capacity, delta_capacity,
            stale_limit(capacity, MAX_STALE_SHARE), self._lut_bits,
            self._block_bits)(a["sorted_ids"], a["shard_rows"])
        if not np.asarray(owned).all():
            raise ValueError(
                "a shard holds ids of another shard's key range "
                f"{np.asarray(owned).tolist()}: the table must be cut where "
                "global_sort.dest_shard cuts (sharded_global_sort does)")
        self._place(*built)
        self.compactions = 0
        self._rebased(np.asarray(self._table.n_base))

    def _place(self, table, shard_rows, block_lut, n_valid) -> None:
        self._table: ChurnTable = table
        self._layout = {"shard_rows": shard_rows, "block_lut": block_lut,
                        "n_valid": n_valid}

    @property
    def view(self) -> TableState:
        t = self._table
        return TableState(
            arrays={"sorted_ids": t.base, "local_lut": t.lut, **self._layout,
                    "tomb_bits": t.tomb_bits, "delta": t.delta,
                    "n_delta": t.n_delta, "delta_lut": t.delta_lut},
            shard_n=self._capacity, lut_bits=self._lut_bits,
            block_bits=self._block_bits)

    @property
    def table(self) -> ChurnTable:
        """The shards' ``ChurnTable`` leaves, stacked along the shard
        axis (what a membership check reads)."""
        return self._table

    def _widths(self, leave_ids, join_ids):
        n_t = self.mesh.shape["t"]
        return (routed_rows(leave_ids.shape[0], n_t),
                routed_rows(join_ids.shape[0], n_t))

    def _run_tick(self, leave_ids, join_ids, start=(0, 0),
                  all_or_nothing=True):
        fn = _build_apply(self.mesh, *self._widths(leave_ids, join_ids))
        self._table, counts = fn(
            self._table, leave_ids, join_ids,
            np.asarray(start, np.int32), np.bool_(all_or_nothing))
        self._counts = np.asarray(counts)
        return (self._counts[:, _LEFT_BASE], self._counts[:, _LEFT_DELTA],
                self._counts[:, _JOINED])

    def _run_compact(self):
        self._place(*_build_compact(self.mesh, self._block_bits)(self._table))
        return np.asarray(self._table.n_base)

    def apply(self, leave_ids, join_ids) -> None:
        """One tick: ``leave_ids`` [E,5] depart, then ``join_ids`` [J,5]
        arrive — GLOBAL device arrays, replicated or sharded however
        they lie, not routed: finding each id's owner is the program's
        work, inside ``dht_table_apply_seconds``.  One launch where the
        batch spreads as uniform ids do; a batch that piles on one
        shard is applied a width at a time (more launches of the same
        executable, the table compacting between them if it must), so a
        row is never dropped."""
        E, J = leave_ids.shape[0], join_ids.shape[0]
        wl, wj = self._widths(leave_ids, join_ids)
        self._make_room(wl, wj)
        self._tick(leave_ids, join_ids)
        own_l = int(self._counts[:, _OWN_LEAVE].max())
        own_j = int(self._counts[:, _OWN_JOIN].max())
        if own_l <= wl and own_j <= wj:
            return
        # the first pass did nothing.  The departures, then the
        # arrivals: a pass that starts past a batch's end takes none of it
        for start in [(s, J) for s in range(0, own_l, max(wl, 1))] \
                + [(E, s) for s in range(0, own_j, max(wj, 1))]:
            self._make_room(wl, wj)
            self._tick(leave_ids, join_ids, start=start,
                       all_or_nothing=False)
