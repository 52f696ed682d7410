"""A sorted id table that changes, resident on the device.

``ops/sorted_table.py`` serves a table that is built once; the served
node's :class:`~opendht_tpu.core.table.ChurnView` absorbs mutations on
the host, one Python call each.  This module is the batched form for the
lookup simulator (core/search.py): a :class:`ChurnTable` is a pytree of
device arrays — a sorted *base*, one liveness bit a row, and a sorted
*delta* slab of the rows that joined since the base was built — and two
device programs that take it to its next state, with no host work per
mutation and no host read of anything table-sized.  Each CONSUMES the
table it is given (its buffers are donated: the base passes through a
tick without a copy, and liveness words, lists and LUTs are rewritten
where they lie), so a caller keeps the table that comes back and
nothing of the one it handed over:

- :func:`churn_apply` — one tick: a batch of departures, found by id and
  marked (base rows in the liveness words, delta rows in their own
  plane), and a batch of arrivals, sorted and merged into the delta;
- :func:`churn_compact` — live base rows and live delta rows merged
  into a new sorted base with its prefix LUT, departed rows dropped.
  Bit for bit the table ``sort_table`` + ``build_prefix_lut`` build
  from the live ids (tests/test_churn_sim.py).

Node identity, as the lookup engine sees it: base row ``p`` is node
``p``; delta slot ``j`` is node ``capacity + j`` (as the churn view
encodes a delta position past the base, ``churn_lookup_topk``).  One
array of liveness words covers both ranges, in the layout
:func:`~opendht_tpu.ops.sorted_table.unpack_tomb_bits` reads: bit ``b``
of word ``w`` is node ``32·w + b``, set = departed.  ``capacity`` is a
multiple of 32, so the delta's words start on a word boundary.

A departed row stays where it is until the next compaction — peers'
buckets lag, so it may still be named in a reply (core/search.py,
CHURN) — and is dropped by it.  Ids are unique among the live nodes
(the caller's contract, as for ``NodeTable``); an id may leave and join
again: the departed base row and the new delta row then share it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..telemetry import device_stage
from .ids import N_LIMBS
from .sorted_table import (_lower_bound, _lut_bits, build_prefix_lut,
                           lut_budget_steps, unpack_tomb_bits)

_U32 = jnp.uint32
_ONES = 0xFFFFFFFF

#: prefix width of the delta's positioning LUT: 2^16 buckets hold eight
#: rows each of a full 2^19-row delta, and the LUT follows a tick by one
#: E-element histogram (never a pass over the delta)
DELTA_LUT_BITS = 16
#: delta rows a lookup is shown around its target (``delta_window``):
#: the reply of k nodes a close peer gives of its new neighbours
DELTA_WINDOW = 8


class ChurnTable(NamedTuple):
    """The mutable table as device arrays (a pytree: jit arguments)."""

    base: jax.Array         # u32 [C, 5] sorted; rows past n_base all-ones
    n_base: jax.Array       # i32 rows of the base, departed ones included
    lut: jax.Array          # i32 [2^bits + 1] prefix LUT of the base
    lut_ok: jax.Array       # bool: every LUT bucket fits the search budget
    tomb_bits: jax.Array    # u32 [(C + D) / 32] liveness words, set = gone
    dead_pos: jax.Array     # i32 [T] base rows departed, as they came
    n_tomb: jax.Array       # i32 departed base rows (entries of dead_pos)
    delta: jax.Array        # u32 [D, 5] sorted; rows past n_delta all-ones
    delta_pos: jax.Array    # i32 [D] base rows below this id; -1-that = gone
    n_delta: jax.Array      # i32 rows of the delta, departed ones included
    delta_lut: jax.Array    # i32 [2^DELTA_LUT_BITS + 1] prefix LUT of it

    @property
    def capacity(self) -> int:
        return self.base.shape[0]

    @property
    def delta_capacity(self) -> int:
        return self.delta.shape[0]


def stale_limit(n_rows: int, share: float) -> int:
    """The departed rows a base of ``n_rows`` may hold before it must be
    compacted — the ONE spelling of the rule, for the served node's
    churn view and for :class:`ChurnTable` (core/table.py
    ``MAX_STALE_SHARE``: one row in ``TOMB_FRAC``)."""
    return int(n_rows * share)


def tomb_words(n_rows: int) -> int:
    """Liveness words that cover ``n_rows`` positions."""
    return (n_rows + 31) // 32


def node_gone(tomb_bits, nodes):
    """The liveness bits of ``nodes`` (any shape; in range): True =
    departed.  One gather of words — the read the engine's stage
    ``expire`` makes of its chosen peers (core/search.py)."""
    return ((jnp.take(tomb_bits, nodes >> 5)
             >> (nodes & 31).astype(_U32)) & 1) != 0


def _lut_fits(lut, n_rows: int):
    """Every bucket of ``lut`` within what ``_lower_bound``'s bounded
    in-bucket search covers at ``lut_steps=None`` (the guard of
    core/search.py ``_guarded_lower_bound``, taken when the LUT is made
    and not on every use)."""
    steps = lut_budget_steps(n_rows, _lut_bits(lut))
    return jnp.max(lut[1:] - lut[:-1]) <= jnp.int32(1 << min(steps - 1, 30))


def _find(sorted_ids, n_valid, lut, lut_ok, queries):
    """Exact 160-bit lower bound of ``queries`` [M,5] — LUT-started where
    the LUT's buckets fit the budget, the full-depth search where not —
    and whether the row there IS the query."""
    pos = lax.cond(
        lut_ok,
        lambda q: _lower_bound(sorted_ids, q, n_valid, lut=lut,
                               lut_steps=None),
        lambda q: _lower_bound(sorted_ids, q, n_valid), queries)
    at = jnp.take(sorted_ids, jnp.clip(pos, 0, sorted_ids.shape[0] - 1),
                  axis=0)
    return pos, (pos < n_valid) & jnp.all(at == queries, axis=1)


def _sorted_rows(ids):
    """``ids`` [J,5] in ascending 160-bit order: five STABLE sorts of
    (one limb in the order so far, the order), least significant limb
    first, and one gather — the build's way
    (parallel/global_sort.py): one sort of five keys compiles for
    minutes on the TPU, this loop body in seconds."""
    planes = ids.T

    def by_limb(p, perm):
        limb = lax.dynamic_index_in_dim(planes, N_LIMBS - 1 - p, 0,
                                        keepdims=False)
        return lax.sort((jnp.take(limb, perm), perm), dimension=0,
                        num_keys=1, is_stable=True)[1]

    perm = lax.fori_loop(0, N_LIMBS, by_limb,
                         jnp.arange(ids.shape[0], dtype=jnp.int32))
    return jnp.take(ids, perm, axis=0)


def _running_sum(x):
    """Inclusive running sum of ``x`` [n] as i32, tiled by hand the way
    the TPU compiler tiles ``jnp.cumsum``: rows of 128, a windowed sum
    along each row, the rows' totals summed the same way one level up.
    ``jnp.cumsum`` reaches the compiler as a shared ``reduce_window_sum``
    function whose operations carry no name of their caller, and the
    compiler's own tiling then drops what name there is: in a trace the
    sums fell under no stage (PERF.md §6, PR 32).  Written out, every
    ``reduce-window`` keeps ``jit(stage_<name>)`` in its ``op_name`` and
    compiles to the same shapes."""
    x = x.astype(jnp.int32)
    n = x.shape[0]
    rows = -(-n // 128)
    tiles = jnp.pad(x, (0, rows * 128 - n)).reshape(rows, 128)
    inner = lax.reduce_window(tiles, jnp.int32(0), lax.add, (1, 128), (1, 1),
                              ((0, 0), (127, 0)))
    if rows > 1:
        total = inner[:, -1]
        inner = inner + (_running_sum(total) - total)[:, None]
    return inner.reshape(-1)[:n]


def _first_of_each(pos, ok, sentinel: int):
    """``pos`` where ``ok`` else ``sentinel``, ascending, every repeat of
    a position after its first turned into ``sentinel`` too (an id named
    twice in one batch leaves once) — so the real ones lie first."""
    p = jnp.sort(jnp.where(ok, pos, sentinel))
    repeat = jnp.concatenate([jnp.zeros((1,), bool), p[1:] == p[:-1]])
    return jnp.sort(jnp.where(repeat, sentinel, p))


@functools.partial(jax.jit, static_argnames=(
    "capacity", "delta_capacity", "stale_rows", "lut_bits"))
def churn_table(sorted_ids, n_valid, *, capacity: int, delta_capacity: int,
                stale_rows: int, lut_bits: int) -> ChurnTable:
    """A :class:`ChurnTable` over a sorted table (``sort_table``'s
    result): ``capacity`` ≥ its rows and a multiple of 32,
    ``stale_rows`` the departed base rows it will be asked to hold."""
    if capacity % 32 or capacity < sorted_ids.shape[0]:
        raise ValueError(f"capacity {capacity}: a multiple of 32, no less "
                         f"than the table's {sorted_ids.shape[0]} rows")
    n = jnp.asarray(n_valid, jnp.int32)
    pad = capacity - sorted_ids.shape[0]
    base = jnp.where((jnp.arange(capacity) < n)[:, None],
                     jnp.pad(sorted_ids, ((0, pad), (0, 0))), _U32(_ONES))
    lut = build_prefix_lut(base, n, bits=lut_bits)
    return _fresh(base, n, lut, delta_capacity, stale_rows)


def _fresh(base, n_base, lut, delta_capacity: int, stale_rows: int):
    """The state right after a build or a compaction: nobody departed,
    an empty delta."""
    C, D = base.shape[0], delta_capacity
    zero = jnp.int32(0)
    return ChurnTable(
        base=base, n_base=n_base, lut=lut, lut_ok=_lut_fits(lut, C),
        tomb_bits=jnp.zeros((tomb_words(C + D),), _U32),
        dead_pos=jnp.full((stale_rows,), C, jnp.int32), n_tomb=zero,
        delta=jnp.full((D, N_LIMBS), _ONES, _U32),
        delta_pos=jnp.zeros((D,), jnp.int32), n_delta=zero,
        delta_lut=jnp.zeros(((1 << DELTA_LUT_BITS) + 1,), jnp.int32))


@functools.partial(jax.jit, donate_argnums=(0,))
def churn_apply(tbl: ChurnTable, leave_ids, join_ids):
    """One tick (stage ``table_apply``): ``leave_ids`` [E,5] depart,
    then ``join_ids`` [J,5] arrive.  Returns the table after the tick
    (``tbl`` is consumed) and ``[left_base, left_delta]`` — how many of
    the departures were live members (an id that is no member, or is
    named twice, leaves nothing).

    Departures: every id is sought in the base and in the delta (one
    exact search each).  A live base row gets its liveness bit and an
    entry in ``dead_pos``; a live delta row the sign of its
    ``delta_pos``.  Arrivals: sorted, positioned in the base (their
    ``delta_pos``: what the compaction needs, found now, 5-limb search
    over E rows, and not then, over the whole delta) and in the delta,
    and merged in by ONE gather over the delta's planes: new row ``j``
    lands at ``(delta rows below it) + j``, an old row moves up by the
    new rows at or below its place.  The caller keeps ``n_delta + J``
    within the delta's capacity and ``n_tomb + E`` within
    ``dead_pos``'s, the T departed rows the table was built to hold
    (core/table.py ``DeviceChurnTable`` compacts first).
    """
    return device_stage("table_apply")(_apply)(tbl, leave_ids, join_ids)


def _apply(tbl: ChurnTable, leave_ids, join_ids, n_real=None):
    """:func:`churn_apply`'s tick.  ``n_real`` = ``(n_leave, n_join)``
    (traced counts) says that only the first ``n_leave`` rows of
    ``leave_ids`` and the ``n_join`` smallest of ``join_ids`` are ids
    and the rest PADDING — what a shard of a row-sharded table is handed
    when a batch is routed to it at a fixed width
    (parallel/churn.py): a padding departure leaves nothing whatever it
    holds, a padding arrival is the all-ones id, sorts behind the real
    ones and lands past the delta's new end, where the all-ones rows
    are."""
    C, D = tbl.capacity, tbl.delta_capacity
    E, J = leave_ids.shape[0], join_ids.shape[0]
    n_leave, n_join = (E, J) if n_real is None else n_real

    # -- positions: departures and arrivals in the base, one search ----
    joins = _sorted_rows(join_ids)
    pos_b, hit_b = _find(tbl.base, tbl.n_base, tbl.lut, tbl.lut_ok,
                         jnp.concatenate([leave_ids, joins]))
    join_pos = pos_b[E:]
    dlut_ok = _lut_fits(tbl.delta_lut, D)
    pos_d, hit_d = _find(tbl.delta, tbl.n_delta, tbl.delta_lut, dlut_ok,
                         jnp.concatenate([leave_ids, joins]))
    join_at = pos_d[E:]

    # -- departures ----------------------------------------------------
    live_b = hit_b[:E] & ~node_gone(tbl.tomb_bits,
                                     jnp.clip(pos_b[:E], 0, C - 1))
    if n_real is not None:
        live_b &= jnp.arange(E, dtype=jnp.int32) < n_leave
    gone_b = _first_of_each(pos_b[:E], live_b, C)
    old_pos = jnp.take(tbl.delta_pos, jnp.clip(pos_d[:E], 0, D - 1))
    live_d = hit_d[:E] & ~live_b & (old_pos >= 0)
    if n_real is not None:
        live_d &= jnp.arange(E, dtype=jnp.int32) < n_leave
    gone_d = _first_of_each(pos_d[:E], live_d, D)
    left_b = jnp.sum(gone_b < C, dtype=jnp.int32)
    left_d = jnp.sum(gone_d < D, dtype=jnp.int32)
    # distinct rows, bits not yet set: adding a bit is setting it
    tomb_bits = tbl.tomb_bits.at[
        jnp.where(gone_b < C, gone_b >> 5, tbl.tomb_bits.shape[0])].add(
        _U32(1) << (gone_b & 31).astype(_U32), mode="drop")
    # the live ones lie first (ascending, C last); what follows them is
    # overwritten by the next tick's
    dead_pos = lax.dynamic_update_slice(tbl.dead_pos, gone_b, (tbl.n_tomb,))
    delta_pos = tbl.delta_pos.at[gone_d].set(
        -1 - jnp.take(tbl.delta_pos, jnp.clip(gone_d, 0, D - 1)),
        mode="drop")

    # -- arrivals: merge into the sorted delta --------------------------
    landing = join_at + jnp.arange(J, dtype=jnp.int32)
    is_new = jnp.zeros((D,), jnp.int32).at[landing].set(1, mode="drop")
    moved_by = _running_sum(is_new)
    src = jnp.clip(jnp.arange(D, dtype=jnp.int32) - moved_by, 0, D - 1)
    planes = jnp.concatenate([tbl.delta.T, lax.bitcast_convert_type(
        delta_pos, _U32)[None]])                                  # [6, D]
    new_planes = jnp.concatenate([joins.T, lax.bitcast_convert_type(
        join_pos, _U32)[None]])                                   # [6, J]
    merged = jnp.take(planes, src, axis=1).at[:, landing].set(
        new_planes, mode="drop")
    delta = merged[:N_LIMBS].T
    delta_pos = lax.bitcast_convert_type(merged[N_LIMBS], jnp.int32)
    n_delta = tbl.n_delta + n_join
    # the delta's liveness words, from the signs as they lie now
    gone = (delta_pos < 0) & (jnp.arange(D, dtype=jnp.int32) < n_delta)
    weights = _U32(1) << jnp.arange(32, dtype=_U32)
    dwords = jnp.sum(jnp.pad(gone, (0, -D % 32)).reshape(-1, 32)
                     * weights[None, :], axis=1, dtype=_U32)
    tomb_bits = lax.dynamic_update_slice(tomb_bits, dwords, (C // 32,))
    prefix = (joins[:, 0] >> _U32(32 - DELTA_LUT_BITS)).astype(jnp.int32)
    if n_real is not None:
        prefix = jnp.where(jnp.arange(J, dtype=jnp.int32) < n_join, prefix,
                           1 << DELTA_LUT_BITS)
    below = _running_sum(jnp.zeros(((1 << DELTA_LUT_BITS),), jnp.int32)
                         .at[prefix].add(1))      # out of range: dropped
    delta_lut = tbl.delta_lut.at[1:].add(below)
    return (tbl._replace(tomb_bits=tomb_bits, dead_pos=dead_pos,
                         n_tomb=tbl.n_tomb + left_b, delta=delta,
                         delta_pos=delta_pos, n_delta=n_delta,
                         delta_lut=delta_lut),
            jnp.stack([left_b, left_d]))


@functools.partial(jax.jit, donate_argnums=(0,))
def churn_compact(tbl: ChurnTable) -> ChurnTable:
    """Merge the live base rows and the live delta rows into a new
    sorted base with its prefix LUT (stage ``table_compact``); departed
    rows are dropped, the delta and the liveness words come back empty;
    ``tbl`` is consumed.

    A merge of two sorted sequences, written as ONE gather over base
    and delta laid end to end (a scatter of the delta's rows into the
    gathered base cost 85 ns a row on a v5e where the gather costs 18:
    PERF.md §6, PR 32).  Output place of live base row
    ``i``: (live base rows below it) + (live delta rows with
    ``delta_pos`` ≤ i); of live delta row ``j``: (live delta rows below
    it) + (live base rows below ``delta_pos[j]``).  Read the other way,
    for the gather: the base row that lands at place ``p`` is ``p`` −
    (delta rows landed at or before ``p``) + (departed base rows that
    would have landed at or before ``p``) — both counts histograms over
    the places, of the delta's ≤ D and the departed's ≤ T entries, and
    one running sum over the capacity.  Nothing table-sized is sorted
    or searched, and the LUT is not rebuilt from the rows: it is the old
    one less the departed rows' and plus the delta rows' prefix counts.
    """
    return device_stage("table_compact")(_compact)(tbl)


def _compact(tbl: ChurnTable) -> ChurnTable:
    C, D = tbl.capacity, tbl.delta_capacity
    at = jnp.arange(C, dtype=jnp.int32)
    live_b = (at < tbl.n_base) & ~unpack_tomb_bits(
        tbl.tomb_bits[:C // 32], C)
    # live base rows below row t, t in [0, C]
    below_b = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               _running_sum(live_b)])
    slot = jnp.arange(D, dtype=jnp.int32)
    live_d = (slot < tbl.n_delta) & (tbl.delta_pos >= 0)
    rank_d = _running_sum(live_d) - 1
    dpos = jnp.clip(tbl.delta_pos, 0, C)
    place_d = jnp.where(live_d, rank_d + jnp.take(below_b, dpos), C)
    # live delta rows with delta_pos <= t
    before = _running_sum(jnp.zeros((C + 1,), jnp.int32).at[
        jnp.where(live_d, dpos, C + 1)].add(1, mode="drop"))
    dead = jnp.where(jnp.arange(tbl.dead_pos.shape[0]) < tbl.n_tomb,
                     tbl.dead_pos, C)
    deadc = jnp.clip(dead, 0, C - 1)
    place_dead = jnp.where(dead < C, jnp.take(below_b, deadc)
                           + jnp.take(before, deadc), C + 1)
    # the delta row that lands at place p, as a row of [base | delta]
    from_delta = jnp.zeros((C,), jnp.int32).at[place_d].set(
        C + slot, mode="drop")
    net = jnp.zeros((C + 1,), jnp.int32).at[place_dead].add(
        1, mode="drop")[:C] - (from_delta > 0)
    src = jnp.where(from_delta > 0, from_delta, jnp.clip(
        at + _running_sum(net), 0, C - 1))
    n_new = below_b[C] + jnp.sum(live_d, dtype=jnp.int32)
    new_t = jnp.take(jnp.concatenate([tbl.base.T, tbl.delta.T], axis=1),
                     src, axis=1)
    base = jnp.where((at < n_new)[:, None], new_t.T, _U32(_ONES))

    bits = _lut_bits(tbl.lut)
    nb = 1 << bits
    shift = _U32(32 - bits)

    def below_prefix(top, ok):
        """Rows of ``top`` (first limbs) where ``ok`` with prefix < p."""
        hist = jnp.zeros((nb,), jnp.int32).at[
            jnp.where(ok, (top >> shift).astype(jnp.int32), nb)].add(
            1, mode="drop")
        return _running_sum(hist)

    lut = tbl.lut.at[1:].add(
        below_prefix(tbl.delta[:, 0], live_d)
        - below_prefix(jnp.take(tbl.base[:, 0], deadc), dead < C))
    return _fresh(base, n_new, lut, D, tbl.dead_pos.shape[0])


def live_rows(tbl: ChurnTable):
    """``(ids [C + D, 5], live [C + D])``: every row of the base and of
    the delta, and which of them is a member now — what a membership
    checksum sums over."""
    C, D = tbl.capacity, tbl.delta_capacity
    node = jnp.arange(C + D, dtype=jnp.int32)
    held = jnp.where(node < C, node < tbl.n_base, node - C < tbl.n_delta)
    return (jnp.concatenate([tbl.base, tbl.delta]),
            held & ~unpack_tomb_bits(tbl.tomb_bits, C + D))
