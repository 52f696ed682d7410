"""The row-sharded table built ACROSS the mesh: a global sort in which
no device, and no host array, ever holds the whole id set.

``shard_table_state`` wants a table that is already globally sorted,
and until this module the only ways to get one were ``sort_table`` on
one device or a host ``lexsort`` — both pass the whole table through
one memory, which is what a table sharded because it outgrows one chip
cannot do.  :func:`sharded_global_sort` takes ids as they lie
row-sharded over ``t`` and range-partitions them by key:

``partition``  every shard finds each row's destination — equal ranges
               of the key space are the splitters, ``dest = floor(top
               bits · t / 2^w)``, exact for any ``t`` and monotone in
               the key — and packs the rows bound for shard ``d`` into
               segment ``d`` of a send buffer of FIXED capacity
               (``segment_rows``), with the count of each segment.
               The counts ([t, t] integers) are the one thing the host
               reads: a segment over its capacity raises, it is never
               truncated.
``exchange``   one ``all_to_all`` of the segments and one of their
               counts over ``t``.
``sort``       every shard sorts what it received on all 160 bits,
               rows beyond a segment's count masked to the end as
               ``sort_table(tbl, valid)`` masks them, and learns its
               ``(base, width)`` in the global order from an
               ``all_gather`` of the widths.
``lut``        per-shard positioning LUT and the replicated block LUT
               (one psum), by ``partition._build_state_luts_weighted``.

The result is the WEIGHTED layout of ``partition.TableState``
(``shard_rows`` [t, 2], ``shard_n`` the per-shard row capacity
``t · segment_rows``): shards of unequal width, each a contiguous range
of the global order — what a range partition of hashed ids yields
(widths differ by a few thousand rows of 25M) and what
``sharded.tp_simulate_lookups(state=)`` already consumes.  Evening the
shards out by a boundary exchange would buy nothing the engine needs.

Uniform splitters assume ids that are uniform hashes, which DHT ids
are (SHA-1 of a key or of a public key).  A clustered id set overflows
a segment and raises; give it ``segment_rows`` (up to the shard's whole
row count, which cannot overflow) or sort it where it fits.

Each phase is a ``dht_table_build_seconds{phase=...}`` span that waits
for its result; ``dht_table_build_rows_exchanged_total`` counts the
rows that changed shard; the kernels carry the ``device_stage`` names
``table_partition``, ``table_exchange``, ``table_sort`` (the LUT build
has no stage: it is ``build_prefix_lut``'s own jit).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .. import telemetry
from ..ops.ids import N_LIMBS
from ..ops.sorted_table import default_lut_bits, fused_gather_planar
from .partition import (TABLE_AXIS_RULES, TableState,
                        _build_state_luts_weighted, shard_put)
from .sharded import _as_operand

_U32 = jnp.uint32
#: a segment's capacity is a whole number of lanes
SEGMENT_ALIGN = 128
#: the splitter reads this many top bits of an id, so t may be up to
#: 2^(32 - SPLIT_BITS) without the product leaving 32 bits
SPLIT_BITS = 24


def dest_shard(limb0, n_t: int):
    """The shard whose equal share of the key space holds an id with
    first limb ``limb0``: ``floor(top SPLIT_BITS bits · n_t /
    2^SPLIT_BITS)``.  Monotone in the key, so shard ``d`` receives one
    contiguous range of the global order.  Works on numpy and jax
    uint32 arrays alike (the tests render it in numpy)."""
    top = limb0 >> (32 - SPLIT_BITS)
    return ((top * n_t) >> SPLIT_BITS).astype("int32")


def default_segment_rows(shard_rows: int, n_t: int) -> int:
    """Capacity of one (source, destination) segment for UNIFORM ids:
    the mean ``shard_rows / t`` plus six standard deviations of the
    binomial count (an overflow then has a probability under 1e-8 a
    build) and one alignment unit, rounded up to whole lanes — 0.24 %
    over the mean at 25M rows a shard."""
    mean = shard_rows / n_t
    rows = math.ceil(mean + 6.0 * math.sqrt(mean)) + SEGMENT_ALIGN
    rows = min(rows, shard_rows)
    return -(-rows // SEGMENT_ALIGN) * SEGMENT_ALIGN


@functools.lru_cache(maxsize=8)
def _build_partition(mesh: Mesh, seg: int):
    n_t = mesh.shape["t"]

    @telemetry.device_stage("table_partition")
    def pack(tbl, val):
        n = tbl.shape[0]
        dest = jnp.where(val, dest_shard(tbl[:, 0], n_t), n_t)
        # a row's rank among the rows of its destination, one running
        # count per destination: no sort, and the order inside a
        # segment does not matter (the receiver sorts)
        rank = jnp.zeros((n,), jnp.int32)
        counts = []
        for d in range(n_t):
            mine = dest == d
            run = jnp.cumsum(mine.astype(jnp.int32))
            rank = jnp.where(mine, run - 1, rank)
            counts.append(run[-1])
        # slot of the send buffer each row goes to; an invalid row, or
        # one past its segment's capacity (which the host's look at the
        # counts raises on), gets a slot of its own beyond the buffer
        # and is dropped — every index is distinct, as the scatter is told
        row = jnp.arange(n, dtype=jnp.int32)
        slot = jnp.where((dest < n_t) & (rank < seg), dest * seg + rank,
                         n_t * seg + row)
        src = jnp.full((n_t * seg,), -1, jnp.int32).at[slot].set(
            row, mode="drop", unique_indices=True)
        # empty slots read a clipped row: garbage the receiver masks by
        # the segment's count
        planes = fused_gather_planar(tbl.T, src)
        return (jnp.stack(planes).reshape(N_LIMBS, n_t, seg),
                jnp.stack(counts)[None])

    fn = jax.shard_map(
        pack, mesh=mesh,
        in_specs=(P("t", None), P("t")),
        out_specs=(P(None, "t", None), P("t", None)),
        check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=8)
def _build_exchange(mesh: Mesh):
    @telemetry.device_stage("table_exchange")
    def swap(send, counts):
        # segment d of every shard goes to shard d; what arrives is
        # indexed by the shard it came from
        return (lax.all_to_all(send, "t", 1, 1),
                lax.all_to_all(counts[0], "t", 0, 0)[None])

    fn = jax.shard_map(
        swap, mesh=mesh,
        in_specs=(P(None, "t", None), P("t", None)),
        out_specs=(P(None, "t", None), P("t", None)),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(0,))


@functools.lru_cache(maxsize=8)
def _build_local_sort(mesh: Mesh):
    n_t = mesh.shape["t"]

    @telemetry.device_stage("table_sort")
    def order(recv, counts):
        seg = recv.shape[2]
        cap = n_t * seg
        valid = (jnp.arange(seg, dtype=jnp.int32)[None, :]
                 < counts[0][:, None]).reshape(cap)
        # the validity mask as sort_table has it, folded into the keys:
        # a row that is not there sorts as the all-ones id, past every
        # id that is (an all-ones id that IS there ties with it, and
        # equal rows are equal wherever they land)
        planes = jnp.stack(
            [jnp.where(valid, recv[l].reshape(cap), _U32(0xFFFFFFFF))
             for l in range(N_LIMBS)])

        # all 160 bits, least significant limb first: five STABLE sorts
        # of (one limb in the order so far, the order) and a gather at
        # the end.  One sort of five keys is fewer passes over the rows
        # but compiles for minutes (the comparator, and an operand a
        # limb: sort_table's 170-210 s at 10M rows, PERF.md section 7);
        # this loop body compiles once, in about a third of a minute.
        def by_limb(p, perm):
            limb = lax.dynamic_index_in_dim(planes, N_LIMBS - 1 - p, 0,
                                            keepdims=False)
            return lax.sort((jnp.take(limb, perm, mode="clip"), perm),
                            dimension=0, num_keys=1, is_stable=True)[1]

        perm = lax.fori_loop(0, N_LIMBS, by_limb,
                             jnp.arange(cap, dtype=jnp.int32))
        out = fused_gather_planar(planes, perm)
        width = jnp.sum(counts[0])
        live = jnp.arange(cap, dtype=jnp.int32) < width
        sorted_ids = jnp.stack(
            [jnp.where(live, plane, _U32(0)) for plane in out], axis=-1)
        widths = lax.all_gather(width, "t")                      # [n_t]
        base = jnp.sum(jnp.where(jnp.arange(n_t) < lax.axis_index("t"),
                                 widths, 0))
        return (sorted_ids, jnp.stack([base, width])[None].astype(jnp.int32),
                jnp.sum(widths).astype(jnp.int32))

    fn = jax.shard_map(
        order, mesh=mesh,
        in_specs=(P(None, "t", None), P("t", None)),
        out_specs=(P("t", None), P("t", None), P()),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(0,))


def sharded_global_sort(mesh: Mesh, table, valid=None, *,
                        segment_rows: Optional[int] = None,
                        block_bits: Optional[int] = None,
                        donate: bool = False) -> TableState:
    """Sort a row-sharded id table globally across the mesh ``t`` axis
    and derive its lookup state — the :class:`TableState` that
    ``tp_simulate_lookups(state=)`` consumes, built without the table
    ever passing through one device or the host (module docstring).

    ``table`` uint32 [N, 5], N divisible by ``mesh.shape['t']``, as it
    lies (or is to be placed) under ``P('t', None)``: which rows start
    on which shard does not matter.  ``valid`` bool [N] marks the rows
    that are ids (pad a table whose size does not divide ``t`` with
    invalid rows); ``None`` means all.  ``segment_rows`` is the
    capacity of one (source, destination) segment
    (:func:`default_segment_rows`: sized for uniform ids); a segment
    that would overflow raises ``OverflowError`` before anything is
    exchanged.  ``block_bits`` defaults to ``default_lut_bits(N)`` — the
    width the single-device engine uses for the same ids, which
    bit-identity needs; the per-shard positioning LUT takes that of the
    shard capacity.
    ``donate=True`` gives the build the caller's placed ``table`` and
    ``valid``: they are deleted once the rows are packed, so the unsorted
    copy does not lie beside the exchange and the sort (0.8 GB a chip at
    25M rows); the caller's arrays are unusable afterwards.
    """
    n_t = int(mesh.shape["t"])
    N = int(table.shape[0])
    if N % n_t:
        raise ValueError(f"table rows ({N}) not divisible by t={n_t}; "
                         "pad with rows marked invalid")
    if n_t > 1 << (32 - SPLIT_BITS):
        raise ValueError(f"t={n_t} exceeds the splitter's range")
    shard_in = N // n_t
    seg = int(segment_rows or default_segment_rows(shard_in, n_t))
    if valid is None:
        valid = np.ones((N,), bool)
    ops = shard_put(mesh, {"table": _as_operand(table, np.uint32),
                           "valid": valid}, TABLE_AXIS_RULES)
    reg = telemetry.get_registry()

    with reg.span("dht_table_build_seconds", phase="partition"):
        send, counts = _build_partition(mesh, seg)(ops["table"], ops["valid"])
        counts_host = np.asarray(counts)              # [source, destination]
    if donate:
        for placed in ops.values():
            placed.delete()
    del ops
    if int(counts_host.max()) > seg:
        src, dst = np.unravel_index(int(counts_host.argmax()),
                                    counts_host.shape)
        raise OverflowError(
            f"{int(counts_host.max())} rows of shard {src} belong to shard "
            f"{dst}, over the segment capacity {seg}: the ids are not "
            "uniform over the key space; pass segment_rows (at most "
            f"{shard_in}, which cannot overflow)")
    reg.counter("dht_table_build_rows_exchanged_total").inc(
        int(counts_host.sum() - np.trace(counts_host)))

    with reg.span("dht_table_build_seconds", phase="exchange"):
        recv, recv_counts = jax.block_until_ready(
            _build_exchange(mesh)(send, counts))       # consumes send
    with reg.span("dht_table_build_seconds", phase="sort"):
        sorted_ids, shard_rows, n_valid = jax.block_until_ready(
            _build_local_sort(mesh)(recv, recv_counts))   # consumes recv
    shard_cap = n_t * seg
    lb = default_lut_bits(shard_cap)
    bb = block_bits or default_lut_bits(N)
    with reg.span("dht_table_build_seconds", phase="lut"):
        local_lut, block_lut = jax.block_until_ready(
            _build_state_luts_weighted(mesh, lb, bb)(sorted_ids, shard_rows))
    widths = counts_host.sum(axis=0)
    return TableState(
        arrays={"sorted_ids": sorted_ids, "local_lut": local_lut,
                "block_lut": block_lut, "n_valid": n_valid,
                "shard_rows": shard_rows},
        shard_n=shard_cap, lut_bits=lb, block_bits=bb,
        boundaries=tuple(int(x) for x in np.cumsum(widths)[:-1]))

