"""Sharded node tables over a device mesh with ICI top-k merge.

The reference scales by adding independent peers over UDP (its NCCL/MPI
analog is the bespoke msgpack engine, src/network_engine.cpp).  The TPU
build scales a *single logical node table* past one chip's HBM instead:

- mesh axis ``t`` (table-parallel): the [N, 5] id matrix is sharded by
  rows across devices; every device scans only its shard.
- mesh axis ``q`` (query/data-parallel): the query batch is sharded;
  each device answers its slice of queries.

One lookup = per-shard exact top-k (a local HBM scan or sorted-window
lookup) followed by an ``all_gather`` of the per-shard winners over the
``t`` axis and one [Q_local, n_t·k]-row lexicographic re-sort.  The
merge is exact: the global top-k is always a subset of the union of
per-shard top-ks.  Collectives ride ICI when the mesh maps to one pod
slice; nothing here assumes host locality, so the same code runs on a
DCN-spanning mesh.

Placement is DECLARATIVE (round 13): every entry point places its
operands by regex partition rules over a named state pytree
(``partition.match_partition_rules`` → per-leaf ``NamedSharding``
shard fns, the standard large-model JAX pattern), and the iterative
engine's table state — sorted rows, per-shard positioning LUT, the
replicated global block LUT, validity — is built ONCE by
``partition.shard_table_state`` (from a table sorted elsewhere) or
``global_sort.sharded_global_sort`` (sorted across the mesh, for a table
no single memory should hold) and reused across waves.  Each ``t``
shard holds ~N/t rows (plus the 4·2^bb-byte block LUT); nothing
table-sized is replicated, so the servable id set scales linearly in
mesh size.  The search STATE is sharded too (PR 38): a ``t``-rank runs
the engine over its own chunk of a wave, and the steady-state search
round costs exactly ONE in-loop lane exchange — the reply-row index
all-gathered, the owners' rows summed back to the lanes' shard,
O(queries·k) bytes — because reply-block edges read the replicated
global LUT locally instead of psumming per-shard edge counts every hop
(stage ``owner_merge``: PERF.md §5).

Compiled programs are cached per (mesh, k, tile/window, shard size) —
repeated calls with the same geometry reuse one XLA executable.

All entry points run on any ``jax.sharding.Mesh`` — including a virtual
CPU mesh (``--xla_force_host_platform_device_count``) — which is how the
tests and the driver's ``dryrun_multichip`` exercise multi-chip paths
without multi-chip hardware.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .partition import (TABLE_AXIS_RULES, DP_AXIS_RULES, TableState,
                        shard_put, shard_table_state)

from ..ops.churn_table import DELTA_WINDOW, node_gone
from ..ops.ids import N_LIMBS
from ..ops.xor_topk import xor_topk, select_topk, mask_invalid
from ..ops.sorted_table import (sort_table, window_topk, build_prefix_lut,
                                default_lut_bits, expand_table, expanded_topk,
                                fused_gather_planar, loop_gather_view, _EROW)
from ..core.search import (simulate_lookups, _lookup_engine, _run_wave,
                           _guarded_lower_bound, _lut_block_bounds,
                           TARGET_NODES, ALPHA, SEARCH_NODES)
from ..telemetry import device_stage

_U32 = jnp.uint32


def make_mesh(n_devices: Optional[int] = None, *, q: Optional[int] = None,
              t: Optional[int] = None) -> Mesh:
    """Build a 2-D (q=data/query, t=table) mesh over the first
    ``n_devices`` devices.  Default split: t gets the larger factor
    (table rows dominate memory; queries are cheap to replicate)."""
    devs = jax.devices()
    if n_devices is None:
        n_devices = len(devs)
    if q is None and t is None:
        # largest power-of-two factor ≤ sqrt for q, rest for t
        q = 1
        while q * 2 <= n_devices // (q * 2) and n_devices % (q * 4) == 0:
            q *= 2
        t = n_devices // q
    elif q is None:
        q = n_devices // t
    elif t is None:
        t = n_devices // q
    if q * t != n_devices:
        raise ValueError(f"mesh {q}x{t} != {n_devices} devices")
    arr = np.asarray(devs[:n_devices]).reshape(q, t)
    return Mesh(arr, ("q", "t"))


def pad_to_multiple(arr: np.ndarray, m: int, axis: int = 0, fill=0):
    """Pad `arr` along `axis` to a multiple of `m`.  Returns (padded, n)."""
    n = arr.shape[axis]
    pad = (-n) % m
    if pad == 0:
        return arr, n
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, pad)
    return np.pad(arr, widths, constant_values=fill), n


def _as_operand(x, dtype=None):
    """Normalize one entry-point operand for declarative placement:
    host data becomes a (cast) numpy array — ``partition``'s shard fns
    then ``device_put`` it straight to its shards, never a replicated
    staging copy — while an already-committed jax array is cast in
    place and resharded by the jitted identity."""
    if hasattr(x, "sharding"):
        return x if dtype is None or x.dtype == dtype else x.astype(dtype)
    return np.asarray(x, dtype)


def _gather_and_merge(dist, gidx, n_t, k):
    """all_gather per-shard winners over ``t`` and re-select the top-k."""
    all_dist = lax.all_gather(dist, "t")                # [n_t, Qs, k, 5]
    all_idx = lax.all_gather(gidx, "t")                 # [n_t, Qs, k]
    Qs = dist.shape[0]
    cd = jnp.moveaxis(all_dist, 0, 1).reshape(Qs, n_t * k, N_LIMBS)
    ci = jnp.moveaxis(all_idx, 0, 1).reshape(Qs, n_t * k)
    d, i, inv = select_topk(cd, ci, (ci < 0).astype(jnp.int32), k)
    return mask_invalid(d, i, inv)


@functools.lru_cache(maxsize=64)
def _build_sharded_xor_topk(mesh: Mesh, k: int, tile: int, shard_n: int):
    n_t = mesh.shape["t"]

    def local(q, tbl, val):
        ti = lax.axis_index("t")
        dist, idx = xor_topk(q, tbl, k=k, tile=tile, valid=val)
        gidx = jnp.where(idx >= 0, idx + ti * shard_n, -1)
        return _gather_and_merge(dist, gidx, n_t, k)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("q", None), P("t", None), P("t")),
        out_specs=(P("q", None, None), P("q", None)),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_xor_topk(mesh: Mesh, queries, table, *, k: int = 8,
                     tile: int = 4096, valid=None):
    """Exact k XOR-closest over a row-sharded table (full-scan path).

    queries: uint32 [Q, 5], Q divisible by mesh.shape['q'].
    table:   uint32 [N, 5], N divisible by mesh.shape['t'] (pad with
             `valid=False` rows via :func:`pad_to_multiple`).
    valid:   bool [N] or None.

    Returns (dist [Q, k, 5], idx [Q, k] int32 global row indices, -1 pad),
    laid out sharded over ``q`` / replicated over ``t``.
    """
    N = table.shape[0]
    shard_n = N // mesh.shape["t"]
    if valid is None:
        valid = jnp.ones((N,), dtype=bool)
    fn = _build_sharded_xor_topk(mesh, k, min(tile, shard_n), shard_n)
    ops = shard_put(mesh, {"queries": _as_operand(queries, np.uint32),
                           "table": _as_operand(table, np.uint32),
                           "valid": _as_operand(valid, bool)},
                    TABLE_AXIS_RULES)
    return fn(ops["queries"], ops["table"], ops["valid"])


@functools.lru_cache(maxsize=8)
def _build_sharded_sort(mesh: Mesh):
    def local(tbl, val):
        sorted_ids, perm, n_valid = sort_table(tbl, val)
        return sorted_ids, perm, jnp.asarray(n_valid, jnp.int32)[None]

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("t", None), P("t")),
        out_specs=(P("t", None), P("t"), P("t")),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_sort_table(mesh: Mesh, table, valid=None):
    """Sort each table shard locally (rows stay on their device; no
    collectives).  Returns (sorted_ids [N,5], perm [N], n_valid [n_t]) —
    all sharded over ``t`` — to feed repeated
    :func:`sharded_window_lookup` calls, so a stable table is sorted once
    and amortized across query batches (mirroring the single-device
    sort_table / window_topk split in ops/sorted_table.py)."""
    N = table.shape[0]
    if valid is None:
        valid = jnp.ones((N,), dtype=bool)
    fn = _build_sharded_sort(mesh)
    ops = shard_put(mesh, {"table": _as_operand(table, np.uint32),
                           "valid": _as_operand(valid, bool)},
                    TABLE_AXIS_RULES)
    return fn(ops["table"], ops["valid"])


@functools.lru_cache(maxsize=8)
def _build_sharded_expand(mesh: Mesh, bits: int):
    def local(sorted_ids, n_valid_shard):
        expanded = expand_table(sorted_ids)
        lut = build_prefix_lut(sorted_ids, n_valid_shard[0], bits=bits)
        return expanded, lut[None]

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("t", None), P("t")),
        out_specs=(P("t", None), P("t", None)),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_expand_table(mesh: Mesh, sorted_ids, n_valid, *, bits: int = 16):
    """Build each shard's expanded window-row table and prefix LUT
    locally (no collectives) from :func:`sharded_sort_table` output.
    Returns (expanded [n_t·NB, 970] sharded over ``t``,
    lut [n_t, 2^bits+1] sharded over ``t``) to feed the expanded fast
    path of :func:`sharded_window_lookup`."""
    fn = _build_sharded_expand(mesh, bits)
    return fn(jnp.asarray(sorted_ids, _U32), jnp.asarray(n_valid, jnp.int32))


@functools.lru_cache(maxsize=64)
def _build_sharded_window_lookup(mesh: Mesh, k: int, window: int,
                                 shard_n: int, use_expanded: bool):
    n_t = mesh.shape["t"]

    def local(q, sorted_ids, perm, n_valid_shard, expanded, lut):
        ti = lax.axis_index("t")
        n_valid = n_valid_shard[0]
        if use_expanded:
            dist, sidx, cert = expanded_topk(sorted_ids, expanded, n_valid,
                                             q, k=k, lut=lut[0])
        else:
            dist, sidx, cert = window_topk(sorted_ids, n_valid, q, k=k,
                                           window=window)

        # Certificate fallback: when any row in this shard's batch is
        # uncertified, rerun the whole shard through the exact scan and
        # keep the certified window rows.  lax.cond keeps the common
        # (all-certified) path free of the O(shard_n) scan — but the
        # branch's buffers are still ALLOCATED, and a 4096-row tile
        # sorts [Q, 4104]x7 u32 temps (~7.5 GB at Q=65536), which OOMs
        # alongside a 64M-id shard's 5 GB of resident tables.  Huge
        # shards take a small tile: the branch only ever executes on
        # adversarial id distributions, so its throughput is secondary
        # to it being allocatable.
        def exact(_):
            fb_tile = min(4096 if shard_n <= 8_000_000 else 512, shard_n)
            d2, i2 = xor_topk(q, sorted_ids, k=k, tile=fb_tile,
                              valid=jnp.arange(shard_n) < n_valid)
            keep = cert[:, None]
            return (jnp.where(keep[..., None], dist, d2),
                    jnp.where(keep, sidx, i2))

        def fast(_):
            return dist, sidx

        dist2, sidx2 = lax.cond(jnp.all(cert), fast, exact, operand=None)
        rows = jnp.where(sidx2 >= 0,
                         jnp.take(perm, jnp.clip(sidx2, 0, shard_n - 1)), -1)
        gidx = jnp.where(rows >= 0, rows + ti * shard_n, -1)
        return _gather_and_merge(dist2, gidx, n_t, k)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("q", None), P("t", None), P("t"), P("t"),
                  P("t", None), P("t", None)),
        out_specs=(P("q", None, None), P("q", None)),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_window_lookup(mesh: Mesh, queries, sorted_ids, perm, n_valid, *,
                          k: int = 8, window: int = 128, expanded=None,
                          lut=None):
    """Exact k XOR-closest over a pre-sorted row-sharded table — the
    repeated-lookup fast path.  Takes the output of
    :func:`sharded_sort_table`; each shard answers with its local window
    top-k (per-query exactness certificate; uncertified batches fall back
    to the shard-local full scan), then the per-shard winners are
    all_gather-merged over ``t``.

    Pass ``expanded``/``lut`` from :func:`sharded_expand_table` to use
    the expanded row-gather fast path per shard (the headline-bench
    kernel) instead of the per-element window gather.

    Same contract as :func:`sharded_xor_topk`: returns
    (dist [Q, k, 5], idx [Q, k]) where idx are **global original-table
    row indices** (-1 padding), sharded over ``q``.
    """
    N = sorted_ids.shape[0]
    n_t = mesh.shape["t"]
    shard_n = N // n_t
    use_expanded = expanded is not None
    if not use_expanded:
        # placeholder operands keep one shard_map signature for both paths
        expanded = jnp.zeros((n_t, N_LIMBS * _EROW), _U32)
        lut = jnp.zeros((n_t, 2), jnp.int32)
    fn = _build_sharded_window_lookup(mesh, k, min(window, shard_n), shard_n,
                                      use_expanded)
    ops = shard_put(mesh, {"queries": _as_operand(queries, np.uint32),
                           "sorted_ids": _as_operand(sorted_ids, np.uint32),
                           "perm": _as_operand(perm, np.int32),
                           "n_valid": _as_operand(n_valid, np.int32),
                           "expanded": _as_operand(expanded, np.uint32),
                           "local_lut": _as_operand(lut, np.int32)},
                    TABLE_AXIS_RULES)
    return fn(ops["queries"], ops["sorted_ids"], ops["perm"],
              ops["n_valid"], ops["expanded"], ops["local_lut"])


def sharded_lookup(mesh: Mesh, queries, table, *, k: int = 8,
                   window: int = 128, valid=None):
    """One-shot convenience: :func:`sharded_sort_table` +
    :func:`sharded_window_lookup`.  Callers with a stable table and many
    query batches should hold the sorted form and call
    ``sharded_window_lookup`` directly to amortize the sort."""
    sorted_ids, perm, n_valid = sharded_sort_table(mesh, table, valid)
    return sharded_window_lookup(mesh, queries, sorted_ids, perm, n_valid,
                                 k=k, window=window)


# The stride, in rows, between the spare rows of two neighbouring lanes
# (owner_local_index).  Odd and prime, so that the spares of a round are
# distinct rows of any shard whose capacity is no multiple of it; wide
# enough that neighbouring lanes never share a 128-row tile of the
# view; small enough that lane * stride stays exact in 32 bits up to
# 4.2M lanes (past that it wraps and scatters all the same).
SPARE_STRIDE = 1021


def owner_local_index(rows, base, n_owned, capacity: int):
    """What one shard hands its gather for global ``rows``: the local
    index of every lane, and ``ok``, the lanes whose row this shard owns
    (``base <= row < base + n_owned``).  An owned lane reads
    ``rows - base``.  Every other lane — another shard's row, or the
    engine's -1 for an absent one — is thrown away behind ``ok``, so
    which row it reads is free to choose, and it reads a spare row of
    its own: its flat position in ``rows`` times :data:`SPARE_STRIDE`,
    modulo the shard's ``capacity``.  In range by construction, and
    scattered over the whole shard.

    Since the gather runs over a lane window (:func:`window_gather`)
    ``rows`` is as a rule one window of a round's index, and the lanes
    that are not owned are the window's slack — the sixteenth it is cut
    wider than a shard's share, the lookups of a neighbouring home that
    fall inside it, the lanes of lookups that are done — and, in a
    bootstrap round, a skewed wave or a pass beyond the first, whatever
    else the pass covers: up to three lanes in four again.

    Why not leave ``rows - base`` to the gather's clip: the clip sends
    every such lane to row 0 or to the last row, three lanes in four at
    ``t`` = 4 where the index is a whole wave, and a gather out of HBM
    whose lanes crowd into few tiles of the view pays 15 ns a row where
    a scattered index pays 9.7
    (PERF.md §7 (1), the probe of PR 33: a 25M-row 2-limb view,
    1,572,864 lanes, a quarter of them owned).  It is the crowding that
    costs, not the one row: the lane's own position as its spare —
    distinct but CONSECUTIVE rows, 128 lanes a tile — reads 15.6, as
    dear as the clip, and the global row wrapped into the shard reads
    9.9 but 14.5 once two lanes in three are absent and fall back on
    their position.  The stride holds 9.6–9.7 at every mix, which is
    why it is the rule; it is a constant of the program.  ``ok`` and
    everything behind the gather are untouched, so every output bit is.
    """
    loc = rows - base
    ok = (loc >= 0) & (loc < n_owned)
    lane = lax.iota(_U32, rows.size).reshape(rows.shape)
    spare = lane * _U32(SPARE_STRIDE) % _U32(capacity)
    return jnp.where(ok, loc, spare.astype(jnp.int32)), ok


# A wave narrower than this keeps its gathers at full width (the toy
# waves of the tests and rehearsals, and the 1,024-lane sub-wave a cell's
# wave ends in: an index of 24,576 rows, a few tenths of a millisecond).
WINDOW_MIN_LANES = 2048
_LANE_TILE = 128


def window_width(lanes: int, n_t: int) -> int:
    """The lane window of a shard's gather (:func:`window_gather`), a
    static function of the lane count alone: a shard's share of the
    lanes and a sixteenth more, rounded up to whole 128-lane tiles —
    17,408 of 65,536 lanes at ``t`` = 4, 2,176 of 8,192 — and ``lanes``
    itself where that is not smaller or the wave is under
    :data:`WINDOW_MIN_LANES`: the gather is then the one full-width
    pass it always was.  The sixteenth holds what a grouped uniform
    wave needs over its share: the up to 127 lanes an aligned start
    gives away, and the spread of a home's count (16,384 ± 111 at
    65,536 targets: 9σ)."""
    if lanes < WINDOW_MIN_LANES:
        return lanes
    width = -(-lanes * 17 // (n_t * 16 * _LANE_TILE)) * _LANE_TILE
    return min(width, lanes)


def lane_window(lane_any, width: int):
    """``(start, passes)`` of the windows that cover the set lanes of
    ``lane_any`` [lanes]: the first set lane rounded down to a 128-lane
    tile, and ``ceil(span / width)`` windows of ``width`` lanes from
    there, ``span`` reaching to the last set lane; no lane set, no pass.
    Pass ``p`` serves lanes ``[start + p·width, start + (p+1)·width)``,
    so every set lane is served by exactly one."""
    lanes = lane_any.shape[0]
    lane = jnp.arange(lanes, dtype=jnp.int32)
    first = jnp.min(jnp.where(lane_any, lane, lanes))
    last = jnp.max(jnp.where(lane_any, lane, -1))
    start = first // _LANE_TILE * _LANE_TILE
    span = jnp.maximum(last + 1 - start, 0)
    return start, (span + (width - 1)) // width


def window_gather(view, rows, base, n_owned, capacity: int, limbs: int,
                  width: int):
    """One shard's part of a distributed row fetch: ``limbs`` planes
    ``[limbs, *rows.shape]`` of the global ``rows`` it owns (``base <=
    row < base + n_owned``), 0 in every other lane, gathered over a
    LANE WINDOW — and whether ONE pass did it (int32 0 / 1).

    A gathered row costs HBM's 9.6 ns whether its lane is owned or
    thrown away (PERF.md §7 (1)), so the cost of a round is the number
    of indices ISSUED, and a shard that issues a whole wave's index to
    keep a quarter of it pays four times its share.  Which lanes it owns
    is nearly a property of the lookup: from loop round 1 on every reply
    row of a lookup lies in the shard whose key range holds its target
    (the reply model answers from the block that shares one more bit
    with the TARGET than the queried peer does, core/search.py
    ``_reply_rows``; only a fallback window that straddles a shard edge
    leaves it).  ``build_tp_lookup`` therefore groups a wave's lanes by
    home shard, and the lanes a shard owns are then one run of about
    ``lanes / t``.  The rule here knows nothing of that; it reads the
    index, as :func:`owner_local_index` does:

    - ``ok`` over the whole index (compares: no memory), the lanes (the
      MINOR axis of ``rows``) with any owned row, their first and last:
      :func:`lane_window`;
    - as many passes of ONE executable as cover them: the index's
      ``width`` lanes from a tile-aligned offset, ``owner_local_index``
      on those, the gather, the mask, written into a zeroed
      ``[limbs, *rows.shape]``.  The last window is held inside the
      index, so it may take lanes of the one before again, to the same
      values.

    One pass is the grouped case.  A bootstrap round (a random peer's
    block: two shards a lookup), a skewed or clustered wave, a wave
    nobody grouped take up to ``t`` passes — a full-width gather's
    indices and a sixteenth — and a shard none of whose rows is asked
    for takes none.  The pass count is this shard's own: keep every
    collective OUTSIDE (the caller's ``psum`` takes the finished
    planes).  With ``width`` = the lane count (:func:`window_width`:
    small waves, and an index whose minor axis is not the wave — the
    final id fetch is lookup-major ``[W, k]``) it is the full-width
    gather, operation for operation, and one pass by definition.
    """
    def part(view, rows):
        loc, ok = owner_local_index(rows, base, n_owned, capacity)
        return jnp.stack([jnp.where(ok, plane, _U32(0)) for plane in
                          fused_gather_planar(view, loc, limbs)])

    lanes, axis = rows.shape[-1], rows.ndim - 1
    if width >= lanes:
        return part(view, rows), jnp.int32(1)
    # a view that is sliced at the gather (ops.sorted_table
    # .loop_gather_view) is sliced once for all passes, where the
    # full-width gather slices it: in the caller's loop body, not in
    # the pass loop's
    view = view[:limbs]
    _, ok = owner_local_index(rows, base, n_owned, capacity)
    start, passes = lane_window(jnp.any(ok, axis=tuple(range(axis))), width)

    def one_pass(p, planes):
        at = jnp.minimum(start + p * width, lanes - width)
        return lax.dynamic_update_slice_in_dim(
            planes,
            part(view, lax.dynamic_slice_in_dim(rows, at, width, axis=axis)),
            at, axis=axis + 1)

    planes = lax.fori_loop(0, passes, one_pass,
                           jnp.zeros((limbs,) + rows.shape, _U32))
    return planes, (passes <= 1).astype(jnp.int32)


def lane_chunk(lanes: int, n_t: int) -> int:
    """The lanes of a ``q``-rank's wave that ONE ``t``-rank runs the
    engine over (:func:`build_tp_lookup`, THE SEARCH STATE), a static
    function of the lane count alone, like :func:`window_width`: an
    equal share, ``lanes / t`` — 16,384 of 65,536 at ``t`` = 4 — and the
    whole wave where ``t`` does not divide it (or is 1): every rank then
    runs every lookup, and :func:`lane_exchange` is one ``psum``."""
    return lanes // n_t if lanes % n_t == 0 else lanes


def lane_exchange(owner_read, chunked: bool, stage: Optional[str] = None,
                  has_aux: bool = False):
    """Turn an owner-shard read written for the WHOLE wave into one for
    this shard's CHUNK of it (:func:`lane_chunk`) — the one way a lookup
    that runs on one shard reaches the rows of another.

    ``owner_read(whole)`` is this shard's PART of a distributed read:
    the answer in the lanes whose row it owns, 0 in every other, so that
    the parts' sum over ``t`` is the read.  The returned ``read(arg,
    axis, part_axis)`` takes the chunk's argument, the wave's lanes on
    its ``axis``:

    - ``all_gather`` it over ``t`` along that axis, tiled — the chunks
      are equal runs of the wave in rank order, so this IS the wave's
      argument, and ``owner_read`` runs on it at full width, exactly as
      when every rank held the whole wave;
    - ``psum`` the part over ``t`` and keep this rank's run of the lanes
      of ``part_axis`` (the lane axis of the answer: ``axis`` unless
      given): every lane's sum goes to the shard that runs the lane.
      Written as the all-reduce and the slice, not as ``psum_scatter``:
      the TPU compiler renders a reduce-scatter as exactly those two
      (v5e 2x2: the optimised program holds no reduce-scatter) and
      gives the all-reduce it makes NO ``op_name``, so the round's
      cross-shard cost would leave its stage and land under none
      (PERF.md §6, PR 38: 6.1 ms a wave did).

    The read is elementwise in its lanes, so any axis all ranks agree on
    gives the same values; which one matters to ``owner_read`` alone
    (:func:`window_gather` windows the minor one).  Not ``chunked`` (a
    chunk that is the whole wave): no gather and no slice, the ``psum``
    it always was.  Both collectives run under device stage ``stage``
    (none: unstaged, as the positioning's always was); ``has_aux``:
    ``owner_read`` returns ``(part, aux)`` and ``aux`` — this shard's
    own report, no lane's — comes back beside the answer.
    """
    staged = device_stage(stage) if stage else (lambda fn: fn)

    def read(arg, axis: int = 0, part_axis: Optional[int] = None):
        part_axis = axis if part_axis is None else part_axis
        width = arg.shape[axis]                    # the chunk's lanes
        if chunked:
            arg = staged(lambda chunk: lax.all_gather(
                chunk, "t", axis=axis, tiled=True))(arg)
        part, aux = owner_read(arg) if has_aux else (owner_read(arg), None)

        @staged
        def own_lanes(part):
            whole = lax.psum(part, "t")
            if not chunked:
                return whole
            return lax.dynamic_slice_in_dim(
                whole, lax.axis_index("t") * width, width, axis=part_axis)

        mine = own_lanes(part)
        return (mine, aux) if has_aux else mine

    return read


def shard_offset(widths, n_t: int):
    """``(rows of the shards before this one, rows of all shards)`` from
    the ``n_t`` shards' ``widths``: where this shard's rows begin in an
    order that is the shards' in turn."""
    before = jnp.arange(n_t) < lax.axis_index("t")
    return jnp.sum(jnp.where(before, widths, 0)), jnp.sum(widths)


def _tp_churn_primitives(shard_n: int, delta_rows: int, n_t: int,
                         chunked: bool, base, n_local, tomb_bits, delta,
                         n_delta, delta_lut):
    """The engine's two CHURN primitives over ONE SHARD's piece of a
    row-sharded mutable table (parallel/churn.py) — the tp twins of
    ``core.search._churn_primitives``, each a shard-local read by the
    owner, for the whole wave, inside one :func:`lane_exchange` over
    ``t`` (``chunked``: the engine runs a chunk of the wave).

    A node is a GLOBAL base row, or ``t·shard_n`` + a place in the
    global order of the shards' deltas; this shard owns base rows
    ``[base, base + n_local)`` and delta places ``[d_base, d_base +
    n_delta)``, ``d_base`` the delta rows of the shards before it (one
    ``all_gather`` of four counts, once a wave).  Its liveness words
    cover its own rows, laid out as the one-device table's: local base
    row ``r`` is bit ``r``, local delta slot ``j`` bit ``shard_n + j``.

    ``alive(nodes)``: the owner reads the bit, every other shard a spare
    word of its own (:func:`owner_local_index`) that it throws away, and
    the shards' answers are summed to the lanes' shard — stage
    ``alive_merge``, inside the engine's ``expire``.
    ``delta_window(targets)``: the targets' place in the global order of
    the deltas is the sum of their places in the shards' (as ``lower``
    is for the base), and the ``DELTA_WINDOW`` rows around it are
    fetched by their owners — a window that straddles a shard edge takes
    rows of both — and summed: two exchanges, stage ``delta_merge``,
    inside the engine's ``delta_window``.
    """
    total = n_t * shard_n                  # where the delta's nodes start
    d_base, d_total = device_stage("delta_merge")(
        lambda n: shard_offset(lax.all_gather(n, "t"), n_t))(n_delta)

    def owner_gone(nodes):
        in_delta = nodes >= total
        loc, ok = owner_local_index(
            jnp.where(in_delta, nodes - total, nodes),
            jnp.where(in_delta, d_base, base),
            jnp.where(in_delta, n_delta, n_local), shard_n)
        gone = ok & node_gone(tomb_bits,
                              jnp.where(ok & in_delta, shard_n + loc, loc))
        return gone.astype(jnp.int32)

    gone = lane_exchange(owner_gone, chunked, "alive_merge")

    def alive(nodes):
        return gone(nodes, nodes.ndim - 1) == 0

    place = lane_exchange(_guarded_lower_bound(delta, n_delta, delta_lut),
                          chunked, "delta_merge")
    delta_t = delta.T

    def owner_rows(slot):
        loc, ok = owner_local_index(slot, d_base, n_delta, delta_rows)
        return jnp.stack([jnp.where(ok, plane, _U32(0)) for plane in
                          fused_gather_planar(delta_t, loc)])

    rows = lane_exchange(owner_rows, chunked, "delta_merge")

    def delta_window(targets):
        slot = (place(targets)[None, :] - DELTA_WINDOW // 2
                + jnp.arange(DELTA_WINDOW, dtype=jnp.int32)[:, None])
        ids = rows(slot, 1, 2)
        node = jnp.where((slot >= 0) & (slot < d_total), total + slot, -1)
        return node.T, [ids[l].T for l in range(N_LIMBS)]

    return {"alive": alive, "delta_window": delta_window}


@functools.lru_cache(maxsize=16)
def build_tp_lookup(mesh: Mesh, shard_n: int, q_total: int, k: int,
                    alpha: int, search_nodes: int, max_hops: int,
                    state_limbs: int = N_LIMBS, weighted: bool = False,
                    delta_rows: int = 0):
    """Compile the table-sharded iterative lookup for one geometry.

    Returns a jitted ``fn(sorted_ids, local_lut, block_lut, n_valid,
    targets, seed)`` over the row-sharded table state a single
    ``partition.shard_table_state`` call builds and places (sorted
    rows + per-shard positioning LUT P('t', None), replicated global
    block LUT, ``targets`` P('q', None)).  Public so honest benchmarks
    can wrap the callable in a serialized rep chain
    (``bench.chain_slope``) instead of wall-timing dispatches —
    :func:`tp_simulate_lookups` is the convenience entry that builds
    and places the state per call.

    THE SEARCH STATE (PR 38) is sharded over ``t`` as well as over
    ``q``: a lookup's rounds run on ONE shard.  After the grouping
    (below) shard ``ti`` keeps lanes ``[ti·Wl, (ti+1)·Wl)`` of its
    ``q``-rank's wave, ``Wl = lane_chunk(q_local, t)`` — equal chunks of
    the grouped order, not "the lanes whose home I am": the split is
    static and exact, a skewed wave stays balanced in compute, and on a
    uniform wave a chunk is its shard's home lanes to within the spread
    of the home counts (``home_lanes`` counts them) — and runs
    ``_lookup_engine`` over those: the LUT edge reads, the merge sorts,
    ``select``, ``converge`` and ``pack`` of a lookup are done by one
    chip, where every chip did them for every lookup while the state
    was replicated.  Only the engine's primitives, the places a lookup
    touches the TABLE, cross the mesh, each through one
    :func:`lane_exchange` — the chunks' argument all-gathered over
    ``t``, the shard's part computed for the whole wave as before, the
    parts summed back to the lanes' shard: ``gather_planar``
    (stage ``owner_merge``: the round's two collectives), ``lower``
    (positioning, once a wave) and under churn ``alive`` and
    ``delta_window``.  ``block_bounds`` needs nothing: the block LUT is
    the replicated GLOBAL prefix LUT (``shard_table_state`` assembled
    it with a single one-shot psum of the per-shard LUTs at table-build
    time, so the values are bit-identical to the sum of per-shard
    counts), and a shard reads the edges of its own lanes.  The engine's
    one cross-lane fact, the live count its loops and its cut read, is
    handed in as the FULLEST shard's (``pmax`` over ``t``): the loop
    bodies hold collectives, so every ``t``-rank runs the same number
    of rounds and cuts in the same one, once every shard's survivors
    fit its ``C`` (``NARROW_MIN_WAVE`` / ``NARROW_DIVISOR`` apply to
    ``Wl``: 16,384 → 2,048 lanes a shard).  Behind the engine the
    outputs are all-gathered over ``t`` (stage ``group``) and
    ``expired_peers`` is summed.  The reply hash is keyed by the global
    ``q_index``, which travels with its lane, so no output bit depends
    on where a lane runs.  A wave ``t`` does not divide takes the same
    code with a chunk that is the whole wave: every rank runs every
    lookup, as all did before PR 38, and the exchange is its ``psum``.

    The exchange's shape is the round's index: ``[α·k, W]`` int32
    gathered and ``[NL, α·k, W]`` scattered in a loop round (``C`` lanes
    a shard once the wave has cut), ``[k, W]`` / ``[NL, k, W]`` in the
    bootstrap round, whose ONE peer answers with k rows (core/search.py
    ``_lookup_engine``, BOOTSTRAP SHAPE; lut mode's bootstrap also
    fetches that peer's top limb, ``[1, W]``), and ``[W, k]`` /
    ``[5, W, k]`` for the final id fetch, whose lanes are its MAJOR
    axis (the wave is an index's longest axis).  What the gathers read
    is decided once, before the engine runs
    (``ops.sorted_table.loop_gather_view``): a shard whose limb view is
    too large for on-chip memory is not sliced inside a loop body.

    THE HOME-LANE WINDOW (PR 35).  The row fetch is NOT full-width: a
    gathered row costs what an issued index costs, owned or thrown
    away, so a shard issues the lanes it owns and little more.  A wave
    of ``WINDOW_MIN_LANES`` lookups or more is grouped by the HOME shard
    of its targets once, before the engine (stage ``group``: one
    ``all_gather`` of ``t`` words, one stable sort on a ``log2 t``-bit
    key; the outputs go back to the caller's order behind the engine),
    and every gather whose index has the lookups on its minor axis runs
    over a lane window of ``window_width(lanes, t)`` — 17,408 of 65,536
    lanes at ``t`` = 4 — in as many passes of one executable as cover
    the lanes the shard owns (:func:`window_gather`): one in a loop
    round of a grouped wave, two to ``t`` in the bootstrap round (a
    random peer's block spans two shards), for a lookup whose fallback
    window straddles a shard edge, or on a skewed wave; ``t`` passes
    are a full-width gather and a sixteenth.  The pass loop holds no
    collective: the round's ``psum`` takes the finished planes.  Inside
    a window every lane reads a row of the shard
    (:func:`owner_local_index`): a lane that is not owned reads a spare
    row of its own, never the one row a clip would send them all to,
    and is zeroed before the collective.  The lookup-major final fetch
    and the churn primitives' owner reads keep one full-width pass.
    The program also returns ``window_rounds``, one count a ``q``-rank:
    the wave's in-loop round gathers that EVERY shard served in one
    pass (the engine sums each shard's report, one ``pmin`` over ``t``
    once a wave) — the wave's loop rounds where the grouping holds —
    and ``home_lanes``, the lanes of the rank's wave that run on their
    home shard (all but a few hundred of a uniform wave's; a skewed
    wave reads low, and says why ``window_rounds`` fell).  Every other
    output bit is the one-chip engine's.

    ``delta_rows`` is part of the geometry like ``shard_n``: the delta
    slab of each shard of a table under membership CHURN
    (parallel/churn.py ``ShardedChurnTable.view``; 0 = a table that is
    built once).  The program then takes the shards' liveness words,
    deltas, delta row counts and delta LUTs after ``seed``, hands the
    engine its two churn primitives (:func:`_tp_churn_primitives`) and
    returns ``expired_peers`` too, one count a ``q``-rank.  With 0 it
    is, operation for operation, the program it was before.
    """
    q_local = q_total // mesh.shape["q"]
    n_t = mesh.shape["t"]
    if delta_rows and not weighted:
        raise ValueError("a table under churn lies in the weighted layout")

    def local(*op):
        op, churn_op = (op[:-4], op[-4:]) if delta_rows else (op, None)
        if weighted:
            # load-aware layout (ISSUE-17): each shard owns rows
            # [base, base+width) of the global sorted order, carried as
            # DATA in the [1, 2] shard_rows slice — the kernel text is
            # identical for every boundary placement, so a hot swap
            # never recompiles.  shard_n is the per-shard row CAPACITY
            # (rows beyond the width are zero padding).
            (sorted_shard, local_lut, block_lut, n_valid, shard_rows,
             targets_local, seed) = op
            base = shard_rows[0, 0]
            n_local = shard_rows[0, 1]
            n = jnp.asarray(n_valid, jnp.int32)
        else:
            (sorted_shard, local_lut, block_lut, n_valid, targets_local,
             seed) = op
            ti = lax.axis_index("t")
            base = (ti * shard_n).astype(jnp.int32)
            n = jnp.asarray(n_valid, jnp.int32)
            n_local = jnp.clip(n - base, 0, shard_n)
        local_lower = _guarded_lower_bound(sorted_shard, n_local,
                                           local_lut[0])
        sorted_t = sorted_shard.T                        # [5, shard_n]
        # what the gathers read, decided once: gather_planar runs inside
        # the engine's while_loop bodies, where a slice of a shard too
        # large for on-chip memory (25M rows: 200 MB) is copied every
        # round for nothing (core/search.py _lookup_engine, the
        # gather_planar contract)
        views = {l: loop_gather_view(sorted_t, l)
                 for l in (1, state_limbs, N_LIMBS)}

        # THE SEARCH STATE: this rank runs the engine over a chunk of the
        # wave, and the engine's primitives exchange lanes across t
        chunk = lane_chunk(q_local, n_t)
        chunked = chunk < q_local

        # global lower bound = Σ_shards (local rows < q): each shard's
        # local lower-bound index IS that count, and the global sorted
        # order is the in-order concatenation of shard ranges — one
        # [M]-int32 exchange over the table axis.  Called ONCE per wave
        # (the pre-loop target positioning), never inside the hop loop.
        lower = lane_exchange(local_lower, chunked)

        def block_bounds(t0, prefix_len):
            # ZERO collectives: the block LUT is the replicated GLOBAL
            # prefix LUT (built once per table — shard_table_state), so
            # both edges are plain local gathers, of this rank's own
            # lanes.  Values are the exact Σ-of-per-shard-counts the
            # round-12 in-loop psum computed, hence bit-identical to the
            # single-device engine at the same block width
            # (default_lut_bits(N), never the shard size — a
            # shard-sized width would make the clamp depth, and hence
            # the reply stream, vary with the mesh split).
            return _lut_block_bounds(block_lut, t0, prefix_len)

        def gather_planar(rows, limbs=N_LIMBS):
            # distributed row fetch: of the WHOLE wave's index (the
            # chunks', all-gathered) the owning shard contributes the
            # row's limbs, every other shard zeros — the exchange's sum
            # reassembles a chunk's rows on its shard.  Rows are
            # pre-clipped to [0, n) by the engine; a -1 (absent) row is
            # owned by no shard and comes back 0, masked by the engine
            # exactly like the unsharded garbage.  With the round-6
            # fused engine this runs ONCE per round (the α·k reply
            # fetch).  The index keeps ``rows``' own shape up to the
            # gather (the engine's: slot-major, the lookups on the
            # lanes) and the planes come back in it
            # (ops.sorted_table.fused_gather_planar).  Ownership:
            # weighted shards own exactly n_local rows (the
            # [b_i, b_{i+1}) ranges partition the valid prefix); the
            # uniform test keeps the static width, equivalent for valid
            # rows.  The shard gathers over the lane window that holds
            # the lanes it owns, in as many passes as cover them
            # (window_gather: one where the wave is grouped by home,
            # below), and says whether one did — the engine's optional
            # count.  What a lane that is not owned reads is
            # owner_local_index's one rule, whatever the layout or the
            # limb count.
            def owner_rows(rows):
                return window_gather(
                    views[limbs], rows, base,
                    n_local if weighted else shard_n, shard_n, limbs,
                    window_width(rows.shape[-1], n_t))

            # the lookups are an index's longest axis: the minor one of
            # a round's slot-major [P·k, W], the major one of the
            # lookup-major final fetch [W, k]
            lanes = int(np.argmax(rows.shape))
            # the round's two collectives, a device stage of their own —
            # around the passes, whose number is each shard's own
            g, one_pass = lane_exchange(owner_rows, chunked, "owner_merge",
                                        has_aux=True)(rows, lanes, lanes + 1)
            return [g[l] for l in range(limbs)], one_pass

        churn = {}
        if delta_rows:
            tomb_bits, delta, n_delta, delta_lut = churn_op
            churn = _tp_churn_primitives(
                shard_n, delta_rows, n_t, chunked, base, n_local, tomb_bits,
                delta, n_delta[0], delta_lut[0])
        q_index = (lax.axis_index("q").astype(jnp.int32) * q_local
                   + jnp.arange(q_local, dtype=jnp.int32))
        grouped = window_width(q_local, n_t) < q_local
        home_lanes = jnp.int32(q_local)    # a chunk that is the whole wave
        if grouped or chunked:
            # GROUP THE WAVE BY HOME SHARD, once a wave: a lookup's home
            # is the shard whose key range holds its target — read off
            # the shards' first rows' top limbs, one all_gather of t
            # words — and from loop round 1 on every reply row of a
            # lookup lies there (window_gather), so with the lanes of a
            # home side by side the lanes a shard owns are one run of
            # about q_local / t, its gather covers them in one window,
            # and its chunk is, to a few hundred lanes, the lookups
            # whose rows it holds.  A stable partition on a log2(t)-bit
            # key that carries the operands (never a sort by the target:
            # a sorted index is dearer out of HBM, PERF.md §7 (1)).  A
            # hint only: a home that is off (shards that share a top
            # limb, an empty shard) costs passes, never an answer.  The
            # reply hash is keyed by the GLOBAL q_index, which travels
            # with its lane; pack keeps lane order, so a shard's narrow
            # sub-wave stays grouped; and the outputs are put back in
            # the caller's order below.  A wave under WINDOW_MIN_LANES
            # is chunked in the caller's order; its homes are counted
            # all the same.
            @device_stage("group")
            def group(first_row, targets, q_index):
                first = lax.all_gather(
                    jnp.where(n_local > 0, first_row, _U32(0xFFFFFFFF)), "t")
                home = sum((targets[:, 0] >= first[s]).astype(jnp.int32)
                           for s in range(1, n_t))
                if grouped:
                    home, q_index, *limbs = lax.sort(
                        (home, q_index) + tuple(targets[:, l]
                                                for l in range(N_LIMBS)),
                        num_keys=1, is_stable=True)
                    targets = jnp.stack(limbs, axis=1)
                if not chunked:
                    return targets, q_index, q_index, home_lanes
                # lane i runs on shard i // chunk
                ran_at = jnp.arange(q_local, dtype=jnp.int32) // chunk
                mine = lax.axis_index("t") * chunk
                return (lax.dynamic_slice_in_dim(targets, mine, chunk),
                        lax.dynamic_slice_in_dim(q_index, mine, chunk),
                        q_index, jnp.sum(home == ran_at, dtype=jnp.int32))

            # wave_order: whose lookup each lane of the wave holds, the
            # chunks side by side in rank order
            targets_local, q_index, wave_order, home_lanes = group(
                sorted_shard[0, 0], targets_local, q_index)
        out = _lookup_engine(
            gather_planar, lower, n, targets_local, q_index, q_total,
            seed.astype(_U32), k=k, alpha=alpha, search_nodes=search_nodes,
            max_hops=max_hops, state_limbs=state_limbs,
            block_bounds=block_bounds, **churn,
            # the fullest shard's live count: the loops hold collectives
            # over t, so every t-rank runs the same rounds and cuts in
            # the same one, when every shard's survivors fit
            live_count=(lambda done: lax.pmax(jnp.sum(~done), "t"))
            if chunked else None)
        # a chunk wide enough for LANE TILES counts its tiled rounds;
        # the one-chip engine's series, not carried over the mesh
        out.pop("tiled_rounds", None)
        per_lookup = {name: out[name]
                      for name in ("nodes", "dist", "hops", "converged")}
        if chunked:
            @device_stage("group")
            def whole_wave(per_lookup, counts):
                # the chunks' outputs, side by side in rank order: the
                # (grouped) wave's; and the shards' counts, summed
                return jax.tree.map(
                    lambda a: lax.all_gather(a, "t", axis=0, tiled=True),
                    per_lookup), lax.psum(counts, "t")

            per_lookup, counts = whole_wave(
                per_lookup,
                {"expired_peers": out["expired_peers"]} if delta_rows else {})
            out.update(counts)
        if grouped:
            @device_stage("group")
            def ungroup(wave_order, per_lookup):
                # lane i holds the lookup of the caller's lane
                # wave_order[i] - wave_order.min(): the inverse of that
                # permutation by one more sort, and a gather each
                back = lax.sort((wave_order, jnp.arange(q_local,
                                                        dtype=jnp.int32)),
                                num_keys=1)[1]
                return jax.tree.map(
                    lambda a: jnp.take(a, back, axis=0), per_lookup)

            per_lookup = ungroup(wave_order, per_lookup)
        out.update(per_lookup, home_lanes=home_lanes)
        # the wave's in-loop gathers that EVERY shard served in one pass
        out["window_rounds"] = lax.pmin(out["window_rounds"], "t")
        # one count a q-rank (t-ranks run the same rounds and cut in the
        # same one; the other counts are sums or the least over them)
        return {name: value[None] if name in per_rank else value
                for name, value in out.items()}

    per_rank = ("narrow_rounds", "expired_peers", "window_rounds",
                "home_lanes")
    in_specs = ((P("t", None), P("t", None), P(), P(), P("t", None),
                 P("q", None), P()) if weighted else
                (P("t", None), P("t", None), P(), P(), P("q", None), P()))
    out_specs = {"nodes": P("q", None), "dist": P("q", None, None),
                 "hops": P("q"), "converged": P("q"), "narrow_rounds": P("q"),
                 "window_rounds": P("q"), "home_lanes": P("q")}
    if delta_rows:
        in_specs += (P("t"), P("t", None), P("t"), P("t", None))
        out_specs["expired_peers"] = P("q")
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(fn)


def tp_simulate_lookups(mesh: Mesh, sorted_ids=None, n_valid=None,
                        targets=None, *, seed: int = 0, k: int = TARGET_NODES,
                        alpha: int = ALPHA, search_nodes: int = SEARCH_NODES,
                        max_hops: int = 48, state_limbs: int = N_LIMBS,
                        state: "TableState | None" = None):
    """Iterative lookups with the sorted table ROW-SHARDED over ``t`` —
    the multi-chip north star: tables larger than one chip's HBM are
    searched iteratively, not just scanned (100M ids over a four-chip
    host is the benchmark cell ``host4-100m.wave-65536``, PERF.md §4).

    The table must be GLOBALLY sorted, each ``t``-shard one contiguous
    range of the global order — the Kademlia analog of a node owning
    the contiguous XOR neighborhood around its id (PARITY.md "t-sharded
    table").  For a table that is sharded because it outgrows one chip,
    build ``state=`` with
    :func:`~opendht_tpu.parallel.global_sort.sharded_global_sort`: it
    sorts ids that already lie row-sharded ACROSS the mesh, and nothing
    of table size passes through one device or the host.  A table that
    fits one memory may still be sorted there (:func:`sort_table`) and
    split by ``shard_table_state``.  That contiguity is what makes the
    distributed primitives cheap:

    - positioning (once per wave): global lower_bound = ONE sum of
      per-shard local counts;
    - reply-block edges (per hop): two LOCAL reads of the replicated
      global block LUT — ZERO collectives (see
      :func:`build_tp_lookup`);
    - row fetch (per hop): owner-shard gather inside ONE lane exchange
      (:func:`lane_exchange`) — the round's only in-loop collectives,
      O(queries·k) bytes, never O(table).  A shard gathers only the
      lane window that holds the lookups whose targets live in its key
      range (a wave is grouped by home shard first;
      :func:`build_tp_lookup`, THE HOME-LANE WINDOW): about a ``t``-th
      of the round's indices and a sixteenth, not all of them.

    Search state is sharded over ``q`` AND over ``t`` (PR 38;
    :func:`build_tp_lookup`, THE SEARCH STATE): each ``t``-rank runs a
    lookup's rounds — LUT edge reads, merge sorts, selection — for an
    equal chunk of its ``q``-rank's wave, ``lane_chunk(Q / q, t)``
    lanes, and only the three reads above cross the mesh; a wave ``t``
    does not divide runs whole on every rank.  Results are
    BIT-IDENTICAL to :func:`~opendht_tpu.core.search.simulate_lookups`
    on the same table, in the caller's order (the reply hash is seeded
    by global query identity, which travels with a lookup's lane) —
    asserted in tests/test_sharded.py.  ``narrow_rounds`` [q] counts
    the rounds after the cut of a CHUNK (``NARROW_MIN_WAVE`` applies to
    the chunk's width).  The result also carries ``window_rounds`` [q]:
    the wave's loop rounds in which every shard gathered in one pass
    (``dht_search_window_rounds{mode="tp"}``), and ``home_lanes`` [q]:
    the lanes that ran on the shard whose key range holds their target
    (``dht_search_home_lanes{mode="tp"}``).

    Callers serving a stable table should pass ``state=`` from
    ``sharded_global_sort`` or
    :func:`~opendht_tpu.parallel.partition.shard_table_state` (built
    once, reused across waves — the sorted rows and positioning LUTs
    then never re-place or re-derive per call); the raw
    ``sorted_ids``/``n_valid`` form takes an already sorted table and
    builds a state pytree on the fly.

    targets [Q, 5]: Q divisible by mesh.shape['q']; N divisible by
    mesh.shape['t'] (pad via :func:`pad_to_multiple` — pad rows land
    on the LAST shard).  Ref: the loop being scaled is searchStep,
    /root/reference/src/dht.cpp:561-654.
    """
    if state is None:
        if sorted_ids is None or n_valid is None:
            raise ValueError("pass either (sorted_ids, n_valid) or state=")
        state = shard_table_state(mesh, sorted_ids, n_valid)
    if targets is None:
        raise ValueError("targets are required")
    Q = targets.shape[0]
    if Q % mesh.shape["q"]:
        raise ValueError(f"targets ({Q}) not divisible by q axis "
                         f"{mesh.shape['q']}")
    a = state.arrays
    weighted = "shard_rows" in a
    # a table under membership churn (parallel/churn.py) brings its
    # shards' deltas and liveness words: read off the table, as
    # simulate_lookups reads a ChurnTable off its first argument
    delta_rows = (a["delta"].shape[0] // mesh.shape["t"]
                  if "delta" in a else 0)
    fn = build_tp_lookup(mesh, state.shard_n, Q, k, alpha, search_nodes,
                         max_hops, state_limbs, weighted, delta_rows)
    targets = shard_put(mesh, {"targets": _as_operand(targets, np.uint32)},
                        TABLE_AXIS_RULES)["targets"]
    if weighted:
        args = (a["sorted_ids"], a["local_lut"], a["block_lut"],
                a["n_valid"], a["shard_rows"], targets,
                jnp.asarray(seed, jnp.int32))
    else:
        args = (a["sorted_ids"], a["local_lut"], a["block_lut"],
                a["n_valid"], targets, jnp.asarray(seed, jnp.int32))
    if delta_rows:
        args += (a["tomb_bits"], a["delta"], a["n_delta"], a["delta_lut"])
    from .. import telemetry
    if not telemetry.get_registry().enabled:
        return fn(*args)
    # the single-device entry's host-side envelope (core/search.py
    # _run_wave): the traced computation is untouched, the wave/hops
    # series and the tracer's wave span land under mode="tp"
    return _run_wave(lambda: fn(*args), Q, "tp")


@functools.lru_cache(maxsize=8)
def _build_sharded_maintenance(mesh: Mesh):
    from ..ops import radix

    def local(self_id, ids, valid, last_reply, now, age, key):
        # per-shard [160, N_s] compare-and-reduce, then one collective
        # per statistic: occupancy sums (int32 — exact) and last-reply
        # maxes (max of per-shard maxes — exact) over the table axis
        counts = lax.psum(radix.bucket_counts(self_id, ids, valid), "t")
        last = lax.pmax(
            radix.bucket_last_seen(self_id, ids, valid, last_reply), "t")
        stale = (counts > 0) & (last < now - age)
        # refresh ids depend only on (self_id, key) — replicated compute,
        # bit-identical to the single-device radix call (same key, same
        # shape => same threefry stream)
        targets = radix.random_id_in_bucket(
            self_id, jnp.arange(radix.ID_BITS, dtype=jnp.int32), key)
        return counts, last, stale, targets

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P("t", None), P("t"), P("t"), P(), P(), P()),
        out_specs=(P(), P(), P(), P(None, None)),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_maintenance_sweep(mesh: Mesh, self_id, ids, valid, last_reply,
                              now, age, key):
    """tp twin of :func:`opendht_tpu.ops.radix.maintenance_sweep` (round
    10): the fused bucket-maintenance pass — occupancy + per-bucket
    last-reply staleness (never-replied ⇒ stale from birth) + a refresh
    target per bucket — over an [N, 5] id matrix ROW-SHARDED across the
    ``t`` axis, so tables past one chip's HBM sweep in one launch.

    Per shard the [160, N_s] compare-and-reduce runs locally; the only
    ICI traffic is one [160]-int32 psum (occupancy) and one [160]-float
    pmax (staleness) — O(buckets), never O(table).  Results are
    BIT-IDENTICAL to the single-device kernel on the same inputs
    (integer sums and maxes are exact under resharding; asserted in
    tests/test_sharded.py).

    ids: uint32 [N, 5] with N divisible by mesh.shape['t'] (pad with
    ``valid=False`` rows via :func:`pad_to_multiple`).  Returns
    (counts [160] int32, last [160], stale [160] bool,
    targets [160, 5] uint32), all replicated.
    """
    N = ids.shape[0]
    if N % mesh.shape["t"]:
        raise ValueError(f"table rows ({N}) not divisible by "
                         f"t={mesh.shape['t']}; pad via pad_to_multiple")
    if valid is None:
        valid = jnp.ones((N,), bool)
    fn = _build_sharded_maintenance(mesh)
    ops = shard_put(mesh, {"ids": _as_operand(ids, np.uint32),
                           "valid": _as_operand(valid, bool),
                           "last_reply": _as_operand(last_reply, np.float32)},
                    TABLE_AXIS_RULES)
    from .. import telemetry
    reg = telemetry.get_registry()
    reg.counter("dht_maintenance_sweeps_total", mode="tp").inc()
    with reg.span("dht_maintenance_sweep_seconds", mode="tp"):
        out = fn(jnp.asarray(self_id, _U32), ops["ids"], ops["valid"],
                 ops["last_reply"], jnp.asarray(now), jnp.asarray(age), key)
        jax.block_until_ready(out)
    return out


@functools.lru_cache(maxsize=8)
def _build_sharded_sketch(mesh: Mesh, depth: int, width: int):
    from ..ops.sketch import BIN_BITS, hash_columns

    def local(sketch, hist, ids, valid):
        # each shard scatter-adds its slice of the observed ids into a
        # ZERO partial sketch/histogram; ONE psum pair merges the
        # partials onto the replicated running state.  Integer adds
        # are associative and exact, so the merged result is
        # bit-identical to the single-device ops.sketch.sketch_update
        # over the same ids (tests/test_keyspace.py).  Pad rows carry
        # weight 0 — they touch cells but add nothing.
        w = valid.astype(jnp.int32)
        cols = hash_columns(ids, depth, width)            # [Qs, depth]
        rows = jnp.broadcast_to(jnp.arange(depth, dtype=jnp.int32),
                                cols.shape)
        part = jnp.zeros_like(sketch).at[
            rows.reshape(-1), cols.reshape(-1)].add(
            jnp.repeat(w, depth))
        bins = (ids[:, 0] >> _U32(32 - BIN_BITS)).astype(jnp.int32)
        ph = jnp.zeros_like(hist).at[bins].add(w)
        return sketch + lax.psum(part, "t"), hist + lax.psum(ph, "t")

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P("t", None), P("t")),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_sketch_update(mesh: Mesh, sketch, hist, ids):
    """tp twin of :func:`opendht_tpu.ops.sketch.sketch_update`
    (ISSUE-10): the wave's observed ids ROW-SPLIT over the ``t`` axis,
    each shard building a partial count-min sketch + top-8-bit
    histogram locally, merged with ONE psum pair — O(depth·width +
    bins) int32 wire, independent of the wave width.  Ragged widths
    pad with weight-0 rows (``pad_to_multiple``), so any Q works.

    Returns the updated replicated ``(sketch, hist)``, BIT-IDENTICAL
    to the single-device update over the same ids (integer adds are
    exact under resharding; pinned in tests/test_keyspace.py)."""
    ids = np.asarray(ids, np.uint32).reshape(-1, N_LIMBS)
    n_t = mesh.shape["t"]
    padded, n = pad_to_multiple(ids, n_t)
    valid = np.arange(padded.shape[0]) < n
    fn = _build_sharded_sketch(mesh, int(sketch.shape[0]),
                               int(sketch.shape[1]))
    ops = shard_put(mesh, {"sketch_ids": padded,
                           "sketch_valid": valid}, TABLE_AXIS_RULES)
    return fn(jnp.asarray(sketch, jnp.int32), jnp.asarray(hist, jnp.int32),
              ops["sketch_ids"], ops["sketch_valid"])


@functools.lru_cache(maxsize=8)
def _build_sharded_cache_probe(mesh: Mesh, capacity: int):
    def local(cache_ids, valid, targets):
        # each shard XOR-compares ITS slice of the wave's targets
        # against the replicated [C, 5] cache table — all-limb equality
        # == XOR distance exactly zero, the ops/cache_probe.py compare,
        # fully data-parallel (no collective: outputs stay t-split and
        # the caller gathers)
        t = targets.astype(_U32)
        c = cache_ids.astype(_U32)
        eq = jnp.all(t[:, None, :] == c[None, :, :], axis=-1) \
            & valid[None, :]
        hit = jnp.any(eq, axis=1)
        slot = jnp.where(hit, jnp.argmax(eq, axis=1).astype(jnp.int32),
                         jnp.int32(-1))
        return hit, slot

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P("t", None)),
        out_specs=(P("t"), P("t")),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_cache_probe(mesh: Mesh, cache_ids, valid, targets):
    """tp twin of :func:`opendht_tpu.ops.cache_probe.cache_probe`
    (ISSUE-11): the wave's probe targets ROW-SPLIT over the ``t`` axis
    against the replicated cache table, each shard answering its slice
    locally — zero collectives (membership is per-target), so the twin
    costs exactly the single-device compare divided by t.  Ragged
    widths pad (pad rows' answers are sliced off host-side), so any Q
    works.

    Returns host ``(hit [Q] bool, slot [Q] int32)``, BIT-IDENTICAL to
    the single-device probe over the same targets (pinned in
    tests/test_hotcache.py)."""
    t_np = np.asarray(targets, np.uint32).reshape(-1, N_LIMBS)
    n_t = mesh.shape["t"]
    padded, n = pad_to_multiple(t_np, n_t)
    fn = _build_sharded_cache_probe(mesh, int(cache_ids.shape[0]))
    ops = shard_put(mesh, {"probe_ids": padded}, TABLE_AXIS_RULES)
    hit, slot = fn(jnp.asarray(cache_ids, _U32),
                   jnp.asarray(np.asarray(valid, bool)),
                   ops["probe_ids"])
    return np.asarray(hit)[:n], np.asarray(slot)[:n]


@functools.lru_cache(maxsize=8)
def _build_sharded_listener_match(mesh: Mesh, capacity: int):
    def local(table_ids, valid, stored):
        # each shard XOR-compares ITS slice of the wave's stored-put
        # keys against the replicated [L, 5] listener table — the
        # ops/listener_match.py compare, fully data-parallel
        # (membership is per-stored-key: no collective; outputs stay
        # t-split and the caller gathers)
        s = stored.astype(_U32)
        t = table_ids.astype(_U32)
        eq = jnp.all(s[:, None, :] == t[None, :, :], axis=-1) \
            & valid[None, :]
        hit = jnp.any(eq, axis=1)
        slot = jnp.where(hit, jnp.argmax(eq, axis=1).astype(jnp.int32),
                         jnp.int32(-1))
        return hit, slot

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P("t", None)),
        out_specs=(P("t"), P("t")),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_listener_match(mesh: Mesh, table_ids, valid, stored):
    """tp twin of :func:`opendht_tpu.ops.listener_match.listener_match`
    (ISSUE-20): the wave's stored-put keys ROW-SPLIT over the ``t``
    axis against the replicated listener table, each shard answering
    its slice locally — zero collectives (membership is per-key), so
    the twin costs exactly the single-device compare divided by t.
    Ragged widths pad (pad rows' answers are sliced off host-side), so
    any S works.

    Returns host ``(hit [S] bool, slot [S] int32)``, BIT-IDENTICAL to
    the single-device match over the same keys (pinned in
    tests/test_listener.py at t∈{2,4})."""
    s_np = np.asarray(stored, np.uint32).reshape(-1, N_LIMBS)
    n_t = mesh.shape["t"]
    padded, n = pad_to_multiple(s_np, n_t)
    fn = _build_sharded_listener_match(mesh, int(table_ids.shape[0]))
    ops = shard_put(mesh, {"probe_ids": padded}, TABLE_AXIS_RULES)
    hit, slot = fn(jnp.asarray(table_ids, _U32),
                   jnp.asarray(np.asarray(valid, bool)),
                   ops["probe_ids"])
    return np.asarray(hit)[:n], np.asarray(slot)[:n]


@functools.lru_cache(maxsize=8)
def _dp_lut_builder(mesh: Mesh, bits: int):
    """Build the dp engine's prefix LUT FROM THE PLACED (replicated)
    table, with the output pinned replicated by
    ``with_sharding_constraint`` — no default-device build followed by
    a re-placement copy."""
    rep = NamedSharding(mesh, P(None))

    def fn(sorted_ids, n_valid):
        lut = build_prefix_lut(sorted_ids, n_valid, bits=bits)
        return lax.with_sharding_constraint(lut, rep)
    return jax.jit(fn)


def dp_simulate_lookups(mesh: Mesh, sorted_ids, n_valid, targets, **kw):
    """Data-parallel batched iterative lookups: targets sharded over the
    whole mesh (both axes), sorted table replicated.  The per-step merge
    sort, window binary search, and while_loop all partition trivially
    along the query axis — XLA inserts no cross-device collectives in
    steady state, so scaling is linear in chips.

    Placement goes through the declarative rule layer
    (``partition.DP_AXIS_RULES``): a host table is ``device_put``
    straight to its replicated sharding — the old ``jnp.asarray`` +
    re-place sequence staged a full extra copy on the default device
    first, a transient 2× HBM spike at exactly the table sizes this
    path serves.  Callers with a stable table should pass ``lut=``
    (built once via ``ops.sorted_table.build_prefix_lut``) so repeated
    waves skip the rebuild; when absent the LUT is derived from the
    PLACED table under one jit whose output is constrained replicated,
    never built on the default device and copied."""
    placed = shard_put(mesh, {"targets": _as_operand(targets, np.uint32),
                              "sorted_ids": _as_operand(sorted_ids,
                                                        np.uint32)},
                       DP_AXIS_RULES)
    targets = placed["targets"]
    sorted_ids = placed["sorted_ids"]
    if kw.get("lut") is None:
        kw["lut"] = _dp_lut_builder(
            mesh, default_lut_bits(sorted_ids.shape[0]))(
                sorted_ids, jnp.asarray(n_valid, jnp.int32))
    return simulate_lookups(sorted_ids, n_valid, targets, **kw)
