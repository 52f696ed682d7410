"""Kernel cost ledger: what every shipped kernel costs, by construction.

Five rounds of kernel work (920× → 46× → 121× → 131× → 213× vs the
scalar baseline) are protected by wall-clock
smokes only — and wall-clock on shared CPU runners is noise.  The
device-side costs XLA itself computes are not: for a fixed kernel at a
fixed shape, the lowered executable's ``cost_analysis()`` (flops, bytes
accessed) and ``memory_analysis()`` (argument/output/temp bytes) are
DETERMINISTIC on a given XLA version, platform-portable in meaning, and
move exactly when someone changes what the kernel does.  This module
turns them into the third observability pillar next to the PR-3 metrics
spine and the PR-4 trace spans:

- :data:`KERNEL_SPECS` — every shipped jitted entry point
  (``find_closest_nodes_batched``'s device program, ``expanded_topk``,
  ``fused_gather_planar``, ``packed_churn_merge``,
  ``churn_lookup_topk``, ``maintenance_sweep``, the round-fused
  ``simulate_lookups`` engine, and the ``parallel/sharded.py`` tp
  twins) pinned at one CANONICAL SHAPE each, small enough to lower in
  seconds on the CI CPU.
- :class:`KernelLedger` — lowers each spec once per process, captures
  the XLA cost model + memory footprint, optionally pairs it with a
  measured per-launch device time (one blocking canonical launch
  through the PR-3 ``span()`` envelope), and derives ROOFLINE
  attribution against the per-platform peaks table
  (:data:`PLATFORM_PEAKS`): achieved bytes/s and flops/s as a % of
  peak, and which bound dominates.
- Export everywhere the spine already reaches: ``dht_kernel_*``
  gauges in the registry (→ ``DhtRunner.get_metrics()`` JSON and the
  proxy's Prometheus ``GET /stats``), the ``kernels`` REPL command in
  tools/dhtnode.py, the ``kernels`` section of ``dhtscanner --json``,
  and per-wave device-cost attributes folded onto the PR-4
  ``dht.search.wave`` trace spans (:func:`wave_attrs`).
- The gate: ``ci/perf_gate.py`` diffs this ledger against the
  committed ``perf_budgets.json`` — a refactor that doubles a kernel's
  HBM bytes/query fails CI deterministically, no accelerator needed.

The ledger NEVER touches the hot path: it lowers *separate* canonical-
shape instances of each kernel (the shipping calls and their compiled
executables are untouched — kernels are pinned bit-identical with the
ledger enabled in tests/test_profiling.py), computes once per process,
and costs a dict lookup thereafter.  ``captures/ledger_overhead.json``
(benchmarks/exp_ledger_r11.py, the exp_trace_r9 paired-delta
methodology) quantifies the on-cost of the one hot-path-adjacent hook
(:func:`wave_attrs` inside ``record_wave``).

Like the reference exposing ``Dht::getNodesStats``/``dumpTables`` as a
product surface, the ledger is introspection-first: compute is lazy and
opt-in (``OPENDHT_TPU_LEDGER=1`` arms it for serving processes; the
REPL/scanner/CI arm it explicitly), so minimal containers without the
jax wheel still import this module (stdlib-only at import time, same
rule as telemetry.py).
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional

__all__ = [
    "KERNEL_SPECS", "PLATFORM_PEAKS", "KernelLedger", "get_ledger",
    "ledger_computed", "maybe_export", "wave_attrs", "ingest_wave_attrs",
]

# --------------------------------------------------------------------------
# Per-device peaks for roofline attribution, keyed by the ``device_kind``
# string jax reports (compared lower-cased), each with its source.  A
# device that is not in the table is an error, never a default: a share
# of an assumed peak is not a measurement.  These are ATTRIBUTION
# DENOMINATORS, not claims: the committed budgets gate the cost model
# (deterministic), never the roofline % (which inherits wall-clock
# noise and these nominal peaks).
# --------------------------------------------------------------------------
PLATFORM_PEAKS = {
    # one v5e chip reports device_kind "TPU v5 lite" (read on the chip
    # by chip_smoke.py: jax 0.9.0 / libtpu 0.0.34)
    "tpu v5 lite": {
        "flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "note": "TPU v5e, per chip: 197 TFLOP/s bf16, 819 GB/s HBM "
                "(Google Cloud documentation, \"TPU v5e\")"},
    # the CPU backend the tests run on — a shared runner has no stable
    # peak; anything divided by this row is indicative, never a device
    # figure
    "cpu": {
        "flops_per_s": 2e11, "hbm_bytes_per_s": 2e10,
        "note": "nominal shared-runner core, for tests (indicative only)"},
}


_PEAKS_MEMO: "list | None" = None


def platform_peaks(device=None) -> dict:
    """Peaks row for the default (or given) jax device; the matched key
    rides along as ``peak_key`` so exports say which row they used.
    The default-device row is memoized — :func:`wave_attrs` sits on the
    record_wave path and must not re-query the jax backend per wave."""
    global _PEAKS_MEMO
    if device is None and _PEAKS_MEMO is not None:
        return dict(_PEAKS_MEMO[0])
    import jax
    if device is None:
        device = jax.devices()[0]
        _PEAKS_MEMO = [_match_peaks(device)]
        return dict(_PEAKS_MEMO[0])
    return _match_peaks(device)


def _match_peaks(device) -> dict:
    kind = getattr(device, "device_kind", "") or ""
    key = kind.lower()
    row = PLATFORM_PEAKS.get(key)
    if row is None:
        raise KeyError(
            f"no peaks recorded for device_kind {kind!r} (known: "
            f"{sorted(PLATFORM_PEAKS)}); add its published per-chip "
            "peaks, with their source, to profiling.PLATFORM_PEAKS")
    return dict(row, peak_key=key)


# --------------------------------------------------------------------------
# Canonical kernel specs.  Each builder returns (lowerable, args, kwargs,
# shape) where ``lowerable`` is a jitted callable supporting
# ``.lower(*args, **kwargs)``.  Shapes are SMALL ON PURPOSE: the ledger
# must lower on the tier-1 CI CPU in seconds, and the XLA cost model is
# what's gated — absolute size only rescales it.  The shape dict is part
# of the budget key: perf_gate refuses to compare entries whose shapes
# drifted (a silent shape change would otherwise masquerade as a cost
# change, or hide one).
# --------------------------------------------------------------------------

_CANON = {
    "N": 4096,          # base table rows
    "Q": 256,           # query batch
    "K": 8,             # protocol k (routing_table.h:26)
    "D": 512,           # churn delta-slab rows
    "GATHER_M": 2048,   # fused-gather row-vector width
    "R": 24,            # alpha*k reply rows per query (alpha=3)
    "W": 256,           # simulate_lookups wave width
    "INGEST_Q": 64,     # wave-builder fill target (config.ingest_fill_target)
    "INGEST_K": 14,     # refill k (live_search.SEARCH_NODES)
}


def _canonical_table(n: int, seed: int = 11):
    import jax
    import jax.numpy as jnp
    from .ops.sorted_table import (sort_table, expand_table,
                                   build_prefix_lut, default_lut_bits)
    ids = jax.random.bits(jax.random.PRNGKey(seed), (n, 5), dtype=jnp.uint32)
    sorted_ids, _perm, n_valid = sort_table(ids)
    expanded = expand_table(sorted_ids)
    lut = build_prefix_lut(sorted_ids, n_valid, bits=default_lut_bits(n))
    return sorted_ids, expanded, n_valid, lut


def _queries(q: int, seed: int = 12):
    import jax
    import jax.numpy as jnp
    return jax.random.bits(jax.random.PRNGKey(seed), (q, 5),
                           dtype=jnp.uint32)


def _spec_find_closest():
    """The SHIPPING find_closest device program — lookup_topk's
    device-resolved path (expanded window kernel + the lax.cond exact
    fallback branch), exactly what ``NodeTable.find_closest`` →
    ``runtime/dht.py find_closest_nodes_batched`` launches per wave."""
    import jax
    from .ops.sorted_table import lookup_topk
    s, e, nv, lut = _canonical_table(_CANON["N"])
    q = _queries(_CANON["Q"])

    def fn(s, e, nv, q, lut):
        return lookup_topk(s, nv, q, k=_CANON["K"], lut=lut, expanded=e)
    return (jax.jit(fn), (s, e, nv, q, lut), {},
            {"N": _CANON["N"], "Q": _CANON["Q"], "k": _CANON["K"]})


def _spec_wave_builder():
    """The ingest wave builder's canonical coalesced launch (round 12,
    runtime/wave_builder.py): ``lookup_topk`` at the fill target
    Q=64 refill targets × k=SEARCH_NODES=14 — the [Q] wave a fully
    coalesced pump of live get/put/listen refills dispatches, vs the
    Q=1 padded launch each op used to pay.  Budgeted from day one so a
    refactor can't silently fatten the new hot path's device program
    (the ISSUE-7 tentpole's cost-gate requirement).

    Round 20 note: the wave pipeline's buffer donation
    (``ops.sorted_table._donating_lookup_topk``) is a runtime-only,
    CPU-gated alias of the same jitted program — the lowered HLO this
    budget pins is unchanged, so no re-base was needed when the
    builder went async (the launch signature and canonical shape are
    identical; donation only marks the query arg's buffer reusable)."""
    import jax
    from .ops.sorted_table import lookup_topk
    s, e, nv, lut = _canonical_table(_CANON["N"])
    q = _queries(_CANON["INGEST_Q"], seed=24)

    def fn(s, e, nv, q, lut):
        return lookup_topk(s, nv, q, k=_CANON["INGEST_K"], lut=lut,
                           expanded=e)
    return (jax.jit(fn), (s, e, nv, q, lut), {},
            {"N": _CANON["N"], "Q": _CANON["INGEST_Q"],
             "k": _CANON["INGEST_K"]})


def _spec_sketch_update():
    """The keyspace observatory's per-wave launch (round 15,
    ops/sketch.py): one batched scatter-add of the ingest fill target
    Q=64 ids into the [depth=4, width=2048] count-min sketch + the
    256-bin top-8-bit keyspace histogram — budgeted from day one so
    the observability layer's only hot-path device work can't silently
    fatten (the ISSUE-10 cost-gate requirement)."""
    import jax
    import jax.numpy as jnp
    from .ops.sketch import BINS, SKETCH_DEPTH, SKETCH_WIDTH, sketch_update
    sketch = jnp.zeros((SKETCH_DEPTH, SKETCH_WIDTH), jnp.int32)
    hist = jnp.zeros((BINS,), jnp.int32)
    ids = _queries(_CANON["INGEST_Q"], seed=26)

    def fn(sketch, hist, ids):
        return sketch_update(sketch, hist, ids)
    return (jax.jit(fn), (sketch, hist, ids), {},
            {"Q": _CANON["INGEST_Q"], "depth": SKETCH_DEPTH,
             "width": SKETCH_WIDTH, "bins": BINS})


def _spec_cache_probe():
    """The hot-cache membership probe (round 16, ops/cache_probe.py):
    one batched XOR-compare of the ingest fill target Q=64 wave
    targets against the default-capacity [64, 5] cache id table — the
    launch ``runtime/wave_builder.py _serve_cached`` runs BEFORE every
    lookup launch, budgeted from day one so the fast path's only new
    device work can't silently fatten (the ISSUE-11 cost-gate
    requirement)."""
    import jax
    import jax.numpy as jnp
    from .ops.cache_probe import CACHE_CAPACITY, cache_probe
    cache_ids = _queries(CACHE_CAPACITY, seed=27)
    valid = jnp.ones((CACHE_CAPACITY,), bool)
    targets = _queries(_CANON["INGEST_Q"], seed=28)

    def fn(cache_ids, valid, targets):
        return cache_probe(cache_ids, valid, targets)
    return (jax.jit(fn), (cache_ids, valid, targets), {},
            {"Q": _CANON["INGEST_Q"], "C": CACHE_CAPACITY})


def _spec_listener_match():
    """The listener-table membership match (round 24,
    ops/listener_match.py): one batched XOR-compare of the ingest fill
    target S=64 stored-put keys against the default-capacity [1024, 5]
    listener id table — the launch ``runtime/dht.py
    flush_listener_wave`` runs once per ingest wave to drive coalesced
    listen/push delivery, budgeted from day one so the delivery path's
    only device work can't silently fatten (the ISSUE-20 cost-gate
    requirement)."""
    import jax
    import jax.numpy as jnp
    from .ops.listener_match import LISTENER_CAPACITY, listener_match
    table_ids = _queries(LISTENER_CAPACITY, seed=29)
    valid = jnp.ones((LISTENER_CAPACITY,), bool)
    stored = _queries(_CANON["INGEST_Q"], seed=30)

    def fn(table_ids, valid, stored):
        return listener_match(table_ids, valid, stored)
    return (jax.jit(fn), (table_ids, valid, stored), {},
            {"S": _CANON["INGEST_Q"], "L": LISTENER_CAPACITY})


def _spec_swarm_step():
    """The chaos swarm stepper's one-launch-per-tick device program
    (round 18, ops/swarm.py): churn draws + partition-aware analytic
    occupancy refresh + the vmapped PR-5 maintenance_sweep over the
    rotating sample + poison admission/decay + the closest-R republish
    re-resolve, at the canonical S=4096-node / M=16-sample / K=32-key
    shape — budgeted from day one so the robustness workload
    generator's only hot launch can't silently fatten (the ISSUE-13
    cost-gate requirement)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from .ops import swarm
    S, M, K, G = 4096, 16, 32, 2
    state = {k: jnp.asarray(v)
             for k, v in swarm.init_swarm(33, S, K, n_groups=G).items()}
    args = (state, np.float32(1.0), np.float32(0.05), np.float32(0.05),
            np.float32(0.1), np.float32(1.0), np.float32(5.0),
            jnp.ones((G, G), bool), True,
            jnp.zeros((S,), bool), np.int32(4), True,
            jnp.arange(M, dtype=jnp.int32),
            jax.random.bits(jax.random.PRNGKey(34), (S, 3), jnp.uint32),
            jax.random.bits(jax.random.PRNGKey(35), (K,), jnp.uint32))
    return (jax.jit(swarm._swarm_step_impl), args, {},
            {"S": S, "M": M, "K": K, "G": G})


def _spec_expanded_topk():
    """The window kernel alone (headline bench core, fast3 select)."""
    from .ops.sorted_table import expanded_topk
    s, e, nv, lut = _canonical_table(_CANON["N"])
    q = _queries(_CANON["Q"])
    return (expanded_topk, (s, e, nv, q),
            {"k": _CANON["K"], "select": "fast3", "lut": lut},
            {"N": _CANON["N"], "Q": _CANON["Q"], "k": _CANON["K"],
             "select": "fast3"})


def _spec_fused_gather():
    """The round-fused [W·α·k] reply gather (ops/sorted_table.py
    fused_gather_planar) — the iterative round's only table access."""
    import jax
    import jax.numpy as jnp
    from .ops.sorted_table import fused_gather_planar
    s, _e, _nv, _lut = _canonical_table(_CANON["N"])
    st = s.T
    rows = (jax.random.bits(jax.random.PRNGKey(13),
                            (_CANON["GATHER_M"], _CANON["R"]),
                            dtype=jnp.uint32)
            % jnp.uint32(_CANON["N"])).astype(jnp.int32)

    def fn(st, rows):
        return fused_gather_planar(st, rows, 5)
    return (jax.jit(fn), (st, rows), {},
            {"N": _CANON["N"], "M": _CANON["GATHER_M"], "R": _CANON["R"],
             "limbs": 5})


def _spec_packed_merge():
    """The lane-packed churn merge at the TPU pack width P=16 (the
    128-lane padding-tax amortizer) — budgeted at pack=16 on every
    platform so the packed kernel's cost is pinned even though cpu
    resolves merge_pack='auto' to 1."""
    import functools
    import jax
    import jax.numpy as jnp
    from .ops.sorted_table import packed_churn_merge
    Q, K = _CANON["Q"], _CANON["K"]
    key = jax.random.PRNGKey(14)
    ks = jax.random.split(key, 4)
    m_dist = tuple(jax.random.bits(ks[i], (Q, K), dtype=jnp.uint32)
                   for i in range(2))
    d_dist = tuple(jax.random.bits(ks[i + 2], (Q, K), dtype=jnp.uint32)
                   for i in range(2))
    m_idx = (jnp.arange(Q * K, dtype=jnp.int32).reshape(Q, K)
             % jnp.int32(_CANON["N"]))
    d_idx = (jnp.arange(Q * K, dtype=jnp.int32).reshape(Q, K)
             % jnp.int32(_CANON["D"]))
    fn = functools.partial(packed_churn_merge, k=K, nl=2, pack=16)
    return (jax.jit(lambda a, b, c, d: fn(a, b, c, d, _CANON["N"])),
            (m_dist, m_idx, d_dist, d_idx), {},
            {"Q": Q, "k": K, "nl": 2, "pack": 16})


def _spec_churn_lookup():
    """The full churn lookup (base ∪ delta, tombstones, packed merge) —
    the kernel behind ``ChurnView.lookup``."""
    import jax.numpy as jnp
    from .ops.sorted_table import churn_lookup_topk
    s, e, nv, lut = _canonical_table(_CANON["N"])
    ds, de, dnv, dlut = _canonical_table(_CANON["D"], seed=15)
    tomb = jnp.zeros((-(-_CANON["N"] // 32),), jnp.uint32)
    q = _queries(_CANON["Q"])
    return (churn_lookup_topk, (s, e, nv, tomb, ds, de, dnv, q, lut, dlut),
            {"k": _CANON["K"], "select": "fast3", "merge_pack": 16},
            {"N": _CANON["N"], "D": _CANON["D"], "Q": _CANON["Q"],
             "k": _CANON["K"], "select": "fast3", "merge_pack": 16})


def _spec_maintenance_sweep():
    """The fused [160, N] bucket-maintenance pass (ops/radix.py)."""
    import jax
    import jax.numpy as jnp
    from .ops.radix import maintenance_sweep
    N = _CANON["N"]
    ids = jax.random.bits(jax.random.PRNGKey(16), (N, 5), dtype=jnp.uint32)
    self_id = jax.random.bits(jax.random.PRNGKey(17), (5,), dtype=jnp.uint32)
    valid = jnp.ones((N,), bool)
    last = jnp.full((N,), 100.0, jnp.float32)
    key = jax.random.PRNGKey(18)
    return (maintenance_sweep,
            (self_id, ids, valid, last, jnp.float32(700.0),
             jnp.float32(600.0), key),
            {}, {"N": N, "buckets": 160})


def _spec_simulate_lookups():
    """The ROUND-FUSED iterative search engine (core/search.py) at the
    config-3 parameterization (alpha=3, k=8, state_limbs=2).  XLA's
    cost model counts a ``while_loop`` body ONCE (trip counts are
    dynamic), so this entry's flops/bytes approximate bootstrap + one
    steady-state round — which is exactly the per-round unit the
    wave-latency bound and :func:`wave_attrs` want."""
    from .core.search import _simulate_lookups_jit
    s, _e, nv, lut = _canonical_table(_CANON["N"])
    t = _queries(_CANON["W"], seed=19)
    return (_simulate_lookups_jit, (s, nv, t),
            {"alpha": 3, "k": _CANON["K"], "lut": lut, "state_limbs": 2},
            {"N": _CANON["N"], "W": _CANON["W"], "alpha": 3,
             "k": _CANON["K"], "state_limbs": 2})


def _spec_tp_simulate_lookups():
    """The table-sharded engine twin (parallel/sharded.py
    build_tp_lookup) on a 1×1 mesh — the same shard_map program CI's
    8-device step runs, lowered at the smallest geometry so the budget
    is computable on any host.  Collective sites still appear in the
    lowering (psum over a 1-ary axis), so a refactor that adds an
    in-loop collective moves this entry.  Round 13: the operands are
    the row-sharded table state a ``partition.shard_table_state`` call
    builds ONCE — sorted rows, per-shard positioning LUT, replicated
    global block LUT — so ``argument_bytes`` now pins the per-device
    resident footprint of the canonical t-sharded lookup (table bytes
    = N/t·5·4 B per shard; a refactor that re-replicates rows or moves
    a LUT rebuild back into the launch moves this entry's
    argument_bytes/bytes_accessed and fails the gate)."""
    from jax.sharding import Mesh
    import numpy as np
    import jax
    from .parallel.partition import shard_table_state
    from .parallel.sharded import build_tp_lookup
    import jax.numpy as jnp
    s, _e, nv, _lut = _canonical_table(_CANON["N"])
    t = _queries(_CANON["W"], seed=20)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("q", "t"))
    state = shard_table_state(mesh, s, nv)
    fn = build_tp_lookup(mesh, state.shard_n, _CANON["W"], _CANON["K"], 3,
                         14, 48, state_limbs=2)
    a = state.arrays
    return (fn, (a["sorted_ids"], a["local_lut"], a["block_lut"],
                 a["n_valid"], t, jnp.int32(0)), {},
            {"N": _CANON["N"], "W": _CANON["W"], "mesh": "1x1",
             "k": _CANON["K"], "state_limbs": 2,
             "layout": "row-sharded-state"})


def _spec_sharded_window_lookup():
    """The per-shard windowed top-k + ONE cross-shard merge kernel
    (parallel/sharded.py sharded_window_lookup, round-13 declarative
    layout) on a 1×1 mesh — the one-shot resolve path the ingest wave
    builder launches when a resolve mesh is configured
    (runtime/config.py resolve_mesh_t)."""
    from jax.sharding import Mesh
    import numpy as np
    import jax
    import jax.numpy as jnp
    from .ops.sorted_table import _EROW
    from .ops.ids import N_LIMBS
    from .parallel.sharded import _build_sharded_window_lookup
    s, _e, nv, _lut = _canonical_table(_CANON["N"])
    q = _queries(_CANON["Q"], seed=25)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("q", "t"))
    fn = _build_sharded_window_lookup(mesh, _CANON["K"], 128, _CANON["N"],
                                      False)
    perm = jnp.arange(_CANON["N"], dtype=jnp.int32)
    expanded = jnp.zeros((1, N_LIMBS * _EROW), jnp.uint32)
    lut = jnp.zeros((1, 2), jnp.int32)
    return (fn, (q, s, perm, jnp.asarray(nv, jnp.int32)[None], expanded,
                 lut), {},
            {"N": _CANON["N"], "Q": _CANON["Q"], "k": _CANON["K"],
             "mesh": "1x1", "window": 128})


def _spec_sharded_maintenance():
    """The tp maintenance-sweep twin on a 1×1 mesh (one [160] psum +
    one [160] pmax — the O(buckets) wire contract)."""
    from jax.sharding import Mesh
    import numpy as np
    import jax
    import jax.numpy as jnp
    from .parallel.sharded import _build_sharded_maintenance
    N = _CANON["N"]
    ids = jax.random.bits(jax.random.PRNGKey(21), (N, 5), dtype=jnp.uint32)
    self_id = jax.random.bits(jax.random.PRNGKey(22), (5,), dtype=jnp.uint32)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("q", "t"))
    fn = _build_sharded_maintenance(mesh)
    return (fn,
            (self_id, ids, jnp.ones((N,), bool),
             jnp.full((N,), 100.0, jnp.float32), jnp.float32(700.0),
             jnp.float32(600.0), jax.random.PRNGKey(23)),
            {}, {"N": N, "mesh": "1x1", "buckets": 160})


def _spec_reshard_state_build():
    """The reshard hot-swap's device cost (ISSUE-17): the weighted
    per-shard LUT rebuild (parallel/partition.py
    _build_state_luts_weighted — per-shard prefix LUT + one psum for
    the replicated global block LUT) on a 1×1 mesh, the only launch a
    boundary swap adds (row movement is a host copy; there is never a
    re-sort).  Budgeted so a refactor that turns the swap into a table
    re-sort or fattens the rebuild's HBM traffic fails the gate."""
    from jax.sharding import Mesh
    import numpy as np
    import jax
    import jax.numpy as jnp
    from .ops.sorted_table import default_lut_bits
    from .parallel import partition
    s, _e, nv, _lut = _canonical_table(_CANON["N"])
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("q", "t"))
    n = int(nv)
    cap = int(-(-_CANON["N"] // partition.RESHARD_ALIGN)
              * partition.RESHARD_ALIGN)
    ids_re = np.zeros((cap, 5), np.uint32)
    ids_re[:_CANON["N"]] = np.asarray(s, np.uint32)
    shard_rows = np.asarray([[0, n]], np.int32)
    placed = partition.shard_put(
        mesh, {"sorted_ids": ids_re, "shard_rows": shard_rows},
        partition.TABLE_AXIS_RULES)
    fn = partition._build_state_luts_weighted(
        mesh, default_lut_bits(cap), default_lut_bits(_CANON["N"]))
    return (fn, (placed["sorted_ids"], placed["shard_rows"]), {},
            {"N": _CANON["N"], "cap": cap, "mesh": "1x1",
             "layout": "weighted"})


#: name -> (builder, paired live telemetry series or None).  The series
#: is the PR-3 histogram that times the SHIPPING launches of the same
#: kernel, so exports can put the live p50 next to the canonical cost.
KERNEL_SPECS = {
    "find_closest_nodes_batched": (_spec_find_closest, None),
    "wave_builder_lookup": (_spec_wave_builder, "dht_ingest_wave_seconds"),
    "sketch_update": (_spec_sketch_update, None),
    "cache_probe": (_spec_cache_probe, None),
    "listener_match": (_spec_listener_match, "dht_listener_match_seconds"),
    "swarm_step": (_spec_swarm_step, None),
    "expanded_topk": (_spec_expanded_topk, None),
    "fused_gather_planar": (_spec_fused_gather, None),
    "packed_churn_merge": (_spec_packed_merge, None),
    "churn_lookup_topk": (_spec_churn_lookup, "dht_churn_lookup_seconds"),
    "maintenance_sweep": (
        _spec_maintenance_sweep, "dht_maintenance_sweep_seconds"),
    "simulate_lookups": (
        _spec_simulate_lookups, 'dht_search_wave_seconds{mode="single"}'),
    "tp_simulate_lookups": (
        _spec_tp_simulate_lookups, 'dht_search_wave_seconds{mode="tp"}'),
    "sharded_window_lookup": (
        _spec_sharded_window_lookup, None),
    "reshard_state_build": (
        _spec_reshard_state_build, "dht_reshard_swap_seconds"),
    "sharded_maintenance_sweep": (
        _spec_sharded_maintenance,
        'dht_maintenance_sweep_seconds{mode="tp"}'),
}


def _cost_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` as a plain dict (``None`` → empty)."""
    return dict(compiled.cost_analysis() or {})


class KernelLedger:
    """Per-process cost ledger over :data:`KERNEL_SPECS`.

    ``compute()`` lowers + compiles each canonical spec once and caches
    the entry; ``measure()`` additionally times one blocking canonical
    launch per kernel and fills the roofline fields.  Thread-safe; all
    jax work happens inside the compute/measure calls, never at import.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[str, dict] = {}
        self._exported = False
        #: master switch consulted by :meth:`computed` (and hence by the
        #: record_wave hook): False restores the exact not-computed
        #: hot-path behavior without dropping the cached entries — the
        #: off-arm of the overhead driver and a kill switch for
        #: latency-critical embeddings
        self.enabled = True

    # ------------------------------------------------------------- compute
    def compute(self, kernels: Optional[List[str]] = None,
                force: bool = False) -> Dict[str, dict]:
        """Lower + compile the named kernels (default: all) and return
        ``{name: entry}``.  Entries carry the XLA cost model
        (``flops``, ``bytes_accessed``), the memory footprint
        (``argument_bytes``/``output_bytes``/``temp_bytes`` and their
        sum ``hbm_bytes``, the device-resident peak the launch needs),
        the canonical ``shape``, and the lowering platform.  Specs that
        fail to build (e.g. no jax wheel) record an ``error`` entry
        instead of raising — the ledger is introspection, it must never
        take a serving process down."""
        import jax
        names = list(KERNEL_SPECS) if kernels is None else list(kernels)
        for name in names:
            if name not in KERNEL_SPECS:
                raise KeyError(f"unknown ledger kernel {name!r} — "
                               f"registered: {sorted(KERNEL_SPECS)}")
            with self._lock:
                if name in self._entries and not force:
                    continue
            builder, series = KERNEL_SPECS[name]
            try:
                fn, args, kwargs, shape = builder()
                lowered = fn.lower(*args, **kwargs)
                compiled = lowered.compile()
                cost = _cost_dict(compiled)
                mem = compiled.memory_analysis()
                entry = {
                    "kernel": name,
                    "shape": shape,
                    "flops": float(cost.get("flops", 0.0)),
                    "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
                    "argument_bytes": int(
                        getattr(mem, "argument_size_in_bytes", 0) or 0),
                    "output_bytes": int(
                        getattr(mem, "output_size_in_bytes", 0) or 0),
                    "temp_bytes": int(
                        getattr(mem, "temp_size_in_bytes", 0) or 0),
                    "platform": jax.devices()[0].platform,
                    "series": series,
                }
                entry["hbm_bytes"] = (entry["argument_bytes"]
                                      + entry["output_bytes"]
                                      + entry["temp_bytes"])
                # entries hold NUMBERS only — no callable, no device
                # buffers: a serving process that computed the ledger
                # (OPENDHT_TPU_LEDGER=1) must not pin the canonical
                # tables in HBM for its lifetime, and compute()'s
                # return must stay json.dumps-able.  measure() rebuilds
                # its launches from the spec builder instead.
                del fn, args, kwargs, lowered, compiled
                with self._lock:
                    self._entries[name] = entry
            except Exception as e:                  # pragma: no cover
                with self._lock:
                    self._entries[name] = {
                        "kernel": name, "error": str(e)[:300],
                        "series": series,
                    }
        with self._lock:
            return {n: dict(self._entries[n]) for n in names
                    if n in self._entries}

    def measure(self, kernels: Optional[List[str]] = None,
                reps: int = 3) -> Dict[str, dict]:
        """One warmed, blocked canonical launch per kernel (min of
        ``reps``) through the PR-3 span envelope, then the roofline
        attribution: achieved bytes/s and flops/s over the platform
        peaks (%), and which bound dominates.  Wall-clock — honest on a
        quiet chip, indicative on shared CPU (the gate never reads
        it)."""
        import time as _time
        import jax
        self.compute(kernels)
        names = list(KERNEL_SPECS) if kernels is None else list(kernels)
        peaks = platform_peaks()
        for name in names:
            with self._lock:
                entry = self._entries.get(name)
                bad = not entry or "error" in entry
            if bad:
                continue
            try:
                # rebuild the canonical launch from the spec (compute()
                # deliberately keeps no callables/buffers alive)
                fn, args, kwargs, _shape = KERNEL_SPECS[name][0]()
                jax.block_until_ready(fn(*args, **kwargs))      # warm
                best = None
                for _ in range(max(1, reps)):
                    t0 = _time.perf_counter()
                    jax.block_until_ready(fn(*args, **kwargs))
                    dt = _time.perf_counter() - t0
                    best = dt if best is None else min(best, dt)
                fields = {"measured_s": best,
                          "roofline": self.roofline(name, best, peaks)}
            except Exception as e:                  # pragma: no cover
                fields = {"measure_error": str(e)[:300]}
            # one locked update: export/snapshot iterate + copy these
            # dicts under the same lock, so a concurrent GET /stats
            # scrape never sees a torn entry
            with self._lock:
                if name in self._entries:
                    self._entries[name].update(fields)
        with self._lock:
            return {n: self._public(self._entries[n]) for n in names
                    if n in self._entries}

    def roofline(self, name: str, elapsed_s: float,
                 peaks: Optional[dict] = None) -> dict:
        """Roofline attribution of one measured launch: the cost
        model's bytes/flops over ``elapsed_s`` as a fraction of the
        platform peaks.  ``bound`` names the larger fraction — the
        resource the kernel is actually pushing on."""
        entry = self._entries.get(name)
        if not entry or "error" in entry or elapsed_s <= 0:
            return {}
        if peaks is None:
            peaks = platform_peaks()
        bps = entry["bytes_accessed"] / elapsed_s
        fps = entry["flops"] / elapsed_s
        hbm_pct = 100.0 * bps / peaks["hbm_bytes_per_s"]
        flops_pct = 100.0 * fps / peaks["flops_per_s"]
        return {
            "hbm_pct_of_peak": round(hbm_pct, 3),
            "flops_pct_of_peak": round(flops_pct, 4),
            "bound": "memory" if hbm_pct >= flops_pct else "compute",
            "peak_key": peaks.get("peak_key", "?"),
            "peak_note": peaks.get("note", ""),
        }

    # -------------------------------------------------------------- export
    @staticmethod
    def _public(entry: dict) -> dict:
        return {k: v for k, v in entry.items() if not k.startswith("_")}

    def computed(self) -> bool:
        if not self.enabled:
            return False
        with self._lock:
            return bool(self._entries)

    def clear(self) -> None:
        """Drop every cached entry (tests; also the 'off' arm of the
        overhead driver)."""
        with self._lock:
            self._entries.clear()
            self._exported = False

    def snapshot(self) -> Dict[str, dict]:
        """JSON-able {kernel: entry} of everything computed so far,
        with the paired live-series p50 folded in when the registry has
        observed that histogram (canonical cost next to shipping
        latency — the REPL/scanner table)."""
        from . import telemetry
        with self._lock:
            out = {n: self._public(e) for n, e in self._entries.items()}
        hists = telemetry.get_registry().snapshot()["histograms"]
        for e in out.values():
            s = e.get("series")
            if s and s in hists:
                e["live_p50_s"] = hists[s]["p50"]
                e["live_count"] = hists[s]["count"]
        return out

    def export_to_registry(self, reg=None) -> int:
        """Publish the computed entries as ``dht_kernel_*{kernel=}``
        gauges on the unified registry — flops, bytes accessed, the
        HBM footprint split, and (when measured) device seconds +
        roofline % — so `get_metrics()` JSON and the proxy's
        Prometheus ``/stats`` carry the ledger with zero extra
        plumbing.  Returns the number of kernels exported."""
        from . import telemetry
        if reg is None:
            reg = telemetry.get_registry()
        with self._lock:
            entries = [self._public(e) for e in self._entries.values()
                       if "error" not in e]
        for e in entries:
            k = e["kernel"]
            reg.gauge("dht_kernel_flops", kernel=k).set(e["flops"])
            reg.gauge("dht_kernel_bytes_accessed", kernel=k).set(
                e["bytes_accessed"])
            reg.gauge("dht_kernel_hbm_bytes", kernel=k).set(e["hbm_bytes"])
            reg.gauge("dht_kernel_temp_bytes", kernel=k).set(
                e["temp_bytes"])
            if "measured_s" in e:
                reg.gauge("dht_kernel_device_seconds", kernel=k).set(
                    e["measured_s"])
                rl = e.get("roofline") or {}
                if rl:
                    reg.gauge("dht_kernel_roofline_hbm_pct", kernel=k).set(
                        rl["hbm_pct_of_peak"])
                    reg.gauge("dht_kernel_roofline_flops_pct",
                              kernel=k).set(rl["flops_pct_of_peak"])
        with self._lock:
            self._exported = True
        return len(entries)

    # ----------------------------------------------------- trace-span hook
    def wave_cost(self, wave_width: int, rounds: int,
                  mode: str = "single", mesh_t: int = 1) -> dict:
        """Cost-model estimate for one LIVE wave, scaled from the
        matching canonical engine entry — ``simulate_lookups`` for
        single-device waves, ``tp_simulate_lookups`` (the shard_map
        program with its collectives, lowered on a 1×1 mesh) for
        ``mode="tp"``: every op in the round body is Q-row batched, so
        flops/bytes scale linearly in wave width, and XLA counts the
        while-loop body once, so the canonical entry ≈ bootstrap + one
        round (its own docstring) — est = canonical × (width / W_c) ×
        rounds.  An APPROXIMATION by construction, and the attrs name
        the entry it came from (for tp the 1×1-mesh base means the
        estimate is whole-program, not per-shard — a larger mesh
        divides the table traffic per chip).  Pure dict math — safe on
        the record_wave path (measured by
        captures/ledger_overhead.json)."""
        src = ("tp_simulate_lookups" if mode == "tp"
               else "simulate_lookups")
        entry = self._entries.get(src)
        if not entry or "error" in entry or rounds <= 0:
            return {}
        w_c = entry["shape"]["W"]
        scale = (wave_width / float(w_c)) * rounds
        # t-sharded waves (round 13): the canonical tp entry lowers on
        # a 1x1 mesh, so its table traffic is whole-table; on a real
        # t-way split each device scans ~1/t of the rows, so the
        # PER-DEVICE estimate divides by mesh_t.  Approximate by
        # construction (the O(queries·k) collective bytes don't divide)
        # and labeled as such in the cost_model string.
        t = max(1, int(mesh_t))
        attrs = {
            "est_device_bytes": int(entry["bytes_accessed"] * scale / t),
            "est_device_flops": int(entry["flops"] * scale / t),
            "cost_model": "%s xla-body-once x width/%d x rounds"
                          % (src, w_c),
        }
        if t > 1:
            attrs["cost_model"] += " / t=%d (row-sharded)" % t
            attrs["table_shard_t"] = t
        return attrs

    def ingest_wave_cost(self, occupancy: int, mesh_t: int = 1) -> dict:
        """Cost-model estimate for one LIVE ingest wave, scaled from
        the canonical coalesced-launch entry (``wave_builder_lookup``)
        by occupancy, with per-device table traffic divided by
        ``mesh_t`` when the resolve actually ran against the t-sharded
        table (round 13).  Approximate by construction (the
        cross-shard merge bytes don't divide); same entry-access
        discipline as :meth:`wave_cost` — pure dict math, safe on the
        wave-scatter path."""
        entry = self._entries.get("wave_builder_lookup")
        if not entry or "error" in entry:
            return {}
        t = max(1, int(mesh_t))
        scale = occupancy / float(entry["shape"]["Q"]) / t
        return {
            "est_device_bytes": int(entry["bytes_accessed"] * scale),
            "cost_model": "wave_builder_lookup x occupancy/%d%s"
                          % (entry["shape"]["Q"],
                             " / t=%d (row-sharded)" % t if t > 1 else ""),
        }


_ledger = KernelLedger()


def get_ledger() -> KernelLedger:
    """The process-global ledger every export surface reads."""
    return _ledger


def ledger_computed() -> bool:
    return _ledger.computed()


def maybe_export(reg=None) -> int:
    """Export hook for ``DhtRunner.get_metrics()`` / the proxy scrape:
    publishes the ledger IF it has been computed, and computes it first
    when ``OPENDHT_TPU_LEDGER=1`` arms eager mode (serving processes
    that want the series on every scrape without an explicit REPL/CI
    nudge).  Never raises; returns kernels exported (0 = ledger off).

    The round-19 ``dht_stage_budget_seconds{stage=}`` gauges do NOT
    ride this hook: the stage profiler publishes them on its own
    registry at construction/configure time (waterfall.py), so a
    ledger-off process still pays nothing here on a scrape."""
    try:
        if not _ledger.computed():
            if os.environ.get("OPENDHT_TPU_LEDGER", "") not in (
                    "1", "true", "on"):
                return 0
            _ledger.compute()
        return _ledger.export_to_registry(reg)
    except Exception:
        return 0


def ingest_wave_attrs(occupancy: int, mesh_t: int = 1) -> dict:
    """Device-cost attributes for an ingest ``dht.search.wave`` span
    (runtime/wave_builder.py) — thin module-level hook over
    :meth:`KernelLedger.ingest_wave_cost`, gated exactly like
    :func:`wave_attrs`: empty dict (a cached-flag check) until the
    ledger is computed."""
    if not _ledger.computed():
        return {}
    return _ledger.ingest_wave_cost(occupancy, mesh_t)


def wave_attrs(wave_width: int, rounds: int, elapsed_s: float,
               mode: str = "single", mesh_t: int = 1) -> dict:
    """Device-cost attributes for a ``dht.search.wave`` trace span
    (core/search.py record_wave; the tp twin passes ``mode="tp"`` and
    its mesh's ``t`` extent so the estimate comes from the sharded
    program's entry with per-device table traffic scaled by 1/t): the
    scaled cost-model estimate plus the achieved HBM fraction over the
    platform peak when the wave's host-measured elapsed is known.
    Empty dict (and ~zero cost) until someone computes the ledger —
    the hot path only ever pays a dict lookup."""
    if not _ledger.computed():
        return {}
    attrs = _ledger.wave_cost(wave_width, rounds, mode, mesh_t)
    if attrs and elapsed_s > 0:
        try:
            peaks = platform_peaks()
            attrs["est_hbm_pct_of_peak"] = round(
                100.0 * (attrs["est_device_bytes"] / elapsed_s)
                / peaks["hbm_bytes_per_s"], 3)
            attrs["peak_key"] = peaks.get("peak_key", "?")
        except Exception:
            pass
    return attrs
