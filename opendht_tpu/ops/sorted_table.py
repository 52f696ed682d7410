"""Sorted-table XOR nearest-neighbor lookup — the fast path.

The reference finds closest nodes two ways: walking k-buckets outward
(src/routing_table.cpp:109-150) or walking a lexicographically-sorted
map outward from ``lower_bound(id)`` picking the XOR-closer side each
step (``NodeCache::getCachedNodes``, src/node_cache.cpp:41-74).  Both
exploit the same property this module vectorizes:

  In lexicographic order, the common-prefix length cp(q, ·) is unimodal
  around q's insertion position, and every node with cp ≥ L forms one
  contiguous run containing that position.  All nodes inside that run
  are XOR-closer to q than any node outside it.

So the k XOR-closest nodes live in a small *window* of the sorted table
around q's position, and we can prove it per query:

  certificate:  cb(q, kth result) > cb(q, nearest excluded neighbor)
                on each side that has excluded nodes.

When the certificate holds (virtually always for random SHA1 ids and
window ≥ 8k), the window result equals the exact full scan; failures
fall back to ops/xor_topk.  This turns the O(Q·N) scan into
O(Q·(log N + W)) — the difference between 1M×10M = 10^13 limb ops and
~1M×300 = 3·10^8, which is what makes the BASELINE.json north star
(<1 ms amortized per lookup) reachable.

All steps are static-shape, batched, and jit/shard_map friendly:
binary search is a fixed ``ceil(log2 N)``-step ``fori_loop``; the window
merge is one 7-key lexicographic sort (see ops/xor_topk.py for the key
layout) or the pallas selection kernel (ops/pallas_select.py).

Negative result (recorded so it isn't retried): fusing the window
*gather* into a pallas kernel — DMAing each query's window straight
from the HBM-resident table via scalar-prefetched start offsets — does
not work on TPU.  Mosaic requires slice offsets aligned to the memref
tiling (1024 elements for 1-D int32, 8 sublanes for 2-D), so arbitrary
per-query window starts either fail to compile or force the window to
be widened ~8× to the alignment grid, destroying the HBM-traffic
saving that motivated the fusion.  XLA's general gather handles the
unaligned access pattern natively; the win that *was* available —
replacing the post-gather sort with VPU min-extraction — is
ops/pallas_select.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry
from .ids import N_LIMBS, xor_ids, common_bits, clz32
from .xor_topk import xor_topk

_U32 = jnp.uint32


@functools.partial(jax.jit, static_argnames=())
def sort_table(ids, valid=None):
    """Sort id rows lexicographically; invalid rows sink to the end.

    Returns (sorted_ids [N,5], perm [N] int32 original row of each sorted
    row, n_valid int32).  ``perm`` is -1 on rows that were invalid.
    """
    N = ids.shape[0]
    if valid is None:
        valid = jnp.ones((N,), dtype=bool)
    inv = (~valid).astype(jnp.int32)
    idx = jnp.arange(N, dtype=jnp.int32)
    ops_in = (inv, ids[:, 0], ids[:, 1], ids[:, 2], ids[:, 3], ids[:, 4], idx)
    out = lax.sort(ops_in, dimension=0, num_keys=6)
    sorted_ids = jnp.stack(out[1:6], axis=-1)
    perm = jnp.where(out[0] == 0, out[6], -1)
    n_valid = jnp.sum(valid.astype(jnp.int32))
    return sorted_ids, perm, n_valid


LUT_BITS = 16


def default_lut_bits(n_rows: int) -> int:
    """Prefix width for :func:`build_prefix_lut` sized to the table:
    ~1-row buckets (bits ≈ log2 N), clamped to [16, 24].  Keeping the
    average bucket ≈ 1 row is what makes the LUT-only (0-step)
    positioning mode safe: positioning error is bounded by bucket size,
    and the expanded window's stride-wide margin absorbs it (a 64M-row
    table at 20 bits has ~61-row average buckets — comparable to the
    margin itself — while 24 bits brings them to ~4).  The 24-bit cap
    costs a 64 MiB LUT — noise next to the expanded table."""
    return min(24, max(16, math.ceil(math.log2(max(n_rows, 2)))))
# binary-search depth inside one LUT bucket: buckets of a 2^16-way
# partition of N uniform ids are ~N/2^16 rows; 4096 (2^12) is a huge
# overshoot for any realistic N, and an adversarial bucket larger than
# that merely yields a wrong window that the exactness certificate
# catches (→ full-scan fallback).  Measured on v5e-lite @ N=1M the LUT
# path is within noise of the plain 21-step search (the per-step gather
# fuses well), so it stays opt-in — it pays when N grows enough that
# log2(N) - LUT_BUCKET_STEPS widens.
LUT_BUCKET_STEPS = 13


@functools.partial(jax.jit, static_argnames=("bits",))
def build_prefix_lut(sorted_ids, n_valid, *, bits: int = LUT_BITS):
    """Top-``bits`` prefix → first sorted row with that prefix or greater.

    Shrinks the per-query binary search from ceil(log2 N)+1 sequential
    gather steps to a handful of in-bucket steps, which is where a third
    of the lookup wall-clock goes at N=1M.  Invalid rows (sorted to the
    end) get the sentinel prefix 2^bits so every real prefix resolves
    below n_valid.  Returns int32 [2^bits + 1]; entry [p+1] bounds
    bucket p.  ``bits`` is recoverable from the result shape, so
    consumers infer it — size it with :func:`default_lut_bits`
    (~1-row buckets at any N, which is what keeps the LUT-only 0-step
    positioning mode inside the expanded window's margin).
    """
    N = sorted_ids.shape[0]
    nb = 1 << bits
    keys = (sorted_ids[:, 0] >> jnp.uint32(32 - bits)).astype(jnp.int32)
    keys = jnp.where(jnp.arange(N) < jnp.asarray(n_valid, jnp.int32),
                     keys, jnp.int32(nb))
    # histogram + exclusive cumsum, NOT searchsorted: on sorted keys
    # "first row with prefix >= p" is exactly sum(counts[< p]), and the
    # scatter-add + scan build is one pass over N + one over 2^bits —
    # measured ~8 ms faster per build at 2^18 probes on v5e, which is
    # what makes the churn path's per-round delta LUT rebuild free
    # (benchmarks/baseline_configs.py config6).
    counts = jnp.zeros((nb + 1,), jnp.int32).at[keys].add(1)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(counts[:nb], dtype=jnp.int32)])


def _lut_bits(lut) -> int:
    """Recover the prefix width from a build_prefix_lut result shape."""
    return (lut.shape[0] - 1).bit_length() - 1


def lut_budget_steps(n_rows: int, bits: int) -> int:
    """In-bucket binary-search depth used when ``lut_steps=None``:
    covers buckets up to 64× the expected N/2^bits size.  THE single
    definition — the soundness guard in core/search.py
    (``_guarded_lower_bound``) certifies the LUT path against exactly
    this budget, so the two must never diverge."""
    return max(6, math.ceil(math.log2(max(n_rows, 2))) - bits + 6)


def fused_gather_planar(sorted_t, rows, limbs: int = N_LIMBS):
    """ONE fused multi-row gather: ``limbs`` limb planes of arbitrary-
    shaped row indices out of the TRANSPOSED [5, N] table, or out of a
    finished ``[limbs, N]`` view of it.

    THE table-access primitive of the iterative search round
    (core/search.py): the round body packs every row it needs — all
    α·k reply rows of every search in the wave — into a single flat
    index vector, so the device issues exactly one gather per round
    instead of one per candidate set (per-element gathers are
    issue-bound at ~190K rows/ms on v5e; what matters is the *number
    of gather ops on the serial chain*, not their element count, once
    waves are small).  The transposed-table / planar-output form is the
    lane-padding rule from the layout note in
    :func:`~opendht_tpu.core.search.simulate_lookups`: a [M, 5] row
    gather pads its minor dim 5 → 128 in TPU tiled layout; [5, M]
    planes stay unpadded.

    LAYOUT: the gather is elementwise in its index, so the flat index
    and the flat planes are simply ``rows`` raveled and un-raveled in
    ``rows``' own order — whoever wants a cheap flatten hands over
    ``rows`` with its long axis last (the engine: slot-major [α·k, W],
    one dense reshape each way and no transpose).  ``rows`` is pinned
    as the caller computed it by an optimization barrier: the gather
    wants its index as ``[M, 1]``, and without the barrier XLA moves
    that reshape UP through every elementwise producer, so the
    caller's whole index arithmetic runs in ``[M, 1]`` — one sublane of
    eight in use on the TPU (6.5 ms a wave at M = 1.57M, PERF.md §6,
    PR 27) — and is charged to this gather's stage.  Behind the barrier
    ``[M, 1]`` is a bare view of a finished flat vector.  The take says
    what happens to an out-of-range row (``mode="clip"``), so there is
    no negative-index wrap before the gather and no fill after it.

    WHO SLICES, AND WHEN: the gather reads the top ``limbs`` limbs,
    ``sorted_t[:limbs]`` — a copy of that many limbs of every row when
    ``sorted_t`` has more (the planes of a [5, N] array share a tile),
    nothing at all when it has exactly ``limbs``.  A caller that
    gathers once hands over the whole table (the build's permutation
    gathers, parallel/global_sort.py).  A caller that gathers inside a
    ``while_loop`` body asks :func:`loop_gather_view` what to hand
    over: the copy is the gather's staging into on-chip memory when
    the view fits there, and dead weight every trip when it does not.

    Exact by construction and pinned against the full-materialization
    oracle :func:`~opendht_tpu.ops.xor_topk.gather_rows`
    (tests/test_topk.py).  Out-of-range rows (e.g. the engine's -1
    "absent" sentinel) are clipped, so their lanes carry garbage —
    every caller masks them (the oracle returns the all-ones sentinel
    there instead).  The clip is correct and not free: every clipped
    lane reads row 0 or the last row, and a gather out of HBM with
    three lanes in four piled on one row pays 15 ns a row where a
    scattered index pays 9.7 (PERF.md §7 (1)), so a caller that would
    clip most of its index hands over in-range, scattered rows instead
    (parallel/sharded.py ``owner_local_index``).
    """
    flat = lax.optimization_barrier(rows).reshape(-1)
    g = jnp.take(sorted_t[:limbs], flat, axis=1, mode="clip")   # [limbs, M]
    return [g[l].reshape(rows.shape) for l in range(limbs)]


# The largest ``[limbs, N]`` view the TPU compiler stages into on-chip
# memory for a gather (v5e: 128 MiB of VMEM).  Read off the optimised
# HLO of ``_simulate_lookups_jit`` compiled for a v5e, where a staged
# buffer carries memory space ``S(1)``: the loop's 2-limb slice is
# staged at 14.68M rows (117.4 MB) and below, and is not at 14.7M rows
# (117.6 MB) and above — 112 MiB, the VMEM less the 16 MiB the compiler
# keeps back for its kernels (PERF.md §6, PR 31).
STAGED_VIEW_MAX_BYTES = 112 << 20


def loop_gather_view(sorted_t, limbs: int):
    """What a closure that gathers ``limbs`` limbs INSIDE a
    ``while_loop`` body hands :func:`fused_gather_planar`: the whole
    transposed table, or the ``[limbs, N]`` view of it taken here, once
    — by whether the view fits the chip's fast memory.

    It fits (a 10M-row table's 2-limb view is 80 MB): hand over the
    table and let ``fused_gather_planar`` slice next to the gather.
    XLA keeps that slice in the loop body and assigns its result to
    on-chip memory (``S(1)`` in the optimised HLO), so the slice IS the
    gather's staging copy — 0.42 ms a round at 10M rows — and the
    gather reads 1.57M rows out of it at 4.3 ns a row.  Out of HBM the
    same gather costs 10 ns a row: with the view taken once outside the
    loop, XLA stages it for the first gather and evicts it before the
    loop, and a wave took 172 ms where it takes 123 (PERF.md §6,
    PR 31).

    It cannot fit (a 25M-row shard's is 200 MB): the slice is staged
    nowhere, the gather pays HBM's price either way (9.6 ns a row),
    and the slice is 200 MB read and written every trip for nothing —
    1.43 ms a round, 11.4 ms of a 323 ms wave.  Take the view once.

    The rule reads one static property, the view's bytes; it is the
    compiler's own (:data:`STAGED_VIEW_MAX_BYTES`), not a knob.
    """
    if limbs * sorted_t.shape[1] * 4 <= STAGED_VIEW_MAX_BYTES:
        return sorted_t
    return sorted_t[:limbs]


def _lex_lt(g, q_l, limbs: int):
    """Planar lexicographic row < query over ``limbs`` uint32 planes:
    ``g`` [limbs, M] gathered rows, ``q_l`` list of [M] query limbs.
    THE single definition — used by the binary-search probe step here
    and by the exact-correction step in core/search.py."""
    lt = g[limbs - 1] < q_l[limbs - 1]
    for l in range(limbs - 2, -1, -1):
        lt = (g[l] < q_l[l]) | ((g[l] == q_l[l]) & lt)
    return lt


def _lower_bound(sorted_ids, queries, n_valid, lut=None,
                 lut_steps: int = LUT_BUCKET_STEPS,
                 limbs: int = N_LIMBS, sorted_t=None):
    """First index i in [0, n_valid] with sorted_ids[i] >= q, batched.

    Fixed-depth binary search (static ceil(log2 N)+1 steps) — no
    data-dependent control flow, so it stays one fused XLA loop.  With a
    prefix ``lut`` (build_prefix_lut) the search starts inside the
    query's 2^16-way bucket and needs only LUT_BUCKET_STEPS steps.

    ``limbs`` restricts the comparison to the top ``limbs`` uint32
    limbs (the probe-step gather is the dominant cost — it is
    per-element issue-bound, so 2 limbs cost 2/5 of 5).  The result is
    then the lower bound in the TRUNCATED key order; see
    core/search.py ``_guarded_lower_bound`` for the exact-correction
    construction (truncated search + one full-width compare step).

    ``sorted_t``: the finished ``[limbs, N]`` view the probes read, from
    a caller that takes it once for several searches (parallel/sharded.py
    ``window_lower_bound``); None takes it here.
    """
    N = sorted_ids.shape[0]
    Q = queries.shape[0]
    if lut is not None:
        bits = _lut_bits(lut)
        p = (queries[:, 0] >> jnp.uint32(32 - bits)).astype(jnp.int32)
        lo = jnp.take(lut, p)
        hi = jnp.take(lut, p + 1)
        if lut_steps is None:
            # larger (adversarial) buckets merely fail the certificate
            lut_steps = lut_budget_steps(N, bits)
        steps = lut_steps
    else:
        steps = max(1, math.ceil(math.log2(max(N, 2))) + 1)
        lo = jnp.zeros((Q,), jnp.int32)
        hi = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), (Q,))

    # gather probe rows limb-planar from the transposed table: a [Q, 5]
    # row gather pads 5 lanes → 128 in TPU tiled layout; [5, Q] columns
    # stay unpadded and the lex compare runs on 1-D planes
    if sorted_t is None:
        sorted_t = sorted_ids.T[:limbs]                      # [limbs, N]
    q_l = [queries[:, l] for l in range(limbs)]

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        g = jnp.take(sorted_t, jnp.clip(mid, 0, N - 1), axis=1)  # [limbs, Q]
        go_right = _lex_lt(g, q_l, limbs) & (lo < hi)
        new_lo = jnp.where(go_right, mid + 1, lo)
        new_hi = jnp.where(go_right | (lo >= hi), hi, mid)
        return new_lo, new_hi

    lo, hi = lax.fori_loop(0, steps, body, (lo, hi))
    return lo


@functools.partial(jax.jit,
                   static_argnames=("k", "window", "select", "lut_steps"))
def window_topk(sorted_ids, n_valid, queries, *, k: int = 8, window: int = 128,
                select: str = "auto", lut=None,
                lut_steps: int = LUT_BUCKET_STEPS):
    """k XOR-closest among the first n_valid rows of a sorted table,
    searched only within a `window`-wide slice around each query's
    sorted position, plus a per-query exactness certificate.

    ``select`` picks the in-window top-k engine: ``"sort"`` = 7-key
    ``lax.sort``; ``"pallas"`` = the VPU min-extraction kernel
    (ops/pallas_select.py); ``"auto"`` = pallas on TPU, sort elsewhere.
    Both are exact and bit-identical (tests/test_topk.py).  ``lut`` is
    an optional prefix table from :func:`build_prefix_lut` that
    shortens the positioning search; a misplaced window from an
    overflowing LUT bucket is caught by the certificate.

    Returns:
      dist      [Q, k, 5] uint32 (all-ones beyond n_valid results)
      idx       [Q, k] int32 indices into the *sorted* table (-1 = none)
      certified [Q] bool — True ⇒ provably equal to the exact full scan
    """
    if window < k:
        raise ValueError(f"window ({window}) must be >= k ({k})")
    if select == "auto":
        select = "pallas" if jax.default_backend() == "tpu" else "sort"
    N = sorted_ids.shape[0]
    Q = queries.shape[0]
    n_valid = jnp.asarray(n_valid, jnp.int32)

    pos = _lower_bound(sorted_ids, queries, n_valid, lut=lut,
                       lut_steps=lut_steps)

    # slide the window to stay inside [0, n_valid) as much as possible
    start = jnp.clip(pos - window // 2, 0, jnp.maximum(n_valid - window, 0))
    offs = jnp.arange(window, dtype=jnp.int32)
    raw = start[:, None] + offs[None, :]                     # [Q, W]
    inv = (raw >= n_valid).astype(jnp.int32)
    gidx = jnp.clip(raw, 0, N - 1)
    win_ids = jnp.take(sorted_ids, gidx.reshape(-1), axis=0).reshape(Q, window, N_LIMBS)

    dist = xor_ids(queries[:, None, :], win_ids)
    if select == "pallas":
        from .pallas_select import lex_topk_select
        sel = lex_topk_select(dist, inv, k=k,
                              interpret=jax.default_backend() != "tpu")
        found = sel >= 0
        selc = jnp.clip(sel, 0, window - 1)
        top_inv = (~found).astype(jnp.int32)
        top_idx = jnp.where(found, jnp.take_along_axis(raw, selc, axis=1), -1)
        top_dist = jnp.where(
            found[..., None],
            jnp.take_along_axis(dist, selc[..., None], axis=1),
            jnp.uint32(0xFFFFFFFF))
    else:
        ops_in = (
            inv,
            dist[..., 0], dist[..., 1], dist[..., 2], dist[..., 3],
            dist[..., 4],
            raw,
        )
        out = lax.sort(ops_in, dimension=1, num_keys=7)
        top_inv = out[0][:, :k]
        top_dist = jnp.stack(out[1:6], axis=-1)[:, :k]
        top_idx = jnp.where(top_inv == 0, out[6][:, :k], -1)
        top_dist = jnp.where((top_inv == 0)[..., None], top_dist,
                             jnp.full_like(top_dist, 0xFFFFFFFF))

    left_ids = jnp.take(sorted_ids, jnp.clip(start - 1, 0, N - 1), axis=0)
    right_ids = jnp.take(sorted_ids, jnp.clip(start + window, 0, N - 1), axis=0)
    # recover the kth id from its distance (id = q ^ dist)
    kth_ids = xor_ids(queries, top_dist[:, k - 1])
    certified = _window_certificate(
        queries, common_bits(queries, kth_ids), top_inv[:, k - 1] == 0,
        left_ids, right_ids, start > 0, (start + window) < n_valid)
    return top_dist, top_idx, certified


def _cb_clamped(queries, ids):
    """Common-prefix bits of ``queries`` [Q,5] vs ``ids`` [Q,L], clamped
    at 32·L when only the top L limbs are available.  Equal to
    ops.ids.common_bits for L=5."""
    L = ids.shape[-1]
    out = jnp.full(queries.shape[:-1], 32 * L, dtype=jnp.int32)
    prev_zero = jnp.ones(queries.shape[:-1], dtype=bool)
    for l in range(L):
        xi = queries[..., l] ^ ids[..., l]
        first = prev_zero & (xi != 0)
        out = jnp.where(first, 32 * l + clz32(xi), out)
        prev_zero = prev_zero & (xi == 0)
    return out


def _window_certificate(queries, cp_k, kth_valid, left_ids, right_ids,
                        left_exists, right_exists):
    """Exactness certificate shared by the window and expanded lookups.

    Nodes excluded on the left are all at sorted index < start; the
    closest-in-order one is start-1 and (prefix monotonicity) carries the
    maximal common prefix cbL among them.  Any excluded node's distance
    is >= 2^(159-cbL), while the kth window result's distance is
    < 2^(160-cp_k); cp_k > cbL makes every window top-k strictly closer
    than every excluded node.  Symmetrically on the right.  ``cp_k`` may
    be a lower bound — that only makes the certificate conservative.

    With 2-limb neighbor ids (the 2-plane fast2 expansion) cbL/cbR clamp
    at 64; since the fast2 ``cp_k`` is itself clamped at 64, the
    comparison ``cp_k > cb`` is unchanged: a true cb ≥ 64 denies the
    certificate either way (cp_k ≤ 64 can never exceed it), and below
    64 the clamped value is exact — so the 2-plane certificate is
    bit-identical to the 5-plane fast2 one (tests/test_topk.py).
    """
    cbL = _cb_clamped(queries, left_ids)
    cbR = _cb_clamped(queries, right_ids)
    covers_all = (~left_exists) & (~right_exists)
    ok_left = (~left_exists) | (cp_k > cbL)
    ok_right = (~right_exists) | (cp_k > cbR)
    return covers_all | (kth_valid & ok_left & ok_right)


# ---------------------------------------------------------------------------
# Expanded-table path: window fetch as ONE row gather.
#
# Measured on the real chip (v5e), XLA lowers the [Q·W]-element window
# gather of window_topk to a per-element gather running at ~190K rows/ms
# (~4 GB/s — 200× under HBM bandwidth), which is >80% of lookup
# wall-clock at Q=131072, N=1M.  Row gathers with wide contiguous rows,
# by contrast, run near memory speed ([131072, 128] uint32 rows in
# ~0.5 ms).  So we trade 3× table memory for gather shape: the sorted
# table is pre-expanded into overlapping window *rows*
#
#   expanded[j] = sorted_ids[64·j : 64·j + 192]        (stride 64, len 192)
#
# built with reshape+concat only (no gather).  Any 128-wide window
# [pos-64, pos+64) is contained in row j = floor((pos-64)/64), so one
# [Q]-index row gather fetches every query's full candidate set; near
# the table end j is clamped so the window's valid part reaches
# n_valid, mirroring window_topk's slide.  The same exactness
# certificate applies with window start 64·j.
# ---------------------------------------------------------------------------

EXPAND_STRIDE = 64
EXPAND_LEN = 3 * EXPAND_STRIDE          # candidate window rows per entry
_EROW = EXPAND_LEN + 2                  # + left/right certificate neighbors

# Strides an expansion may be built with.  A closed set on purpose: the
# consumer (:func:`expanded_topk`) infers (erow, stride) from
# width // planes, and a MIS-DECLARED ``planes`` can alias
# arithmetically — e.g. a 5-plane stride-64 row (970 lanes) read as
# planes=2 parses to a "valid-looking" erow=485 / stride=161 and
# produces silently wrong, certificate-passing windows (ADVICE r5
# finding 1).  No supported stride is reachable by any cross-planes
# misparse of another supported stride (asserted in tests/test_topk.py),
# so validating the inferred stride against this set turns the silent
# corruption into a loud ValueError.  Extend the set when sweeping new
# geometries — membership is the only constraint.
SUPPORTED_STRIDES = frozenset({8, 16, 24, 32, 42, 48, 64, 96, 128})


@functools.partial(jax.jit, static_argnames=("stride", "limbs"))
def expand_table(sorted_ids, *, stride: int = EXPAND_STRIDE,
                 limbs: int = N_LIMBS):
    """[N, 5] sorted ids → [ceil(N/s), limbs·(3s+2)] overlapping window
    rows (s = ``stride``; default 64 → 194-lane planes).

    ``limbs`` < 5 builds only the top limb planes — the **2-plane form**
    is sufficient for the ``select="fast2"`` lookup (nodes-not-distances
    contract): the fast2 sort consumes planes 0-1 only, and its
    exactness certificate clamps the kth result's common prefix at 64
    bits (:func:`expanded_topk`), so the neighbor-lane comparison needs
    the same two planes.  That cuts the dominant per-query row-gather
    traffic by 3/5 and the expansion memory from 3× to 1.2× of the
    table (the round-4 verdict's ask #2).

    Row j holds sorted rows [s·j-1, s·j+3s+1) in **limb-planar** order:
    lanes [l·(3s+2), (l+1)·(3s+2)) are limb l of those 3s+2 rows.
    Within each plane, lane 0 is the *left certificate neighbor* (row
    s·j-1; zeros sentinel for j=0), lanes 1..3s the candidate window
    [s·j, s·j+3s), lane 3s+1 the *right certificate neighbor* — so one
    row gather fetches both the full candidate set and the rows the
    exactness certificate compares against.  Window length is fixed at
    3·stride: the middle third is the positioning target, leaving a
    ``stride``-row margin on each side (which is also the tolerance the
    LUT-only zero-step positioning mode relies on — see
    :func:`expanded_topk`).

    Limb-planar layout matters: a [Q, W, 5] candidate tensor pads its
    minor dim 5 → 128 lanes in TPU tiled layout (25× physical memory,
    measured ~13 GB of traffic per 131K-query batch).  Keeping each
    limb a contiguous lane slice of a 2-D row keeps every downstream
    op 2-D and unpadded.  Rows past the end are zero-padded (excluded
    at lookup time via n_valid masking).  Pure pad/reshape/concat — no
    gather.  Memory is 3× the table at any stride; halving the stride
    halves the per-query gather traffic and the in-window sort width.
    ``stride`` must be registered in :data:`SUPPORTED_STRIDES` — the
    closed set is what lets :func:`expanded_topk` reject a mis-declared
    ``planes`` loudly instead of misparsing the row geometry.
    """
    if stride not in SUPPORTED_STRIDES:
        raise ValueError(f"stride {stride} not in SUPPORTED_STRIDES "
                         f"{sorted(SUPPORTED_STRIDES)} — register new "
                         "sweep geometries there")
    N = sorted_ids.shape[0]
    NB = -(-N // stride)
    nblk = NB + 4
    pad = nblk * stride - N - 1
    padded = jnp.pad(sorted_ids, ((1, pad), (0, 0)))    # padded[i] = sorted[i-1]
    planes = []
    for l in range(limbs):
        Bl = padded[:, l].reshape(nblk, stride)
        planes.append(jnp.concatenate(
            [Bl[:NB], Bl[1:NB + 1], Bl[2:NB + 2], Bl[3:NB + 3, :2]], axis=1))
    return jnp.concatenate(planes, axis=1)


def expand_table_chunked(sorted_ids, *, stride: int = EXPAND_STRIDE,
                         chunks: int = 8, limbs: int = N_LIMBS):
    """Same window-row table as :func:`expand_table`, built in
    ``chunks`` pieces with a donated in-place row update.

    :func:`expand_table`'s one-shot build peaks at ~2.5× the output
    size (padded copy + per-limb planes + the concatenated result live
    together), which OOMs a 64M-id table (3.9 GB output) on this
    chip's effective HBM.  Here each piece covers NB/chunks output
    rows (one gather from the sorted table with sentinel masking at
    the edges), and ``lax.dynamic_update_slice`` with a donated
    destination keeps exactly one output-sized buffer alive — peak =
    output + input + one piece.

    The result may carry a few zero-padded trailing rows (NB rounded
    up to a multiple of ``chunks``); lookups never touch them (the
    ``jmax`` clamp in :func:`expanded_topk` is bounded by ``n_valid``).
    Bit-identical to ``expand_table`` on the common rows
    (tests/test_topk.py).
    """
    if stride not in SUPPORTED_STRIDES:
        raise ValueError(f"stride {stride} not in SUPPORTED_STRIDES "
                         f"{sorted(SUPPORTED_STRIDES)} — register new "
                         "sweep geometries there")
    N = sorted_ids.shape[0]
    NB = -(-N // stride)
    NBc = -(-NB // chunks)
    erow = 3 * stride + 2
    src_rows = (NBc + 3) * stride          # per-piece source span

    @jax.jit
    def build_piece(sorted_ids, start):
        # rows [start, start+src_rows) of the sentinel-padded table
        # (padded[i] = sorted[i-1]); out-of-range rows are zeros
        idx = start + jnp.arange(src_rows, dtype=jnp.int32) - 1
        ok = (idx >= 0) & (idx < N)
        src = jnp.where(ok[:, None],
                        jnp.take(sorted_ids, jnp.clip(idx, 0, N - 1),
                                 axis=0), jnp.uint32(0))
        planes = []
        for l in range(limbs):
            Bl = src[:, l].reshape(NBc + 3, stride)
            planes.append(jnp.concatenate(
                [Bl[:NBc], Bl[1:NBc + 1], Bl[2:NBc + 2], Bl[3:NBc + 3, :2]],
                axis=1))
        return jnp.concatenate(planes, axis=1)          # [NBc, limbs·erow]

    @functools.partial(jax.jit, donate_argnums=(0,))
    def upd(out, piece, row0):
        return lax.dynamic_update_slice(out, piece, (row0, jnp.int32(0)))

    out = jnp.zeros((chunks * NBc, limbs * erow), jnp.uint32)
    for c in range(chunks):
        piece = build_piece(sorted_ids, jnp.int32(c * NBc * stride))
        out = upd(out, piece, jnp.int32(c * NBc))
    return out


def unpack_tomb_bits(tomb_bits, n: int):
    """Packed little-endian uint32 tombstone words → bool [n] mask.
    Word w bit b covers sorted position 32·w + b (the packing
    :func:`churn_lookup_topk` and core/table.py agree on)."""
    nw = tomb_bits.shape[0]
    words = jnp.repeat(tomb_bits, 32)[:n]
    shifts = jnp.tile(jnp.arange(32, dtype=jnp.uint32), nw)[:n]
    return ((words >> shifts) & 1) != 0


@functools.partial(jax.jit, static_argnames=("k", "select", "lut_steps",
                                             "fast2_limbs", "planes"))
def expanded_topk(sorted_ids, expanded, n_valid, queries, *, k: int = 8,
                  select: str = "auto", lut=None, lut_steps=None,
                  tomb_bits=None, fast2_limbs: bool = False,
                  planes: int = N_LIMBS):
    """k XOR-closest via the expanded table — one row gather per query.

    ``planes`` declares how many limb planes ``expanded`` carries
    (``expand_table(..., limbs=planes)``).  ``planes=2`` is valid only
    with ``select="fast2"`` — the sort and the (clamped) certificate
    consume planes 0-1 only, so the gathered row shrinks 5→2 planes
    (the dominant HBM traffic of the headline kernel; results are
    bit-identical to the 5-plane fast2 path).

    ``select``: ``"pallas"`` = fused min-extraction kernel
    (ops/pallas_window_topk.py — exact 5-limb ordering, but measured
    slower than the sorts on v5e; see below); ``"sort"`` = full 7-key
    lexicographic sort (always exact
    in-window); ``"fast3"`` = 3-key comparator (invalid, d0, d1) with
    limbs 2-4 riding as payload — exact unless two candidates tie on
    the top 64 distance bits (≈2^-47 per pair; detected by an
    adjacent-tie check over the first k+1 sorted rows and folded into
    ``certified``, so ties fall back like any uncertified query).
    ``"fast2"`` = like fast3 but limbs 2-4 are not carried at all and
    the invalid flag is folded into sentinel key values — the sort
    moves 3 operands instead of 7 (sort cost is linear in operand
    count; measured 7.5 ms for the 4-operand form vs 14.8 ms for 7 per
    131K×192 batch on v5e) and ``dist`` comes back as ``None``.  The
    certificate then uses a
    *lower bound* on the kth result's common prefix (exact below 64
    bits, clamped at 64 above — conservative, so borderline queries
    decertify rather than mis-certify).  Use it when the caller needs
    nodes, not distances — the reference's ``findClosestNodes``
    contract (src/routing_table.cpp:109-150).
    ``"auto"`` = fast3 everywhere — measured on v5e, the XLA bitonic
    sort beats the pallas min-extraction kernel (17.7 ms vs ~78 ms per
    131K×192 batch; Mosaic cross-lane reductions cost ~1000 cycles
    each, and the kernel needs 6 per extraction round), so the pallas
    path stays opt-in as a recorded negative result.

    Returns (dist [Q,k,5] — ``None`` for fast2, idx [Q,k] sorted-table
    rows, certified [Q]) with the same contract as :func:`window_topk`.
    """
    if select == "auto":
        select = "fast3"
    if planes != N_LIMBS and select != "fast2":
        raise ValueError(f"planes={planes} requires select='fast2' "
                         f"(got {select!r}) — only the fast2 sort and "
                         "certificate are sound on partial limb planes")
    if planes < 2:
        raise ValueError("planes must be >= 2 (fast2 sorts on d0, d1)")
    if expanded.shape[1] % planes:
        # catches the easy mismatch now that 2- and 5-plane expansions
        # coexist for one table (e.g. a 2-plane stride-64 row is 388
        # lanes — not divisible by the default planes=5).
        raise ValueError(
            f"expanded width {expanded.shape[1]} is not a multiple of "
            f"planes={planes} — pass the planes= the expansion was "
            "built with (expand_table limbs=)")
    NB = expanded.shape[0]
    erow = expanded.shape[1] // planes      # lanes per limb plane = 3s+2
    wlen = erow - 2                         # candidate window rows = 3s
    stride = wlen // 3
    if wlen != 3 * stride or stride not in SUPPORTED_STRIDES:
        # the divisibility check above cannot catch every mis-declared
        # `planes` (a 5-plane stride-64 row is 970 lanes — divisible by
        # 2 — and would silently misparse to stride 161); no supported
        # stride is reachable by a cross-planes misparse of another, so
        # this turns silently-wrong certified windows into a loud error
        # (ADVICE r5 finding 1).
        raise ValueError(
            f"expanded width {expanded.shape[1]} with planes={planes} "
            f"infers stride {wlen / 3:g} not in SUPPORTED_STRIDES "
            f"{sorted(SUPPORTED_STRIDES)} — `planes` does not match the "
            "expand_table(limbs=) the expansion was built with, or the "
            "stride is unregistered")
    n_valid = jnp.asarray(n_valid, jnp.int32)

    pos = _lower_bound(sorted_ids, queries, n_valid, lut=lut,
                       lut_steps=lut_steps)
    # slide at the table end like window_topk: clamp j so the window's
    # valid part always reaches n_valid (jmax start + 3s ≥ n_valid, at
    # most s-1 masked lanes at the top).  Without this clamp, queries in
    # the last ~2s rows keep a one-sided window and decertify — which
    # is sound but needlessly falls back (and in the sharded path flips
    # the whole-shard exact-scan cond).
    jmax = jnp.clip(-((wlen - n_valid) // stride), 0, NB - 1)
    j = jnp.clip((pos - stride) // stride, 0, jmax)
    start = j * stride

    # Tombstones (churn path, core/table.py): a packed bitmask over
    # *sorted positions* folds dead rows into the in-window invalid
    # lanes, so evictions need no re-sort.  stride % 32 == 0 keeps the
    # extraction gather-free: window starts land on word boundaries, so
    # each query reads wlen/32 whole words (one tiny [Q, nw] gather) and
    # the per-lane bit is static (lane L → word L//32, bit L%32 — a
    # repeat/tile, not a gather).  The exactness certificate is
    # unaffected: it bounds rows *outside* the window via the edge
    # neighbors' sorted-order position, which liveness doesn't change,
    # and dead in-window rows are merely unselectable.
    tomb = None
    if tomb_bits is not None:
        if stride % 32:
            raise ValueError(
                f"tomb_bits requires stride % 32 == 0 (got {stride})")
        Q = queries.shape[0]
        sw = stride // 32
        nw = wlen // 32                         # = 3·sw
        # Block the word array into per-window ROWS (same shifted-slice
        # trick as expand_table) so the per-query fetch is one row
        # gather — a flat [Q·nw] element gather is issue-rate-bound and
        # measured ~7 ms/131K-batch; the [NB, nw] build is one pass
        # over the (tiny) word array, fused into the same program.
        padw = (NB + 2) * sw - tomb_bits.shape[0]
        Bw = jnp.pad(tomb_bits, (0, max(padw, 0)))[:(NB + 2) * sw] \
            .reshape(NB + 2, sw)
        tomb_rows = jnp.concatenate([Bw[:NB], Bw[1:NB + 1], Bw[2:NB + 2]],
                                    axis=1)     # [NB, nw]
        words = jnp.take(tomb_rows, j, axis=0)  # [Q, nw] row gather
        shifts = jnp.tile(jnp.arange(32, dtype=jnp.uint32), nw)
        tomb = ((jnp.repeat(words, 32, axis=1) >> shifts[None, :]) & 1) != 0

    rows = jnp.take(expanded, j, axis=0)             # [Q, planes·(3s+2)]
    # limb planes — contiguous lane slices, everything stays 2-D
    plane = [rows[:, l * erow:(l + 1) * erow] for l in range(planes)]
    left_ids = jnp.stack([p[:, 0] for p in plane], axis=-1)
    right_ids = jnp.stack([p[:, erow - 1] for p in plane], axis=-1)

    if select == "pallas":
        from .pallas_window_topk import window_select
        if tomb is not None:
            raise ValueError("tomb_bits is not supported by the pallas "
                             "select (bounds-based masking only)")
        if erow != _EROW:
            raise ValueError("pallas window_select supports only the "
                             f"default stride {EXPAND_STRIDE}")
        Q = queries.shape[0]
        q8 = jnp.pad(queries, ((0, 0), (0, 8 - N_LIMBS)))
        bounds = jnp.broadcast_to(
            jnp.clip(n_valid - start, 0, wlen)[:, None], (Q, 8)
        ).astype(jnp.int32)
        packed = window_select(rows, q8, bounds, k=k,
                               interpret=jax.default_backend() != "tpu")
        local = packed[:, N_LIMBS * k:(N_LIMBS + 1) * k].astype(jnp.int32)
        gidx = start[:, None] + local
        valid_k = (local < wlen) & (gidx < n_valid)
        top_limbs = [jnp.where(valid_k, packed[:, l * k:(l + 1) * k],
                               jnp.uint32(0xFFFFFFFF))
                     for l in range(N_LIMBS)]
        top_idx = jnp.where(valid_k, gidx, -1)
        top_dist = jnp.stack(top_limbs, axis=-1)           # single 3-D build
    elif select == "fast2":
        # 3-OPERAND sort: the invalid flag is folded into sentinel
        # values — invalid lanes get (d0, d1, gr) = (~0, ~0, GR_SENT),
        # which sorts after every valid candidate (a genuine candidate
        # with an all-ones top-64 distance still wins the gr tiebreak,
        # and its cp_k lower bound is 0, so the certificate can never
        # certify that query — the ambiguity is unreachable in
        # certified output).  Sort cost is linear in operand count:
        # 4 → 3 operands is 25% off the headline kernel's largest term.
        big = jnp.uint32(0xFFFFFFFF)
        gr = start[:, None] + jnp.arange(wlen, dtype=jnp.int32)[None, :]
        inv_m = gr >= n_valid
        if tomb is not None:
            inv_m = inv_m | tomb
        gr_sent = jnp.int32(0x7FFFFFFF)
        d0 = jnp.where(inv_m, big, plane[0][:, 1:erow - 1]
                       ^ queries[:, 0:1])
        d1 = jnp.where(inv_m, big, plane[1][:, 1:erow - 1]
                       ^ queries[:, 1:2])
        grm = jnp.where(inv_m, gr_sent, gr)
        out = lax.sort((d0, d1, grm), dimension=1, num_keys=3)
        valid_k = out[2][:, :k] != gr_sent
        top_limbs = [jnp.where(valid_k, out[l][:, :k], big)
                     for l in range(2)]
        top_idx = jnp.where(valid_k, out[2][:, :k], -1)
        # fast2_limbs: hand the sorted top-64 distance bits to the
        # caller as a TUPLE of 2-D [Q, k] planes (churn_lookup_topk
        # merges on them without re-gathering ids).  Planes, not a
        # [Q, k, 2] stack: a minor dim of 2 pads to 128 lanes in TPU
        # tiled layout — the stacked form materializes 64× the bytes
        # (the same pad tax PERF.md §6, PR 27, measured on the
        # simulator's reply path).
        top_dist = (tuple(top_limbs) if fast2_limbs else None)
        # tie-check operands (same layout as the keyed form below)
        tie_a0, tie_a1 = out[0][:, :k + 1], out[1][:, :k + 1]
        tie_av = out[2][:, :k + 1] != gr_sent
    else:
        nd = N_LIMBS
        d = [plane[l][:, 1:erow - 1] ^ queries[:, l:l + 1]
             for l in range(nd)]                           # nd × [Q, 3s]
        gr = start[:, None] + jnp.arange(wlen, dtype=jnp.int32)[None, :]
        inv_b = gr >= n_valid
        if tomb is not None:
            inv_b = inv_b | tomb
        inv = inv_b.astype(jnp.int32)

        num_keys = 7 if select == "sort" else 3
        out = lax.sort((inv,) + tuple(d) + (gr,),
                       dimension=1, num_keys=num_keys)
        top_inv = out[0][:, :k]
        valid_k = top_inv == 0
        top_limbs = [jnp.where(valid_k, out[1 + l][:, :k],
                               jnp.uint32(0xFFFFFFFF))
                     for l in range(nd)]
        top_idx = jnp.where(valid_k, out[1 + nd][:, :k], -1)
        top_dist = jnp.stack(top_limbs, axis=-1)           # single 3-D build
        tie_a0, tie_a1 = out[1][:, :k + 1], out[2][:, :k + 1]
        tie_av = out[0][:, :k + 1] == 0

    # window certificate (same argument as window_topk, start = 64j);
    # neighbor rows came along in the gathered row — no extra gather.
    if select != "fast2":
        kth_ids = xor_ids(queries, top_dist[:, k - 1])
        cp_k = common_bits(queries, kth_ids)
    else:
        # fast2: exact cp below 64 bits, clamped (lower bound) above —
        # conservative: a clamp can only turn certified → uncertified
        x0 = top_limbs[0][:, k - 1]
        x1 = top_limbs[1][:, k - 1]
        cp_k = jnp.where(x0 != 0, clz32(x0), 32 + clz32(x1))
    certified = _window_certificate(
        queries, cp_k, valid_k[:, k - 1], left_ids, right_ids,
        start > 0, (start + wlen) < n_valid)

    if select in ("fast3", "fast2"):
        # fast3/fast2 exactness: no adjacent (d0, d1) tie among the
        # first k+1 valid sorted rows (a tie anywhere in the sorted
        # order is an adjacent tie; ties past position k cannot change
        # the top-k set or its order).
        tie = jnp.any((tie_a0[:, 1:] == tie_a0[:, :-1])
                      & (tie_a1[:, 1:] == tie_a1[:, :-1])
                      & tie_av[:, 1:] & tie_av[:, :-1], axis=1)
        certified = certified & ~tie
    return top_dist, top_idx, certified


@functools.partial(jax.jit, static_argnames=("k", "select", "cap", "planes",
                                             "fast2_limbs"))
def cascade_topk(sorted_ids, exp_fast, exp_wide, n_valid, queries, lut, *,
                 k: int = 8, select: str = "fast2", cap: int = 512,
                 planes: int = N_LIMBS, fast2_limbs: bool = False):
    """Two-stage certified lookup in ONE device call — the headline
    kernel (bench.py).

    Stage 1: :func:`expanded_topk` over the narrow fast expansion with
    LUT-only positioning.  At the headline geometry (stride 32 →
    96-row windows that sort in 128 padded lanes) ~0.9987 of uniform
    queries certify — ~164 repairs per 131K batch at k=16; narrower
    margins decertify more (stride 24 measured 0.974 — past the
    optimum).  Stage 2: up to ``cap`` uncertified rows are selected ON
    DEVICE (``jnp.nonzero(size=cap)`` — static shape, no host sync, no
    cond) and re-looked-up against the wide stride-64 expansion, whose
    64-row margins certify everything stage 1 missed on non-adversarial
    tables.  Size ``cap`` ≥ a few × the expected stage-1 miss count
    (the 512 default covers the headline geometry ~3×; stage-2 cost is
    insensitive to it).  Rows neither stage certifies (> cap failures,
    or adversarial clustering) come back with ``certified=False`` and
    the caller falls back exactly (lookup_topk's host path).

    This replaces a full-scan fallback that cost 520 ms per batch at
    Q=128×N=1M (the tiled scan serializes ~245 tiny sort steps) with a
    ~0.5 ms always-on second pass.  Returns (dist|None, idx, certified)
    with the :func:`expanded_topk` contract.
    """
    d, idx, cert = expanded_topk(sorted_ids, exp_fast, n_valid, queries,
                                 k=k, select=select, lut=lut, lut_steps=0,
                                 planes=planes, fast2_limbs=fast2_limbs)
    # fill_value=0 pads `bad` with duplicate index 0 when fewer than
    # `cap` rows decertify, so the .at[bad].set scatters below write row
    # 0 repeatedly.  That is deterministic ONLY because every duplicate
    # writes an identical value by construction: for a padded entry
    # was_bad=False, so the write is the row's own current value (and
    # the cert update ORs a True with anything).  If a future edit makes
    # per-row scatter values diverge (e.g. mixes in per-slot data), the
    # duplicates become racy — use a unique fill row or mask first
    # (as _lookup_engine's pack in core/search.py does: its fill lanes
    # point past the end and the write-back drops them).
    bad = jnp.nonzero(~cert, size=cap, fill_value=0)[0]
    qb = jnp.take(queries, bad, axis=0)
    # LUT-started bounded positioning for the rescue rows too: the
    # sequential probe-gather steps are the stage's serial cost (full
    # depth = 17-21 steps; the budget search ≈ 6), and a mispositioned
    # rescue on an adversarial table merely stays uncertified — the
    # residual flag routes it to the caller's exact fallback, so
    # soundness never depends on the LUT.  (Full-depth stage 2 measured
    # 3× the whole delta-cascade cost at cap=4096 in the churn round.)
    d2, i2, c2 = expanded_topk(sorted_ids, exp_wide, n_valid, qb,
                               k=k, select=select, lut=lut, lut_steps=None,
                               planes=planes, fast2_limbs=fast2_limbs)
    was_bad = jnp.take(~cert, bad)
    take = was_bad & c2
    old_idx = jnp.take(idx, bad, axis=0)
    idx = idx.at[bad].set(jnp.where(take[:, None], i2, old_idx))
    if d is not None and d2 is not None:
        if isinstance(d, tuple):               # fast2_limbs 2-D planes
            d = tuple(
                dp.at[bad].set(jnp.where(take[:, None], d2p,
                                         jnp.take(dp, bad, axis=0)))
                for dp, d2p in zip(d, d2))
        else:
            old_d = jnp.take(d, bad, axis=0)
            d = d.at[bad].set(jnp.where(take[:, None, None], d2, old_d))
    cert = cert.at[bad].set(jnp.take(cert, bad) | c2)
    return d, idx, cert


@functools.partial(jax.jit, static_argnames=("k", "window", "select",
                                             "lut_steps", "tile"))
def _lookup_topk_device(sorted_ids, expanded, n_valid, queries, lut, *,
                        k, window, select, lut_steps, tile):
    """Fast lookup + device-side exact fallback in ONE device call.

    ``lax.cond`` on the all-certified predicate keeps the common path
    free of the O(N) scan (same pattern as the sharded shard-local
    fallback, parallel/sharded.py); when any query decertifies, the
    whole batch is rescanned and certified rows keep their window
    result.  No host sync — the data-dependent choice stays on device.
    """
    if expanded is not None:
        dist, idx, cert = expanded_topk(sorted_ids, expanded, n_valid,
                                        queries, k=k, select=select,
                                        lut=lut, lut_steps=lut_steps)
    else:
        dist, idx, cert = window_topk(sorted_ids, n_valid, queries, k=k,
                                      window=window, lut=lut,
                                      lut_steps=(LUT_BUCKET_STEPS
                                                 if lut_steps is None
                                                 else lut_steps))
    valid_rows = jnp.arange(sorted_ids.shape[0]) < n_valid

    def exact(_):
        d2, i2 = xor_topk(queries, sorted_ids, k=k, tile=tile,
                          valid=valid_rows)
        keep = cert[:, None]
        i_out = jnp.where(keep, idx, i2)
        if dist is None:                      # fast2 carries no distances
            return (i_out,)
        return (i_out, jnp.where(keep[..., None], dist, d2))

    def fast(_):
        return (idx,) if dist is None else (idx, dist)

    out = lax.cond(jnp.all(cert), fast, exact, operand=None)
    if dist is None:
        return None, out[0], jnp.ones_like(cert)
    return out[1], out[0], jnp.ones_like(cert)


_DONATING_LOOKUP = None


def _donating_lookup_topk():
    """The same compiled program as :func:`_lookup_topk_device` with the
    per-wave query buffer donated (``donate_argnums=3`` — round-20 wave
    pipeline: the wave builder uploads a fresh [Q,5] buffer per wave
    and never re-reads it, so the backend may reuse its pages instead
    of allocating per launch).  On the CPU backend donation is
    unimplemented (and our query buffer never aliases the [Q,k,·]
    outputs, so XLA would warn "donated buffers were not usable") —
    there the plain jit is returned and the knob is a no-op."""
    global _DONATING_LOOKUP
    if _DONATING_LOOKUP is None:
        if jax.default_backend() == "cpu":
            _DONATING_LOOKUP = _lookup_topk_device
        else:
            import warnings
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            _DONATING_LOOKUP = jax.jit(
                _lookup_topk_device.__wrapped__,
                static_argnames=("k", "window", "select", "lut_steps",
                                 "tile"),
                donate_argnums=(3,))
    return _DONATING_LOOKUP


def lookup_topk(sorted_ids, n_valid, queries, *, k: int = 8, window: int = 128,
                fallback: bool = True, lut=None,
                lut_steps=None, expanded=None,
                select: str = "fast3", host_fallback: bool = False,
                donate_queries: bool = False):
    """Window lookup with exact fallback: uncertified queries re-run
    through the full-scan oracle so the result is always exact (when
    ``fallback=True``; with ``fallback=False`` rows where the returned
    ``certified`` mask is False may be inexact).

    With ``expanded`` (from :func:`expand_table`) the fast row-gather
    path (:func:`expanded_topk`) replaces the per-element window gather.

    The default fallback is resolved ON DEVICE (``lax.cond`` exact
    rescan) so the certified common case costs exactly one device call
    with no host round-trip.  ``host_fallback=True`` keeps the old
    host-driven path — it fetches the certificate and rescans only the
    uncertified rows, which is cheaper when misses are frequent *and*
    the batch is huge, at the price of a blocking device→host sync per
    call.  Returns (dist [Q,k,5], idx [Q,k] int32 into the *sorted*
    table, certified [Q] bool).

    ``donate_queries=True`` (round-20 wave pipeline) donates the query
    buffer to the device-fallback jit — callers must pass a buffer they
    own and never re-read (the wave builder's per-wave upload).  No-op
    on CPU and on the host-fallback paths (which re-read ``queries``).
    """
    # Same OOM guard as the sharded shard-local fallback
    # (parallel/sharded.py): past 8M rows a 4096-row tile's [Q, 4104]x7
    # u32 sort temps cannot sit alongside the resident table, and the
    # exact branch's buffers are allocated even when lax.cond never
    # takes it.  Small tile past 8M — the branch is rare, so its
    # throughput is secondary to it being allocatable.
    n_rows = int(sorted_ids.shape[0])
    tile = max(1, min(4096 if n_rows <= 8_000_000 else 512, n_rows))
    if fallback and not host_fallback:
        fn = _donating_lookup_topk() if donate_queries \
            else _lookup_topk_device
        return fn(sorted_ids, expanded, n_valid, queries,
                  lut, k=k, window=window, select=select,
                  lut_steps=lut_steps, tile=tile)
    if expanded is not None:
        dist, idx, cert = expanded_topk(sorted_ids, expanded, n_valid,
                                        queries, k=k, select=select,
                                        lut=lut, lut_steps=lut_steps)
    else:
        dist, idx, cert = window_topk(sorted_ids, n_valid, queries, k=k,
                                      window=window, lut=lut,
                                      lut_steps=(LUT_BUCKET_STEPS
                                                 if lut_steps is None
                                                 else lut_steps))
    if not fallback:
        return dist, idx, cert
    cert_host = jax.device_get(cert)
    if cert_host.all():
        return dist, idx, cert
    bad = jnp.nonzero(~cert)[0]
    valid_rows = jnp.arange(sorted_ids.shape[0]) < n_valid
    fb_dist, fb_idx = xor_topk(queries[bad], sorted_ids, k=k, tile=tile,
                               valid=valid_rows)
    if dist is not None:                      # fast2 returns no distances
        dist = dist.at[bad].set(fb_dist)
    idx = idx.at[bad].set(fb_idx)
    return dist, idx, jnp.ones_like(cert)


# ---------------------------------------------------------------------------
# Churn path: append+tombstone lookups without re-sorting (SURVEY §7
# "incremental updates": append+tombstone slabs with periodic compaction,
# not per-insert device round-trips; reference mutation path
# src/routing_table.cpp:204-262).
#
# The immutable base (sorted + expanded table) absorbs mutations two ways:
#   evictions  → one bit in a packed tombstone mask over sorted positions,
#                folded into the window kernel's invalid lanes
#                (expanded_topk tomb_bits) — dead rows stay in the array
#                as mere sort keys;
#   inserts    → rows of a fixed-capacity *delta slab*, kept as its own
#                mini sorted+expanded table (re-sorted per mutation
#                batch — one cheap device sort at slab sizes, amortized
#                over the batch; a brute-force delta scan would be
#                O(Q·D) and dominate the whole lookup past D≈1K).
# A lookup is then: tombstone-masked window top-k over the base, window
# top-k over the delta, and one [Q, 2k]-wide merge sort.  Correctness
# never depends on churn volume — heavily-tombstoned windows simply
# decertify into the exact fallback — so compaction (full re-sort +
# re-expand) is purely a performance policy, scheduled by core/table.py.
# ---------------------------------------------------------------------------

_ENC_SENT = 0x7FFFFFFF                  # invalid-lane sentinel (sorts last)


def _fallback_tile(n_rows: int, q: int) -> int:
    """Exact-scan tile for a lax.cond fallback branch: the branch's
    buffers are ALLOCATED even when never taken, and one merge step
    holds ~Q·(tile+k)·7 uint32 sort temps.  Cap the product at ~1 GiB
    (tile floor 512 — the branch is rare, so its throughput is
    secondary to it being allocatable); same rule served the >8M-row
    guard in lookup_topk / parallel/sharded.py, generalized to large
    query batches."""
    t = 4096
    while t > 512 and q * t * 28 > (1 << 30):
        t //= 2
    return max(1, min(n_rows, t))


def _resolve_merge_pack(pack, k: int) -> int:
    """``merge_pack="auto"`` → as many queries per 128-lane physical row
    as k allows (P·k ≤ 128; 16 at the protocol k=8) on TPU, where the
    minor-dim pad tax the packing amortizes exists — and 1 elsewhere
    (no pad to amortize, only the packing's own reshapes to pay), the
    same backend split window_topk's ``select="auto"`` makes; packed
    against unpacked has no time on the chip yet (PERF.md §7).  Any int ≥ 1
    is valid — P=1 is the unpacked merge.  Pure resolution — the
    telemetry lives at the jit boundary (``churn_lookup_topk`` counts
    ``dht_churn_merge_pack_resolved_total{pack=}`` once per trace, so
    that counter records which pack paths got COMPILED this process;
    the per-call path counter is core/table.ChurnView.lookup's)."""
    if pack == "auto":
        return (max(1, 128 // k)
                if jax.default_backend() == "tpu" else 1)
    p = int(pack)
    if p < 1:
        raise ValueError(f"merge_pack must be >= 1 (got {pack!r})")
    return p


def packed_churn_merge(m_dist, m_idx, d_dist, d_idx, n_base, *, k: int,
                       nl: int, pack: int = 1):
    """Lane-packed base∪delta candidate merge — the churn round's
    padding-tax amortizer.

    The merge operands are intrinsically k lanes wide ([Q, k] carried
    distance planes + index planes), and TPU tiled layout pads every
    minor dim to 128 lanes: at the protocol k=8 each elementwise mask /
    sentinel / sort step moves 16× the useful bytes.  The standard
    lane-occupancy trick from batched serving kernels applies because
    the per-query merges are independent: pack P queries' k-lane planes
    into one [Q/P, P·k] physical row (P·k = 128 exactly at k=8), pay
    the pad once per P queries, and keep the merge a single row-wise
    ``lax.sort`` by prepending a query-slot key — within a packed row
    the sort groups each query's 2k candidates contiguously and orders
    them by exactly the comparison the unpacked merge used, so the
    extracted prefixes are bit-identical for every P (pinned across
    pack widths, ragged Q, and tombstone densities in
    tests/test_table_churn.py).  Ragged Q pads the tail with sentinel
    slots (enc = _ENC_SENT, all-ones distances) that sort behind every
    real candidate of their slot and are sliced off on unpack.

    Args: ``m_dist``/``d_dist`` carried distance keys — a tuple of nl
    2-D [Q, k] planes (the fast2_limbs form) or an [Q, k, nl] stack;
    ``m_idx``/``d_idx`` int32 [Q, k] candidate encodings (-1 invalid,
    base sorted positions / delta sorted positions); ``n_base`` the
    base table row count (delta encodings come back offset by it, the
    churn_lookup_topk contract).

    Returns ``(enc [Q, w], limbs [nl × [Q, w]])`` — the first
    w = min(k+1, 2k) rows of each query's merged order (k results + one
    lookahead row for the fast2 tie check), masked lanes carrying
    _ENC_SENT / all-ones.
    """
    Q = m_idx.shape[0]
    big = jnp.uint32(0xFFFFFFFF)
    w = min(k + 1, 2 * k)
    P = int(pack)
    QB = -(-Q // P)
    Qp = QB * P

    def _pl(x, l):
        return x[l] if isinstance(x, (tuple, list)) else x[..., l]

    def pk(x, fill):
        if Qp != Q:
            x = jnp.concatenate(
                [x, jnp.full((Qp - Q, k), fill, x.dtype)], axis=0)
        return x.reshape(QB, P * k)

    # masking runs on the packed rows: these wheres (and the sort
    # below) are the ops the [Q, k] layout paid the 128-lane pad on
    mi = pk(m_idx, jnp.int32(-1))
    di = pk(d_idx, jnp.int32(-1))
    mv = mi >= 0
    dv = di >= 0
    enc = jnp.concatenate([jnp.where(mv, mi, _ENC_SENT),
                           jnp.where(dv, di + n_base, _ENC_SENT)], axis=1)
    limbs = tuple(
        jnp.concatenate([jnp.where(mv, pk(_pl(m_dist, l), big), big),
                         jnp.where(dv, pk(_pl(d_dist, l), big), big)],
                        axis=1)
        for l in range(nl))
    if P > 1:
        # slot-segmented sort: the slot key confines every comparison
        # to one query's segment, so adding it changes nothing about
        # the within-query order.  Lanes with fully-equal key tuples
        # are byte-identical in every operand (the all-ones sentinel),
        # so the unstable sort cannot change extracted values.
        slot = jnp.repeat(jnp.arange(P, dtype=jnp.int32), k)
        slot = jnp.broadcast_to(jnp.concatenate([slot, slot])[None, :],
                                (QB, 2 * P * k))
        out = lax.sort((slot,) + limbs + (enc,), dimension=1,
                       num_keys=nl + 2)[1:]
    else:
        out = lax.sort(limbs + (enc,), dimension=1, num_keys=nl + 1)

    def unpk(a):
        # slot s owns lanes [2k·s, 2k·(s+1)) after the segmented sort
        return a.reshape(QB, P, 2 * k)[:, :, :w].reshape(Qp, w)[:Q]

    return unpk(out[nl]), [unpk(out[l]) for l in range(nl)]


@functools.partial(jax.jit, static_argnames=("k", "select", "lut_steps",
                                             "d_lut_steps", "planes",
                                             "d_cap", "merge_pack"))
def churn_lookup_topk(sorted_ids, expanded, n_valid, tomb_bits,
                      d_sorted, d_expanded, d_n_valid, queries,
                      lut=None, d_lut=None, d_exp_wide=None, *, k: int = 8,
                      select: str = "fast3", lut_steps=None,
                      d_lut_steps=None, planes: int = N_LIMBS,
                      d_cap: int = 1024, merge_pack="auto"):
    """Exact k XOR-closest over (live base rows ∪ delta slab).

    Args: base table as in :func:`expanded_topk` (``expanded`` must use
    a stride divisible by 32), ``tomb_bits`` packed uint32 [ceil(N/32)]
    over base sorted positions (1 = dead); ``d_sorted``/``d_expanded``/
    ``d_n_valid`` the delta slab as its own small sorted+expanded table
    (any stride); optional positioning LUTs (+ ``*_steps``, forwarded
    to :func:`expanded_topk` — pass 0 for LUT-only positioning when
    the LUT bits match the table size, the big win at bench scale).

    Returns (dist, idx [Q,k] int32, certified [Q] all-True).  ``idx``
    encodes the source: values in [0, N) are *sorted positions* of the
    base; values in [N, N+D) are ``N + delta sorted position``; -1 =
    fewer than k live rows exist.  ``dist`` is [Q,k,5] for
    ``select="fast3"``/``"sort"`` (full limbs ride the window sorts —
    no extra gathers) and ``None`` for ``"fast2"`` (the
    findClosestNodes contract: nodes, not distances).

    ``merge_pack`` sets the lane-packing width of the final merge
    (:func:`packed_churn_merge`): ``"auto"`` packs 128//k queries per
    physical row on TPU (the 128-lane padding-tax amortizer — P=16 at
    k=8) and resolves to 1 elsewhere (no pad tax to amortize; measured
    slightly negative on cpu).  Any int ≥ 1 forces that width.
    Results are bit-identical for every width.

    Everything is gather-free past the window row fetches: the merge
    sorts the *carried* distance keys — 6 operands for fast3, 3 for
    fast2 (top-64 bits + source key).  fast2's 64-bit merge can tie
    (p≈2⁻⁴⁷·k per query); ties are detected on the merged k+1 prefix
    and repaired under a ``lax.cond`` that re-merges on full gathered
    distances — allocated but ~never executed, like the exact-scan
    fallbacks that repair uncertified window rows (tombstone-aware for
    the base; ``_fallback_tile`` bounds every branch's buffers).  The
    result is unconditionally exact — bit-identical to a full re-sort
    of the mutated id set (tests/test_table_churn.py proves it against
    that oracle).
    """
    N = sorted_ids.shape[0]
    D = d_sorted.shape[0]
    Q = queries.shape[0]
    n_valid = jnp.asarray(n_valid, jnp.int32)
    d_n_valid = jnp.asarray(d_n_valid, jnp.int32)
    big = jnp.uint32(0xFFFFFFFF)
    fast2 = select == "fast2"
    nl = 2 if fast2 else N_LIMBS

    m_dist, idx, cert = expanded_topk(sorted_ids, expanded, n_valid,
                                      queries, k=k, select=select, lut=lut,
                                      lut_steps=lut_steps,
                                      tomb_bits=tomb_bits, fast2_limbs=True,
                                      planes=planes)

    def exact(_):
        live = (jnp.arange(N) < n_valid) & ~unpack_tomb_bits(tomb_bits, N)
        dx, i2 = xor_topk(queries, sorted_ids, k=k,
                          tile=_fallback_tile(N, Q), valid=live)
        keep = cert[:, None]
        i_out = jnp.where(keep, idx, i2)
        if fast2:
            return (i_out, tuple(jnp.where(keep, m_dist[l], dx[..., l])
                                 for l in range(nl)))
        return (i_out, jnp.where(keep[..., None], m_dist, dx[..., :nl]))

    m_idx, m_dist = lax.cond(jnp.all(cert), lambda _: (idx, m_dist),
                             exact, operand=None)

    if d_exp_wide is not None:
        # NARROW-delta cascade: the delta slab takes a stride-16
        # expansion (48-row windows sort in 64 padded lanes — measured
        # 27× cheaper per 131K batch than stride 32's 128-lane sorts)
        # whose ~0.7% uncertified rows are repaired on device against
        # the wide expansion, exactly like the headline cascade_topk.
        # Without this, one decertified row would flip the whole batch
        # into the O(Q·D) exact scan every round.
        dd, d_idx, d_cert = cascade_topk(
            d_sorted, d_expanded, d_exp_wide, d_n_valid, queries, d_lut,
            k=k, select=select, cap=d_cap, planes=planes, fast2_limbs=True)
    else:
        dd, d_idx, d_cert = expanded_topk(d_sorted, d_expanded, d_n_valid,
                                          queries, k=k, select=select,
                                          lut=d_lut, lut_steps=d_lut_steps,
                                          fast2_limbs=True, planes=planes)

    def d_exact(_):
        dx, i2 = xor_topk(queries, d_sorted, k=k,
                          tile=_fallback_tile(D, Q),
                          valid=jnp.arange(D) < d_n_valid)
        keep = d_cert[:, None]
        i_out = jnp.where(keep, d_idx, i2)
        if fast2:
            return (i_out, tuple(jnp.where(keep, dd[l], dx[..., l])
                                 for l in range(nl)))
        return (i_out, jnp.where(keep[..., None], dd, dx[..., :nl]))

    d_idx, dd = lax.cond(jnp.all(d_cert), lambda _: (d_idx, dd),
                         d_exact, operand=None)

    # merge: one slot-segmented sort over P packed queries' 2k
    # candidates per physical row on the CARRIED distance keys + a
    # source key (packed_churn_merge — the 128-lane padding-tax
    # amortizer).  Invalid lanes get all-ones limbs + the ENC sentinel;
    # a *real* candidate with an all-ones distance still wins via the
    # smaller enc key.  Live ids are unique across base and delta
    # (core/table.py re-adds a revived id to the delta only while its
    # base position is tombstoned), so full distances never tie and
    # fast3's 5-limb merge order is exact.
    m_valid = m_idx >= 0
    d_valid = d_idx >= 0
    P = _resolve_merge_pack(merge_pack, k)
    # trace-time (runs once per compilation of this shape): record which
    # pack path got compiled
    telemetry.get_registry().counter(
        "dht_churn_merge_pack_resolved_total", pack=P).inc()
    enc_p, limbs_p = packed_churn_merge(m_dist, m_idx, dd, d_idx, N,
                                        k=k, nl=nl, pack=P)
    enc_k = enc_p[:, :k]
    ok = enc_k != _ENC_SENT

    if not fast2:
        f_idx = jnp.where(ok, enc_k, -1)
        f_dist = jnp.stack([jnp.where(ok, limbs_p[l][:, :k], big)
                            for l in range(nl)], axis=-1)
        return f_dist, f_idx, jnp.ones((Q,), bool)

    # fast2: the merge ordered on 64 distance bits only — an adjacent
    # tie among the first k+1 merged rows means the true 160-bit order
    # is undetermined.  Repair by re-merging the same 2k candidates on
    # FULL distances (id gathers live only inside this ~never-taken
    # branch, unpacked — its cost does not matter, its allocation does:
    # _fallback_tile bounds the rest of the branch family).
    t0, t1, tv = limbs_p[0], limbs_p[1], enc_p != _ENC_SENT
    tie = jnp.any((t0[:, 1:] == t0[:, :-1]) & (t1[:, 1:] == t1[:, :-1])
                  & tv[:, 1:] & tv[:, :-1])

    def exact_merge(_):
        enc_all = jnp.concatenate(
            [jnp.where(m_valid, m_idx, _ENC_SENT),
             jnp.where(d_valid, d_idx + N, _ENC_SENT)], axis=1)
        m_ids = jnp.take(sorted_ids, jnp.clip(m_idx, 0, N - 1).reshape(-1),
                         axis=0).reshape(Q, k, N_LIMBS)
        d_ids = jnp.take(d_sorted, jnp.clip(d_idx, 0, D - 1).reshape(-1),
                         axis=0).reshape(Q, k, N_LIMBS)
        fm = xor_ids(queries[:, None, :], m_ids)
        fd = xor_ids(queries[:, None, :], d_ids)
        ops_f = tuple(
            jnp.concatenate([jnp.where(m_valid, fm[..., l], big),
                             jnp.where(d_valid, fd[..., l], big)], axis=1)
            for l in range(N_LIMBS)
        ) + (enc_all,)
        o2 = lax.sort(ops_f, dimension=1, num_keys=N_LIMBS + 1)
        return o2[N_LIMBS][:, :k]

    enc_k = lax.cond(tie, exact_merge, lambda _: enc_k, operand=None)
    ok = enc_k != _ENC_SENT
    f_idx = jnp.where(ok, enc_k, -1)
    return None, f_idx, jnp.ones((Q,), bool)
