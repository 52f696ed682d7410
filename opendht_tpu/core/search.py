"""Batched iterative Kademlia lookup engine.

The reference resolves each ``get()`` with a sequential state machine:
``Dht::searchStep`` (src/dht.cpp:561-654) keeps a sorted set of ≤ 14
candidates per target (``Search::insertNode``, src/search.h:636-722),
keeps α = 4 requests in flight (dht.h:321), inserts every reply's nodes
back into the set, and is done when the first k = 8 candidates have all
replied (``isSynced``, src/search.h:734-747).

Here the *entire population of concurrent lookups* advances together:
one device step selects the next α unqueried candidates for every one of
Q searches, resolves all Q·α simulated replies against the global node
matrix, and merges them back — all as fixed-shape array ops inside a
``lax.while_loop``.  A million lookups cost a few dozen fused device
steps instead of millions of scalar iterations.  As of round 6 the
steady-state round is ROUND-FUSED: all α·k reply rows of the whole wave
are fetched by ONE fused gather (``ops.sorted_table.fused_gather_planar``
over a single [α·k·W] index vector, slot-major), the reply blocks are positioned
from the *carried* candidate distance limb instead of a per-round peer
gather, and both LUT block edges ride one stacked read — so a round's
serial chain is one gather + one LUT read + two merge sorts, the
minimum issue structure the reply model admits (see PARITY.md for the
measured wave-latency bound that follows).

State layout (fixed shapes; "no candidate" = node index -1):

    cand_node [Q, S]     int32   node of each candidate: its row in the
                                 sorted table (under churn also
                                 ``capacity`` + a slot of the delta)
    cand_l    NL×[Q, S]  uint32  XOR distance limb planes (sort key;
                                 kept planar — see layout note below)
    queried   [Q, S]     int32   0 = not asked yet, 1 = asked and
                                 replied, 2 = asked and EXPIRED (the
                                 node was gone; under churn only)
    hops      [Q]        int32   rounds taken until convergence
    done      [Q]        bool

Simulated network model (for hop-count/convergence studies, mirroring
the role of the reference's netns cluster harness,
python/tools/dht/tests.py): node x, asked for target t, answers with k
nodes drawn from the prefix block sharing ``commonBits(x, t) + 1``
leading bits with t — exactly what x's deepest relevant k-bucket holds
in a converged Kademlia network (every hop gains ≥ 1 prefix bit, ~3 in
expectation with k = 8 samples).  When that block is smaller than k the
reply is the k rows straddling t's sorted position — the closest set a
real peer that close would answer with (model validated against the
live protocol path at matched N, tests/test_hop_parity.py).  Replies
are deterministic in (seed, round, search, slot) via a counter-based
hash, so runs are reproducible and shardable.  On a table that is
built once a reply is instantaneous and certain, so one flag says
both "asked" and "replied".

Under membership CHURN (a table that departs, joins and compacts
between waves: ``ops/churn_table.py``, ``core.table.DeviceChurnTable``;
OpenDHT's ``NODE_EXPIRE_TIME`` turns a node table over every ten
minutes) the model is the lossy one that splits them again: a reply
draws from the table as last compacted, so it may name a node that has
left; a request to such a node expires (``src/request.h:108-112``), the
candidate is marked expired, and ``isSynced`` counts the first k
candidates that are NOT expired (``src/search.h:734-747``), which are
also what a lookup returns; a node that joined is named at once by the
peers close to it.  :func:`_lookup_engine` (CHURN) states it in full,
:func:`scalar_churn_lookup` is its plain reference.

This module is the *simulation* engine (hop-count / convergence
studies over the synthetic reply model).  The LIVE serving path's
batched-resolve seam is ``runtime.dht.Dht.find_closest_nodes_launch``
→ ``core.table.NodeTable.find_closest_launch`` →
``core.table.Snapshot.lookup_launch`` — since round 20 every layer of
that chain returns a launch handle (``core.table.PendingLookup`` /
``runtime.dht.BatchedResolve``) whose ``consume()`` materializes the
result, so ``runtime/wave_builder.py`` can keep ``ingest_pipeline_depth``
≥ 2 waves in flight while the simulation engine here stays a
synchronous whole-population ``lax.while_loop``.
"""

from __future__ import annotations

import functools
import math
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..ops.churn_table import DELTA_WINDOW, ChurnTable, node_gone
from ..ops.ids import N_LIMBS, ID_BITS, ids_to_bytes, clz32
from ..ops.radix import _PREFIX_MASKS
from ..ops.sorted_table import (_lex_lt, _lower_bound, _lut_bits,
                                build_prefix_lut, default_lut_bits,
                                fused_gather_planar, loop_gather_view,
                                lut_budget_steps)
from ..telemetry import device_stage

_U32 = jnp.uint32

ALPHA = 4            # in-flight requests per search (dht.h:321)
SEARCH_NODES = 14    # candidate set size (dht.h:308)
TARGET_NODES = 8     # convergence set (routing_table.h:26)

# SURVIVOR COMPACTION (_lookup_engine): a wave's loop hands over to one
# an eighth as wide as soon as the live lookups fit it.  An eighth sits
# between the shares the last rounds fall to (6.4% at 10M uniform ids,
# 2.2% at 100M) and those before them (58%, 35%; PERF.md §5), so the cut
# comes where the live count drops.
NARROW_DIVISOR = 8
# Waves under this width keep one loop: a round's fixed cost does not
# shrink with its width.  Chosen from the chip (PERF.md §6, PR 29): at
# 10M ids a cut of the last two rounds took 3.2 ms off a 4,096-lookup
# wave of 28.2 and ADDED 3.0 to a 2,048-lookup wave of 15.2.
NARROW_MIN_WAVE = 4096
# LANE TILES (_lookup_engine): a loop wider than this runs each round
# over its lanes a tile at a time.  What a round of this many lanes
# writes and reads again — 25 MB of gathered rows behind a 12.6 MB
# index, 3 MB of LUT edges — the TPU keeps in on-chip memory beside the
# staged table view and the LUT; at eight times the width it cannot
# (201 MB, 101 MB, 25 MB), and a gathered row then costs 6.04 ns where
# this width pays 4.31, a LUT element 8.58 for 7.13.  Chosen from the
# chip (PERF.md §6, PR 39): a wave of 1,048,576 lookups over 10M ids
# takes 2,063.0 ms of device time untiled, 1,709.9 in tiles of this
# width, 1,723.0 in tiles half as wide and 1,987.4 in tiles twice as
# wide, whose rows fall out of on-chip memory again.
ROUND_TILE_LANES = 131072


def lane_tiles(width: int) -> int:
    """The LANE TILES a loop of ``width`` lookups runs a round in: 1
    (the round at once) up to ``ROUND_TILE_LANES`` and where the width
    is no whole number of tiles."""
    tiles = width // ROUND_TILE_LANES
    return tiles if tiles > 1 and width % ROUND_TILE_LANES == 0 else 1


def _mix32(x):
    """Counter-based uint32 hash (splitmix-style) for reply sampling."""
    x = x.astype(_U32)
    x = x ^ (x >> 16)
    x = x * _U32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * _U32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _increment(ids):
    """160-bit +1 over [..., 5] uint32 limbs (wraps to zero)."""
    out = []
    carry = jnp.ones(ids.shape[:-1], dtype=_U32)
    for i in range(N_LIMBS - 1, -1, -1):
        s = ids[..., i] + carry
        carry = jnp.where((s == 0) & (carry == 1), _U32(1), _U32(0))
        out.append(s)
    return jnp.stack(out[::-1], axis=-1)


def _prefix_block_bounds(lower, n, targets, prefix_len):
    """[lo, ub) sorted-index range of ids sharing `prefix_len` leading bits
    with each target.  ``lower``: flat [M,5] → [M] lower-bound positions;
    targets [..., 5]; prefix_len [...] int32.

    Both block edges go through ONE batched ``lower`` call: the search
    is a fixed number of SEQUENTIAL gather steps, so two M-row calls
    cost twice the serial latency of one 2M-row call (per-element
    gathers are issue-bound, and each step's gather is latency-, not
    bandwidth-, limited at these sizes)."""
    masks = jnp.take(jnp.asarray(_PREFIX_MASKS),
                     jnp.clip(prefix_len, 0, ID_BITS), axis=0)
    p_lo = targets & masks
    p_hi_inc = _increment(p_lo | ~masks)
    both = jnp.concatenate([p_lo.reshape(-1, N_LIMBS),
                            p_hi_inc.reshape(-1, N_LIMBS)], axis=0)
    pos = lower(both)
    M = both.shape[0] // 2
    lo = pos[:M].reshape(targets.shape[:-1])
    ub = pos[M:].reshape(targets.shape[:-1])
    # p_hi of all-ones wraps to zero on increment → block extends to n
    wrapped = jnp.all(p_hi_inc == 0, axis=-1)
    ub = jnp.where(wrapped, n, ub)
    return lo, ub


def _lut_block_bounds(lut, t0, prefix_len):
    """[lo, ub) sorted-index range of ids sharing ``prefix_len`` leading
    bits with targets whose FIRST LIMB is ``t0`` — as two LUT reads, no
    binary search.

    ``build_prefix_lut``'s entry p is the count of valid rows with
    top-``bits`` prefix < p, so for any prefix length L ≤ bits the block
    edges are EXACT on any table: lo = lut[pfx], ub = lut[pfx + 2^(bits−L)]
    (the +1 sentinel entry covers the all-ones wrap).  Deeper prefixes
    clamp to their containing LUT bucket — an over-approximation whose
    only observable effect is the reply model's ``size ≥ k`` branch: at
    the default ~1-row buckets (default_lut_bits) a clamped bucket is
    ~never ≥ k rows, so both the exact and clamped computations take
    the near-target fallback window and the trajectory is unchanged
    (measured: hop distribution and convergence identical at 10M).

    This removes the per-round batched binary search, which was most
    of a round before it (the LUT reads that replaced it are stage
    ``block_bounds``, 19% of a wave: PERF.md §5).  The sharded twin
    computes the same values as a psum of per-shard LUT reads (global lower
    bound = Σ shard-local counts), so tp/single-device bit-identity is
    preserved (tests/test_sharded.py).
    """
    bits = _lut_bits(lut)
    Lc = jnp.clip(prefix_len, 0, bits)
    shift = (jnp.int32(bits) - Lc).astype(_U32)
    top = (t0 >> _U32(32 - bits)).astype(_U32)
    pfx = (top >> shift) << shift
    # ONE stacked take for both edges: LUT reads are per-element
    # issue-bound gathers like every other table access in the round,
    # so what matters is the number of gather ops on the serial chain —
    # fusing lo and ub into a single [2, ...] index vector halves it
    # (and in the sharded twin the psum over the stacked pair is ONE
    # collective per round instead of two — parallel/sharded.py).
    edges = jnp.stack([pfx, pfx + (_U32(1) << shift)]).astype(jnp.int32)
    g = jnp.take(lut, edges)
    return g[0], g[1]


def _guarded_lower_bound(sorted_ids, n, lut):
    """Positioning closure: LUT-started bounded search when every LUT
    bucket fits the in-bucket step budget, else the full-depth binary
    search — decided ON DEVICE with one ``lax.cond`` per call site.

    The bounded LUT search is silently wrong when a bucket holds more
    than 2^steps rows (possible only on clustered/adversarial id
    distributions); there is no exactness certificate inside the search
    simulation to catch it, so the guard makes the LUT path *sound*
    rather than merely fast: ``max(diff(lut))`` bounds every bucket, and
    oversized tables simply pay the log2(N)-step search.

    The fast path additionally searches on the TOP 64 BITS only (the
    probe-step gather is per-element issue-bound — ~70% of the whole
    search-sim round was these gathers at 5 limbs) and then restores
    the exact 160-bit answer with ONE full-width compare: when no two
    ADJACENT valid rows share their top 64 bits (checked on device in
    one scan), at most one row can satisfy row64 == q64, so the 160-bit
    lower bound is the 64-bit one plus at most 1 —
    ``lb160 = lb64 + (row[lb64] < q)``.  Tables violating the
    precondition (64-bit duplicate neighbors) take the full 5-limb
    search instead — exactness never depends on probabilistic
    assumptions.
    """
    N = sorted_ids.shape[0]
    # same budget _lower_bound will actually use (ONE shared definition)
    steps = lut_budget_steps(N, _lut_bits(lut))
    # a B-row bucket needs ceil(log2 B)+1 search steps; with `steps`
    # available, buckets up to 2^(steps-1) rows are provably covered
    lut_ok = jnp.max(lut[1:] - lut[:-1]) <= jnp.int32(
        1 << min(steps - 1, 30))
    nn = jnp.asarray(n, jnp.int32)
    s0, s1 = sorted_ids[:, 0], sorted_ids[:, 1]
    if N > 1:
        adj_valid = (jnp.arange(N - 1, dtype=jnp.int32) + 1) < nn
        tie64 = jnp.any((s0[1:] == s0[:-1]) & (s1[1:] == s1[:-1])
                        & adj_valid)
    else:
        tie64 = jnp.bool_(False)
    sorted_t_full = sorted_ids.T

    def fast(q):
        lb = _lower_bound(sorted_ids, q, n, lut=lut, lut_steps=None,
                          limbs=2)
        # exact correction: row[lb] < q is only possible when the row's
        # top 64 bits EQUAL the probe's (the 64-bit search guarantees
        # row64 >= q64), so gather 2 limbs to detect equality and fetch
        # the tail limbs only in that astronomically rare case (a
        # random probe matches some row's 64-bit prefix with
        # probability ~N/2^64) — the common path pays 2/5 of the
        # correction gather
        cl = jnp.clip(lb, 0, N - 1)
        g2 = jnp.take(sorted_t_full[:2], cl, axis=1)
        eq64 = (g2[0] == q[:, 0]) & (g2[1] == q[:, 1]) & (lb < nn)

        def tail_bump(_):
            g3 = jnp.take(sorted_t_full[2:], cl, axis=1)
            lt = _lex_lt(g3, [q[:, l] for l in range(2, N_LIMBS)],
                         N_LIMBS - 2)
            return (eq64 & lt).astype(jnp.int32)

        bump = lax.cond(jnp.any(eq64), tail_bump,
                        lambda _: jnp.zeros_like(lb), operand=None)
        return jnp.minimum(lb + bump, nn)

    def lower(flat):
        # three tiers: 64-bit search + exact correction (needs tie-free
        # top-64 neighbors) → full-limb LUT-bounded search (sound for
        # any data as long as buckets fit the budget) → full-depth
        # un-LUT'd search (always sound)
        return lax.cond(
            lut_ok & ~tie64,
            fast,
            lambda q: lax.cond(
                lut_ok,
                lambda q2: _lower_bound(sorted_ids, q2, n, lut=lut,
                                        lut_steps=None),
                lambda q2: _lower_bound(sorted_ids, q2, n),
                q),
            flat)
    return lower


def _common_bits_planar(a_l, b_l):
    """commonBits over limb-plane lists (same math as ids.common_bits)."""
    out = jnp.full(a_l[0].shape, ID_BITS, dtype=jnp.int32)
    prev_zero = jnp.ones(a_l[0].shape, dtype=bool)
    for i in range(N_LIMBS):
        xi = a_l[i] ^ b_l[i]
        is_first = prev_zero & (xi != 0)
        out = jnp.where(is_first, 32 * i + clz32(xi), out)
        prev_zero = prev_zero & (xi == 0)
    return out


def _reply_rows(pt, qidx, x_rows, round_no, lo, ub, *, n, k, R, q_total,
                seed_u):
    """The reply model proper (stage ``reply_rows``): block edges → the
    k sampled (or fallback-window) rows of each queried peer per search,
    as a SLOT-MAJOR plane ``[P·k, W]`` (slot r = a·k + j is sample j of
    peer a).

    ``pt``, ``qidx`` [W]; ``x_rows``, ``lo``, ``ub`` PEER-MAJOR
    ``[P, W]``: the P peers this call answers for — α in a loop round,
    ONE in the bootstrap round.  ``R`` is the WAVE's α·k whatever P is:
    it strides the hash counter and spans the fallback window, so slot
    r of a one-peer call is slot r of the α-peer call whose other peers
    sent nothing, bit for bit (:func:`_lookup_engine`, BOOTSTRAP
    SHAPE).  Every value here keeps the W lookups on the minor
    axis: the logical shape ``[W, α, k]`` this used to compute in is
    tiled (4, 128) over its minor dims (3, 8) on the TPU — 21× the
    bytes of the dense array, written once and read twice more per
    round by the reshapes to the gather's flat index — which made a
    hash, a modulo and two selects the second stage of the round
    (35 ms of a 186 ms wave at W=65,536; PERF.md §6, PR 27).  A peer's
    value reaches its k slots as a replication along the major axis
    (``jnp.repeat`` — a sublane broadcast), never through ``[W, α, k]``.
    The numbers are those of the ``[W, α, k]`` formula, element for
    element (tests/test_search.py renders it in numpy).
    """
    def per_slot(x):                    # [P, W] → [P·k, W]
        return jnp.repeat(x, k, axis=0)

    size = jnp.maximum(ub - lo, 0)                                   # [P,W]
    # slot = a·k + j, so ((c·α + a)·k + j) == c·(α·k) + slot (mod 2^32)
    slot = jnp.arange(x_rows.shape[0] * k, dtype=_U32)[:, None]
    qi = qidx.astype(_U32)[None, :]                # GLOBAL query ids
    ctr = ((round_no.astype(_U32) * _U32(q_total) + qi) * _U32(R)
           + slot) ^ seed_u
    h = _mix32(ctr)                                                  # [R,W]

    blk = per_slot(lo) + (h % per_slot(jnp.maximum(size, 1).astype(_U32))
                          ).astype(jnp.int32)
    # fallback: block too small → the peer knows the target's
    # neighborhood and answers with rows from the (alpha·k)-wide
    # window straddling pos_t, each queried slot contributing a
    # distinct k-slice so one round covers the window determinist-
    # ically (a real node replies with the closest set it knows, not
    # a uniform sample — the round-1 uniform model overestimated
    # terminal hops ~2x; validated against the live protocol path in
    # tests/test_hop_parity.py)
    base = jnp.clip(pt - R // 2, 0, jnp.maximum(n - R, 0))[None, :]
    fb = jnp.clip(base + slot.astype(jnp.int32), 0, jnp.maximum(n - 1, 0))
    rows = jnp.where(per_slot(size >= k), blk, fb)
    return jnp.where(per_slot(x_rows >= 0), rows, -1)


def _lookup_engine(gather_planar, lower, n, targets, q_index, q_total,
                   seed_u, *, k, alpha, search_nodes, max_hops,
                   state_limbs: int = N_LIMBS,
                   block_bounds=None, alive=None, delta_window=None,
                   live_count=None):
    """The iterative-lookup state machine, abstracted over table access.

    ALL access to the (possibly distributed) sorted node table flows
    through two injected primitives, which is what lets the same engine
    run single-device (:func:`simulate_lookups`) and with the table
    row-sharded over a mesh axis (parallel/sharded.py:
    ``tp_simulate_lookups`` — each shard runs the engine over its own
    chunk of the wave, and each primitive becomes a shard-local partial
    computation for the whole wave between two collectives over the
    table axis, ``lane_exchange``).  The engine itself names no mesh
    axis and calls no collective:

      gather_planar(rows [...], limbs) -> limbs×[...] uint32 limb
          planes (top limbs first) of the globally-sorted table rows,
          in ``rows``' own shape and order (the engine hands over
          peer-major [P, W] and slot-major [P·k, W] indices; the gather
          is elementwise in its index, so any shape means the same);
          entries for out-of-range rows (the −1 of an unsent slot) may
          be garbage — every caller masks them.  The engine calls it
          inside its ``while_loop`` bodies, so WHOEVER BUILDS THE
          CLOSURE OWNS THE TABLE VIEW: what it hands
          ``fused_gather_planar`` for each limb count is decided
          before the engine is called, once, by
          ``ops.sorted_table.loop_gather_view`` — the table itself
          where the ``limbs``-limb view fits on-chip memory (the slice
          at the gather is then its staging copy, and sets its price),
          the finished view where it cannot (the slice would be a
          table-sized copy every round for nothing).  The engine asks
          for ``limbs`` ∈ {1, ``state_limbs``, 5}.  THE OPTIONAL COUNT:
          a closure may return ``(planes, one_pass)`` instead —
          ``one_pass`` an int32 0 / 1 of its own meaning (the tp twin's:
          this shard served the index in ONE pass over its lane window,
          ``parallel/sharded.py window_gather``).  The engine then
          carries the sum of the IN-LOOP round gathers' reports through
          its loops and the survivors' sub-waves, as it carries
          ``expired_peers``, and returns it as ``window_rounds``; the
          bootstrap's and the final fetch's reports are dropped.  A
          closure that returns bare planes gets the program it always
          got, operation for operation (tests/test_sharded.py pins the
          lowered text's hash).
      lower(flat [M, 5]) -> [M] int32 global lower-bound positions.
      block_bounds(t0, prefix_len) -> (lo, ub) prefix-block edges
          (optional third primitive): t0 = targets' first limb
          (broadcastable against prefix_len).  When provided (the
          :func:`_lut_block_bounds` fast path — one stacked LUT read
          for both edges), the per-round positioning search disappears,
          which the round-body attribution measured as 85% of the
          round; when None the engine falls back to the exact search
          via ``lower`` (:func:`_prefix_block_bounds`).
      alive(nodes [P, W]) -> bool, delta_window(targets [Q, 5]) ->
          (node [Q, DW], 5 id planes [Q, DW]): the two optional CHURN
          primitives (below).  With neither the engine lowers to the
          program it was before they existed, operation for operation
          (the committed goldens of tests/test_search.py and
          tests/test_sharded.py).  The tp twin hands it both over a
          ``parallel.churn.ShardedChurnTable`` (owner-shard reads and
          one lane exchange each, ``parallel/sharded.py
          _tp_churn_primitives``) and neither over a table built once.
      live_count(done [W] bool) -> int32, the optional HOOK for the one
          cross-lane fact the engine acts on: the live count that its
          loop conditions and its cut rule read (SURVIVOR COMPACTION).
          Left out it is ``jnp.sum(~done)``, the operation that was
          there.  A caller whose wave is one CHUNK of a larger one hands
          in the count all chunks must agree on (the tp twin: the
          fullest shard's), so that loops which hold a collective run
          the same number of rounds and cut in the same one.

    CHURN (PR 32): the table is a sorted BASE as last compacted, a
    liveness bit a node, and a sorted DELTA of the nodes that joined
    since (``ops/churn_table.py``); a node is a base row or ``capacity``
    + a delta slot.  The model, which :func:`scalar_churn_lookup`
    follows step for step:

      1. Replies draw from the base as last compacted (peers' buckets
         lag): a block sample or a window row may be a node that has
         left, and is never a node of the delta.
      2. Stage ``expire``: of the α peers ``select`` chose, those that
         have left are found by ONE read of [α, W] liveness bits
         (``alive``), not by anything reply-sized.  Such a peer's
         request is spent — its k slots of the round send nothing, and
         the round is a hop if anything was sent — and its candidate
         gets ``queried`` = 2, expired.  An expired candidate keeps its
         place in the set (so a later reply that names it again does
         not make it new), is skipped by ``synced`` — the first k
         candidates that are not expired have all replied — and is
         never among the k nodes returned (``first_k_live``).
      3. Stage ``delta_window``: a node that joined is known to its
         neighbourhood at once.  Once a wave the targets are positioned
         in the delta and the DW = 8 delta rows that straddle each are
         fetched (``delta_window``); a lookup one of whose peers
         answered with the window around the target — the reply of a
         peer close to it — merges them with that round's replies, DW
         more slot-major rows, departed-again ones included (rule 2
         holds for them).  Far peers do not name a joined node before
         the next compaction.  The ids of delta nodes among the result
         come out of the same window, so the final id fetch reads the
         base only.
      4. The bootstrap reply is the lookup's own starting knowledge,
         not a request: it is drawn as base row ``boot`` would answer
         whether or not that node has left.

    The engine also counts the requests that found their peer gone, a
    scalar carried through the loops and the survivors' sub-waves, and
    returns it as ``expired_peers``.

    ROUND-FUSED GATHER (round 6): with ``block_bounds`` provided, the
    steady-state round body issues exactly ONE ``gather_planar`` call —
    the fused [α·k·W] reply-distance fetch inside the merge.  The
    round-5 engine also gathered the α queried peers' top limb each
    round (to position the reply blocks); that value is ``x0 ^ t0`` —
    the very distance limb the candidate state already carries — so it
    now rides the α-selection max-reductions instead (bit-identical;
    tests/test_search.py pins the engine's outputs against committed
    goldens so any reply-stream drift fails loudly).  In the
    table-sharded twin the same change removes one of the per-round
    psum sites (parallel/sharded.py).

    BOOTSTRAP SHAPE (PR 31): a lookup boots from ONE peer, so the
    bootstrap round runs at its own static shape — ``boot`` is [1, Q],
    its replies [k, Q], its merge sorts [Q, S + k] — through the same
    ``reply_gather`` and ``merge`` as a loop round, which read the
    number of peers off ``x_rows.shape[0]`` and the number of replies
    off ``new_rows.shape[0]``.  Run at the loop's shape it issued α·k·Q
    gather indices, 2·α·Q LUT reads and (on a mesh) an [NL, α·k, Q]
    psum of which the last α − 1 parts in α were the −1 of peers that
    do not exist (6.8 ms of a 119 ms wave on one chip at 10M ids, 25 of
    323 on a 25M-row shard; PERF.md §6, PR 31).  What does NOT follow
    the shape is the wave's ``R`` = α·k inside the reply model: the
    hash counter's stride and the fallback window's span
    (:func:`_reply_rows`).  The columns that went were invalid (−1)
    and sorted behind the S initial −1 candidates, all with one key, so
    the state after the bootstrap is the α-wide one's bit for bit
    (tests/test_search.py keeps the α-wide bootstrap as its reference).

    REPLY-PATH LAYOUT (PR 27): from ``select``'s output to ``insert``'s
    concatenate every per-peer value is a PEER-MAJOR plane [alpha, W]
    and every per-reply value a SLOT-MAJOR plane [R, W] (R = α·k, slot
    r = sample r % k of peer r // k) — the W lookups on the minor axis,
    which the TPU puts on the 128 lanes.  That is the layout XLA already
    gives the candidate state: a logical [W, S] array is stored
    {0,1:T(8,128)}, physically [S, W], dense, and the merge sorts run
    along the slot axis there; so ``.T`` of a slot-major plane joins
    the state without a copy.  The logical shape [W, α, k] the reply
    model used to compute in is tiled (4, 128) over its minor dims
    (3, 8): 21× the bytes (134 MB for each u32[65536,3,8], five a
    round, each read twice more by the reshapes to the gather's index),
    and the gather's flat index in lookup-major order cost a transpose
    before the gather and one per limb plane after it — together a
    fifth of the wave (:func:`_reply_rows`,
    ``ops.sorted_table.fused_gather_planar``; PERF.md §6, PR 27).

    DEVICE STAGES: each part of a round runs through
    ``telemetry.device_stage`` — ``select``, ``block_bounds``,
    ``reply_rows``, ``fetch_ids``, ``merge``, ``converge``, once a
    cut ``pack``, and around a tile of a tiled round ``tile`` — an inner
    jit named ``stage_<name>``.  A part that is a function of its
    arguments alone is decorated where it is defined; one that closes
    over values of the trace it runs in (the table, the LUT, the seed)
    is wrapped where it is CALLED, a fresh jit per call.  XLA inlines
    them; their only effect is the ``jit(stage_<name>)`` component in every
    operation's ``op_name``, by which a device trace charges kernel
    time to a stage.  The bootstrap round outside the loop goes through
    the same functions and so the same names.

    ``q_index``/``q_total`` are each query's GLOBAL index and the global
    batch size — the deterministic reply hash is seeded by global query
    identity, so a sharded run is bit-identical to the unsharded one.

    ``state_limbs`` picks how many distance limbs the candidate state
    carries through the per-round merge sorts: 5 (exact 160-bit
    ordering) or 2 (rank by the top 64 distance bits only — the merge
    sorts move 5 operands instead of 8 and the per-round reply-distance
    gather fetches 2 planes instead of 5; bitwise identical to the
    exact mode unless two distinct candidates tie on their top 64
    distance bits, ~2^-58 per merge at S+R=44 rows).  Either way the
    returned ``dist`` carries all 5 limbs (reconstructed from the final
    node ids in one gather).

    SURVIVOR COMPACTION: a round's cost is set by the index elements it
    issues — α·k·W for the reply gather, 2·α·W for the LUT reads —
    whatever the number of lookups that still need the round, and the
    last rounds of a wave run for a few per cent of it (10M uniform
    ids: 6.4% are live after loop round 7 and 0.04% after round 8 of
    9; PERF.md §5).  So a loop also stops once the live count
    ``sum(~done)`` — which the engine observes, nobody configures — is
    at or under ``C = W // NARROW_DIVISOR``.  The survivors are then
    packed into a ``C``-wide sub-batch on the device
    (``jnp.nonzero(size=C)``, no host sync; stage ``pack``) and run on
    as a wave of ``C`` lookups through the same round body — to which
    the same rule applies, so a 65,536-lookup wave steps down to 8,192
    and then 1,024 lanes — and what the caller reads of them (the
    first k candidates, ``hops``, ``converged``) is written back to
    their rows.  A cut waits until the survivors fit, so the cap always
    holds and no loop has to catch an overflow.  Reply streams are
    keyed by (global query id, round number): a narrower loop carries
    the round counter on from the cut and the survivors' own
    ``q_index``, so every output equals the one-loop engine's bit for
    bit, a lookup that runs into ``max_hops`` included (no loop runs
    past it).  Waves under ``NARROW_MIN_WAVE`` keep one loop.  On a mesh
    the search state is SHARDED over ``t`` as well as over ``q`` (PR 38:
    a ``t``-rank runs this engine over its own ``W/t`` lanes, and both
    constants apply to that width): the ``t``-ranks hold different
    ``done`` flags, so the tp twin hands in ``live_count`` — the fullest
    shard's count — and every ``t``-rank runs the same rounds, cuts in
    the same one, when every shard's survivors fit its ``C``, and packs
    its own; ``q``-ranks may cut in different rounds (a round's
    collectives are over ``t`` only).  The engine also returns
    ``narrow_rounds``, the number of rounds it ran under the wave's full
    width (0 = never cut).

    LANE TILES: a round's lookups do not depend on each other (the one
    cross-lane fact is the live count of the loop conditions), and what
    a round costs on the TPU depends on where its intermediates live:
    the gathered rows, their index and the LUT edges of up to
    ``ROUND_TILE_LANES`` lanes stay in on-chip memory beside the staged
    table view; those of a wider loop go through HBM.  So a loop wider
    than that (:func:`lane_tiles`) runs each round as a ``fori_loop``
    over tiles of its lanes: a tile's slice of the search state and of
    the lanes' targets, positions, global indices and delta windows is
    cut out, run through the round function a narrower loop runs whole,
    and written back in place (stage ``tile``: the two copies, all that
    tiling adds).  Reply streams are keyed by the lanes' global indices,
    which the slice carries, so every output is the untiled engine's
    bit for bit; counts that are sums add up over the tiles, and a
    closure's ``one_pass`` counts a round once, where every tile's
    gather was one.  Nothing selects this either: it is read off the
    loop's width, a loop of one tile lowers to the program it was, and
    a wave of 1,048,576 lookups runs eight tiles a round until its
    first cut, to 131,072 lanes: one.  The engine returns
    ``tiled_rounds``, the rounds it ran so, where the wave is wider
    than a tile.  The bootstrap round and the once-a-wave work around
    the loops stay at full width.
    """
    Q = targets.shape[0]
    S = search_nodes
    R = alpha * k            # reply entries merged per round
    NL = state_limbs

    pos_t_full = lower(targets)                        # [Q], fallback replies
    churn = alive is not None
    # CHURN: the delta rows around each target, positioned and fetched
    # once a wave — (node [Q, DW], 5 id planes [Q, DW]), node −1 = none
    dwin = (None if delta_window is None else
            device_stage("delta_window")(delta_window)(targets))

    def fetch_ids(rows, limbs):
        """Stage ``fetch_ids``: limb planes of table rows — the round's
        one fused gather, and the final id fetch — and the closure's
        report on how it served them (None from one that gives none)."""
        got = device_stage("fetch_ids")(
            lambda r: gather_planar(r, limbs))(rows)
        return got if isinstance(got, tuple) else (got, None)

    def reply_gather(tgt, pt, qidx, x_rows, round_no, x_d0=None):
        """Simulated answers of the queried nodes per search.
        x_rows [P, W] int32 (−1 = no request; P = α in the loop, 1 in
        the bootstrap) → node rows [P·k, W] (peer-major in, slot-major
        out: W stays on the lanes).

        ``x_d0``: the queried peers' top distance limb ``x0 ^ t0``
        carried from the candidate state (the ROUND-FUSED form — see
        the round body), or None to gather it from the table (the
        bootstrap call, whose peer is not a candidate yet)."""
        if block_bounds is not None:
            # 1-LIMB cb: the LUT block read clamps prefixes at its
            # ≤24-bit width, so any cb ≥ 32 yields the same clamped
            # edges — computing cb from limb 0 alone (exact below 32,
            # 32 for deeper) is BIT-IDENTICAL through the LUT.
            # ROUND-FUSED GATHER (round 6): inside the loop x_d0 comes
            # from the candidate state (cand_l[0] IS x0 ^ t0 — the
            # merge computed it when the peer was first heard of), so
            # the per-round 1-plane peer gather of round 5 (~1 ms of
            # the ~5.5 ms round at W=16K, and one whole psum site in
            # the sharded engine) disappears: the round's ONLY table
            # gather is the fused [α·k·W] reply gather in merge().
            # block_mode="exact" keeps the full-width gathered path.
            t0 = tgt[:, 0][None, :]              # [1, W] against [P, W]
            if x_d0 is None:
                x_d0 = fetch_ids(x_rows, 1)[0][0] ^ t0

            def edges(x_d0):
                b = clz32(x_d0)                  # clz32(0) == 32 by contract
                return block_bounds(t0, b + 1)

            lo, ub = device_stage("block_bounds")(edges)(x_d0)
        else:
            def edges(x_l):
                t_l = [tgt[:, l][None, :] for l in range(N_LIMBS)]
                b = _common_bits_planar(x_l, t_l)                    # [P,W]
                prefix_len = jnp.clip(b + 1, 0, ID_BITS)
                return _prefix_block_bounds(
                    lower, n,
                    jnp.broadcast_to(tgt[None], x_rows.shape + (N_LIMBS,)),
                    prefix_len)

            lo, ub = device_stage("block_bounds")(edges)(
                fetch_ids(x_rows, N_LIMBS)[0])       # full ids: exact cb
        rows = device_stage("reply_rows")(functools.partial(
            _reply_rows, n=n, k=k, R=R, q_total=q_total, seed_u=seed_u))(
            pt, qidx, x_rows, round_no, lo, ub)
        if dwin is None:
            return rows, None
        # CHURN: a lookup one of whose peers answered with the window
        # around the target is near it, and hears of who joined there
        return rows, device_stage("delta_window")(
            lambda lo, ub, x: jnp.any((ub - lo < k) & (x >= 0), axis=0))(
            lo, ub, x_rows)

    def merge(tgt, cand_node, cand_l, queried, new_rows, dw=None,
              near=None):
        """Fetch the replies' ids (stage ``fetch_ids``) and insert them
        (stage ``merge``); under CHURN the delta rows ``dw`` around the
        target join the replies of a lookup that is ``near`` it (stage
        ``delta_window``), DW more slot-major rows.  Returns the new
        candidate state and the gather's report (:func:`fetch_ids`)."""
        new_l, one_pass = fetch_ids(new_rows, NL)               # NL×[P·k,W]
        if dw is not None:
            @device_stage("delta_window")
            def with_delta(new_rows, new_l, dw, near):
                node, ids = dw
                return (jnp.concatenate(
                            [new_rows, jnp.where(near[None, :], node.T, -1)]),
                        [jnp.concatenate([new_l[l], ids[l].T])
                         for l in range(NL)])

            new_rows, new_l = with_delta(new_rows, new_l, dw, near)
        return (insert(tgt, cand_node, cand_l, queried, new_rows, new_l),
                one_pass)

    @device_stage("merge")
    def insert(tgt, cand_node, cand_l, queried, new_rows, new_l):
        """Insert replies, dedupe by node, keep the S closest
        (↔ Search::insertNode, src/search.h:636-722).  ``cand_l`` is the
        candidate distance as NL limb planes [W, S]; ``new_rows`` and
        ``new_l`` arrive slot-major [P·k, W] (R rows from a loop round,
        k from the bootstrap).  On the TPU a [W, S] array
        is laid out with W on the lanes (physically [S, W]), so the
        ``.T`` of a slot-major plane is a change of name, not a copy,
        and the concatenate joins along the physical major axis."""
        W = tgt.shape[0]
        node = jnp.concatenate([cand_node, new_rows.T], axis=1) # [W,S+P·k]
        d_l = [jnp.concatenate([cand_l[l],
                                (new_l[l] ^ tgt[:, l][None, :]).T],
                               axis=1) for l in range(NL)]
        qd = jnp.concatenate(
            [queried, jnp.zeros((W, new_rows.shape[0]), jnp.int32)], axis=1)
        inv = (node < 0).astype(jnp.int32)
        # new entries beyond the valid table (padded fallback rows for
        # empty/absent requests) already arrive as -1 via reply_gather;
        # their distance planes are garbage but masked by inv.
        big = jnp.uint32(0xFFFFFFFF)
        d_l = [jnp.where(inv == 0, dl, big) for dl in d_l]
        # sort by (invalid, dist, node, not-queried) so that among
        # duplicates of a node the already-queried copy comes first
        out = lax.sort(
            (inv,) + tuple(d_l) + (node, 1 - qd),
            dimension=1, num_keys=3 + NL,
        )
        inv_s, node_s = out[0], out[1 + NL]
        qd_s = 1 - out[2 + NL]
        # dedupe: same node appears adjacently (same dist); drop repeats
        dup = jnp.concatenate(
            [jnp.zeros((W, 1), bool),
             (node_s[:, 1:] == node_s[:, :-1]) & (node_s[:, 1:] >= 0)], axis=1)
        inv2 = jnp.where(dup, 1, inv_s)
        out2 = lax.sort(
            (inv2,) + tuple(out[1:1 + NL]) + (node_s, 1 - qd_s),
            dimension=1, num_keys=2 + NL,
        )
        present = out2[0][:, :S] == 0
        node_f = jnp.where(present, out2[1 + NL][:, :S], -1)
        d_f = [jnp.where(present, out2[1 + l][:, :S], big)
               for l in range(NL)]
        qd_f = (1 - out2[2 + NL])[:, :S] * present
        return node_f, d_f, qd_f

    # -- bootstrap: cold start from ONE pseudo-random bootstrap peer per
    # search (like a node boots from a single well-known host), at the
    # shape of one peer (BOOTSTRAP SHAPE) ----------------------------------
    empty = n <= 0
    boot = jnp.where(
        empty, -1,
        (_mix32(q_index.astype(_U32) ^ seed_u)
         % jnp.maximum(n, 1).astype(_U32)).astype(jnp.int32))[None, :]
    cand_node = jnp.full((Q, S), -1, jnp.int32)
    cand_l = [jnp.full((Q, S), 0xFFFFFFFF, _U32) for _ in range(NL)]
    queried = jnp.zeros((Q, S), jnp.int32)
    first, near = reply_gather(targets, pos_t_full, q_index, boot,
                               jnp.int32(0))
    dw_full = None if dwin is None else (dwin[0], dwin[1][:NL])
    (cand_node, cand_l, queried), one_pass = merge(
        targets, cand_node, cand_l, queried, first, dw_full, near)
    # the counts a wave carries through its loops and its survivors'
    # sub-waves, each only where it exists: the requests that found
    # their peer gone (CHURN), and the round gathers the closure served
    # in one pass (a closure that reports; the bootstrap's is not one)
    counts = {}
    if churn:
        counts["expired_peers"] = jnp.int32(0)
    if one_pass is not None:
        counts["window_rounds"] = jnp.int32(0)
    if lane_tiles(Q) > 1:
        counts["tiled_rounds"] = jnp.int32(0)

    @device_stage("converge")
    def synced(cand_node, queried):
        """First min(k, #candidates) candidates all answered
        (↔ isSynced, search.h:734-747).  On a table that is built once
        replies are instantaneous and certain, so ``queried`` > 0 says
        'replied' too.  Under CHURN ``queried`` has three states — 0 not
        asked, 1 asked and replied, 2 asked and expired — and the rule
        is upstream's in full: the first k candidates that are NOT
        expired have all replied."""
        if churn:
            # CHURN: the first k candidates that are NOT EXPIRED
            # (queried == 2) have all replied (queried == 1)
            live = (cand_node >= 0) & (queried != 2)
            first_k = live & (jnp.cumsum(live.astype(jnp.int32), axis=1) <= k)
            return jnp.all(~first_k | (queried == 1), axis=1) & \
                jnp.any(live, axis=1)
        present = cand_node[:, :k] >= 0
        return jnp.all(~present | (queried[:, :k] > 0), axis=1) & \
            jnp.any(present, axis=1)

    @device_stage("select")
    def select(cand_node, cand_l0, queried, done):
        """Stage ``select``: the closest α unqueried candidates per active
        search (↔ searchSendGetValues picking SearchNodes with canGet,
        src/dht.cpp:628-639) as peer-major rows [alpha, W] (−1 pad),
        marked queried."""
        can = (cand_node >= 0) & (queried == 0) & ~done[:, None]
        rank = jnp.cumsum(can.astype(jnp.int32), axis=1)
        sel = can & (rank <= alpha)
        # gather selected rows into [alpha, W] (−1 pad): α static
        # masked max-reductions — a scatter-max here measured slower —
        # handed over as the α [W] vectors they are (W on the lanes)
        x_rows = jnp.stack(
            [jnp.max(jnp.where(sel & (rank == j + 1), cand_node, -1),
                     axis=1) for j in range(alpha)], axis=0)
        if block_bounds is not None:
            # ROUND FUSION: the selected peers' top distance limb
            # rides the same masked max-reductions (cand_l[0] is
            # x0 ^ t0 — computed by the merge that first admitted
            # the peer), so reply_gather needs NO table access to
            # position the reply blocks and the round's only
            # gather is the fused α·k-row reply fetch.  Bit-exact:
            # a selected lane is unique per rank (cumsum), and
            # unselected slots (x_rows = -1) get d0 = 0 → their
            # replies are masked exactly as the gathered path
            # masked them.
            x_d0 = jnp.stack(
                [jnp.max(jnp.where(sel & (rank == j + 1), cand_l0,
                                   _U32(0)), axis=1)
                 for j in range(alpha)], axis=0)
        else:
            x_d0 = None
        return sel, x_rows, x_d0, jnp.where(sel, 1, queried)

    @device_stage("converge")
    def converge(cand_node, queried, sel, hops, done):
        """Stage ``converge``: hop counts and the done flags after a
        round's merge."""
        now_done = synced(cand_node, queried)
        stalled = ~jnp.any((cand_node >= 0) & (queried == 0), axis=1)
        sent = jnp.any(sel, axis=1)
        # a stalling round sends nothing → costs no hop (matches the
        # scalar reference's stall return path)
        hops = jnp.where(~done & sent, hops + 1, hops)
        return hops, done | now_done | stalled

    def expire(cand_node, x_rows, queried):
        """Stage ``expire`` (CHURN): which of the chosen peers are gone
        — one read of [P, W] liveness bits — and their marking: the
        request is spent (the peer's slot sends nothing, ``x_rows`` −1),
        the candidate is expired (``queried`` 2) and counted."""
        gone = (x_rows >= 0) & ~alive(x_rows)
        for j in range(x_rows.shape[0]):
            queried = jnp.where((cand_node == x_rows[j][:, None])
                                & gone[j][:, None], 2, queried)
        return (jnp.where(gone, -1, x_rows), queried,
                jnp.sum(gone, dtype=jnp.int32))

    def round_of(round_no):
        """One loop round over SOME of a wave's lanes (all of them, or
        one of its LANE TILES): ``step(lanes, state, counts)`` with
        ``lanes`` their targets, positions, global indices and delta
        windows and ``state`` their search state, returns the state and
        the counts."""
        def step(lanes, state, counts):
            tgt, pt, qidx, dw = lanes
            cand_node, cand_l, queried, hops, done = state
            sel, x_rows, x_d0, queried = select(cand_node, cand_l[0],
                                                queried, done)
            if churn:
                x_rows, queried, gone_now = device_stage("expire")(expire)(
                    cand_node, x_rows, queried)
                counts = dict(counts, expired_peers=counts["expired_peers"]
                              + gone_now)
            new_rows, near = reply_gather(tgt, pt, qidx, x_rows,
                                          round_no + 1, x_d0)
            (cand_node, cand_l, queried), one_pass = merge(
                tgt, cand_node, cand_l, queried, new_rows, dw, near)
            if one_pass is not None:
                counts = dict(counts, window_rounds=counts["window_rounds"]
                              + one_pass)
            hops, done = converge(cand_node, queried, sel, hops, done)
            return (cand_node, cand_l, queried, hops, done), counts
        return step

    def by_tiles(step, lanes, state, counts):
        """LANE TILES: ``step`` over the lanes of ``state`` — at once
        where they are no more than ``ROUND_TILE_LANES`` (or no whole
        number of tiles), else a tile after the other inside the round:
        a tile's slice of every array is cut out, stepped and written
        back where it was (stage ``tile``: the copies are all this adds).
        Returns the state, the counts and the number of tiles."""
        tiles = lane_tiles(lanes[0].shape[0])
        if tiles == 1:
            return (*step(lanes, state, counts), 1)

        @device_stage("tile")
        def cut(i, wide):
            return jax.tree.map(lambda a: lax.dynamic_slice_in_dim(
                a, i * ROUND_TILE_LANES, ROUND_TILE_LANES), wide)

        @device_stage("tile")
        def paste(i, wide, part):
            return jax.tree.map(lambda w, p: lax.dynamic_update_slice_in_dim(
                w, p, i * ROUND_TILE_LANES, 0), wide, part)

        def tile(i, carried):
            state, counts = carried
            part, counts = step(cut(i, lanes), cut(i, state), counts)
            return paste(i, state, part), counts

        return (*lax.fori_loop(0, tiles, tile, (state, counts)), tiles)

    def make_body(tgt, pt, qidx, dw):
        def body(state):
            *lane_state, round_no, counts = state
            lane_state, after, tiles = by_tiles(
                round_of(round_no), (tgt, pt, qidx, dw), tuple(lane_state),
                counts)
            if tiles > 1:
                after = dict(after, tiled_rounds=counts["tiled_rounds"] + 1)
                if "window_rounds" in counts:
                    # a round is one pass where every tile of it was
                    after["window_rounds"] = counts["window_rounds"] + (
                        after["window_rounds"] - counts["window_rounds"]
                    ) // tiles
            return (*lane_state, round_no + 1, after)
        return body

    if live_count is None:
        def live_count(done):
            return jnp.sum(~done)

    def live_over(cap):
        """Loop condition: more than ``cap`` lookups live, rounds left."""
        def cond(state):
            done, round_no = state[4], state[5]
            return (live_count(done) > cap) & (round_no < max_hops)
        return cond

    def first_k_live(cand_node, cand_l, queried):
        """CHURN: the first k candidates that are not expired — an
        expired node is never among those returned — by k masked
        reductions, as ``select`` picks its α."""
        live = (cand_node >= 0) & (queried != 2)
        rank = jnp.cumsum(live.astype(jnp.int32), axis=1)
        pick = [live & (rank == j + 1) for j in range(k)]
        return (jnp.stack([jnp.max(jnp.where(p, cand_node, -1), axis=1)
                           for p in pick], axis=1),
                [jnp.stack([jnp.min(jnp.where(p, cl, _U32(0xFFFFFFFF)),
                                    axis=1) for p in pick], axis=1)
                 for cl in cand_l])

    def head(cand_node, cand_l, queried):
        """What the outputs read of a search state: the first k
        candidates, their carried distance planes (exact mode only; the
        2-limb mode fetches ids instead) and the converged flags."""
        if churn:
            return (*device_stage("converge")(first_k_live)(
                cand_node, cand_l if NL == N_LIMBS else [], queried),
                synced(cand_node, queried))
        return (cand_node[:, :k],
                [cl[:, :k] for cl in cand_l] if NL == N_LIMBS else [],
                synced(cand_node, queried))

    def run(width, tgt, pt, qidx, dw, state):
        """Run the ``width`` lookups of ``state`` to the end (SURVIVOR
        COMPACTION): the loop at this width until the live ones fit
        ``C`` lanes, then — packed — as a wave of ``C`` lookups, to
        which the same rule applies.  ``C`` = 0 (a wave under the
        threshold): the loop runs until nobody is live and is the last.
        Returns what the outputs read (:func:`head` and ``hops``), the
        round this width's loop ended in, the round the last one did
        and the wave's counts."""
        C = width // NARROW_DIVISOR if width >= NARROW_MIN_WAVE else 0
        (cand_node, cand_l, queried, hops, done, round_no,
         counts) = lax.while_loop(
            live_over(C), make_body(tgt, pt, qidx, dw), state)
        outs = (*head(cand_node, cand_l, queried), hops)
        if not C:
            return outs, round_no, round_no, counts

        @device_stage("pack")
        def pack(done, wide):
            """The live lookups' rows (fill lanes: ``width``, past the
            end) and their slice of every array of ``wide``; a fill
            lane reads the last row and is marked done, so it sends
            nothing."""
            rows = jnp.nonzero(~done, size=C, fill_value=width)[0]
            return rows, rows >= width, jax.tree.map(
                lambda a: jnp.take(a, rows, axis=0, mode="clip"), wide)

        @device_stage("pack")
        def unpack(rows, wide, narrow):
            """Write the survivors' outputs back to their rows (a fill
            lane's row is out of range and dropped)."""
            return jax.tree.map(
                lambda w, n: w.at[rows].set(n, mode="drop"), wide, narrow)

        rows, filled, (tgt, pt, qidx, dw, *sub) = pack(
            done, (tgt, pt, qidx, dw, cand_node, cand_l, queried, hops))
        sub_outs, _, last_round, counts = run(
            C, tgt, pt, qidx, dw, (*sub, filled, round_no, counts))
        return unpack(rows, outs, sub_outs), round_no, last_round, counts

    (nodes_k, dist_k, converged, hops), cut_round, last_round, counts = run(
        Q, targets, pos_t_full, q_index, dw_full,
        (cand_node, cand_l, queried, jnp.zeros((Q,), jnp.int32),
         synced(cand_node, queried) | empty, jnp.int32(0), counts))

    if NL == N_LIMBS:
        dist = jnp.stack(dist_k, axis=-1)
    else:
        # reconstruct the full 160-bit distances from the final node ids
        # in ONE gather — the merge loop never carried limbs 2-4.
        # Lookup-major on purpose: a lookup's k nodes are neighbours in
        # the sorted table, and in that order this once-a-wave fetch
        # measured 1.75 ms on each of four table shards against 8.90
        # slot-major (one chip: 7.60 against 6.81; PERF.md §6, PR 27)
        id_l, _ = fetch_ids(nodes_k, N_LIMBS)
        if dwin is not None:
            # CHURN: a delta node's id is in the window it came from
            @device_stage("delta_window")
            def delta_ids(id_l, nodes_k, dwin):
                node, ids = dwin
                for j in range(node.shape[1]):
                    hit = (nodes_k == node[:, j:j + 1]) & (nodes_k >= 0)
                    id_l = [jnp.where(hit, ids[l][:, j:j + 1], id_l[l])
                            for l in range(N_LIMBS)]
                return id_l

            id_l = delta_ids(id_l, nodes_k, dwin)
        dist = jnp.stack(
            [jnp.where(nodes_k >= 0, id_l[l] ^ targets[:, l:l + 1],
                       jnp.uint32(0xFFFFFFFF)) for l in range(N_LIMBS)],
            axis=-1)
    return {
        "nodes": nodes_k,
        "dist": dist,
        "hops": hops,
        "converged": converged & ~empty,
        "narrow_rounds": last_round - cut_round,
        **counts,
    }


@functools.partial(
    jax.jit,
    static_argnames=("k", "alpha", "search_nodes", "max_hops",
                     "state_limbs", "block_mode"),
)
def _simulate_lookups_jit(sorted_ids, n_valid, targets, *, seed: int = 0,
                          k: int = TARGET_NODES, alpha: int = ALPHA,
                          search_nodes: int = SEARCH_NODES, max_hops: int = 48,
                          lut=None, state_limbs: int = N_LIMBS,
                          block_mode: str = "lut"):
    """Compiled core of :func:`simulate_lookups` (same contract; the
    public wrapper adds the host-side telemetry envelope).

    Args:
      sorted_ids: uint32 [N, 5], lexicographically sorted network ids
                  (node identity == sorted row index) — or a
                  ``ops.churn_table.ChurnTable`` (``DeviceChurnTable
                  .view``), the table under membership churn: the
                  engine then runs its CHURN model over the table's
                  base, liveness words and delta, ``n_valid`` and
                  ``lut`` are the table's own (pass None), and the
                  result gains ``expired_peers`` (int32: the wave's
                  requests to nodes that were gone).  Nothing selects
                  this: it is read off what is handed over.
      n_valid:    number of real rows in sorted_ids.
      targets:    uint32 [Q, 5] lookup keys.

    Returns dict of:
      nodes     [Q, k] int32  — the k closest nodes found (sorted rows)
      dist      [Q, k, 5]     — their XOR distances
      hops      [Q] int32     — rounds until the first-k set had replied
      converged [Q] bool
      narrow_rounds int32     — rounds the wave ran under its full width
                                (:func:`_lookup_engine`, SURVIVOR
                                COMPACTION; 0 = one loop did it all)
      tiled_rounds int32      — of a wave wider than ``ROUND_TILE_LANES``
                                only: rounds it ran tile by tile
                                (:func:`_lookup_engine`, LANE TILES)

    Single-device instantiation of :func:`_lookup_engine`.  The
    table-sharded multi-chip form (table rows partitioned over a mesh
    axis, exceeding one chip's HBM) is
    ``parallel.tp_simulate_lookups`` — same engine, same results.
    ``state_limbs=2`` ranks merge candidates by the top 64 distance
    bits only (5-operand merge sorts instead of 8 — see
    :func:`_lookup_engine`); bitwise identical to the default absent
    64-bit distance ties.

    ``block_mode`` selects how the simulated reply model computes each
    peer's prefix-block edges: ``"lut"`` (default) = two LUT reads per
    edge (:func:`_lut_block_bounds`) — exact for prefixes up to the LUT
    width, clamped to the containing bucket beyond it; ``"exact"`` =
    the per-round batched binary search (the pre-round-5 model, exact
    at any depth and most of a round's time at 10M).  On uniform
    tables at ``default_lut_bits`` the two are statistically indistinguishable
    (a clamped bucket with ≥ k rows exists for ~4 of 16.7M buckets at
    N=10M and affects a reply only when a target lands in it past the
    LUT depth); on heavily CLUSTERED tables the clamp widens deep
    blocks, so hop-trajectory studies of adversarial id distributions
    should pass ``block_mode="exact"`` (cf. the positioning guard
    ``_guarded_lower_bound``, which handles clustering for the
    positioning search automatically).
    """
    if block_mode not in ("lut", "exact"):
        raise ValueError(f"block_mode must be 'lut' or 'exact', "
                         f"got {block_mode!r}")
    churn = {}
    if isinstance(sorted_ids, ChurnTable):
        # CHURN: the engine reads it off what it is handed — the base
        # is the table, with its own row count and LUT, and the
        # liveness words and the delta become the two churn primitives
        tbl = sorted_ids
        if n_valid is not None or lut is not None:
            raise ValueError("a ChurnTable brings its row count and LUT")
        sorted_ids, n_valid, lut = tbl.base, tbl.n_base, tbl.lut
        churn = _churn_primitives(tbl)
    N = sorted_ids.shape[0]
    Q = targets.shape[0]
    n = jnp.asarray(n_valid, jnp.int32)
    seed_u = jnp.asarray(seed, dtype=jnp.int32).astype(_U32)

    # Layout note (measured on v5e): any [.., .., 5] intermediate pads
    # its 5-lane minor dim to 128 in TPU tiled layout (25× physical
    # traffic — ~2.7 GB per materialized [Q, S+R, 5] at Q=131072), and
    # per-element row gathers run issue-bound at ~190K rows/ms.  So the
    # loop state keeps distances as 5 separate [Q, S] limb planes, id
    # gathers go through the transposed [5, N] table (planar output,
    # no lane padding), and the positioning searches use the prefix LUT
    # behind a device-side soundness guard (_guarded_lower_bound):
    # clustered tables whose largest bucket exceeds the bounded
    # in-bucket budget take the full-depth search instead.
    # The same rule for the reply path (PR 27): a [Q, alpha, k]
    # intermediate pads (3, 8) to a (4, 128) tile, 21× the bytes, so a
    # round's replies are slot-major planes [alpha·k, Q] from the reply
    # model through the gather to the merge's concatenate — Q on the
    # lanes, where XLA keeps the [Q, S] state anyway (physically
    # [S, Q]) — and the gather's flat index and planes use that order,
    # so neither side of it transposes (_lookup_engine, REPLY-PATH
    # LAYOUT).
    sorted_t = sorted_ids.T                            # [5, N] one transpose
    if lut is None:
        # callers with a stable table should build this once with
        # build_prefix_lut and pass it in — rebuilt here it costs a
        # device searchsorted over N keys on every invocation
        lut = build_prefix_lut(sorted_ids, n, bits=default_lut_bits(N))
    # sound positioning: LUT fast path only when every bucket fits the
    # bounded in-bucket budget, else full-depth search (lax.cond)
    lower = _guarded_lower_bound(sorted_ids, n, lut)

    # what the engine's gathers read, decided HERE, once: the engine
    # calls gather_planar inside its while_loop bodies, where a slice of
    # the table is the gather's staging copy if the view fits on-chip
    # memory and a table-sized copy every round for nothing if it does
    # not (_lookup_engine, the gather_planar contract)
    views = {l: loop_gather_view(sorted_t, l)
             for l in (1, state_limbs, N_LIMBS)}

    def gather_planar(rows, limbs=N_LIMBS):
        """rows [...] int32 → list of `limbs` limb arrays shaped like
        rows (top limbs first — all the merge ranking needs).  ONE
        fused take per call — ops.sorted_table.fused_gather_planar is
        the shared primitive (pinned against the xor_topk.gather_rows
        oracle)."""
        return fused_gather_planar(views[limbs], rows, limbs)

    return _lookup_engine(gather_planar, lower, n, targets,
                          jnp.arange(Q, dtype=jnp.int32), Q, seed_u,
                          k=k, alpha=alpha, search_nodes=search_nodes,
                          max_hops=max_hops, state_limbs=state_limbs,
                          block_bounds=(
                              (lambda t0, L: _lut_block_bounds(lut, t0, L))
                              if block_mode == "lut" else None), **churn)


def _churn_primitives(tbl: ChurnTable) -> dict:
    """The engine's two CHURN primitives over a device-resident
    :class:`~opendht_tpu.ops.churn_table.ChurnTable`.

    ``alive(nodes)``: one gather of liveness words (a node is a base
    row, or ``capacity`` + a delta slot; both ranges in one array).
    ``delta_window(targets)``: the ``DELTA_WINDOW`` delta rows that
    straddle each target's place in the sorted delta — positioned by
    the delta's own LUT behind the same soundness guard as the base —
    as ``(node [Q, DW], 5 id planes [Q, DW])``, node −1 past either end
    of the delta.  Fetched slot-major (the Q lookups on the lanes) and
    handed over as the ``.T`` of that."""
    C, D = tbl.capacity, tbl.delta_capacity

    def alive(nodes):
        return ~node_gone(tbl.tomb_bits, jnp.clip(nodes, 0, C + D - 1))

    lower_d = _guarded_lower_bound(tbl.delta, tbl.n_delta, tbl.delta_lut)
    delta_t = tbl.delta.T

    def delta_window(targets):
        slot = (lower_d(targets)[None, :] - DELTA_WINDOW // 2
                + jnp.arange(DELTA_WINDOW, dtype=jnp.int32)[:, None])
        ids = fused_gather_planar(delta_t, jnp.clip(slot, 0, D - 1))
        node = jnp.where((slot >= 0) & (slot < tbl.n_delta), C + slot, -1)
        return node.T, [plane.T for plane in ids]

    return {"alive": alive, "delta_window": delta_window}


def _is_tracer(x) -> bool:
    try:
        return isinstance(x, jax.core.Tracer)
    except AttributeError:          # jax moved core — fail open (no
        return False                # instrumentation, never a crash)


def record_wave(out, elapsed_s: float, wave_width: int, *,
                mode: str = "single") -> None:
    """Feed one completed search wave into the telemetry spine
    (ISSUE-3): ``dht_search_wave_seconds``,
    ``dht_search_round_seconds`` (a QUOTIENT, not a timing: wave wall /
    deepest lookup's rounds — the rounds run in lockstep inside the
    compiled while_loop and no host probe sees one; where a round's
    time goes is read off a device trace by the stages
    ``_lookup_engine`` names), the wave-width / hops distributions,
    and ``dht_search_narrow_rounds``: how many of the wave's rounds ran
    under its full width (the engine's own count, 0 = it never cut);
    of a wave wider than ``ROUND_TILE_LANES`` also
    ``dht_search_tiled_rounds``: how many of its rounds ran over its
    lanes tile by tile (:func:`_lookup_engine`, LANE TILES);
    from the tp twin also ``dht_search_window_rounds``: how many of its
    loop rounds every shard gathered in one pass over its lane window,
    and ``dht_search_home_lanes``: how many of its lanes ran on the
    shard that holds their rows.
    Shared by the single-device engine and the tp-sharded twin
    (``mode="tp"``, parallel/sharded.py); both time the whole of this
    call as ``dht_search_record_seconds``.

    ISSUE-4: when an ambient trace context is active the same envelope
    records the wave into the distributed tracer as ONE
    ``dht.search.wave`` child span (attribute ``rounds``: the deepest
    lookup's hops).  Context-gated ON PURPOSE: an untraced bench loop
    would otherwise mint a root span per wave into the shared ring and
    evict the flight-recorder events it exists to retain (found by
    review) — to trace a wave, activate a root first (``with
    tracing.activate(TraceContext.new_root()): simulate_lookups(...)``).
    Host-side only: the traced computation ran BEFORE this
    call — tracing cannot perturb the kernels (pinned in
    tests/test_tracing.py)."""
    from .. import telemetry, tracing
    reg = telemetry.get_registry()
    reg.histogram("dht_search_wave_seconds", mode=mode).observe(elapsed_s)
    reg.histogram("dht_search_wave_width", mode=mode).observe(wave_width)
    # ONE fetch for all: the counts ride the copy of ``hops``
    hops, narrow, expired, windowed, at_home, tiled = jax.device_get(
        (out["hops"], out["narrow_rounds"], out.get("expired_peers"),
         out.get("window_rounds"), out.get("home_lanes"),
         out.get("tiled_rounds")))
    if tiled is not None:
        # a wave wider than ROUND_TILE_LANES: its rounds that ran over
        # the lanes tile by tile (the engine's own count; a narrower
        # wave runs none and the series does not exist)
        reg.histogram("dht_search_tiled_rounds", mode=mode).observe(
            int(tiled))
    if expired is not None:
        # CHURN: the wave's queried peers that were gone (the engine's
        # own count; on a frozen table the series does not exist)
        reg.histogram("dht_search_expired_peers", mode=mode).observe(
            int(np.sum(expired)))
    if windowed is not None:
        # the tp twin's: the wave's in-loop round gathers that every
        # shard served in one pass over its lane window (one value a
        # q-rank, each the least over its t-ranks; parallel/sharded.py
        # window_gather) — the wave's loop rounds where its lanes
        # grouped by home shard, 0 where they pile on one
        reg.histogram("dht_search_window_rounds", mode=mode).observe(
            int(np.min(windowed)))
    if at_home is not None:
        # the tp twin's: the wave's lanes that ran on their HOME shard,
        # the one whose key range holds the lookup's target (a sum over
        # the q-ranks; parallel/sharded.py build_tp_lookup, THE SEARCH
        # STATE) — all but a few hundred of a uniform wave's, few of a
        # skewed one's: the lanes whose rows their own shard holds
        reg.histogram("dht_search_home_lanes", mode=mode).observe(
            int(np.sum(at_home)))
    reg.histogram("dht_search_hops", mode=mode).observe_many(hops)
    # one value a wave: on a mesh the slowest q-rank's (each cuts when
    # its own survivors fit)
    reg.histogram("dht_search_narrow_rounds", mode=mode).observe(
        int(np.max(narrow)))
    rounds = int(hops.max()) if hops.size else 0
    if rounds > 0:
        reg.histogram("dht_search_round_seconds", mode=mode).observe(
            elapsed_s / rounds)
    tr = tracing.get_tracer()
    ctx = tracing.current()
    # ISSUE-15: the search wave IS the device stage of every op it
    # carries — feed the waterfall the same timed span, split
    # compile-vs-execute per launch shape (mode × width) so the bench
    # loops measure the profiler at its real per-wave hook cost
    from .. import waterfall
    wf = waterfall.get_profiler()
    if wf.enabled:
        key = ("search", mode, int(wave_width))
        stage = ("device_compile" if wf.first_launch(key)
                 else "device_wait")
        wf.observe(stage, elapsed_s,
                   exemplar=tracing.current_trace_hex())
    if tr.enabled and ctx is not None:
        end = time.time()
        start = end - elapsed_s
        tr.record("dht.search.wave", start, elapsed_s, parent=ctx,
                  mode=mode, width=int(wave_width), rounds=rounds)


def _run_wave(launch, wave_width: int, mode: str):
    """Run one compiled wave (``launch()`` returns its result pytree)
    under the simulator's host envelope and feed it to
    :func:`record_wave` — the ONE spelling of the three spans, shared
    by :func:`simulate_lookups` (``mode="single"``) and
    ``parallel.sharded.tp_simulate_lookups`` (``mode="tp"``):
    ``dht_search_wave_seconds`` around dispatch and
    ``block_until_ready``, inside it ``dht_search_dispatch_seconds``
    around the jit call until it returns, and after it
    ``dht_search_record_seconds`` around all of ``record_wave``."""
    from .. import telemetry
    reg = telemetry.get_registry()
    with reg.span("dht_search_wave_seconds", record=False) as sp:
        with reg.span("dht_search_dispatch_seconds", mode=mode):
            out = launch()
        jax.block_until_ready(out)
    with reg.span("dht_search_record_seconds", mode=mode):
        record_wave(out, sp.elapsed, wave_width, mode=mode)
    return out


def simulate_lookups(sorted_ids, n_valid, targets, **kw):
    """Run Q iterative lookups to convergence — the public entry point;
    see :func:`_simulate_lookups_jit` for the full argument contract.

    A wave of ``NARROW_MIN_WAVE`` lookups or more runs its straggler
    rounds at the width of the lookups still alive: the engine watches
    the live count and, once the survivors fit an eighth of the wave,
    packs them and runs them on as a wave of that width
    (:func:`_lookup_engine`, SURVIVOR COMPACTION).  Nothing to
    configure, and results are bit-identical to one full-width loop;
    ``narrow_rounds`` in the result says how many rounds ran narrow.
    A wave wider than ``ROUND_TILE_LANES`` runs its full-width rounds
    over its lanes a tile at a time (LANE TILES, bit-identical too;
    ``tiled_rounds``).

    Telemetry envelope over the compiled engine: :func:`_run_wave`'s
    three host-side spans (``perf_counter`` plus the matching
    ``jax.profiler.TraceAnnotation``, so all three lie on a device
    trace's clock).  With one wave in flight the device idles exactly
    during the dispatch and the record span.  Host-side ONLY — the
    traced computation is byte-for-byte :func:`_simulate_lookups_jit`,
    so results are bit-identical with telemetry on or off (pinned in
    tests/test_telemetry.py).  Under an outer trace (a caller that jits
    a body which calls this) or with the registry disabled, the
    envelope vanishes and the call degrades to the bare jit — no
    blocking, no transfers."""
    from .. import telemetry
    reg = telemetry.get_registry()
    if not reg.enabled or _is_tracer(targets) or _is_tracer(sorted_ids):
        return _simulate_lookups_jit(sorted_ids, n_valid, targets, **kw)
    return _run_wave(
        lambda: _simulate_lookups_jit(sorted_ids, n_valid, targets, **kw),
        targets.shape[0], "single")


# ---------------------------------------------------------------------------
# Scalar reference implementation (oracle for hop-count parity and the CPU
# baseline) — same network model, sequential python, one lookup at a time,
# mirroring the shape of the reference's searchStep loop.
# ---------------------------------------------------------------------------

def scalar_lookup(sorted_ids_np: np.ndarray, n: int, target_np: np.ndarray,
                  *, seed: int = 0, k: int = TARGET_NODES, alpha: int = ALPHA,
                  search_nodes: int = SEARCH_NODES, max_hops: int = 48,
                  rng=None):
    """Sequential lookup with the same candidate-set/α/convergence
    semantics and the same network reply model as simulate_lookups (reply
    sampling is random rather than counter-hashed, so parity is
    statistical, not bitwise).  Returns (nodes, hops, converged)."""
    if rng is None:
        rng = np.random.default_rng(seed)

    def row_int(i):
        return int.from_bytes(ids_to_bytes(sorted_ids_np[i]).tobytes(), "big")

    t_int = int.from_bytes(ids_to_bytes(target_np).tobytes(), "big")

    def lower_bound(v: int) -> int:
        lo, hi = 0, n
        while lo < hi:
            mid = (lo + hi) // 2
            if row_int(mid) < v:
                lo = mid + 1
            else:
                hi = mid
        return lo

    pos_t = lower_bound(t_int)

    def reply(x_row: int) -> list:
        x_int = row_int(x_row)
        cb = 160 - (x_int ^ t_int).bit_length() if x_int != t_int else 160
        plen = min(cb + 1, 160)
        mask = ((1 << plen) - 1) << (160 - plen) if plen else 0
        p_lo = t_int & mask
        p_hi = p_lo | ((1 << (160 - plen)) - 1)
        lo = lower_bound(p_lo)
        ub = lower_bound(p_hi + 1)
        size = ub - lo
        if size >= k:
            return [lo + int(v) for v in rng.integers(0, size, k)]
        R = alpha * k
        base = min(max(pos_t - R // 2, 0), max(n - R, 0))
        j = int(rng.integers(0, alpha))          # this peer's window slice
        return [min(base + j * k + jj, n - 1) for jj in range(k)]

    # candidate set: list of (dist, row, queried, replied)
    cands: dict[int, list] = {}

    def insert(row):
        if row in cands:
            return
        cands[row] = [row_int(row) ^ t_int, row, False, False]

    boot = int(rng.integers(0, n))
    for r in reply(boot):
        insert(r)

    hops = 0
    while hops < max_hops:
        ordered = sorted(cands.values())[:search_nodes]
        cands = {c[1]: c for c in ordered}
        topk = ordered[:k]
        if topk and all(c[3] for c in topk):
            return [c[1] for c in topk], hops, True
        to_query = [c for c in ordered if not c[2]][:alpha]
        if not to_query:
            return [c[1] for c in topk], hops, False
        hops += 1
        for c in to_query:
            c[2] = c[3] = True
            for r in reply(c[1]):
                insert(r)
    ordered = sorted(cands.values())[:k]
    return [c[1] for c in ordered], hops, False


def scalar_churn_lookup(base_np: np.ndarray, n: int, target_np: np.ndarray,
                        *, departed=frozenset(), joined=None,
                        joined_departed=frozenset(), node_base=None,
                        seed: int = 0, k: int = TARGET_NODES,
                        alpha: int = ALPHA,
                        search_nodes: int = SEARCH_NODES, max_hops: int = 48,
                        delta_window: int = DELTA_WINDOW, rng=None):
    """:func:`scalar_lookup` over a network whose membership changes —
    the plain reference of the engine's CHURN model, sequential Python,
    one lookup at a time, independent of the engine.

    The network: ``base_np`` [≥n, 5], the sorted table as last
    compacted (node = row), of which the rows in ``departed`` have left
    since; ``joined`` [J, 5], sorted, the ids that joined since (node =
    ``node_base`` + its place there, ``node_base`` defaulting to ``n``),
    of which the places in ``joined_departed`` have left again.

    The model, beside :func:`scalar_lookup`'s: (1) replies draw from the
    base as last compacted, so they may name a departed node; (2) a
    queried node that has departed answers nothing — it is marked
    expired, its request is spent (the round is a hop if anything was
    sent) and its slot of the fallback window with it; (3) a lookup is
    synced when the first k candidates that are NOT expired have all
    replied, and returns those; (4) a peer that answers with the window
    around the target (its block held fewer than k rows) also names the
    ``delta_window`` joined nodes that straddle the target's place
    among the joined ids, departed-again ones included — a node that
    joined is known to its neighbourhood at once, and to nobody else
    before the next compaction; (5) the bootstrap reply is the
    lookup's own starting knowledge, not a request: it never expires.
    The peer in place ``a`` of a round's α answers a window reply with
    slice ``a`` (as the engine's slot ``a·k + j`` does), so where every
    reply is a window the model is deterministic and the two agree
    lookup for lookup; block samples are random here and hashed there.

    Returns ``(nodes, hops, converged, expired)``: expired = the
    requests this lookup sent to nodes that were gone."""
    if rng is None:
        rng = np.random.default_rng(seed)
    if node_base is None:
        node_base = n
    joined = np.zeros((0, N_LIMBS), np.uint32) if joined is None else joined

    def as_ints(rows) -> list:
        """[m, 5] uint32 limbs, most significant first -> m Python ints."""
        rows = np.asarray(rows, dtype=np.uint32).astype(object)
        return [int(v) for v in sum(rows[:, l] << (32 * (N_LIMBS - 1 - l))
                                    for l in range(N_LIMBS))] if len(rows) \
            else []

    base_int = as_ints(base_np[:n])
    joined_int = as_ints(joined)
    t_int = as_ints(target_np[None])[0]

    def node_int(node: int) -> int:
        return (joined_int[node - node_base] if node >= node_base
                else base_int[node])

    def gone(node: int) -> bool:
        return (node - node_base in joined_departed if node >= node_base
                else node in departed)

    def lower_bound(keys: list, v: int) -> int:
        lo, hi = 0, len(keys)
        while lo < hi:
            mid = (lo + hi) // 2
            if keys[mid] < v:
                lo = mid + 1
            else:
                hi = mid
        return lo

    pos_t = lower_bound(base_int, t_int)
    pos_d = lower_bound(joined_int, t_int)
    window_d = [node_base + j for j in range(pos_d - delta_window // 2,
                                             pos_d + delta_window // 2)
                if 0 <= j < len(joined_int)]

    def reply(x: int, place: int):
        """(nodes named, whether it was the window around the target)."""
        x_int = node_int(x)
        cb = 160 - (x_int ^ t_int).bit_length() if x_int != t_int else 160
        plen = min(cb + 1, 160)
        mask = ((1 << plen) - 1) << (160 - plen) if plen else 0
        p_lo = t_int & mask
        lo = lower_bound(base_int, p_lo)
        ub = lower_bound(base_int, (p_lo | ((1 << (160 - plen)) - 1)) + 1)
        if ub - lo >= k:
            return [lo + int(v) for v in rng.integers(0, ub - lo, k)], False
        R = alpha * k
        first = min(max(pos_t - R // 2, 0), max(n - R, 0))
        return [min(first + place * k + jj, n - 1) for jj in range(k)], True

    cands: dict = {}        # node -> [dist, node, 0 new | 1 replied | 2 expired]

    def hear(nodes, near: bool):
        for node in list(nodes) + (window_d if near else []):
            if node not in cands:
                cands[node] = [node_int(node) ^ t_int, node, 0]

    hear(*reply(int(rng.integers(0, n)), 0))

    hops = expired = 0
    while True:
        ordered = sorted(cands.values())[:search_nodes]
        cands = {c[1]: c for c in ordered}
        first_k = [c for c in ordered if c[2] != 2][:k]
        found = [c[1] for c in first_k]
        if first_k and all(c[2] == 1 for c in first_k):
            return found, hops, True, expired
        to_query = [c for c in ordered if c[2] == 0][:alpha]
        if not to_query or hops >= max_hops:
            return found, hops, False, expired
        hops += 1
        heard, near = [], False
        for place, c in enumerate(to_query):
            if gone(c[1]):
                c[2] = 2
                expired += 1
                continue
            c[2] = 1
            nodes, window = reply(c[1], place)
            heard += nodes
            near |= window
        hear(heard, near)
