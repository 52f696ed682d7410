"""Declarative sharding rules for mesh-placed DHT state.

Large-model JAX codebases place parameter-sized state with one pattern
(SNIPPETS.md retrieved three instances of it): a list of **regex
partition rules** matched against the /-joined names of a state pytree
yields a pytree of :class:`~jax.sharding.PartitionSpec`, which turns
into per-leaf :class:`~jax.sharding.NamedSharding` **shard/gather
functions** — host arrays go straight to their device slices (no
replicated staging copy), device arrays reshard in place, and loop
bodies pin intermediates with ``with_sharding_constraint``.  This
module is that layer for the DHT's table state, replacing the
hand-rolled per-entry ``jnp.asarray`` + ``device_put`` placement that
``parallel/sharded.py`` grew one function at a time.

The named state it exists for is :func:`shard_table_state`'s pytree —
the row-sharded sorted table that scales the iterative search engine
past one chip's HBM (ROADMAP item 1); ``global_sort.sharded_global_sort``
builds the same pytree from unsorted row-sharded ids:

``sorted_ids``   uint32 [N, 5]        ``P('t', None)`` — each ``t``
                 shard owns one contiguous range of the global sorted
                 order (the Kademlia analog: a node owns the contiguous
                 XOR neighborhood around its id, PARITY.md).
``local_lut``    int32 [n_t, 2^lb+1]  ``P('t', None)`` — per-shard
                 positioning LUT over the shard's own rows, built once
                 (the old layout re-derived it inside every launch).
``block_lut``    int32 [2^bb+1]       replicated — the GLOBAL prefix
                 LUT, assembled as ONE one-shot psum of the per-shard
                 LUTs at table-build time.  Entry p of a shard's LUT is
                 its local count of valid rows with prefix < p, and the
                 global count is the sum, so the replicated table is
                 bit-identical to ``build_prefix_lut`` over the whole
                 id set.  This is what removes the per-hop block-edge
                 psum from the engine's steady-state round: reply-block
                 edges become two LOCAL reads, and the round's only
                 collective is the reply-row merge
                 (``sharded.build_tp_lookup``).
``n_valid``      int32 scalar         replicated.

Rules are matched first-hit in order; every leaf must match (the
catch-all ``.*`` → replicated rule closes the list, as in the
reference pattern).  Scalars and 0-d leaves never partition.
"""

from __future__ import annotations

import functools
import re
from typing import NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def tree_paths(tree):
    """Pytree of '/'-joined string names, one per leaf (dict keys and
    sequence indices), the name space the partition rules match."""
    paths_leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    names = []
    for path, _leaf in paths_leaves:
        parts = []
        for entry in path:
            key = getattr(entry, "key", getattr(entry, "idx",
                                                getattr(entry, "name", None)))
            parts.append(str(key))
        names.append("/".join(parts))
    treedef = jax.tree_util.tree_structure(tree)
    return jax.tree_util.tree_unflatten(treedef, names)


def match_partition_rules(rules, tree):
    """Pytree of PartitionSpec from ``rules``: an ordered list of
    ``(regex, PartitionSpec)`` searched against each leaf's /-joined
    name — the declarative placement pattern of large-model JAX
    codebases (SNIPPETS.md).  Scalar leaves are never partitioned;
    a leaf matching no rule is an error (close rule lists with
    ``(".*", P())``)."""
    def spec_of(name, leaf):
        shape = getattr(leaf, "shape", ())
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return P()                        # never partition scalars
        for rule, spec in rules:
            if re.search(rule, name) is not None:
                return spec
        raise ValueError(f"no partition rule matches leaf {name!r} "
                         f"(shape {shape}) — add a rule or a catch-all")
    return jax.tree_util.tree_map(spec_of, tree_paths(tree), tree)


def make_shard_and_gather_fns(mesh: Mesh, partition_specs):
    """Per-leaf (shard_fns, gather_fns) pytrees from a PartitionSpec
    pytree.

    A shard fn places ONE leaf under its NamedSharding: host (numpy)
    arrays are ``device_put`` **directly to the sharding** — each
    device receives only its slice, never a replicated staging copy
    (the transient 2× HBM spike of ``jnp.asarray`` + re-placement that
    ``dp_simulate_lookups`` used to pay); committed device arrays
    reshard via a jitted identity pinned by ``out_shardings``.  A
    gather fn is the inverse: one jitted identity to the fully
    replicated spec, returned as numpy.
    """
    shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), partition_specs,
        is_leaf=lambda x: isinstance(x, P))
    is_ns = lambda x: isinstance(x, NamedSharding)     # noqa: E731
    return (jax.tree_util.tree_map(_shard_fn_for, shardings, is_leaf=is_ns),
            jax.tree_util.tree_map(_gather_fn_for, shardings, is_leaf=is_ns))


@functools.lru_cache(maxsize=256)
def _shard_fn_for(sharding: NamedSharding):
    """Placement fn for one NamedSharding (memoized — repeated waves
    reuse one compiled reshard identity per sharding)."""
    @functools.partial(jax.jit, out_shardings=sharding)
    def _reshard(x):
        return jnp.asarray(x)

    def shard_fn(x):
        if getattr(x, "sharding", None) == sharding:
            return x                          # already placed
        if isinstance(x, (np.ndarray, np.generic)) or np.isscalar(x):
            return jax.device_put(x, sharding)
        return _reshard(x)
    return shard_fn


@functools.lru_cache(maxsize=256)
def _gather_fn_for(sharding: NamedSharding):
    rep = NamedSharding(sharding.mesh, P())

    @functools.partial(jax.jit, out_shardings=rep)
    def _gather(x):
        return jnp.asarray(x)

    def gather_fn(x):
        return np.asarray(_gather(x))
    return gather_fn


def shard_put(mesh: Mesh, tree, rules):
    """Place a whole named pytree by rule match — the one-call form the
    ``parallel/sharded.py`` entry points use."""
    specs = match_partition_rules(rules, tree)
    shard_fns, _ = make_shard_and_gather_fns(mesh, specs)
    return jax.tree_util.tree_map(lambda fn, x: fn(x), shard_fns, tree)


def constrain(tree, mesh: Mesh, rules):
    """``with_sharding_constraint`` every leaf of a named pytree to its
    rule-matched spec — for use INSIDE jitted bodies (the dp engine's
    query-axis pin), where placement is a compiler constraint rather
    than a transfer."""
    specs = match_partition_rules(rules, tree)
    return jax.tree_util.tree_map(
        lambda x, spec: lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec)),
        tree, specs)


# --------------------------------------------------------------------------
# The DHT table-state rules.  First match wins; names are the keys of
# the pytrees the parallel/ entry points build.
# --------------------------------------------------------------------------

#: row-sharded table state (the t axis owns rows; see module docstring)
TABLE_AXIS_RULES = (
    (r"sorted_ids$|^ids$|^table$|expanded$", P("t", None)),
    (r"local_lut$", P("t", None)),
    (r"block_lut$", P()),
    # load-aware reshard geometry (ISSUE-17): per-shard (base, width)
    # row ranges of a traffic-weighted split — a [t, 2] int32 operand,
    # one row per shard, so boundary moves are data, never a recompile
    (r"shard_rows$", P("t", None)),
    # `valid$` also covers the sketch twin's `sketch_valid` mask
    (r"perm$|valid$|n_local$|last_reply$", P("t")),
    # keyspace sketch traffic (ISSUE-10): the wave's observed ids split
    # over the table axis — each shard builds a partial sketch, one
    # psum pair merges (sharded.py sharded_sketch_update)
    (r"sketch_ids$", P("t", None)),
    # hot-cache probe traffic (ISSUE-11): the wave's probe targets
    # split over the table axis — the tiny [C, 5] cache table rides
    # replicated, each shard XOR-compares its target rows locally
    # (sharded.py sharded_cache_probe; fully data-parallel, no
    # collective)
    (r"probe_ids$", P("t", None)),
    (r"targets$|queries$", P("q", None)),
    (r".*", P()),
)

#: data-parallel engine state (table replicated, queries over the
#: whole mesh) — dp_simulate_lookups
DP_AXIS_RULES = (
    (r"targets$|queries$", P(("q", "t"), None)),
    (r".*", P()),
)


class TableState(NamedTuple):
    """A row-sharded sorted table, placed once and reused across waves
    (:func:`shard_table_state`).  ``arrays`` is the named pytree whose
    leaves sit under :data:`TABLE_AXIS_RULES`; the ints are the static
    geometry ``sharded.build_tp_lookup`` compiles against."""
    arrays: dict
    shard_n: int
    lut_bits: int
    block_bits: int
    #: interior row boundaries of a load-aware split (None = uniform
    #: N/t rows per shard).  When set, ``arrays`` carries a
    #: ``shard_rows`` [t, 2] operand and ``shard_n`` is the rounded-up
    #: per-shard row CAPACITY, not the uniform width.
    boundaries: Optional[tuple] = None

    @property
    def sorted_ids(self):
        return self.arrays["sorted_ids"]

    def table_bytes_per_shard(self) -> int:
        """Resident sorted-table bytes on ONE device — the N/t·5·4 B
        figure the per-shard HBM budget bounds."""
        return self.shard_n * self.sorted_ids.shape[1] * 4


@functools.lru_cache(maxsize=16)
def _build_state_luts(mesh: Mesh, shard_n: int, lut_bits: int,
                      block_bits: int):
    from ..ops.sorted_table import build_prefix_lut

    def local(sorted_shard, n_valid):
        ti = lax.axis_index("t")
        n_local = jnp.clip(jnp.asarray(n_valid, jnp.int32)
                           - ti.astype(jnp.int32) * shard_n, 0, shard_n)
        lut = build_prefix_lut(sorted_shard, n_local, bits=lut_bits)
        part = (lut if block_bits == lut_bits else
                build_prefix_lut(sorted_shard, n_local, bits=block_bits))
        # entry p of each shard's LUT counts LOCAL valid rows with
        # prefix < p; the sum over shards is the global count — ONE
        # one-shot psum yields the replicated global prefix LUT,
        # bit-identical to build_prefix_lut over the whole table
        block_lut = lax.psum(part, "t")
        return lut[None], block_lut

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("t", None), P()),
        out_specs=(P("t", None), P()),
        check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=16)
def _build_state_luts_weighted(mesh: Mesh, lut_bits: int, block_bits: int):
    """Weighted-split twin of :func:`_build_state_luts`: each shard's
    valid width comes from its ``shard_rows`` row instead of the
    uniform ``n - ti*shard_n`` clip.  Because the (base, width) ranges
    PARTITION the valid rows exactly, the psum of per-shard prefix LUTs
    is still bit-identical to ``build_prefix_lut`` over the whole
    table — the exactness argument never depended on equal widths."""
    from ..ops.sorted_table import build_prefix_lut

    def local(sorted_shard, shard_rows):
        n_local = shard_rows[0, 1]
        lut = build_prefix_lut(sorted_shard, n_local, bits=lut_bits)
        part = (lut if block_bits == lut_bits else
                build_prefix_lut(sorted_shard, n_local, bits=block_bits))
        block_lut = lax.psum(part, "t")
        return lut[None], block_lut

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("t", None), P("t", None)),
        out_specs=(P("t", None), P()),
        check_vma=False,
    )
    return jax.jit(fn)


#: weighted shard capacities round up to a multiple of this, so a
#: boundary nudge reuses the compiled kernels instead of recompiling
#: for every new max-width
RESHARD_ALIGN = 256


def shard_table_state(mesh: Mesh, sorted_ids, n_valid, *,
                      lut_bits: Optional[int] = None,
                      block_bits: Optional[int] = None,
                      boundaries=None) -> TableState:
    """Split an ALREADY globally sorted id table over the mesh ``t``
    axis and derive its lookup state — built ONCE per table, reused
    across every wave (``tp_simulate_lookups(..., state=)``).  For a
    table that has not been sorted, or that no single device or host
    array should hold whole, use
    :func:`~opendht_tpu.parallel.global_sort.sharded_global_sort`: it
    sorts row-sharded ids across the mesh and returns the same
    :class:`TableState` (in the weighted layout below), so nothing here
    asks for a one-device ``sort_table`` or a host sort of the id set.

    Row count must divide ``mesh.shape['t']`` (pad with invalid rows
    via :func:`~opendht_tpu.parallel.sharded.pad_to_multiple`; pad rows
    land on the LAST shard since padding appends past the valid
    prefix).  Placement goes through :data:`TABLE_AXIS_RULES` — a host
    array is sliced straight onto its owners.  ``lut_bits`` sizes the
    per-shard positioning LUT (default ``default_lut_bits(shard_n)``);
    ``block_bits`` the replicated global block LUT (default
    ``default_lut_bits(N)`` — it must match the single-device engine's
    width for bit-identity, core/search.py ``_lut_block_bounds``).

    ``boundaries`` (ISSUE-17, load-aware resharding) is an optional
    sequence of ``t-1`` interior row indices into the VALID prefix of
    the sorted order (:func:`solve_shard_boundaries`).  Shard ``i``
    then owns rows ``[b_i, b_{i+1})`` — still contiguous in the global
    sort, just not equal-width.  Because ``P('t', None)`` placement
    needs equal chunks per device, the weighted layout is physically
    realized as a REARRANGED equal-capacity table: each shard's rows
    are copied to the start of a ``shard_cap``-row slab (capacity =
    max width rounded up to :data:`RESHARD_ALIGN`), and a ``shard_rows``
    [t, 2] operand carries each shard's (base, width).  Reshard is row
    movement + LUT rebuild — never a re-sort."""
    from ..ops.sorted_table import default_lut_bits
    N = sorted_ids.shape[0]
    n_t = mesh.shape["t"]
    if boundaries is not None:
        return _shard_table_state_weighted(
            mesh, sorted_ids, n_valid, boundaries,
            lut_bits=lut_bits, block_bits=block_bits)
    if N % n_t:
        raise ValueError(f"table rows ({N}) not divisible by t={n_t}; "
                         f"pad with invalid rows via pad_to_multiple")
    shard_n = N // n_t
    lb = lut_bits or default_lut_bits(shard_n)
    bb = block_bits or default_lut_bits(N)
    # normalize dtype BEFORE placement: the kernels are uint32-limb
    # programs, and an int64 table silently produces wrong lookups
    if hasattr(sorted_ids, "sharding"):
        if sorted_ids.dtype != jnp.uint32:
            sorted_ids = sorted_ids.astype(jnp.uint32)
    else:
        sorted_ids = np.asarray(sorted_ids, np.uint32)
    placed = shard_put(mesh, {"sorted_ids": sorted_ids}, TABLE_AXIS_RULES)
    nv = jnp.asarray(n_valid, jnp.int32)
    local_lut, block_lut = _build_state_luts(mesh, shard_n, lb, bb)(
        placed["sorted_ids"], nv)
    return TableState(
        arrays={"sorted_ids": placed["sorted_ids"], "local_lut": local_lut,
                "block_lut": block_lut, "n_valid": nv},
        shard_n=shard_n, lut_bits=lb, block_bits=bb)


def _shard_table_state_weighted(mesh: Mesh, sorted_ids, n_valid, boundaries,
                                *, lut_bits=None, block_bits=None):
    from ..ops.sorted_table import default_lut_bits
    N = int(sorted_ids.shape[0])
    n_t = int(mesh.shape["t"])
    n = int(n_valid)
    ids_host = np.asarray(sorted_ids, np.uint32)
    b = np.asarray(boundaries, np.int64).reshape(-1)
    if b.shape[0] != n_t - 1:
        raise ValueError(f"expected {n_t - 1} interior boundaries for "
                         f"t={n_t}, got {b.shape[0]}")
    bounds = np.concatenate([[0], np.clip(b, 0, n), [n]])
    bounds = np.maximum.accumulate(bounds)
    widths = np.diff(bounds)
    shard_cap = int(-(-max(int(widths.max()), 1) // RESHARD_ALIGN)
                    * RESHARD_ALIGN)
    ids_re = np.zeros((n_t * shard_cap, ids_host.shape[1]), np.uint32)
    for i in range(n_t):
        w = int(widths[i])
        ids_re[i * shard_cap:i * shard_cap + w] = (
            ids_host[int(bounds[i]):int(bounds[i + 1])])
    shard_rows = np.stack([bounds[:-1], widths], axis=1).astype(np.int32)
    lb = lut_bits or default_lut_bits(shard_cap)
    # block width stays keyed to the ORIGINAL table size: bit-identity
    # with the single-device engine requires the same global LUT shape
    # regardless of how the rows are cut
    bb = block_bits or default_lut_bits(N)
    placed = shard_put(mesh, {"sorted_ids": ids_re,
                              "shard_rows": shard_rows}, TABLE_AXIS_RULES)
    nv = jnp.asarray(n, jnp.int32)
    local_lut, block_lut = _build_state_luts_weighted(mesh, lb, bb)(
        placed["sorted_ids"], placed["shard_rows"])
    return TableState(
        arrays={"sorted_ids": placed["sorted_ids"], "local_lut": local_lut,
                "block_lut": block_lut, "n_valid": nv,
                "shard_rows": placed["shard_rows"]},
        shard_n=shard_cap, lut_bits=lb, block_bits=bb,
        boundaries=tuple(int(x) for x in bounds[1:-1]))


# --------------------------------------------------------------------------
# Load-aware boundary solver (ISSUE-17).  Pure numpy — it runs on the
# node scheduler thread per rebalance tick, not on device.
# --------------------------------------------------------------------------

def _blend_bin_weights(meas, loads, load_weight):
    """Per-bin weight: ``(1-λ)·rows/R + λ·loads/L``.  λ clips to
    [0, 1]; a cold table (zero observed load) forces λ=0 so the solve
    degrades to the row-uniform split."""
    meas = np.asarray(meas, np.float64).reshape(-1)
    if loads is None:
        loads = np.zeros_like(meas)
    else:
        loads = np.asarray(loads, np.float64).reshape(-1)
    if loads.shape != meas.shape:
        raise ValueError(f"bin shapes differ: {meas.shape} vs {loads.shape}")
    lam = min(max(float(load_weight), 0.0), 1.0)
    L = float(loads.sum())
    R = float(meas.sum())
    if L <= 0.0:
        lam = 0.0
    w = np.zeros_like(meas)
    if R > 0.0 and lam < 1.0:
        w += (1.0 - lam) * meas / R
    if lam > 0.0:
        w += lam * np.clip(loads, 0.0, None) / L
    return w


def _solve_crossings(w, t):
    """Interior equal-weight crossings of a per-bin weight profile.

    Returns ``t-1`` pairs ``(bin, frac)``: crossing ``i`` sits at
    fraction ``frac ∈ (0, 1]`` through ``bin`` — the first point where
    cumulative weight reaches ``i/t`` of the total (weight is treated
    as uniform WITHIN a bin, the same assumption ``keyspace.fold_bins``
    makes when apportioning a straddled bin by overlap)."""
    w = np.asarray(w, np.float64)
    cumw = np.concatenate([[0.0], np.cumsum(w)])
    W = float(cumw[-1])
    out = []
    for i in range(1, int(t)):
        if W <= 0.0:
            out.append((0, 0.0))
            continue
        T = W * i / float(t)
        # first e with cumw[e] >= T; e >= 1 since cumw[0] = 0 < T
        e = int(np.searchsorted(cumw, T, side="left"))
        e = min(max(e, 1), len(w))
        bin_ = e - 1
        frac = (T - cumw[bin_]) / w[bin_] if w[bin_] > 0.0 else 1.0
        out.append((bin_, float(min(max(frac, 0.0), 1.0))))
    return out


def solve_shard_boundaries(bin_rows, bin_loads, t, *, load_weight=1.0):
    """Traffic-weighted split points, snapped to real row boundaries.

    ``bin_rows[b]`` counts the sorted table's valid rows whose top id
    byte is ``b`` (the same 256-bin space as the keyspace observatory's
    load histogram ``bin_loads``).  Returns ``t-1`` nondecreasing row
    indices in ``[0, n]``: boundary ``i`` is the SMALLEST row count r
    such that the blended weight of rows ``[0, r)`` reaches ``i/t`` of
    the total — each shard ``[b_i, b_{i+1})`` then carries ~equal
    weighted traffic.  With ``load_weight=0`` (or a cold histogram)
    this is the row-uniform split ``ceil(i·n/t)``."""
    bin_rows = np.asarray(bin_rows, np.int64).reshape(-1)
    n = int(bin_rows.sum())
    w = _blend_bin_weights(bin_rows, bin_loads, load_weight)
    row_start = np.concatenate([[0], np.cumsum(bin_rows)])
    out = np.zeros(int(t) - 1, np.int64)
    for i, (b, frac) in enumerate(_solve_crossings(w, t)):
        r_b = int(bin_rows[b]) if b < bin_rows.shape[0] else 0
        # within-bin row offset: smallest j with j/r_b >= frac (uniform
        # weight within the bin ⇒ weight of j rows is frac·w_b at
        # j = frac·r_b); the tiny eps keeps exact multiples from
        # rounding up a row
        j = int(np.ceil(frac * r_b - 1e-9)) if r_b > 0 else 0
        out[i] = int(row_start[b]) + min(max(j, 0), r_b)
    out = np.clip(out, 0, n)
    return np.maximum.accumulate(out)


def solve_shard_edges(bin_loads, t, *, load_weight=1.0, bin_rows=None):
    """Fractional-bin-coordinate form of the solve, for VIRTUAL
    attribution (no live mesh): returns ``t-1`` nondecreasing floats in
    ``[0, bins]``, directly consumable by ``keyspace.fold_bins``.  The
    cold measure defaults to a uniform ring (ones per bin), so a cold
    table yields exactly ``keyspace.bin_edges_uniform(t)``."""
    bin_loads = np.asarray(bin_loads, np.float64).reshape(-1)
    meas = (np.ones_like(bin_loads) if bin_rows is None
            else np.asarray(bin_rows, np.float64).reshape(-1))
    w = _blend_bin_weights(meas, bin_loads, load_weight)
    if float(w.sum()) <= 0.0:
        bins = bin_loads.shape[0]
        return np.asarray([bins * i / float(t) for i in range(1, int(t))],
                          np.float64)
    edges = np.asarray([b + frac for b, frac in _solve_crossings(w, t)],
                       np.float64)
    return np.maximum.accumulate(edges)
