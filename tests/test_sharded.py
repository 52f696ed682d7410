"""Multi-chip sharding tests on a virtual 8-device CPU mesh.

Validates that the sharded table-parallel top-k (all_gather merge over
the ``t`` axis) and the data-parallel iterative lookup produce exactly
the single-device results — the correctness contract of the ICI merge
(global top-k ⊆ union of per-shard top-ks).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from opendht_tpu.ops.xor_topk import xor_topk
from opendht_tpu.ops.sorted_table import sort_table
from opendht_tpu.core.search import simulate_lookups
from opendht_tpu.parallel import (
    make_mesh, pad_to_multiple, sharded_xor_topk, sharded_lookup,
    sharded_sort_table, sharded_window_lookup, sharded_maintenance_sweep,
    dp_simulate_lookups, sharded_global_sort, tp_simulate_lookups,
)


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return make_mesh(8)


def _rand_ids(rng, n):
    return rng.integers(0, 2**32, size=(n, 5), dtype=np.uint32)


def test_mesh_shape(mesh):
    assert mesh.shape["q"] * mesh.shape["t"] == 8


def test_sharded_xor_topk_matches_single_device(mesh):
    rng = np.random.default_rng(7)
    table = _rand_ids(rng, 512)
    queries = _rand_ids(rng, 16 * mesh.shape["q"])

    d_ref, i_ref = xor_topk(jnp.asarray(queries), jnp.asarray(table), k=8)
    d_sh, i_sh = sharded_xor_topk(mesh, queries, table, k=8)

    np.testing.assert_array_equal(np.asarray(i_sh), np.asarray(i_ref))
    np.testing.assert_array_equal(np.asarray(d_sh), np.asarray(d_ref))


def test_sharded_xor_topk_with_invalid_rows(mesh):
    rng = np.random.default_rng(8)
    table = _rand_ids(rng, 256)
    valid = rng.random(256) > 0.3
    queries = _rand_ids(rng, 8 * mesh.shape["q"])

    d_ref, i_ref = xor_topk(jnp.asarray(queries), jnp.asarray(table), k=8,
                            valid=jnp.asarray(valid))
    d_sh, i_sh = sharded_xor_topk(mesh, queries, table, k=8,
                                  valid=jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(i_sh), np.asarray(i_ref))
    np.testing.assert_array_equal(np.asarray(d_sh), np.asarray(d_ref))


def test_sharded_xor_topk_padded_table(mesh):
    """Tables whose row count isn't divisible by n_t are padded with
    invalid rows; results must be unchanged."""
    rng = np.random.default_rng(9)
    table = _rand_ids(rng, 301)   # not divisible by n_t=4 ⇒ real padding
    queries = _rand_ids(rng, 4 * mesh.shape["q"])

    d_ref, i_ref = xor_topk(jnp.asarray(queries), jnp.asarray(table), k=8)
    padded, n = pad_to_multiple(table, mesh.shape["t"])
    valid = np.arange(padded.shape[0]) < n
    d_sh, i_sh = sharded_xor_topk(mesh, queries, padded, k=8,
                                  valid=jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(i_sh), np.asarray(i_ref))
    np.testing.assert_array_equal(np.asarray(d_sh), np.asarray(d_ref))


def test_sharded_window_lookup_matches_full_scan(mesh):
    """Sorted-window fast path over shards returns the same *ids* (and
    distances) as the exact scan.  Row indices may differ under duplicate
    ids; random 160-bit ids make collisions impossible here, so indices
    must match too after mapping shard-sorted order back to rows."""
    rng = np.random.default_rng(10)
    table = _rand_ids(rng, 1024)
    queries = _rand_ids(rng, 8 * mesh.shape["q"])

    d_ref, i_ref = xor_topk(jnp.asarray(queries), jnp.asarray(table), k=8)
    d_sh, rows_sh = sharded_lookup(mesh, queries, table, k=8, window=64)
    np.testing.assert_array_equal(np.asarray(d_sh), np.asarray(d_ref))
    np.testing.assert_array_equal(np.asarray(rows_sh), np.asarray(i_ref))


def test_sharded_sort_once_lookup_many(mesh):
    """The two-step API (sort once, look up many batches) matches the
    full-scan oracle for every batch — the amortized production path."""
    rng = np.random.default_rng(12)
    table = _rand_ids(rng, 512)
    sorted_ids, perm, n_valid = sharded_sort_table(mesh, table)
    for batch in range(3):
        queries = _rand_ids(rng, 8 * mesh.shape["q"])
        d_ref, i_ref = xor_topk(jnp.asarray(queries), jnp.asarray(table), k=8)
        d_sh, rows = sharded_window_lookup(mesh, queries, sorted_ids, perm,
                                           n_valid, k=8, window=64)
        np.testing.assert_array_equal(np.asarray(d_sh), np.asarray(d_ref))
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(i_ref))


@pytest.mark.slow
def test_dp_simulate_matches_unsharded(mesh):
    """The data-parallel iterative lookup is bitwise identical to the
    single-device run (the reply model is counter-hashed, not
    device-dependent)."""
    rng = np.random.default_rng(11)
    ids = _rand_ids(rng, 2048)
    sorted_ids, _, n_valid = sort_table(jnp.asarray(ids))
    targets = _rand_ids(rng, 16 * len(jax.devices()))

    ref = simulate_lookups(sorted_ids, n_valid, jnp.asarray(targets), seed=3)
    out = dp_simulate_lookups(mesh, sorted_ids, n_valid, targets, seed=3)

    np.testing.assert_array_equal(np.asarray(out["nodes"]), np.asarray(ref["nodes"]))
    np.testing.assert_array_equal(np.asarray(out["hops"]), np.asarray(ref["hops"]))
    np.testing.assert_array_equal(
        np.asarray(out["converged"]), np.asarray(ref["converged"]))


def test_tp_simulate_matches_unsharded(mesh):
    """The TABLE-SHARDED iterative lookup (sorted table P('t', None),
    positioning and row fetch each one psum over the t axis) is bitwise
    identical to the single-device engine — the contract that lets a
    table larger than one chip's HBM be *searched*, not just scanned
    (round-2 review, item 1)."""
    rng = np.random.default_rng(13)
    ids = _rand_ids(rng, 4096)
    sorted_ids, _, n_valid = sort_table(jnp.asarray(ids))
    targets = _rand_ids(rng, 16 * mesh.shape["q"])

    ref = simulate_lookups(sorted_ids, n_valid, jnp.asarray(targets), seed=5)
    out = tp_simulate_lookups(mesh, np.asarray(sorted_ids), n_valid,
                              targets, seed=5)
    for key in ("nodes", "hops", "converged", "dist"):
        np.testing.assert_array_equal(np.asarray(out[key]),
                                      np.asarray(ref[key]))


def test_tp_simulate_padded_table(mesh):
    """Row counts not divisible by n_t are padded; padding content is
    irrelevant by construction (rows >= n_valid are excluded from both
    distributed primitives) — zero padding, which sorts BEFORE real ids,
    must still give exact results."""
    rng = np.random.default_rng(14)
    ids = _rand_ids(rng, 1021)               # prime → real padding
    sorted_ids, _, n_valid = sort_table(jnp.asarray(ids))
    targets = _rand_ids(rng, 8 * mesh.shape["q"])

    ref = simulate_lookups(sorted_ids, n_valid, jnp.asarray(targets), seed=2)
    padded, _ = pad_to_multiple(np.asarray(sorted_ids), mesh.shape["t"])
    out = tp_simulate_lookups(mesh, padded, n_valid, targets, seed=2)
    for key in ("nodes", "hops", "converged"):
        np.testing.assert_array_equal(np.asarray(out[key]),
                                      np.asarray(ref[key]))


def test_tp_simulate_clustered_ids(mesh):
    """Adversarially clustered ids overflow per-shard LUT buckets; the
    device-side soundness guard must drop to the full-depth search and
    still match the unsharded engine exactly."""
    rng = np.random.default_rng(15)
    ids = _rand_ids(rng, 2048)
    ids[:1500, 0] = 0x41414141               # 73% share the top 32 bits
    sorted_ids, _, n_valid = sort_table(jnp.asarray(ids))
    targets = _rand_ids(rng, 8 * mesh.shape["q"])
    targets[: 4 * mesh.shape["q"], 0] = 0x41414141   # half hit the cluster

    ref = simulate_lookups(sorted_ids, n_valid, jnp.asarray(targets), seed=6)
    out = tp_simulate_lookups(mesh, np.asarray(sorted_ids), n_valid,
                              targets, seed=6)
    for key in ("nodes", "hops", "converged"):
        np.testing.assert_array_equal(np.asarray(out[key]),
                                      np.asarray(ref[key]))


def test_sharded_expanded_lookup_matches_full_scan(mesh):
    """The per-shard expanded row-gather path (sharded_expand_table +
    expanded lookup) is exact vs the full-scan oracle — the headline
    kernel under table-parallel sharding."""
    from opendht_tpu.parallel import sharded_expand_table
    rng = np.random.default_rng(21)
    table = _rand_ids(rng, 1024)
    sorted_ids, perm, n_valid = sharded_sort_table(mesh, table)
    expanded, lut = sharded_expand_table(mesh, sorted_ids, n_valid)
    for batch in range(2):
        queries = _rand_ids(rng, 8 * mesh.shape["q"])
        d_ref, i_ref = xor_topk(jnp.asarray(queries), jnp.asarray(table), k=8)
        d_sh, rows = sharded_window_lookup(mesh, queries, sorted_ids, perm,
                                           n_valid, k=8, expanded=expanded,
                                           lut=lut)
        np.testing.assert_array_equal(np.asarray(d_sh), np.asarray(d_ref))
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(i_ref))


@pytest.mark.slow
@pytest.mark.parametrize("q,t", [(1, 8), (4, 2), (8, 1)])
def test_tp_simulate_mesh_geometries(q, t):
    """The table-sharded engine must be exact for ANY mesh split — pure
    table-parallel (q=1), query-heavy (q=4,t=2), and the degenerate
    single-shard (t=1) all reduce to the same bit-exact results."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    m = make_mesh(8, q=q, t=t)
    rng = np.random.default_rng(40 + q)
    ids = _rand_ids(rng, 2048)
    sorted_ids, _, n_valid = sort_table(jnp.asarray(ids))
    targets = _rand_ids(rng, 8 * q)

    ref = simulate_lookups(sorted_ids, n_valid, jnp.asarray(targets), seed=4)
    out = tp_simulate_lookups(m, np.asarray(sorted_ids), n_valid,
                              targets, seed=4)
    for key in ("nodes", "hops", "converged"):
        np.testing.assert_array_equal(np.asarray(out[key]),
                                      np.asarray(ref[key]))


@pytest.mark.parametrize("layout", ["uniform", "weighted"])
@pytest.mark.parametrize("q,t", [(1, 4), (2, 2)])
def test_tp_simulate_equals_one_chip_with_the_cut_engaged(q, t, layout):
    """SURVIVOR COMPACTION on a mesh (core/search.py _lookup_engine):
    the search state is sharded over ``t`` too (PR 38), so the width
    that cuts is a shard's CHUNK: each t-rank's chunk of each q-rank's
    wave is wide enough to cut, so every rank packs its own survivors
    and runs its last rounds narrow — with the round's exchange at the
    narrow width — and the results equal one chip's, which cuts too.
    The t-ranks hold different lookups but are handed ONE live count
    (the fullest shard's), so they cut together; a q-rank's ranks cut
    when THEIR survivors fit (one count a q-rank).  Lookups are
    already done in the wide rounds before the cut (their rows are -1),
    and every shard's gather reads spare rows for those lanes and for
    the lanes other shards own (``owner_local_index``): on the uniform
    split of a host-sorted table and on the WEIGHTED state of
    ``sharded_global_sort``, whose shards own unequal row counts under
    one capacity."""
    from opendht_tpu.core.search import NARROW_MIN_WAVE
    from opendht_tpu.parallel.sharded import lane_chunk
    m = make_mesh(4, q=q, t=t)
    k1, k2 = jax.random.split(jax.random.PRNGKey(3000))
    n = 3000 if layout == "uniform" else 4096
    ids = jax.random.bits(k1, (n, 5), dtype=jnp.uint32)
    sorted_ids, _, n_valid = sort_table(ids)
    Q = q * t * NARROW_MIN_WAVE
    assert lane_chunk(Q // q, t) == NARROW_MIN_WAVE
    targets = jax.random.bits(k2, (Q, 5), dtype=jnp.uint32)
    kw = dict(seed=11, alpha=2, state_limbs=2)
    ref = simulate_lookups(sorted_ids, n_valid, targets, **kw)
    if layout == "uniform":
        out = tp_simulate_lookups(m, np.asarray(sorted_ids), n_valid,
                                  np.asarray(targets), **kw)
    else:
        state = sharded_global_sort(m, np.asarray(ids))
        widths = np.asarray(state.arrays["shard_rows"])[:, 1]
        assert len(set(widths.tolist())) > 1 and widths.max() < state.shard_n
        out = tp_simulate_lookups(m, targets=np.asarray(targets),
                                  state=state, **kw)
    narrow = np.asarray(out["narrow_rounds"])
    assert narrow.shape == (q,) and (narrow >= 1).all()
    assert int(ref["narrow_rounds"]) >= 1
    # lookups were dead in a wide round: some took fewer hops than the
    # round the wave cut in, so their lanes carried -1 rows at full width
    hops = np.asarray(ref["hops"])
    assert hops.min() < hops.max() - int(narrow.max())
    for key in ("nodes", "dist", "hops", "converged"):
        np.testing.assert_array_equal(np.asarray(out[key]),
                                      np.asarray(ref[key]), err_msg=key)


def _owner_index(rows, shard, shard_n, weighted):
    """``owner_local_index`` as ``build_tp_lookup`` calls it for one
    shard: the weighted layout owns ``n_local`` rows of its capacity
    (here all but 1,000 of them), the uniform one tests the static
    width."""
    from opendht_tpu.parallel.sharded import owner_local_index
    n_owned = shard_n - 1000 if weighted else shard_n
    base = shard * n_owned
    # the weighted count is data (a traced scalar), the uniform one static
    loc, ok = owner_local_index(
        rows, np.int32(base), np.int32(n_owned) if weighted else n_owned,
        shard_n)
    return np.asarray(loc), np.asarray(ok), base, n_owned


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["uniform", "weighted"])
@pytest.mark.parametrize("t", [2, 4, 8])
def test_owner_local_index_keeps_owned_lanes_and_spreads_the_rest(t,
                                                                  weighted):
    """The index a shard hands its gather (parallel/sharded.py
    ``owner_local_index``), at the cell's shapes: 1,572,864 lanes
    (slot-major [24, 65536]) of uniform global rows into ``t`` shards
    of 25M, an eighth of the lanes dead (-1).  An owned lane keeps
    ``rows - base``; every other lane, dead ones included, lies in
    ``[0, shard_n)``, and no row is the spare of more than 8 lanes —
    where the parent's index, ``rows - base`` left to the gather's
    clip, piles every lane of the shards above on shard 0's last
    row."""
    shard_n = 25_000_000
    rng = np.random.default_rng(33 + t)
    rows = rng.integers(0, t * (shard_n - 1000 * weighted),
                        size=(24, 65536)).astype(np.int32)
    dead = rng.random(rows.shape) < 0.125
    rows[dead] = -1
    for shard in (0, t - 1):
        loc, ok, base, n_owned = _owner_index(rows, shard, shard_n, weighted)
        owned = (rows >= base) & (rows < base + n_owned)
        np.testing.assert_array_equal(ok, owned)
        assert not ok[dead].any()
        np.testing.assert_array_equal(loc[ok], rows[ok] - base)
        assert loc.min() >= 0 and loc.max() < shard_n
        assert 0.8 / t < ok.mean() < 1.0 / t
        assert np.unique(loc[~ok], return_counts=True)[1].max() <= 8
    # the case the rule exists for: the parent's index on shard 0
    piled = (np.clip(rows, 0, shard_n - 1) == shard_n - 1).sum()
    assert piled > (1 - 1 / t) * 0.85 * rows.size
    if t == 4 and not weighted:
        rows = rng.integers(0, 4 * shard_n, size=rows.shape)
        assert 1_170_000 < (np.clip(rows, 0, shard_n - 1)
                            == shard_n - 1).sum() < 1_190_000


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["uniform", "weighted"])
def test_owner_local_index_stays_in_a_shard_smaller_than_the_wave(weighted):
    """A toy shard of 1,500 rows under a wave of 24 x 512 lanes: the
    spare rows wrap into the shard, each the spare of at most
    ceil(lanes / shard_n) lanes."""
    shard_n = 1500
    rng = np.random.default_rng(7)
    rows = rng.integers(-1, 4 * (shard_n - 1000 * weighted),
                        size=(24, 512)).astype(np.int32)
    for shard in range(4):
        loc, ok, base, n_owned = _owner_index(rows, shard, shard_n, weighted)
        np.testing.assert_array_equal(
            ok, (rows >= base) & (rows < base + n_owned))
        np.testing.assert_array_equal(loc[ok], rows[ok] - base)
        assert loc.min() >= 0 and loc.max() < shard_n
        assert np.unique(loc[~ok], return_counts=True)[1].max() \
            <= -(-rows.size // shard_n)


def test_dp_simulate_equals_one_chip_with_the_cut_engaged(mesh):
    """The data-parallel entry runs the same engine under XLA's own
    partitioning: the live count is global there, so the wave cuts as
    one — the pack gathers its survivors across the shards — and the
    results equal one chip's."""
    from opendht_tpu.core.search import NARROW_MIN_WAVE
    k1, k2 = jax.random.split(jax.random.PRNGKey(3000))
    sorted_ids, _, n_valid = sort_table(jax.random.bits(
        k1, (3000, 5), dtype=jnp.uint32))
    targets = jax.random.bits(k2, (NARROW_MIN_WAVE, 5), dtype=jnp.uint32)
    kw = dict(seed=11, alpha=2, state_limbs=2)
    ref = simulate_lookups(sorted_ids, n_valid, targets, **kw)
    out = dp_simulate_lookups(mesh, np.asarray(sorted_ids), n_valid,
                              np.asarray(targets), **kw)
    assert int(out["narrow_rounds"]) == int(ref["narrow_rounds"]) >= 1
    for key in ("nodes", "dist", "hops", "converged"):
        np.testing.assert_array_equal(np.asarray(out[key]),
                                      np.asarray(ref[key]), err_msg=key)


def test_sharded_maintenance_sweep_matches_single_device(mesh):
    """The round-10 maintenance sweep over a row-sharded table must be
    BIT-IDENTICAL to the single-device radix kernel: occupancy psum and
    staleness pmax are exact under resharding, and the refresh targets
    come from the same replicated threefry stream."""
    from opendht_tpu.ops import radix

    rng = np.random.default_rng(55)
    N = 4096
    ids = _rand_ids(rng, N)
    self_id = _rand_ids(rng, 1).reshape(-1)
    valid = rng.random(N) > 0.1
    # a mix of replied and never-replied rows (the never-replied-is-
    # stale rule must survive the shard split)
    last = np.where(rng.random(N) > 0.3,
                    rng.uniform(1.0, 100.0, N), 0.0).astype(np.float32)
    key = jax.random.PRNGKey(9)
    now, age = 700.0, 600.0

    ref = radix.maintenance_sweep(
        jnp.asarray(self_id), jnp.asarray(ids), jnp.asarray(valid),
        jnp.asarray(last), now, age, key)
    got = sharded_maintenance_sweep(mesh, self_id, ids, valid, last,
                                    now, age, key)
    for a, b, name in zip(got, ref, ("counts", "last", "stale", "targets")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


# ---------------------------------------------------------------------------
# Round 13: declarative partition layer + row-sharded geometry sweep
# ---------------------------------------------------------------------------

def test_match_partition_rules_names_and_scalars():
    """Rule matching follows /-joined leaf names, first hit wins, and
    scalar leaves never partition regardless of rule."""
    from jax.sharding import PartitionSpec as P
    from opendht_tpu.parallel import partition

    tree = {"sorted_ids": np.zeros((8, 5), np.uint32),
            "local_lut": np.zeros((2, 9), np.int32),
            "block_lut": np.zeros((17,), np.int32),
            "n_valid": np.int32(7),
            "nested": {"targets": np.zeros((4, 5), np.uint32)}}
    specs = partition.match_partition_rules(partition.TABLE_AXIS_RULES, tree)
    assert specs["sorted_ids"] == P("t", None)
    assert specs["local_lut"] == P("t", None)
    assert specs["block_lut"] == P()
    assert specs["n_valid"] == P()               # scalar guard
    assert specs["nested"]["targets"] == P("q", None)
    with pytest.raises(ValueError, match="no partition rule"):
        partition.match_partition_rules(
            [(r"^only_this$", P("t"))], {"other": np.zeros((4,))})


def test_shard_and_gather_fns_roundtrip(mesh):
    """shard fn places a host array straight onto its shards (per-device
    bytes = N/t rows — the whole point of the layout); gather fn
    returns the exact original."""
    from opendht_tpu.parallel import partition

    rng = np.random.default_rng(70)
    tree = {"sorted_ids": _rand_ids(rng, 64 * mesh.shape["t"])}
    specs = partition.match_partition_rules(partition.TABLE_AXIS_RULES, tree)
    shard_fns, gather_fns = partition.make_shard_and_gather_fns(mesh, specs)
    placed = shard_fns["sorted_ids"](tree["sorted_ids"])
    shard = placed.addressable_shards[0].data
    assert shard.shape[0] == 64 * mesh.shape["t"] // mesh.shape["t"]
    assert shard.nbytes == placed.nbytes // mesh.shape["t"]
    np.testing.assert_array_equal(gather_fns["sorted_ids"](placed),
                                  tree["sorted_ids"])
    # placement is idempotent: re-sharding an already-placed array is
    # the identity (the Snapshot resolve cache depends on this)
    assert shard_fns["sorted_ids"](placed) is placed


def test_shard_table_state_block_lut_is_global(mesh):
    """The replicated block LUT assembled from per-shard psums must
    equal build_prefix_lut over the whole table — the bit-identity
    basis for the zero-collective in-loop block edges."""
    from opendht_tpu.ops.sorted_table import build_prefix_lut
    from opendht_tpu.parallel import shard_table_state

    rng = np.random.default_rng(71)
    ids = _rand_ids(rng, 2048)
    sorted_ids, _, n_valid = sort_table(jnp.asarray(ids))
    state = shard_table_state(mesh, np.asarray(sorted_ids), n_valid)
    ref = build_prefix_lut(sorted_ids, jnp.asarray(n_valid, jnp.int32),
                           bits=state.block_bits)
    np.testing.assert_array_equal(np.asarray(state.arrays["block_lut"]),
                                  np.asarray(ref))
    assert state.table_bytes_per_shard() == 2048 // mesh.shape["t"] * 20


def test_shard_table_state_casts_dtype(mesh):
    """A non-uint32 id table must be cast before placement — the limb
    kernels silently mis-rank on int64 otherwise (review finding)."""
    rng = np.random.default_rng(74)
    ids = _rand_ids(rng, 1024)
    sorted_ids, _, n_valid = sort_table(jnp.asarray(ids))
    targets = _rand_ids(rng, 8 * mesh.shape["q"])
    ref = simulate_lookups(sorted_ids, n_valid, jnp.asarray(targets), seed=7)
    out = tp_simulate_lookups(mesh, np.asarray(sorted_ids).astype(np.int64),
                              n_valid, targets, seed=7)
    for key in ("nodes", "hops", "converged"):
        np.testing.assert_array_equal(np.asarray(out[key]),
                                      np.asarray(ref[key]))


def test_tp_simulate_with_prebuilt_state(mesh):
    """The state= fast path (table placed once, reused across waves)
    returns exactly what the raw-array path returns."""
    from opendht_tpu.parallel import shard_table_state

    rng = np.random.default_rng(72)
    ids = _rand_ids(rng, 2048)
    sorted_ids, _, n_valid = sort_table(jnp.asarray(ids))
    targets = _rand_ids(rng, 8 * mesh.shape["q"])
    ref = simulate_lookups(sorted_ids, n_valid, jnp.asarray(targets), seed=9)
    state = shard_table_state(mesh, np.asarray(sorted_ids), n_valid)
    for _ in range(2):                    # second wave reuses everything
        out = tp_simulate_lookups(mesh, targets=targets, seed=9, state=state)
        for key in ("nodes", "hops", "converged", "dist"):
            np.testing.assert_array_equal(np.asarray(out[key]),
                                          np.asarray(ref[key]))


@pytest.mark.parametrize("q,t", [(1, 2), (2, 2), (1, 4), (4, 1)])
def test_row_sharded_geometry_sweep(q, t):
    """ISSUE-8 satellite: every entry point — iterative lookup,
    window-lookup, xor-topk, maintenance sweep — pinned bit-identical
    to single-device across q×t splits on the ROW-SHARDED table,
    including ragged N (pad rows land on the last shard) and an
    ALL-INVALID shard."""
    if len(jax.devices()) < q * t:
        pytest.skip(f"needs {q * t} virtual devices")
    from opendht_tpu.ops import radix
    m = make_mesh(q * t, q=q, t=t)
    rng = np.random.default_rng(60 + 4 * q + t)
    N_ragged = 1021                       # prime → real padding
    ids = _rand_ids(rng, N_ragged)
    sorted_ids, _, n_valid = sort_table(jnp.asarray(ids))
    padded, _ = pad_to_multiple(np.asarray(sorted_ids), t * 4)
    targets = _rand_ids(rng, 8 * q)

    # iterative engine on the ragged row-sharded table
    ref = simulate_lookups(sorted_ids, n_valid, jnp.asarray(targets), seed=8)
    out = tp_simulate_lookups(m, padded, n_valid, targets, seed=8)
    for key in ("nodes", "hops", "converged"):
        np.testing.assert_array_equal(np.asarray(out[key]),
                                      np.asarray(ref[key]), err_msg=key)

    # full-scan + window top-k with an entirely invalid shard: valid
    # rows only in the first global quarter, so on t=4 the later
    # shards hold zero valid rows
    table = _rand_ids(rng, 64 * t * 4)
    valid = np.zeros(table.shape[0], bool)
    valid[:table.shape[0] // 4] = True
    queries = _rand_ids(rng, 8 * q)
    d_ref, i_ref = xor_topk(jnp.asarray(queries), jnp.asarray(table), k=8,
                            valid=jnp.asarray(valid))
    d_sh, i_sh = sharded_xor_topk(m, queries, table, k=8,
                                  valid=jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(i_sh), np.asarray(i_ref))
    np.testing.assert_array_equal(np.asarray(d_sh), np.asarray(d_ref))
    d_w, rows_w = sharded_lookup(m, queries, table, k=8, window=32,
                                 valid=jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(rows_w), np.asarray(i_ref))
    np.testing.assert_array_equal(np.asarray(d_w), np.asarray(d_ref))

    # maintenance sweep on the same all-invalid-shard layout
    self_id = _rand_ids(rng, 1).reshape(-1)
    last = rng.uniform(1.0, 100.0, table.shape[0]).astype(np.float32)
    key = jax.random.PRNGKey(31)
    ref_m = radix.maintenance_sweep(
        jnp.asarray(self_id), jnp.asarray(table), jnp.asarray(valid),
        jnp.asarray(last), 700.0, 600.0, key)
    got_m = sharded_maintenance_sweep(m, self_id, table, valid, last,
                                      700.0, 600.0, key)
    for a, b, name in zip(got_m, ref_m, ("counts", "last", "stale",
                                         "targets")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_snapshot_lookup_sharded_matches_unsharded(mesh):
    """The t-sharded snapshot resolve (config.resolve_mesh_t wiring,
    core/table.py Snapshot.lookup mesh=) returns exactly the
    single-device resolve — rows and distances."""
    from opendht_tpu.core.table import NodeTable
    from opendht_tpu.infohash import InfoHash

    rng = np.random.default_rng(73)
    nt = NodeTable(InfoHash.get_random(), capacity=512)
    now = 100.0
    for i in range(300):
        nt.insert(InfoHash.get_random(), ("10.0.0.%d" % (i % 250), 4222),
                  now=now, confirm=2)
    snap = nt.snapshot(now)
    q = _rand_ids(rng, 16)
    rows_ref, dist_ref = snap.lookup(q, k=8)
    rows_sh, dist_sh = snap.lookup(q, k=8, mesh=mesh)
    np.testing.assert_array_equal(rows_sh, rows_ref)
    np.testing.assert_array_equal(dist_sh, dist_ref)
    # second call reuses the cached placed shards (no re-pad, no copy)
    rows_sh2, _ = snap.lookup(q, k=8, mesh=mesh)
    np.testing.assert_array_equal(rows_sh2, rows_ref)


def test_dht_resolve_mesh_knob(mesh):
    """config.resolve_mesh_t builds the (q=1, t) mesh lazily; 0 keeps
    the unsharded path; an over-sized t degrades with a warning, never
    fails."""
    from opendht_tpu.runtime.config import Config
    from opendht_tpu.runtime.dht import Dht

    d0 = Dht(lambda data, addr: 0, Config())
    assert d0.resolve_mesh() is None and d0.resolve_mesh_t() == 1
    d4 = Dht(lambda data, addr: 0, Config(resolve_mesh_t=4))
    m = d4.resolve_mesh()
    assert m is not None and m.shape["t"] == 4 and m.shape["q"] == 1
    assert d4.resolve_mesh_t() == 4
    assert d4.wave_builder.snapshot()["table_shard_t"] == 4
    d_big = Dht(lambda data, addr: 0, Config(resolve_mesh_t=512))
    assert d_big.resolve_mesh() is None and d_big.resolve_mesh_t() == 1


def test_sharded_maintenance_sweep_padded_table(mesh):
    """Invalid pad rows (the pad_to_multiple contract) contribute to no
    bucket and no staleness."""
    from opendht_tpu.ops import radix

    rng = np.random.default_rng(56)
    ids = _rand_ids(rng, 1000)
    self_id = _rand_ids(rng, 1).reshape(-1)
    last = rng.uniform(1.0, 100.0, 1000).astype(np.float32)
    padded, n = pad_to_multiple(ids, mesh.shape["t"] * 256)
    valid = np.arange(padded.shape[0]) < n
    last_p, _ = pad_to_multiple(last, mesh.shape["t"] * 256)
    key = jax.random.PRNGKey(10)

    ref = radix.maintenance_sweep(
        jnp.asarray(self_id), jnp.asarray(ids),
        jnp.ones(1000, bool), jnp.asarray(last), 700.0, 600.0, key)
    got = sharded_maintenance_sweep(mesh, self_id, padded, valid, last_p,
                                    700.0, 600.0, key)
    for a, b, name in zip(got, ref, ("counts", "last", "stale", "targets")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


# -- PR 35: the home-lane window (parallel/sharded.py window_gather) ----------
# A wave of WINDOW_MIN_LANES lookups or more is grouped by the home shard
# of its targets, and a shard gathers over one lane window of a
# sixteenth more than its share, in as many passes as cover the lanes it
# owns.  Every case below runs with the window engaged (Wh < W).

WINDOW_Q = 2048
WINDOW_KW = dict(seed=35, alpha=3, state_limbs=2)


@pytest.fixture(scope="module")
def window_network():
    """16,384 uniform ids: as they come, sorted on one device, and as the
    key-range state of ``sharded_global_sort`` on a t=4 mesh."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    ids = jax.random.bits(jax.random.PRNGKey(3500), (16384, 5),
                          dtype=jnp.uint32)
    sorted_ids, _, n_valid = sort_table(ids)
    mesh = make_mesh(4, q=1, t=4)
    return (np.asarray(ids), sorted_ids, n_valid, mesh,
            sharded_global_sort(mesh, np.asarray(ids)))


def _uniform_targets(seed, q=WINDOW_Q):
    return np.array(jax.random.bits(jax.random.PRNGKey(seed), (q, 5),
                                    dtype=jnp.uint32))


def _assert_equals_one_chip(out, sorted_ids, n_valid, targets):
    ref = simulate_lookups(sorted_ids, n_valid, jnp.asarray(targets),
                           **WINDOW_KW)
    assert np.asarray(ref["converged"]).all()
    for key in ("nodes", "dist", "hops", "converged"):
        np.testing.assert_array_equal(np.asarray(out[key]),
                                      np.asarray(ref[key]), err_msg=key)
    return ref


def test_window_width_is_a_function_of_the_lane_count():
    from opendht_tpu.parallel.sharded import WINDOW_MIN_LANES, window_width
    assert window_width(65536, 4) == 17408 and window_width(8192, 4) == 2176
    assert window_width(WINDOW_Q, 4) == 640 and WINDOW_Q == WINDOW_MIN_LANES
    # small waves, one shard, and a minor axis that is not the wave (the
    # lookup-major final fetch [W, k]): one full-width pass
    for lanes, n_t in ((1024, 4), (256, 4), (8, 4), (65536, 1), (2048, 1)):
        assert window_width(lanes, n_t) == lanes
    assert window_width(65536, 2) == 34816 and window_width(65536, 8) == 8704
    for lanes in (2048, 4096, 8192, 65536):
        for n_t in (2, 4, 8):
            w = window_width(lanes, n_t)
            assert w % 128 == 0 and lanes / n_t < w < lanes


@pytest.mark.parametrize("layout", ["key_range", "row_split", "q2"])
def test_tp_simulate_equals_one_chip_over_lane_windows(window_network,
                                                       layout):
    """(a) ``tp_simulate_lookups`` with the window engaged equals
    ``simulate_lookups`` bit for bit, in the caller's order: over the
    key-range shards of ``sharded_global_sort``, over the uniform row
    split of a host-sorted table of 15,000 rows (shard edges on no key
    prefix, the last shard part padding), and with ``q`` = 2 (each
    q-rank groups its own 2,048 lanes)."""
    from opendht_tpu.parallel.sharded import window_width
    ids, sorted_ids, n_valid, mesh, state = window_network
    targets = _uniform_targets(3501)
    assert window_width(WINDOW_Q, 4) < WINDOW_Q
    if layout == "key_range":
        out = tp_simulate_lookups(mesh, targets=targets, state=state,
                                  **WINDOW_KW)
    elif layout == "row_split":
        sorted_ids, _, n_valid = sort_table(jnp.asarray(ids[:15000]))
        out = tp_simulate_lookups(mesh, np.asarray(sorted_ids), n_valid,
                                  targets, **WINDOW_KW)
    else:
        mesh2 = make_mesh(8, q=2, t=4)
        targets = _uniform_targets(3502, 2 * WINDOW_Q)
        out = tp_simulate_lookups(mesh2, targets=targets,
                                  state=sharded_global_sort(mesh2, ids),
                                  **WINDOW_KW)
        assert np.asarray(out["window_rounds"]).shape == (2,)
    ref = _assert_equals_one_chip(out, sorted_ids, n_valid, targets)
    assert (np.asarray(out["window_rounds"]) >= 0).all()
    assert (np.asarray(out["window_rounds"])
            <= np.asarray(ref["hops"]).max()).all()


def _edge_targets(rng, sorted_ids, state):
    """Targets within 12 rows of a shard edge: the ids of those rows with
    their low limbs redrawn, so the 24-row fallback window around each
    straddles the edge."""
    edges = np.cumsum(np.asarray(state.arrays["shard_rows"])[:, 1])[:-1]
    rows = (rng.choice(edges, WINDOW_Q)
            + rng.integers(-12, 13, WINDOW_Q)).astype(np.int64)
    targets = np.asarray(sorted_ids)[rows].copy()
    targets[:, 3:] = rng.integers(0, 2 ** 32, size=(WINDOW_Q, 2),
                                  dtype=np.uint32)
    return targets


@pytest.mark.parametrize("wave", ["one_home", "shard_edge", "empty_shard"])
def test_tp_simulate_equals_one_chip_on_adversarial_waves(window_network,
                                                          wave):
    """(b) Waves the grouping cannot help cost passes, never an answer:
    every target in ONE shard's key range (its shard owns every lane and
    takes t passes a round, so no round counts: ``window_rounds`` 0);
    every target within 12 rows of a shard edge (fallback windows that
    straddle it: a lookup's rows in two shards to the end); a table
    whose last shard holds no row."""
    ids, sorted_ids, n_valid, mesh, state = window_network
    rng = np.random.default_rng(3510)
    if wave == "one_home":
        targets = _uniform_targets(3511)
        targets[:, 0] = (targets[:, 0] >> 2) | np.uint32(2 << 30)
    elif wave == "shard_edge":
        targets = _edge_targets(rng, sorted_ids, state)
    else:
        targets = _uniform_targets(3512)
    if wave == "empty_shard":
        sorted_ids, _, n_valid = sort_table(jnp.asarray(ids[:3000]))
        padded, _ = pad_to_multiple(np.asarray(sorted_ids), 4096)
        out = tp_simulate_lookups(mesh, padded, n_valid, targets,
                                  **WINDOW_KW)
    else:
        out = tp_simulate_lookups(mesh, targets=targets, state=state,
                                  **WINDOW_KW)
    _assert_equals_one_chip(out, sorted_ids, n_valid, targets)
    if wave == "one_home":
        assert int(out["window_rounds"][0]) == 0


def test_window_rounds_are_the_loop_rounds_of_a_grouped_wave(window_network):
    """(d) A wave whose homes are balanced (512 lanes each: every group
    starts on a lane tile) and whose targets lie in the middle half of
    their home's key range (no fallback window reaches a shard edge):
    from loop round 1 on every reply row of a lookup lies in its home
    shard, every shard serves every round's gather in one pass, and
    ``window_rounds`` is the number of loop rounds — the deepest
    lookup's hops."""
    _ids, sorted_ids, n_valid, mesh, state = window_network
    targets = _uniform_targets(3520)
    home = np.arange(WINDOW_Q, dtype=np.uint32) % 4
    rng = np.random.default_rng(3521)
    rng.shuffle(home)
    mid = rng.integers(1, 3, WINDOW_Q).astype(np.uint32)
    targets[:, 0] = (home << 30) | (mid << 28) | (targets[:, 0] >> 4)
    out = tp_simulate_lookups(mesh, targets=targets, state=state,
                              **WINDOW_KW)
    ref = _assert_equals_one_chip(out, sorted_ids, n_valid, targets)
    rounds = int(np.asarray(ref["hops"]).max())
    assert rounds >= 4
    assert np.asarray(out["window_rounds"]).tolist() == [rounds]


def _np_lane_window(lane_any, width):
    """The window rule in numpy."""
    if not lane_any.any():
        return None, 0
    first, last = np.flatnonzero(lane_any)[[0, -1]]
    start = first // 128 * 128
    return start, -(-(last + 1 - start) // width)


WINDOW_MASKS = ["grouped", "ungrouped", "none", "stray_lane", "tail",
                "two_homes", "one_lane", "all", "ragged_lanes"]


@pytest.mark.parametrize("mask", WINDOW_MASKS)
def test_window_passes_cover_every_owned_lane_once(mask):
    """(c) The window rule against its numpy rendering, on index masks
    of every kind: the passes number ``ceil(span / Wh)``, the lanes each
    SERVES partition the span (every owned lane in exactly one), each
    window as the program slices it (held inside the index) contains
    what its pass serves, and ``window_gather`` returns the owned rows'
    limbs, 0 elsewhere, and whether one pass did it."""
    from opendht_tpu.parallel.sharded import (lane_window, window_gather,
                                              window_width)
    rng = np.random.default_rng(WINDOW_MASKS.index(mask))
    lanes = 4000 if mask == "ragged_lanes" else 4096
    width = window_width(lanes, 4)
    assert width == 1152
    shard_n, slots = 5000, 6
    base = 2 * shard_n                           # shard 2 of 4
    home = np.sort(rng.integers(0, 4, lanes))
    if mask == "ungrouped":
        rng.shuffle(home)
    elif mask == "none":
        home = np.where(home == 2, 3, home)
    elif mask == "stray_lane":
        home[5] = 2
    elif mask == "tail":
        home = np.sort(rng.choice([0, 1, 3, 3], lanes))
        home[-700:] = 2
    elif mask == "one_lane":
        home = np.where(home == 2, 1, home)
        home[1234] = 2
    elif mask == "all":
        home[:] = 2
    rows = home[None, :] * shard_n + rng.integers(0, shard_n, (slots, lanes))
    if mask == "two_homes":
        rows = np.where(rng.random(rows.shape) < 0.5, rows, rows - shard_n)
    rows[rng.random(rows.shape) < 0.1] = -1      # lookups that are done
    rows = rows.astype(np.int32)
    ok = (rows >= base) & (rows < base + shard_n)
    lane_any = ok.any(axis=0)

    start, passes = (int(x) for x in lane_window(jnp.asarray(lane_any),
                                                 width))
    want_start, want_passes = _np_lane_window(lane_any, width)
    assert passes == want_passes
    covered = np.zeros(lanes, int)
    if passes:
        assert start == want_start and start % 128 == 0
        span = np.flatnonzero(lane_any)[-1] + 1 - start
        assert passes == -(-span // width)
        for p in range(passes):
            lo = start + p * width
            at = min(lo, lanes - width)          # the program's slice
            assert at <= lo and min(lo + width, lanes) <= at + width
            covered[lo:lo + width] += 1
    assert (covered[lane_any] == 1).all() and covered.max() <= 1
    assert {"grouped": 1, "ungrouped": 4, "none": 0, "stray_lane": 3,
            "tail": 1, "one_lane": 1, "all": 4}.get(mask, passes) == passes

    view = rng.integers(0, 2 ** 32, (2, shard_n), dtype=np.uint32)
    planes, one_pass = jax.jit(
        lambda v, r: window_gather(v, r, np.int32(base), np.int32(shard_n),
                                   shard_n, 2, width))(view, rows)
    want = np.where(ok[None], view[:, np.clip(rows - base, 0, shard_n - 1)],
                    0)
    np.testing.assert_array_equal(np.asarray(planes), want)
    assert int(one_pass) == (passes <= 1)
    # and at full width: the one pass it always was
    full, one = window_gather(view, rows, np.int32(base), np.int32(shard_n),
                              shard_n, 2, lanes)
    np.testing.assert_array_equal(np.asarray(full), want)
    assert int(one) == 1


def test_one_chip_program_is_the_parents():
    """(e) The one-chip engine never sees the window: with a gather
    closure that reports nothing ``_lookup_engine`` lowers to the
    program it was before the optional count existed — the sha-256 of
    ``_simulate_lookups_jit``'s lowered text at a toy shape, taken on
    the parent commit of PR 35 (abstract operands: nothing runs)."""
    import hashlib
    from opendht_tpu.core.search import _simulate_lookups_jit
    from opendht_tpu.ops.sorted_table import default_lut_bits
    A = jax.ShapeDtypeStruct
    u32, i32 = jnp.uint32, jnp.int32
    text = _simulate_lookups_jit.lower(
        A((16384, 5), u32), A((), i32), A((4096, 5), u32), seed=A((), i32),
        lut=A(((1 << default_lut_bits(16384)) + 1,), i32), k=8, alpha=3,
        search_nodes=14, state_limbs=2).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "e13fcbd00248f195034072b8f79c8e6d90dee22651f20afdd2ee17fa6cdff206")


# -- PR 38: the search state sharded over t (parallel/sharded.py
# build_tp_lookup, THE SEARCH STATE; lane_chunk, lane_exchange) ---------------
# A t-rank runs the engine over its own chunk of the (grouped) wave, and
# only the engine's primitives cross the mesh.  Every bit-identity test
# above already runs that way (any wave t divides is chunked); the cases
# below are the ones the chunking itself brings.

def test_lane_chunk_is_a_function_of_the_lane_count():
    from opendht_tpu.parallel.sharded import lane_chunk
    assert lane_chunk(65536, 4) == 16384 and lane_chunk(2048, 4) == 512
    assert lane_chunk(64, 8) == 8 and lane_chunk(4, 4) == 1
    # one shard, or a wave t does not divide: the chunk is the whole wave
    for lanes, n_t in ((65536, 1), (2050, 4), (66, 4), (3, 4)):
        assert lane_chunk(lanes, n_t) == lanes


def test_deep_lookups_in_one_shards_chunk_cut_with_the_rest():
    """(a) Live counts that differ across ``t``: three shards of a
    row-split table hold ids spread over the key space, the last holds
    as many in a band 2^-14 of it wide, so the lookups whose home it is
    run hops longer than everybody else's — and, grouped, they ARE the
    last rank's chunk (4,096 lanes a home, exactly).  The other ranks'
    lookups are done rounds before: every rank keeps running rounds
    (the loop bodies hold collectives over ``t``) and cuts when the
    FULLEST shard's survivors fit, in the same round — no hang, one
    ``narrow_rounds`` — and the outputs are the one-chip engine's."""
    from opendht_tpu.core.search import NARROW_MIN_WAVE
    from opendht_tpu.parallel.sharded import lane_chunk
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    t, per_shard = 4, 2048
    rng = np.random.default_rng(3800)
    band = np.uint32(0xFFFC0000)
    ids = _rand_ids(rng, t * per_shard)
    ids[:3 * per_shard, 0] = rng.integers(0, band, 3 * per_shard,
                                          dtype=np.uint32)
    ids[3 * per_shard:, 0] |= band
    sorted_ids, _, n_valid = sort_table(jnp.asarray(ids))
    first = np.asarray(sorted_ids)[::per_shard, 0].astype(np.int64)
    assert first[3] >= band                       # shard 3 is the band
    Q = t * NARROW_MIN_WAVE
    assert lane_chunk(Q, t) == NARROW_MIN_WAVE
    home = rng.permutation(np.arange(Q) % t)      # 4,096 lanes a home
    targets = _rand_ids(rng, Q)
    edges = np.append(first, 1 << 32)
    targets[:, 0] = (edges[home] + rng.integers(0, 1 << 32, Q)
                     % (edges[home + 1] - edges[home])).astype(np.uint32)
    kw = dict(seed=38, alpha=3, state_limbs=2)
    ref = simulate_lookups(sorted_ids, n_valid, jnp.asarray(targets), **kw)
    hops = np.asarray(ref["hops"])
    # the band's lookups are the deep ones: when the last of the others
    # ends, three ranks in four have nobody live, and most of the
    # band's run on for rounds
    others = hops[home != 3].max()
    assert (hops[home == 3] > others).mean() > 0.5 and hops.max() > others + 2
    out = tp_simulate_lookups(make_mesh(t, q=1, t=t), np.asarray(sorted_ids),
                              n_valid, targets, **kw)
    for key in ("nodes", "dist", "hops", "converged"):
        np.testing.assert_array_equal(np.asarray(out[key]),
                                      np.asarray(ref[key]), err_msg=key)
    assert np.asarray(out["converged"]).all()
    assert np.asarray(out["home_lanes"]).tolist() == [Q]
    narrow = np.asarray(out["narrow_rounds"])
    assert narrow.shape == (1,) and 1 <= narrow[0] < hops.max()


@pytest.mark.parametrize("q,t,lanes,chunked,grouped", [
    pytest.param(1, 4, 66, False, False, id="t_does_not_divide"),
    pytest.param(1, 4, 2050, False, True, id="t_does_not_divide_grouped"),
    pytest.param(1, 4, 256, True, False, id="under_window_min_lanes"),
    pytest.param(1, 4, 4, True, False, id="one_lane_a_shard"),
    pytest.param(2, 2, 4096, True, True, id="q2_t2_sharded_over_both"),
    pytest.param(2, 2, 1030, False, False, id="q2_t2_whole_waves"),
])
def test_tp_simulate_equals_one_chip_at_every_chunking(window_network, q, t,
                                                       lanes, chunked,
                                                       grouped):
    """(b), (c) One rule from ``(q_local, t)`` (``lane_chunk``), one
    program: a wave ``t`` does not divide runs whole on every rank (the
    exchange is its ``psum``; ``home_lanes`` is then every lane), a toy
    wave under ``WINDOW_MIN_LANES`` is chunked in the caller's order,
    not grouped, and with ``q`` = 2, ``t`` = 2 the state is sharded
    over both axes, each q-rank grouping and chunking its own lanes.
    All equal the one-chip engine bit for bit, in the caller's order."""
    from opendht_tpu.parallel.sharded import lane_chunk, window_width
    ids, sorted_ids, n_valid, _mesh, _state = window_network
    q_local = lanes // q
    assert (lane_chunk(q_local, t) < q_local) == chunked
    assert (window_width(q_local, t) < q_local) == grouped
    mesh = make_mesh(q * t, q=q, t=t)
    targets = _uniform_targets(3800 + lanes, lanes)
    out = tp_simulate_lookups(mesh, targets=targets,
                              state=sharded_global_sort(mesh, ids),
                              **WINDOW_KW)
    _assert_equals_one_chip(out, sorted_ids, n_valid, targets)
    at_home = np.asarray(out["home_lanes"])
    assert at_home.shape == (q,) == np.asarray(out["window_rounds"]).shape
    if chunked:
        assert ((0 <= at_home) & (at_home <= q_local)).all()
        assert at_home.sum() > lanes // 2 or not grouped
    else:
        assert at_home.tolist() == [q_local] * q


@pytest.mark.parametrize("wave", ["one_a_home_in_order", "one_home"])
def test_home_lanes_counts_the_lanes_that_run_where_their_rows_are(
        window_network, wave):
    """(f) ``home_lanes``: a toy wave of one target a home, in the order
    of the shards (chunks of ONE lane, each its shard's own), reads W;
    a wave whose targets all lie in shard 2's key range reads only the
    lanes that happen to run there — a quarter — toy or grouped."""
    _ids, sorted_ids, n_valid, mesh, state = window_network
    if wave == "one_a_home_in_order":
        targets = _uniform_targets(3830, 4)
        targets[:, 0] = (np.arange(4, dtype=np.uint32) << 30) | (
            targets[:, 0] >> 2)
        want = [4]
    else:
        targets = _uniform_targets(3831)
        targets[:, 0] = (targets[:, 0] >> 2) | np.uint32(2 << 30)
        want = [WINDOW_Q // 4]
    out = tp_simulate_lookups(mesh, targets=targets, state=state,
                              **WINDOW_KW)
    _assert_equals_one_chip(out, sorted_ids, n_valid, targets)
    assert np.asarray(out["home_lanes"]).tolist() == want


def test_a_lookups_rounds_run_on_one_shard_by_the_lowered_shapes():
    """(e) The counts, from shapes: in the lowered text of
    ``build_tp_lookup`` at a toy geometry (256 lookups, t = 4, α = 3,
    S = 14, k = 8) every read of the replicated block LUT has 2·P·W/t
    indices (P = α peers in a loop round, 1 in the bootstrap) and every
    merge sort has W/t rows of S + P·k — where the program whose state
    was replicated over ``t`` read 2·P·W and sorted W rows on every
    chip — while the owner-shard gather still takes the whole wave's
    index, all-gathered."""
    import re
    from opendht_tpu.parallel.sharded import build_tp_lookup
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    A = jax.ShapeDtypeStruct
    u32, i32 = jnp.uint32, jnp.int32
    rows, W, t, k, alpha, S = 4096, 256, 4, 8, 3, 14
    block_lut = (1 << 12) + 1          # no other operand has this length
    text = build_tp_lookup(make_mesh(t, q=1, t=t), rows, W, k, alpha, S, 48,
                           2, True).lower(
        A((t * rows, 5), u32), A((t, (1 << 10) + 1), i32),
        A((block_lut,), i32), A((), i32), A((t, 2), i32), A((W, 5), u32),
        A((), i32)).as_text()
    lut_reads = {int(np.prod([int(d) for d in m.split("x")]))
                 for m in re.findall(
                     r'"stablehlo\.gather"[^\n]*: \(tensor<%dxi32>, '
                     r'tensor<([0-9x]+)x1xi32>\)' % block_lut, text)}
    assert lut_reads == {2 * alpha * W // t, 2 * W // t}
    sorts = set(re.findall(
        r'"stablehlo\.sort".*?\}\) : \(tensor<(\d+)x(\d+)xi32>', text,
        flags=re.S))
    assert sorts == {(str(W // t), str(S + alpha * k)),
                     (str(W // t), str(S + k))}
    # the row fetch crosses the mesh: the chunks' index all-gathered,
    # the parts summed and cut back to the chunk, under stage owner_merge
    assert "tensor<%dx%dxi32>) -> tensor<%dx%dxi32>" % (
        alpha * k, W // t, alpha * k, W) in text
    assert "tensor<2x%dx%dxui32>) -> tensor<2x%dx%dxui32>" % (
        alpha * k, W, alpha * k, W // t) in text
    assert '"stablehlo.all_reduce"' in text
    assert '"stablehlo.all_gather"' in text


# -- PR 46: the positioning over a lane window (parallel/sharded.py
# window_lower_bound) ---------------------------------------------------------
# A shard answers the targets outside its key range by two compares and
# searches the rest over lane windows: every lane must read what the
# full-width search gives, on every shard, whatever the wave or table.

POSITION_Q = 4096
POSITION_CASES = ["grouped", "edge_rows", "top32", "top64", "one_shard",
                  "empty_shard", "small_wave", "tie64", "clustered"]


def _positioning_parts(mesh, sorted_ids, n_valid, targets):
    """Every shard's windowed positioning, its passes, and its
    full-width ``_guarded_lower_bound``, over the uniform row split of a
    sorted table: ``[t, M]``, ``[t]``, ``[t, M]``."""
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from opendht_tpu.core.search import _guarded_lower_bound
    from opendht_tpu.parallel import shard_table_state
    from opendht_tpu.parallel.sharded import window_lower_bound
    state = shard_table_state(mesh, sorted_ids, n_valid)
    shard_n, n_t = state.shard_n, mesh.shape["t"]

    def local(rows, lut, n, targets):
        n_local = jnp.clip(n - lax.axis_index("t") * shard_n, 0, shard_n)
        part, passes = window_lower_bound(rows, n_local, lut[0], n_t)(targets)
        full = _guarded_lower_bound(rows, n_local, lut[0])(targets)
        return part[None], passes[None], full[None]

    fn = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("t", None), P("t", None), P(), P()),
        out_specs=(P("t", None), P("t"), P("t", None)), check_vma=False))
    a = state.arrays
    return [np.asarray(x) for x in fn(a["sorted_ids"], a["local_lut"],
                                      a["n_valid"], jnp.asarray(targets))]


def _keys(ids):
    """160-bit ids as byte strings that sort as the ids do."""
    return np.ascontiguousarray(ids.astype(">u4")).view("S20").ravel()


def _step(ids, delta):
    """160-bit ``ids + delta``, wrapping."""
    out = []
    for row in ids.tolist():
        v = (int.from_bytes(np.array(row, ">u4").tobytes(), "big")
             + delta) % 2 ** 160
        out.append(np.frombuffer(v.to_bytes(20, "big"), ">u4"))
    return np.array(out, np.uint32)


@pytest.mark.parametrize("case", POSITION_CASES)
def test_windowed_positioning_is_the_full_width_search_on_every_shard(case):
    """The windowed part of every shard equals ``_guarded_lower_bound``'s
    full-width part lane for lane, and both the count of the shard's
    valid rows below each target: on a grouped uniform wave (one pass a
    shard); on targets equal to each shard's first and last row and
    both ± 1; on targets that share the top 32 or 64 bits with a shard's
    first row (where the top-limb home hint is off); on a wave skewed
    into one shard (``t`` passes there, none elsewhere); with an empty
    shard (no pass, all 0); on a wave under ``WINDOW_MIN_LANES`` (the
    full-width search, one pass); and on tables that send the guard to
    its slower tiers (64-bit ties; LUT buckets past the step budget)."""
    from opendht_tpu.parallel.sharded import window_width
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    t, rows = 4, 16384
    mesh = make_mesh(t, q=1, t=t)
    rng = np.random.default_rng(4600 + POSITION_CASES.index(case))
    ids = _rand_ids(rng, rows)
    if case == "tie64":
        ids[100:4200, :2] = ids[100, :2]     # ties within and across shards
    elif case == "clustered":
        ids[:12000, 0] = 0x7777AAAA          # buckets far past the budget
    n_valid = 12000 if case == "empty_shard" else rows
    sorted_ids = np.asarray(sort_table(jnp.asarray(ids[:n_valid]))[0])
    sorted_ids = pad_to_multiple(sorted_ids, rows)[0]
    shard_n = rows // t
    n_local = np.clip(n_valid - np.arange(t) * shard_n, 0, shard_n)
    first = sorted_ids[::shard_n]
    last = sorted_ids[np.arange(t) * shard_n + np.maximum(n_local - 1, 0)]
    lanes = 1024 if case == "small_wave" else POSITION_Q
    targets = _rand_ids(rng, lanes)
    if case in ("grouped", "edge_rows", "top32", "top64"):
        # a uniform wave grouped by home as the program groups it, a
        # quarter of the lanes a home: each shard's run is one window
        draw = _rand_ids(rng, 4 * lanes)
        home = (draw[:, 0][:, None] >= first[1:, 0][None, :]).sum(axis=1)
        targets = np.concatenate([draw[home == s][:lanes // t]
                                  for s in range(t)])
    if case == "edge_rows":
        # each kind of edge target in a tile of its own, in shard 0's
        # run: for every other shard the lanes it must search lie
        # outside its home run, and an edge taken for the wrong side
        # leaves them outside its windows
        for at, edge in enumerate((last, _step(first, 1), _step(last, -1),
                                   first, _step(first, -1), _step(last, 1))):
            targets[128 * at:128 * at + t] = edge
    elif case in ("top32", "top64"):
        # in each home's run, targets that share the top limbs of the
        # home's first row: the home hint is the shard's, and those
        # below its first row are the shard before's
        shared = 1 if case == "top32" else 2
        for s in range(t):
            run = s * (lanes // t) + rng.choice(lanes // t, 128,
                                                replace=False)
            targets[run, :shared] = first[s, :shared]
    elif case == "one_shard":
        lo, hi = int(first[2, 0]) + 1, int(last[2, 0])
        targets[:, 0] = rng.integers(lo, hi, lanes, dtype=np.uint32)
    elif case == "tie64":
        targets[:lanes // 4, :2] = ids[100, :2]
    elif case == "clustered":
        targets[:lanes // 2, 0] = 0x7777AAAA
    part, passes, full = _positioning_parts(mesh, sorted_ids, n_valid,
                                            targets)
    np.testing.assert_array_equal(part, full)
    for s in range(t):
        mine = _keys(sorted_ids[s * shard_n:s * shard_n + n_local[s]])
        np.testing.assert_array_equal(
            part[s], np.searchsorted(mine, _keys(targets)), err_msg=str(s))
    width = window_width(lanes, t)
    inside = [((_keys(targets) > _keys(first[s:s + 1]))
               & (_keys(targets) <= _keys(last[s:s + 1]))).any()
              and n_local[s] > 0 for s in range(t)]
    if case == "small_wave":
        assert width == lanes and passes.tolist() == [1] * t
    else:
        assert width < lanes
        assert [bool(p) for p in passes] == inside
    if case == "grouped":
        assert passes.tolist() == [1] * t
    elif case == "one_shard":
        assert passes.tolist() == [0, 0, t, 0]
    elif case == "empty_shard":
        assert passes[3] == 0 and (part[3] == 0).all()


@pytest.mark.parametrize("wave", ["grouped", "one_home"])
def test_position_passes_count_the_windows_a_shard_searched(window_network,
                                                            wave):
    """``position_passes``, the most lane windows any shard's positioning
    searched: 1 on a grouped wave whose homes are balanced, ``t`` on a
    wave whose targets all lie in one shard's key range — and the wave's
    outputs are the one-chip engine's either way.  ``record_wave`` files
    it as ``dht_search_position_passes{mode="tp"}``."""
    from opendht_tpu import telemetry
    _ids, sorted_ids, n_valid, mesh, state = window_network
    targets = _uniform_targets(4610)
    if wave == "grouped":
        home = np.arange(WINDOW_Q, dtype=np.uint32) % 4
        np.random.default_rng(4611).shuffle(home)
        targets[:, 0] = (home << 30) | (np.uint32(1) << 29) | (
            targets[:, 0] >> 3)
        want = 1
    else:
        targets[:, 0] = (targets[:, 0] >> 2) | np.uint32(2 << 30)
        want = mesh.shape["t"]
    reg = telemetry.get_registry()
    before = reg.snapshot()
    out = tp_simulate_lookups(mesh, targets=targets, state=state,
                              **WINDOW_KW)
    _assert_equals_one_chip(out, sorted_ids, n_valid, targets)
    assert np.asarray(out["position_passes"]).tolist() == [want]
    moved = telemetry.snapshot_diff(before, reg.snapshot())["histograms"]
    filed = moved['dht_search_position_passes{mode="tp"}']
    assert filed["count"] == 1 and filed["sum"] == want
