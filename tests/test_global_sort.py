"""The table built across the mesh (``parallel.sharded_global_sort``)
against plain references on the virtual CPU mesh: ``np.lexsort`` for the
build, ``simulate_lookups`` on ``sort_table`` of the same ids for the
sharded lookups (bit for bit), ``dhtbench.reference.XorIndex`` for the
answers."""

import numpy as np
import pytest

import jax.numpy as jnp

from dhtbench import reference
from opendht_tpu import telemetry
from opendht_tpu.core.search import simulate_lookups
from opendht_tpu.ops.sorted_table import build_prefix_lut, sort_table
from opendht_tpu.parallel import (make_mesh, sharded_global_sort,
                                  tp_simulate_lookups)
from opendht_tpu.parallel.global_sort import (default_segment_rows,
                                              dest_shard)


def _rand_ids(rng, n):
    return rng.integers(0, 2 ** 32, size=(n, 5), dtype=np.uint32)


def _lexsorted(ids):
    return ids[np.lexsort(ids.T[::-1])]


def _valid_rows(state, n_t):
    """The shards' valid rows, concatenated in shard order, and each
    shard's (base, width)."""
    rows = np.asarray(state.arrays["shard_rows"])
    table = np.asarray(state.sorted_ids).reshape(n_t, state.shard_n, 5)
    return np.concatenate([table[i, :rows[i, 1]] for i in range(n_t)]), rows


def _uniform(rng, n_t):
    return _rand_ids(rng, 4096), None, {}


def _duplicates(rng, n_t):
    # 4096 rows drawn from 200 distinct ids: runs of twenty equal rows,
    # and shards of very unequal width, so the segments are given room
    pool = _rand_ids(rng, 200)
    return pool[rng.integers(0, 200, size=4096)], None, \
        {"segment_rows": 4096 // n_t}


def _ties_in_the_top_limbs(rng, n_t):
    # rows that agree on their first 64 (and 128) bits and differ below:
    # the order has to come from every limb
    ids = _rand_ids(rng, 4096)
    ids[1::2, :2] = ids[::2, :2]
    ids[3::4, :4] = ids[2::4, :4]
    return ids, None, {}


def _size_not_a_multiple_of_t(rng, n_t):
    n = 4096 - 3
    padded = np.concatenate([_rand_ids(rng, n),
                             np.full((3, 5), 7, np.uint32)])
    # the pad rows lie in the MIDDLE of the table as placed: validity is
    # a mask, not a suffix
    order = rng.permutation(4096)
    return padded[order], (np.arange(4096) < n)[order], {}


@pytest.mark.parametrize("t", [4, 8])
@pytest.mark.parametrize("case", [_uniform, _duplicates,
                                  _ties_in_the_top_limbs,
                                  _size_not_a_multiple_of_t])
def test_build_is_the_lexsorted_table_row_for_row(case, t):
    rng = np.random.default_rng(21)
    mesh = make_mesh(t, q=1, t=t)
    ids, valid, kw = case(rng, t)
    state = sharded_global_sort(mesh, ids, valid, **kw)
    got, rows = _valid_rows(state, t)
    want = _lexsorted(ids if valid is None else ids[valid])
    np.testing.assert_array_equal(got, want)
    assert int(state.arrays["n_valid"]) == want.shape[0]
    # each shard is one contiguous range of the global order: the ids of
    # its equal share of the key space, in the weighted layout
    assert rows[0, 0] == 0
    np.testing.assert_array_equal(rows[1:, 0], np.cumsum(rows[:-1, 1]))
    assert state.boundaries == tuple(int(b) for b in rows[1:, 0])
    np.testing.assert_array_equal(
        dest_shard(want[:, 0], t),
        np.repeat(np.arange(t), rows[:, 1]))
    assert state.shard_n == t * kw.get(
        "segment_rows", default_segment_rows(4096 // t, t))
    # rows past a shard's width are zero padding, as in the weighted
    # layout of shard_table_state
    table = np.asarray(state.sorted_ids).reshape(t, state.shard_n, 5)
    assert all(not table[i, rows[i, 1]:].any() for i in range(t))
    # the replicated block LUT is build_prefix_lut over the whole table
    np.testing.assert_array_equal(
        np.asarray(state.arrays["block_lut"]),
        np.asarray(build_prefix_lut(jnp.asarray(want), want.shape[0],
                                    bits=state.block_bits)))


def test_build_on_a_mesh_with_a_query_axis():
    rng = np.random.default_rng(22)
    mesh = make_mesh(8, q=2, t=4)
    ids = _rand_ids(rng, 2048)
    got, _ = _valid_rows(sharded_global_sort(mesh, ids), 4)
    np.testing.assert_array_equal(got, _lexsorted(ids))


def test_a_segment_over_its_capacity_raises_and_nothing_is_truncated():
    rng = np.random.default_rng(23)
    mesh = make_mesh(4, q=1, t=4)
    ids = _rand_ids(rng, 4096)
    ids[:, 0] >>= 3                      # every id in shard 0's range
    with pytest.raises(OverflowError, match="segment capacity"):
        sharded_global_sort(mesh, ids)
    # with room for a whole shard in every segment the same ids build
    state = sharded_global_sort(mesh, ids, segment_rows=1024)
    got, rows = _valid_rows(state, 4)
    np.testing.assert_array_equal(got, _lexsorted(ids))
    assert rows[:, 1].tolist() == [4096, 0, 0, 0]
    with pytest.raises(ValueError, match="not divisible"):
        sharded_global_sort(mesh, ids[:4095])


def test_build_records_its_phases_and_the_rows_it_moved():
    rng = np.random.default_rng(24)
    mesh = make_mesh(4, q=1, t=4)
    ids = _rand_ids(rng, 4096)
    registry = telemetry.get_registry()
    before = registry.snapshot()
    sharded_global_sort(mesh, ids)
    moved = telemetry.snapshot_diff(before, registry.snapshot())
    assert {k: h["count"] for k, h in moved["histograms"].items()
            if k.startswith("dht_table_build_seconds")} == {
        f'dht_table_build_seconds{{phase="{p}"}}': 1
        for p in ("partition", "exchange", "sort", "lut")}
    # rows that lay on another shard than the one their key belongs to
    placed_on = np.repeat(np.arange(4), 1024)
    assert moved["counters"]["dht_table_build_rows_exchanged_total"] \
        == int((dest_shard(ids[:, 0], 4) != placed_on).sum())


def _identical(out, ref):
    for key in ("nodes", "hops", "converged", "dist"):
        np.testing.assert_array_equal(np.asarray(out[key]),
                                      np.asarray(ref[key]))


def _agreement(nodes, sorted_ids, targets, k=8):
    index = reference.XorIndex(sorted_ids)
    return sum(set(nodes[j].tolist())
               == set(index.closest(targets[j], k).tolist())
               for j in range(targets.shape[0]))


@pytest.mark.parametrize("q,t", [(1, 4), (2, 4), (1, 8)])
def test_lookups_on_the_built_state_match_one_device_bit_for_bit(q, t):
    """As ``test_sharded.py::test_tp_simulate_matches_unsharded`` holds
    for the host-sorted form: same ids, ``sort_table`` and
    ``simulate_lookups`` on one device against the build and
    ``tp_simulate_lookups`` across the mesh."""
    rng = np.random.default_rng(25)
    mesh = make_mesh(q * t, q=q, t=t)
    ids = _rand_ids(rng, 4096)
    targets = _rand_ids(rng, 64)
    sorted_ids, _, n_valid = sort_table(jnp.asarray(ids))
    ref = simulate_lookups(sorted_ids, n_valid, jnp.asarray(targets), seed=5,
                           k=8, alpha=3, search_nodes=14, state_limbs=2)
    state = sharded_global_sort(mesh, ids)
    out = tp_simulate_lookups(mesh, targets=targets, state=state, seed=5,
                              k=8, alpha=3, search_nodes=14, state_limbs=2)
    _identical(out, ref)
    assert bool(np.asarray(out["converged"]).all())
    # and the answers are the exact XOR top-8 of the plain reference
    assert _agreement(np.asarray(out["nodes"]), np.asarray(sorted_ids),
                      targets) >= 0.9 * 64


def test_six_rows_a_block_lut_bucket_is_the_100m_regime_at_toy_size():
    """At 100M ids the replicated block LUT (clamped to 24 bits) has six
    rows a bucket where 10M has 0.6; ``block_bits`` forces that here:
    6 x 2^11 ids under an 11-bit block LUT.  Lookups converge, stay
    bit-identical to one device under the same LUT width, and keep the
    guaranteed agreement with the exact answer."""
    rng = np.random.default_rng(26)
    mesh = make_mesh(4, q=1, t=4)
    bits = 11
    ids = _rand_ids(rng, 6 << bits)
    targets = _rand_ids(rng, 128)
    sorted_ids, _, n_valid = sort_table(jnp.asarray(ids))
    lut = build_prefix_lut(sorted_ids, n_valid, bits=bits)
    assert 5.9 < float(np.diff(np.asarray(lut)).mean()) < 6.1
    ref = simulate_lookups(sorted_ids, n_valid, jnp.asarray(targets), seed=9,
                           k=8, alpha=3, search_nodes=14, lut=lut,
                           state_limbs=2)
    state = sharded_global_sort(mesh, ids, block_bits=bits)
    assert state.block_bits == bits
    out = tp_simulate_lookups(mesh, targets=targets, state=state, seed=9,
                              k=8, alpha=3, search_nodes=14, state_limbs=2)
    _identical(out, ref)
    assert bool(np.asarray(out["converged"]).all())
    hops = np.asarray(out["hops"])
    assert 1 <= hops.min() and hops.max() <= 47
    assert _agreement(np.asarray(out["nodes"]), np.asarray(sorted_ids),
                      targets) >= 0.9 * 128


def test_donate_consumes_the_placed_table_and_only_then():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    rng = np.random.default_rng(27)
    mesh = make_mesh(4, q=1, t=4)
    ids = _rand_ids(rng, 2048)
    placed = jax.device_put(ids, NamedSharding(mesh, P("t", None)))
    kept, _ = _valid_rows(sharded_global_sort(mesh, placed), 4)
    assert not placed.is_deleted()
    given, _ = _valid_rows(sharded_global_sort(mesh, placed, donate=True), 4)
    assert placed.is_deleted()
    np.testing.assert_array_equal(kept, _lexsorted(ids))
    np.testing.assert_array_equal(given, kept)
