"""Iterative lookups over a table that departs, joins and compacts
(ISSUE 32): the device-resident mutable table (``ops/churn_table.py``,
``core.table.DeviceChurnTable``) and the lookup engine's CHURN model
(``core/search.py``), each held to a plain oracle — a Python set for
membership, ``sort_table`` of the live ids for a compaction, the numpy
XOR top-k over the live ids and ``scalar_churn_lookup`` for lookups.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from opendht_tpu import telemetry
from opendht_tpu.core import search as S
from opendht_tpu.core.search import (scalar_churn_lookup, simulate_lookups,
                                     _simulate_lookups_jit)
from opendht_tpu.core.table import (DeviceChurnTable, MAX_STALE_SHARE,
                                    TOMB_FRAC)
from opendht_tpu.ops import churn_table as CT
from opendht_tpu.ops.sorted_table import (build_prefix_lut, sort_table,
                                          unpack_tomb_bits)

KW = dict(k=8, alpha=3, search_nodes=14)
OUTPUTS = ("nodes", "dist", "hops", "converged", "narrow_rounds")


def _ids(rng, n):
    return rng.integers(0, 2 ** 32, size=(n, 5), dtype=np.uint32)


def _table(ids, *, delta_capacity, lut_bits):
    """The simulator's table over ``ids``, its LUT narrowed to
    ``lut_bits`` (the wrapper sizes it to the table, 16 bits at least:
    under a row a bucket here) so that buckets hold rows to search."""
    s, _, n = sort_table(jnp.asarray(ids))
    tbl = DeviceChurnTable(s, n, delta_capacity=delta_capacity)
    tbl.view = CT.churn_table(
        s, n, capacity=tbl.view.capacity, delta_capacity=delta_capacity,
        stale_rows=tbl.view.dead_pos.shape[0], lut_bits=lut_bits)
    return tbl, s, n


def _live_ids(tbl) -> set:
    ids, live = CT.live_rows(tbl.view)
    return {tuple(r) for r in np.asarray(ids)[np.asarray(live)].tolist()}


def _checksum(ids: np.ndarray) -> list:
    """The driver's order-free fingerprint, in Python integers."""
    from dhtbench import reference_churn
    return reference_churn.checksum(ids).tolist()


def _tick(tbl, book: list, rng, leave_n, join_n, *, leave=None):
    """One tick against a Python book (a list of id tuples)."""
    if leave is None:
        at = rng.choice(len(book), leave_n, replace=False)
        leave = np.array([book[i] for i in at], dtype=np.uint32)
    join = _ids(rng, join_n)
    gone = {tuple(r) for r in leave.tolist()}
    book[:] = [b for b in book if b not in gone] \
        + [tuple(r) for r in join.tolist()]
    tbl.apply(jnp.asarray(leave), jnp.asarray(join))
    return leave, join


# -- no churn == frozen ----------------------------------------------------

@pytest.fixture(scope="module")
def network():
    rng = np.random.default_rng(32)
    ids = _ids(rng, 20000)
    tbl, s, n = _table(ids, delta_capacity=1024, lut_bits=15)
    targets = jnp.asarray(_ids(rng, 4096))      # wide enough to cut (PR 29)
    return ids, tbl, s, n, build_prefix_lut(s, n, bits=15), targets


@pytest.mark.parametrize("state_limbs", [2, 5])
def test_no_tick_applied_equals_the_frozen_table(network, state_limbs):
    _ids_np, tbl, s, n, lut, targets = network
    assert tbl.n_tomb == tbl.n_delta == 0
    kw = dict(KW, seed=5, state_limbs=state_limbs)
    frozen = simulate_lookups(s, n, targets, lut=lut, **kw)
    mutable = simulate_lookups(tbl.view, None, targets, **kw)
    for key in OUTPUTS:
        np.testing.assert_array_equal(np.asarray(frozen[key]),
                                      np.asarray(mutable[key]), err_msg=key)
    assert int(frozen["narrow_rounds"]) > 0           # the cut path too
    assert "expired_peers" not in frozen
    assert int(mutable["expired_peers"]) == 0


def test_a_churn_table_brings_its_own_row_count_and_lut(network):
    _ids_np, tbl, _s, n, lut, targets = network
    with pytest.raises(ValueError):
        _simulate_lookups_jit(tbl.view, n, targets, **KW)
    with pytest.raises(ValueError):
        _simulate_lookups_jit(tbl.view, None, targets, lut=lut, **KW)


def test_churn_stages_are_in_the_churn_program_only(network):
    _ids_np, tbl, s, n, lut, targets = network
    kw = dict(KW, state_limbs=2)
    frozen = _simulate_lookups_jit.lower(s, n, targets, lut=lut, **kw).as_text()
    churn = _simulate_lookups_jit.lower(tbl.view, None, targets, **kw).as_text()
    for stage in ("stage_expire", "stage_delta_window"):
        assert stage in churn and stage not in frozen
    for stage in ("stage_select", "stage_fetch_ids", "stage_merge",
                  "stage_block_bounds", "stage_reply_rows", "stage_converge"):
        assert stage in churn and stage in frozen
    assert "stage_table_apply" in CT.churn_apply.lower(
        tbl.view, targets[:8], targets[:8]).as_text()
    assert "stage_table_compact" in CT.churn_compact.lower(tbl.view).as_text()


# -- after a compaction == re-sort ------------------------------------------

@pytest.mark.parametrize("state_limbs", [2, 5])
def test_after_a_compaction_equals_the_resorted_live_ids(state_limbs):
    rng = np.random.default_rng(7)
    ids = _ids(rng, 12000)
    tbl, _s, _n = _table(ids, delta_capacity=1024, lut_bits=14)
    book = [tuple(r) for r in ids.tolist()]
    for _ in range(4):
        _tick(tbl, book, rng, 150, 170)
    tbl.compact()
    assert tbl.n_tomb == tbl.n_delta == 0 and tbl.n_base == len(book)
    live = np.array(sorted(book), dtype=np.uint32)
    s2, _, n2 = sort_table(jnp.asarray(live))
    lut2 = build_prefix_lut(s2, n2, bits=14)
    # the table itself is the re-sorted one, its LUT the rebuilt one
    np.testing.assert_array_equal(np.asarray(tbl.view.base)[:len(live)],
                                  np.asarray(s2))
    assert (np.asarray(tbl.view.base)[len(live):] == 0xFFFFFFFF).all()
    np.testing.assert_array_equal(np.asarray(tbl.view.lut), np.asarray(lut2))
    targets = jnp.asarray(_ids(rng, 512))
    kw = dict(KW, seed=11, state_limbs=state_limbs)
    oracle = simulate_lookups(s2, n2, targets, lut=lut2, **kw)
    got = simulate_lookups(tbl.view, None, targets, **kw)
    for key in OUTPUTS:
        np.testing.assert_array_equal(np.asarray(oracle[key]),
                                      np.asarray(got[key]), err_msg=key)
    assert int(got["expired_peers"]) == 0


# -- between compactions ------------------------------------------------------

@pytest.fixture(scope="module")
def churned():
    """4,096 nodes of which just under one in sixteen has departed and
    as many have joined: the stale share at its limit."""
    rng = np.random.default_rng(99)
    ids = _ids(rng, 4096)
    tbl, s, n = _table(ids, delta_capacity=512, lut_bits=12)
    book = [tuple(r) for r in ids.tolist()]
    for _ in range(5):
        _tick(tbl, book, rng, 50, 50)
    assert tbl.compactions == 0
    assert tbl.n_tomb + tbl.n_delta_gone == 250
    assert tbl.n_tomb + 50 > tbl.stale_rows_max      # the next tick compacts
    targets = _ids(rng, 640)
    out = simulate_lookups(tbl.view, None, jnp.asarray(targets), seed=3,
                           state_limbs=2, **KW)
    return tbl, np.asarray(s), book, targets, jax.device_get(out)


def test_no_returned_id_is_a_departed_one(churned):
    tbl, _base, book, targets, out = churned
    assert out["converged"].all() and (out["nodes"] >= 0).all()
    found = out["dist"] ^ targets[:, None, :]
    live = set(book)
    assert all(tuple(r) in live for r in found.reshape(-1, 5).tolist())
    # joined nodes are found (node index past the base's capacity), and a
    # departed node was asked now and then
    assert (out["nodes"] >= tbl.view.capacity).any()
    assert int(out["expired_peers"]) > 0


def test_closest_k_over_the_live_set_at_the_configurations_floor(churned):
    from dhtbench import reference
    _tbl, _base, book, targets, out = churned
    live = np.array(book, dtype=np.uint32)
    found = out["dist"] ^ targets[:, None, :]
    agree = sum(
        {tuple(r) for r in found[j].tolist()}
        == {tuple(r) for r in
            live[reference.xor_closest(live, targets[j], 8)].tolist()}
        for j in range(256))
    assert agree >= int(np.ceil(0.90 * 256)), agree


def test_hops_and_convergence_equal_the_scalar_references(churned):
    """Statistical parity over 640 targets: the reference draws block
    samples from a random generator where the engine hashes a counter,
    and (the frozen model's own difference, tests/test_search.py) takes
    exact prefix blocks where the engine's LUT clamps deep ones.  Mean
    hops of two samples of 640 lookups with a standard deviation of
    about 0.8 hops differ by 0.045 at one sigma: 0.25 is five sigma and
    a quarter of the distance to the next whole hop.  Expired peers a
    lookup, a count with a standard deviation near 1: 0.2, as wide."""
    tbl, base, _book, targets, out = churned
    view = tbl.view
    n, C = int(view.n_base), view.capacity
    gone = np.asarray(unpack_tomb_bits(view.tomb_bits, C + view.delta_capacity))
    departed = set(np.nonzero(gone[:n])[0].tolist())
    n_delta = int(view.n_delta)
    joined = np.asarray(view.delta)[:n_delta]
    joined_departed = set(np.nonzero(gone[C:C + n_delta])[0].tolist())
    assert len(departed) == tbl.n_tomb
    assert len(joined_departed) == tbl.n_delta_gone > 0
    ref = [scalar_churn_lookup(
        base, n, t, departed=departed, joined=joined,
        joined_departed=joined_departed, node_base=C, alpha=3,
        rng=np.random.default_rng([5, j])) for j, t in enumerate(targets)]
    ref_hops = np.array([r[1] for r in ref])
    assert all(r[2] for r in ref) and out["converged"].all()
    assert abs(ref_hops.mean() - out["hops"].mean()) < 0.25, \
        (ref_hops.mean(), out["hops"].mean())
    hist = lambda h: np.bincount(h, minlength=12)[:12] / len(h)  # noqa: E731
    assert np.abs(hist(ref_hops) - hist(out["hops"])).max() < 0.10
    ref_expired = sum(r[3] for r in ref) / len(ref)
    got_expired = int(out["expired_peers"]) / len(targets)
    assert abs(ref_expired - got_expired) < 0.2, (ref_expired, got_expired)
    live = {*range(n)} - departed
    assert all(node in live or (node >= C
                                and node - C not in joined_departed)
               for r in ref for node in r[0])


@pytest.mark.parametrize("state_limbs", [2, 5])
def test_where_every_reply_is_the_window_engine_and_reference_agree(
        state_limbs):
    """Seven nodes: every block holds fewer than k rows, so every reply
    is the window around the target and the model is deterministic —
    nodes, hops, convergence and the count of expired peers agree lookup
    for lookup."""
    rng = np.random.default_rng(3)
    s, _, n = sort_table(jnp.asarray(_ids(rng, 7)))
    base = np.asarray(s)
    # two of seven depart: past the wrapper's one in sixteen, so the
    # device programs are driven directly
    view = CT.churn_table(s, n, capacity=32, delta_capacity=32,
                          stale_rows=16, lut_bits=16)
    view, left = CT.churn_apply(view, jnp.asarray(base[[1, 4]]),
                                jnp.asarray(_ids(rng, 3)))
    assert left.tolist() == [2, 0]
    first = np.asarray(view.delta)[:3]
    view, left = CT.churn_apply(view, jnp.asarray(first[[1]]),
                                jnp.asarray(_ids(rng, 1)))
    assert left.tolist() == [0, 1]
    assert (int(view.n_tomb), int(view.n_delta)) == (2, 4)
    joined = np.asarray(view.delta)[:4]
    joined_departed = {i for i in range(4) if (joined[i] == first[1]).all()}
    targets = _ids(rng, 64)
    out = jax.device_get(simulate_lookups(
        view, None, jnp.asarray(targets), seed=9,
        state_limbs=state_limbs, **KW))
    expired = 0
    for q, target in enumerate(targets):
        nodes, hops, converged, gone = scalar_churn_lookup(
            base, 7, target, departed={1, 4}, joined=joined,
            joined_departed=joined_departed, node_base=view.capacity,
            alpha=3, rng=np.random.default_rng(q))
        assert [int(x) for x in out["nodes"][q] if x >= 0] == nodes
        assert (int(out["hops"][q]), bool(out["converged"][q])) \
            == (hops, converged)
        expired += gone
    assert int(out["expired_peers"]) == expired > 0
    # 7 - 2 + 4 - 1 live nodes: every lookup returns all eight of them
    assert (np.sort(out["nodes"], axis=1) == np.sort(out["nodes"][0])).all()


# -- membership ---------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_membership_through_random_schedules(seed):
    """Join-then-leave inside one compaction period, a departure of a
    delta row, an id named twice, an id that is no member, an id that
    leaves and joins again, a compaction triggered by the stale share
    and one by the delta's capacity: the live set is the book's, and
    its checksum the book's, after every tick."""
    rng = np.random.default_rng(seed)
    ids = _ids(rng, 3000)
    tbl, _s, _n = _table(ids, delta_capacity=256, lut_bits=12)
    book = [tuple(r) for r in ids.tolist()]
    why = []
    for t in range(14):
        before = tbl.compactions
        leave_n, join_n = (40, 10) if t < 6 else (5, 60)
        at = rng.choice(len(book), leave_n, replace=False)
        leave = np.array([book[i] for i in at], dtype=np.uint32)
        if t in (2, 9):
            newest = np.array(book[-3:], dtype=np.uint32)
            leave[:3] = newest              # joined last tick: delta rows
        if t == 3:
            leave[5] = leave[4]                           # named twice
            leave[6] = _ids(rng, 1)[0]                    # no member
        tomb, delta = tbl.n_tomb, tbl.n_delta
        left, joined = _tick(tbl, book, rng, leave_n, join_n, leave=leave)
        if t == 4:          # an id that just left joins again next tick
            rejoin = left[:1]
            tbl.apply(jnp.asarray(_ids(rng, 0).reshape(0, 5)),
                      jnp.asarray(rejoin))
            book.append(tuple(rejoin[0].tolist()))
        if tbl.compactions != before:
            why.append("stale" if tomb + leave_n > tbl.stale_rows_max
                       else "delta" if delta + join_n > 256 else "?")
        assert _live_ids(tbl) == set(book) and tbl.n_live == len(book)
        ids_all, live = CT.live_rows(tbl.view)
        assert _checksum(np.asarray(ids_all)[np.asarray(live)]) \
            == _checksum(np.array(book, dtype=np.uint32))
        assert tbl.n_tomb <= tbl.stale_rows_max      # never later
    assert "stale" in why and "delta" in why and "?" not in why, why
    assert tbl.n_delta_gone > 0 or tbl.compactions   # a delta row departed


def test_a_tick_that_cannot_fit_is_refused():
    rng = np.random.default_rng(4)
    tbl, s, _n = _table(_ids(rng, 640), delta_capacity=64, lut_bits=10)
    with pytest.raises(ValueError):         # more than a sixteenth departs
        tbl.apply(s[:41], jnp.asarray(_ids(rng, 4)))
    with pytest.raises(ValueError):         # more than the delta holds
        tbl.apply(s[:4], jnp.asarray(_ids(rng, 65)))
    assert tbl.n_live == 640
    with pytest.raises(ValueError):
        CT.churn_table(s, 640, capacity=650, delta_capacity=64,
                       stale_rows=40, lut_bits=10)


@pytest.mark.parametrize("n", [1, 127, 128, 129, 16385, 40001])
def test_the_running_sum_written_out_equals_cumsum(n):
    """``_running_sum`` tiles by hand (so that its operations keep their
    stage's name through the TPU compiler): one row, a full row, a row
    and one, two levels, three; negative entries as ``net`` has."""
    x = np.random.default_rng(n).integers(-3, 9, size=n).astype(np.int32)
    got = np.asarray(jax.jit(CT._running_sum)(jnp.asarray(x)))
    np.testing.assert_array_equal(got, np.cumsum(x, dtype=np.int32))
    flags = x > 0
    np.testing.assert_array_equal(np.asarray(CT._running_sum(jnp.asarray(flags))),
                                  np.cumsum(flags, dtype=np.int32))


def test_a_tick_and_a_compaction_consume_the_table_they_are_given():
    """The table's buffers are donated: the base passes through a tick
    in place (no 200 MB copy a tick at the cell's size), and nothing of
    the table handed over can be read afterwards."""
    rng = np.random.default_rng(12)
    tbl, s, _n = _table(_ids(rng, 3000), delta_capacity=256, lut_bits=12)
    before = tbl.view
    base_at = before.base.unsafe_buffer_pointer()
    tbl.apply(s[:20], jnp.asarray(_ids(rng, 20)))
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(before))
    assert tbl.view.base.unsafe_buffer_pointer() == base_at
    before = tbl.view
    tbl.compact()
    assert before.base.is_deleted() and before.delta.is_deleted()
    assert tbl.n_live == 3000 and not s.is_deleted()


def test_the_stale_rule_is_the_churn_views():
    assert MAX_STALE_SHARE == 1 / TOMB_FRAC
    for n in (0, 15, 16, 4097, 10_000_000, 11_048_576):
        assert CT.stale_limit(n, MAX_STALE_SHARE) == n // TOMB_FRAC
    assert CT.tomb_words(64) == 2 and CT.tomb_words(65) == 3


def test_table_telemetry_spans_counters_and_the_waves_histogram():
    rng = np.random.default_rng(8)
    reg = telemetry.get_registry()
    tbl, _s, _n = _table(_ids(rng, 2000), delta_capacity=128, lut_bits=11)
    book = [tuple(r) for r in np.asarray(tbl.view.base)[:2000].tolist()]
    before = reg.snapshot()
    for _ in range(5):
        _tick(tbl, book, rng, 30, 30)
    out = simulate_lookups(tbl.view, None, jnp.asarray(_ids(rng, 64)),
                           seed=1, state_limbs=2, **KW)
    moved = telemetry.snapshot_diff(before, reg.snapshot())
    assert tbl.compactions == 1
    assert moved["counters"]["dht_table_compactions_total"] == 1
    assert moved["counters"]["dht_table_rows_departed_total"] == 150
    assert moved["counters"]["dht_table_rows_joined_total"] == 150
    hists = moved["histograms"]
    assert hists["dht_table_apply_seconds"]["count"] == 5
    assert hists["dht_table_compact_seconds"]["count"] == 1
    expired = hists['dht_search_expired_peers{mode="single"}']
    assert expired["count"] == 1
    assert expired["sum"] == int(out["expired_peers"])
    gauges = reg.snapshot()["gauges"]
    assert gauges["dht_churn_tombstones"] == tbl.n_tomb
    assert gauges["dht_churn_delta_rows"] == tbl.n_delta


def test_lane_tiles_sum_a_rounds_expired_peers(churned, small_tiles):
    """Under churn a round in LANE TILES marks and counts the same
    expired requests as the round at once (the tiles' counts add up)."""
    tbl, _, _, targets, ref = churned
    small_tiles(128)
    out = jax.device_get(simulate_lookups(
        tbl.view, None, jnp.asarray(targets), seed=3, state_limbs=2, **KW))
    assert int(out["tiled_rounds"]) > 0 and "tiled_rounds" not in ref
    for key in ref:
        np.testing.assert_array_equal(out[key], ref[key], err_msg=key)
    assert int(out["expired_peers"]) > 0


def test_record_wave_on_a_frozen_table_has_no_expired_series():
    rng = np.random.default_rng(9)
    s, _, n = sort_table(jnp.asarray(_ids(rng, 512)))
    reg = telemetry.get_registry()
    before = reg.snapshot()
    simulate_lookups(s, n, jnp.asarray(_ids(rng, 16)), seed=1, **KW)
    moved = telemetry.snapshot_diff(before, reg.snapshot())["histograms"]
    assert not [k for k in moved if k.startswith("dht_search_expired_peers")]
    assert S.DELTA_WINDOW == CT.DELTA_WINDOW == 8
