"""The mutable table row-sharded over a mesh (ISSUE 34:
``parallel/churn.py ShardedChurnTable``, the churn primitives of
``parallel/sharded.py build_tp_lookup``), held to its two references:
the one-chip program driven by the same calls
(``core.table.DeviceChurnTable`` + ``simulate_lookups``), and the
re-sort (``sharded_global_sort`` of the live ids).  On the forced host
devices, ``make_mesh(4, q=1, t=4)`` and one ``t=2`` case.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from opendht_tpu import telemetry
from opendht_tpu.core.search import simulate_lookups
from opendht_tpu.core.table import DeviceChurnTable
from opendht_tpu.ops import churn_table as CT
from opendht_tpu.ops.sorted_table import sort_table
from opendht_tpu.parallel import (make_mesh, shard_table_state,
                                  sharded_global_sort, tp_simulate_lookups)
from opendht_tpu.parallel import churn as PC
from opendht_tpu.parallel.global_sort import dest_shard

KW = dict(k=8, alpha=3, search_nodes=14, state_limbs=2)
N = 16384


def _ids(rng, n):
    return rng.integers(0, 2 ** 32, size=(n, 5), dtype=np.uint32)


def _pair(rng, t, *, delta_capacity=1024, block_bits=None):
    """The same ids as a table sharded over ``t`` chips and on one."""
    ids = _ids(rng, N)
    mesh = make_mesh(t, q=1, t=t)
    state = sharded_global_sort(mesh, ids, block_bits=block_bits)
    tb = PC.ShardedChurnTable(mesh, state, delta_capacity=delta_capacity)
    s, _, n = sort_table(jnp.asarray(ids))
    one = DeviceChurnTable(s, n, delta_capacity=4 * delta_capacity)
    return mesh, tb, one, [tuple(r) for r in ids.tolist()]


def _shards(tb):
    """Each shard's own ``ChurnTable``, on the host."""
    n_t = tb.mesh.shape["t"]
    for i in range(n_t):
        yield CT.ChurnTable(**{
            name: (np.asarray(leaf)[i] if name in PC._PER_SHARD else
                   np.asarray(leaf).reshape(n_t, -1, *leaf.shape[1:])[i])
            for name, leaf in tb.table._asdict().items()})


def _live_ids(tb) -> set:
    out = set()
    for i, tbl in enumerate(_shards(tb)):
        ids, live = (np.asarray(x) for x in CT.live_rows(
            jax.tree.map(jnp.asarray, tbl)))
        mine = ids[live]
        # placement: a live id lies on the shard of its key range
        assert (dest_shard(mine[:, 0], tb.mesh.shape["t"]) == i).all()
        out |= {tuple(r) for r in mine.tolist()}
    return out


def _tick(tables, book, rng, leave_n, join_n, *, leave=None, join=None):
    if leave is None:
        at = rng.choice(len(book), leave_n, replace=False)
        leave = np.array([book[i] for i in at], dtype=np.uint32)
    if join is None:
        join = _ids(rng, join_n)
    gone = {tuple(r) for r in leave.reshape(-1, 5).tolist()}
    book[:] = [b for b in book if b not in gone] \
        + [tuple(r) for r in join.tolist()]
    for tbl in tables:
        tbl.apply(jnp.asarray(leave.reshape(-1, 5)), jnp.asarray(join))
    return leave, join


def _same_lookups(mesh, tb, one, targets, seed):
    """``tp_simulate_lookups`` over the sharded table against
    ``simulate_lookups`` over the one-chip table, lookup for lookup.  A
    delta node is ``capacity + slot`` on one chip and ``t·capacity +
    slot`` sharded, the slot the place in the one sorted order of the
    joined ids either way.  ``expired_peers`` is the one-chip count:
    each shard counts the requests of ITS chunk of the wave, and the
    program sums them over ``t`` (PR 38).  ``narrow_rounds`` is not
    compared: the width that cuts is a shard's chunk."""
    got = tp_simulate_lookups(mesh, targets=targets, state=tb.view,
                              seed=seed, **KW)
    want = simulate_lookups(one.view, None, targets, seed=seed, **KW)
    for key in ("hops", "converged", "dist"):
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(want[key]), err_msg=key)
    assert np.asarray(got["expired_peers"]).shape == (mesh.shape["q"],)
    assert int(np.sum(got["expired_peers"])) == int(want["expired_peers"])
    total = mesh.shape["t"] * tb.view.shard_n
    nodes = np.asarray(got["nodes"])
    np.testing.assert_array_equal(
        np.where(nodes >= total, nodes - total + one.view.capacity, nodes),
        want["nodes"])
    return got


# -- the one-chip program is the reference --------------------------------

@pytest.mark.parametrize("t", [4, 2])
def test_equals_the_one_chip_table_lookup_for_lookup(t):
    rng = np.random.default_rng(34 + t)
    mesh, tb, one, book = _pair(rng, t)
    # a chunk of 4,096 lanes a shard: wide enough to cut (PR 29, PR 38)
    targets = jnp.asarray(_ids(rng, t * 4096))
    got = _same_lookups(mesh, tb, one, targets, 1)  # nobody left or joined
    assert int(got["narrow_rounds"][0]) >= 1
    expired = delta_nodes = 0
    for tick in range(5):
        _tick((tb, one), book, rng, 60, 60)
        got = _same_lookups(mesh, tb, one, targets, 2 + tick)
        expired += int(np.sum(got["expired_peers"]))
        delta_nodes += int((np.asarray(got["nodes"])
                            >= t * tb.view.shard_n).sum())
        if tick == 2:
            tb.compact(), one.compact()
            _same_lookups(mesh, tb, one, targets, 9)
    assert tb.compactions == one.compactions == 1
    assert expired and delta_nodes      # the churn model did something
    assert _live_ids(tb) == set(book) and tb.n_live == one.n_live == N


@pytest.mark.parametrize("wave", ["uniform", "one_home", "shard_edge"])
def test_equals_the_one_chip_table_over_lane_windows(wave):
    """PR 35: a wave wide enough that a shard gathers over a lane window
    (2,048 lanes at t = 4: windows of 640), over a table that has
    ticked: lookup for lookup the one-chip table's answers, in the
    caller's order — for uniform targets, for targets that all lie in
    ONE shard's key range (that shard takes t passes a round, so no
    round counts for ``window_rounds``) and for targets within 12 live ids of a key
    range's edge (fallback windows that straddle it).  ``alive`` and
    ``delta_window`` keep their full-width owner reads, of the
    all-gathered wave."""
    from opendht_tpu.parallel.sharded import window_width
    rng = np.random.default_rng(35)
    mesh, tb, one, book = _pair(rng, 4)
    for _ in range(3):
        _tick((tb, one), book, rng, 60, 60)
    Q = 2048
    assert window_width(Q, 4) == 640
    targets = _ids(rng, Q)
    if wave == "one_home":
        targets[:, 0] = (targets[:, 0] >> 2) | np.uint32(1 << 30)
    elif wave == "shard_edge":
        live = np.array(sorted(book), dtype=np.uint32)
        edges = np.searchsorted(live[:, 0], [1 << 30, 2 << 30, 3 << 30])
        targets[:, :3] = live[rng.choice(edges, Q)
                              + rng.integers(-12, 13, Q), :3]
    got = _same_lookups(mesh, tb, one, jnp.asarray(targets), 7)
    assert np.asarray(got["converged"]).all()
    rounds = np.asarray(got["window_rounds"])
    assert rounds.shape == (1,) and 0 <= rounds[0] <= np.max(got["hops"])
    if wave == "one_home":
        # (only a last round, whose few lookups still live — expired
        # peers keep stragglers going — span less than a window)
        assert rounds[0] <= 1 < np.max(got["hops"]) - 2


@pytest.mark.parametrize("lanes, chunked", [(256, True), (258, False)],
                         ids=["a_chunk_a_shard", "t_does_not_divide"])
def test_expired_peers_is_the_one_chip_count(lanes, chunked):
    """PR 38: each shard runs ``expire`` for ITS chunk of the wave and
    counts the requests that found their peer gone; the program sums
    the shards' counts once a wave, so the wave's ``expired_peers`` is
    the one-chip engine's — also where ``t`` does not divide the wave
    and every shard runs all of it (counted once, not ``t`` times)."""
    from opendht_tpu.parallel.sharded import lane_chunk
    rng = np.random.default_rng(38)
    mesh, tb, one, book = _pair(rng, 4)
    assert (lane_chunk(lanes, 4) < lanes) == chunked
    for _ in range(5):
        _tick((tb, one), book, rng, 60, 60)
    assert tb.compactions == one.compactions == 0
    got = _same_lookups(mesh, tb, one, jnp.asarray(_ids(rng, lanes)), 3)
    assert int(got["expired_peers"][0]) > 0
    assert np.asarray(got["converged"]).all()


def test_one_chip_churn_program_is_the_parents():
    """PR 35: the one-chip engine under churn lowers to the program it
    was before the gather closure could report its passes (sha-256 of
    the lowered text at a toy shape, taken on the parent commit;
    abstract operands: nothing runs)."""
    import functools
    import hashlib
    from opendht_tpu.core.search import _simulate_lookups_jit
    from opendht_tpu.core.table import (MAX_STALE_SHARE, stale_limit,
                                        tomb_words)
    from opendht_tpu.ops.sorted_table import default_lut_bits
    A = jax.ShapeDtypeStruct
    u32, i32 = jnp.uint32, jnp.int32
    capacity = 32 * tomb_words(N + 1024)
    view = jax.eval_shape(functools.partial(
        CT.churn_table, capacity=capacity, delta_capacity=1024,
        stale_rows=stale_limit(capacity, MAX_STALE_SHARE),
        lut_bits=default_lut_bits(N)), A((N, 5), u32), A((), i32))
    text = _simulate_lookups_jit.lower(view, None, A((4096, 5), u32),
                                       seed=A((), i32), **KW).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "5336a9979e6a46efb34b1093a5cca245fee48084eeb7377de6ec89172125b25c")


@pytest.mark.parametrize("block_bits", [None, 18])
def test_after_a_compaction_equals_a_fresh_build_of_the_live_ids(block_bits):
    """Bit for bit; with a block LUT wider than the shards' own
    (``block_bits`` 18 over 16-bit shard LUTs) the relayout rebuilds it
    from the rows instead of summing the shards' LUTs."""
    rng = np.random.default_rng(5)
    mesh, tb, _one, book = _pair(rng, 4, block_bits=block_bits)
    for _ in range(3):
        _tick((tb,), book, rng, 50, 70)
    tb.compact()
    fresh = sharded_global_sort(
        mesh, np.array(book, dtype=np.uint32), block_bits=tb.view.block_bits)
    targets = jnp.asarray(_ids(rng, 512))
    got = tp_simulate_lookups(mesh, targets=targets, state=tb.view, seed=3,
                              **KW)
    want = tp_simulate_lookups(mesh, targets=targets, state=fresh, seed=3,
                               **KW)
    for key in ("nodes", "dist", "hops", "converged", "narrow_rounds"):
        np.testing.assert_array_equal(got[key], want[key])
    assert int(np.sum(got["expired_peers"])) == 0
    for key in ("shard_rows", "block_lut", "n_valid"):
        np.testing.assert_array_equal(tb.view.arrays[key], fresh.arrays[key])
    assert len(book) == N + 60 == tb.n_live == tb.n_base


@pytest.mark.parametrize("chunked", [False, True],
                         ids=["whole_wave", "chunked"])
def test_a_window_that_straddles_a_shard_edge_takes_rows_of_both_shards(
        chunked):
    """The two churn primitives themselves, against the one-chip ones:
    the window of a target at the edge between shards 0 and 1 is the
    last four rows of shard 0's delta and the first four of shard 1's;
    liveness is read on the owner, base row or delta row.  With every
    rank holding the whole wave (the exchange is one ``psum``) and with
    each holding a quarter of it (``lane_exchange``: the shard that
    asks is not the shard that owns)."""
    from jax.sharding import PartitionSpec as P
    from opendht_tpu.core.search import _churn_primitives
    from opendht_tpu.parallel.sharded import _tp_churn_primitives
    rng = np.random.default_rng(6)
    mesh, tb, one, book = _pair(rng, 4)
    edge = 1 << 30                       # where shard 0 ends and 1 begins
    join = _ids(rng, 8)
    join[:, 0] = [edge - 4 + i for i in range(8)]
    join[4, 1] = 0xFFFFFFFF              # above every target below
    _tick((tb, one), book, rng, 40, 8, join=join)
    _tick((tb, one), book, rng, 0, 30, leave=join[2:6])   # two a side leave
    assert np.asarray(tb.table.n_delta)[:2].min() >= 4
    targets = _ids(rng, 64)
    targets[:32, 0], targets[:32, 1] = edge, targets[:32, 1] >> 1
    total, c_one = 4 * tb.view.shard_n, one.view.capacity
    want_node, want_ids = _churn_primitives(one.view)["delta_window"](
        jnp.asarray(targets))
    want_node = np.asarray(want_node)
    probe = np.concatenate([rng.integers(0, N, 500), want_node.reshape(-1)])
    probe = probe[probe >= 0]
    probe = probe[:probe.size // 4 * 4].astype(np.int32).reshape(1, -1)
    want_alive = _churn_primitives(one.view)["alive"](jnp.asarray(probe))

    def local(shard_rows, tomb_bits, delta, n_delta, delta_lut, targets,
              nodes):
        prim = _tp_churn_primitives(
            tb.view.shard_n, 1024, 4, chunked, shard_rows[0, 0],
            shard_rows[0, 1], tomb_bits, delta, n_delta[0], delta_lut[0])
        node, ids = prim["delta_window"](targets)
        return node, jnp.stack(ids), prim["alive"](nodes)

    a = tb.view.arrays
    lanes = "t" if chunked else None     # who holds which of the lookups
    node, ids, alive = jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("t", None), P("t"), P("t", None), P("t"), P("t", None),
                  P(lanes, None), P(None, lanes)),
        out_specs=(P(lanes, None), P(None, lanes, None), P(None, lanes)),
        check_vma=False))(
        a["shard_rows"], a["tomb_bits"], a["delta"], a["n_delta"],
        a["delta_lut"], jnp.asarray(targets),
        jnp.asarray(np.where(probe >= c_one, probe - c_one + total, probe)))
    node = np.asarray(node)
    np.testing.assert_array_equal(
        np.where(node >= 0, node - total + c_one, -1), want_node)
    there = want_node >= 0
    for limb in range(5):
        np.testing.assert_array_equal(np.asarray(ids[limb])[there],
                                      np.asarray(want_ids[limb])[there])
    np.testing.assert_array_equal(alive, want_alive)
    assert not np.asarray(alive).all() and np.asarray(alive).any()
    # the edge targets' windows: the eight ids next to the edge, in order
    got = np.stack([np.asarray(ids[limb])[0] for limb in range(5)], axis=-1)
    np.testing.assert_array_equal(got, join)
    if chunked:
        _same_lookups(mesh, tb, one, jnp.asarray(targets), 4)


# -- membership (the cases of tests/test_churn_sim.py, over shards) ---------

def _one_shards_ids(rng, n):
    ids = _ids(rng, n)
    ids[:, 0] >>= 2                      # all in shard 0's key range of 4
    return ids


@pytest.mark.parametrize("case", [
    "joins and leaves before a compaction", "no member", "twice in a batch",
    "leaves and joins again", "arrivals all on one shard"])
def test_membership(case):
    rng = np.random.default_rng(7)
    mesh, tb, _one, book = _pair(rng, 4)
    if case == "joins and leaves before a compaction":
        _leave, join = _tick((tb,), book, rng, 40, 40)
        _tick((tb,), book, rng, 0, 40, leave=join[:10])
        assert tb.n_delta_gone == 10 and tb.compactions == 0
    elif case == "no member":
        _tick((tb,), book, rng, 0, 40, leave=_ids(rng, 12))
        assert tb.n_tomb == 0
    elif case == "twice in a batch":
        leave = np.array([book[3], book[900], book[3]], dtype=np.uint32)
        _tick((tb,), book, rng, 0, 3, leave=leave)
        assert tb.n_tomb == 2
    elif case == "leaves and joins again":
        leave, _join = _tick((tb,), book, rng, 30, 30)
        _tick((tb,), book, rng, 0, 0, leave=leave[:0], join=leave[:5])
    else:
        # 2,048 arrivals, all of shard 0's key range, at a routed width of
        # 896: the first pass does nothing, the batch goes in in three,
        # the shard's slab (1,024) makes ALL compact between them
        assert PC.routed_rows(2048, 4) == 896
        _tick((tb,), book, rng, 8, 0, join=_one_shards_ids(rng, 2048))
        assert tb.compactions == 2
        assert tb._n_base[0] + tb._n_delta[0] > 4096 + 2000
        assert np.asarray(tb.view.arrays["shard_rows"])[:, 0].tolist() \
            == np.concatenate([[0], np.cumsum(tb._n_base)[:-1]]).tolist()
    assert _live_ids(tb) == set(book) and tb.n_live == len(book)
    tb.compact()
    assert _live_ids(tb) == set(book) and tb.n_base == len(book)


def test_past_a_shards_capacity_is_a_value_error_and_drops_nothing():
    rng = np.random.default_rng(8)
    mesh, tb, _one, book = _pair(rng, 4)
    _tick((tb,), book, rng, 0, 0, join=_one_shards_ids(rng, 2048))
    before = set(book)
    join = _one_shards_ids(rng, 2048)    # shard 0 holds 6,656 rows at most
    with pytest.raises(ValueError, match="capacity"):
        tb.apply(jnp.asarray(_ids(rng, 0)), jnp.asarray(join))
    live = _live_ids(tb)
    assert before <= live <= before | {tuple(r) for r in join.tolist()}
    assert tb.n_live == len(live)
    with pytest.raises(ValueError):      # wider than a shard's slab
        tb.apply(jnp.asarray(_ids(rng, 0)), jnp.asarray(_ids(rng, 5000)))


def test_a_table_not_cut_by_key_range_is_refused():
    rng = np.random.default_rng(9)
    mesh = make_mesh(4, q=1, t=4)
    s, _, n = sort_table(jnp.asarray(_ids(rng, 4096)))
    with pytest.raises(ValueError, match="sharded_global_sort"):
        PC.ShardedChurnTable(mesh, shard_table_state(mesh, s, n),
                             delta_capacity=256)
    skewed = shard_table_state(mesh, s, n, boundaries=[100, 200, 300])
    with pytest.raises(ValueError, match="key range"):
        PC.ShardedChurnTable(mesh, skewed, delta_capacity=256)


# -- no executable is rebuilt; buffers; series ------------------------------

def test_later_ticks_compactions_and_waves_build_no_executable():
    from dhtbench.run import CompileLog
    rng = np.random.default_rng(10)
    mesh, tb, _one, book = _pair(rng, 4)
    targets = jnp.asarray(_ids(rng, 256))
    _tick((tb,), book, rng, 50, 50)
    tb.compact()
    jax.block_until_ready(tp_simulate_lookups(
        mesh, targets=targets, state=tb.view, seed=1, **KW))
    log = CompileLog()
    rows = np.asarray(tb.view.arrays["shard_rows"]).copy()
    for seed in (2, 3):                 # second tick, compaction and wave
        _tick((tb,), book, rng, 50, 50)
        tb.compact()
        jax.block_until_ready(tp_simulate_lookups(
            mesh, targets=targets, state=tb.view, seed=seed, **KW))
    assert (np.asarray(tb.view.arrays["shard_rows"]) != rows).any()
    assert log.since()["executables"] == 0, log.since()


def test_a_tick_and_a_compaction_consume_the_table_they_are_given():
    rng = np.random.default_rng(11)
    _mesh, tb, _one, book = _pair(rng, 4)
    before = tb.table
    _tick((tb,), book, rng, 20, 20)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(before))
    before = tb.table
    tb.compact()
    assert before.base.is_deleted() and before.delta.is_deleted()
    assert tb.n_live == len(book)


def test_the_spans_counters_and_gauges_are_the_one_chip_tables():
    rng = np.random.default_rng(12)
    reg = telemetry.get_registry()
    mesh, tb, _one, book = _pair(rng, 4, delta_capacity=64)
    before = reg.snapshot()
    for _ in range(8):
        _tick((tb,), book, rng, 30, 30)
    out = tp_simulate_lookups(mesh, targets=jnp.asarray(_ids(rng, 64)),
                              state=tb.view, seed=1, **KW)
    moved = telemetry.snapshot_diff(before, reg.snapshot())
    assert tb.compactions >= 1
    assert moved["counters"]["dht_table_compactions_total"] == tb.compactions
    assert moved["counters"]["dht_table_rows_departed_total"] == 240
    assert moved["counters"]["dht_table_rows_joined_total"] == 240
    hists = moved["histograms"]
    assert hists["dht_table_apply_seconds"]["count"] == 8
    assert hists["dht_table_compact_seconds"]["count"] == tb.compactions
    expired = hists['dht_search_expired_peers{mode="tp"}']
    assert expired["count"] == 1
    assert expired["sum"] == int(np.sum(out["expired_peers"]))
    gauges = reg.snapshot()["gauges"]
    assert gauges["dht_churn_tombstones"] == tb.n_tomb
    assert gauges["dht_churn_delta_rows"] == tb.n_delta
    assert gauges["dht_churn_delta_rows_max"] == int(tb._n_delta.max())
    # the rule is per shard: the fullest slab decides
    assert (tb._n_delta <= 64).all()


def test_the_new_stages_are_named_where_they_run():
    import inspect
    from opendht_tpu.parallel import sharded
    for name in ("table_route", "table_apply", "table_compact",
                 "table_relayout"):
        assert f'device_stage("{name}")' in inspect.getsource(PC)
    for name in ("alive_merge", "delta_merge"):      # lane_exchange's stage
        assert f'"{name}"' in inspect.getsource(sharded._tp_churn_primitives)
    rng = np.random.default_rng(13)
    mesh, tb, _one, _book = _pair(rng, 4)
    a = tb.view.arrays
    from opendht_tpu.parallel.sharded import build_tp_lookup
    lowered = build_tp_lookup(mesh, tb.view.shard_n, 64, 8, 3, 14, 48, 2,
                              True, 1024).lower(
        a["sorted_ids"], a["local_lut"], a["block_lut"], a["n_valid"],
        a["shard_rows"], jnp.asarray(_ids(rng, 64)), jnp.int32(1),
        a["tomb_bits"], a["delta"], a["n_delta"], a["delta_lut"]).as_text()
    for name in ("alive_merge", "delta_merge", "expire", "delta_window",
                 "owner_merge"):
        assert f"stage_{name}" in lowered
