"""Batched iterative lookup engine tests: convergence, exactness of the
found set, determinism, and hop-count parity with the scalar reference
port (model of the reference's searchStep loop, src/dht.cpp:561-654)."""

import functools

import numpy as np
import pytest

import jax.numpy as jnp

from opendht_tpu.ops import ids as K
from opendht_tpu.ops.sorted_table import sort_table
from opendht_tpu.ops.xor_topk import xor_topk
from opendht_tpu.core.search import simulate_lookups, scalar_lookup


def _network(n, seed):
    rng = np.random.default_rng(seed)
    raw = rng.integers(0, 256, (n, 20), dtype=np.uint8)
    ids = jnp.asarray(K.ids_from_bytes(raw))
    sorted_ids, _, n_valid = sort_table(ids)
    return sorted_ids, n_valid


def test_lookups_converge_and_find_closest():
    sorted_ids, n = _network(4000, 0)
    rng = np.random.default_rng(1)
    q_raw = rng.integers(0, 256, (64, 20), dtype=np.uint8)
    targets = jnp.asarray(K.ids_from_bytes(q_raw))
    out = simulate_lookups(sorted_ids, n, targets, seed=7)
    conv = np.asarray(out["converged"])
    hops = np.asarray(out["hops"])
    nodes = np.asarray(out["nodes"])
    assert conv.all()
    assert (hops >= 1).all() and (hops <= 30).all()

    # the found set must match the true global top-8 closely
    true_dist, true_idx = xor_topk(targets, sorted_ids, k=8)
    true_idx = np.asarray(true_idx)
    recall = np.mean([
        len(set(nodes[i]) & set(true_idx[i])) / 8 for i in range(64)
    ])
    assert recall >= 0.95, recall


def test_lookup_deterministic():
    sorted_ids, n = _network(1000, 2)
    rng = np.random.default_rng(3)
    targets = jnp.asarray(K.ids_from_bytes(
        rng.integers(0, 256, (16, 20), dtype=np.uint8)))
    a = simulate_lookups(sorted_ids, n, targets, seed=42)
    b = simulate_lookups(sorted_ids, n, targets, seed=42)
    np.testing.assert_array_equal(np.asarray(a["nodes"]), np.asarray(b["nodes"]))
    np.testing.assert_array_equal(np.asarray(a["hops"]), np.asarray(b["hops"]))
    c = simulate_lookups(sorted_ids, n, targets, seed=43)
    assert not np.array_equal(np.asarray(a["hops"]), np.asarray(c["hops"]))


def test_tiny_network():
    sorted_ids, n = _network(5, 4)
    rng = np.random.default_rng(5)
    targets = jnp.asarray(K.ids_from_bytes(
        rng.integers(0, 256, (8, 20), dtype=np.uint8)))
    out = simulate_lookups(sorted_ids, n, targets, seed=1)
    nodes = np.asarray(out["nodes"])
    # every real node should be found; padding is -1
    for row in nodes:
        assert set(row[row >= 0]) == {0, 1, 2, 3, 4}


def test_hop_parity_with_scalar_reference():
    sorted_ids, n = _network(5000, 6)
    ids_np = np.asarray(sorted_ids)
    n_int = int(n)
    rng = np.random.default_rng(7)
    q_raw = rng.integers(0, 256, (48, 20), dtype=np.uint8)
    targets = jnp.asarray(K.ids_from_bytes(q_raw))

    out = simulate_lookups(sorted_ids, n, targets, seed=8)
    hops_batched = np.asarray(out["hops"])

    hops_scalar = []
    for i in range(48):
        _, h, conv = scalar_lookup(ids_np, n_int, np.asarray(targets[i]),
                                   rng=np.random.default_rng(100 + i))
        assert conv
        hops_scalar.append(h)
    hops_scalar = np.array(hops_scalar)

    # same convergence law → medians within 2 rounds of each other
    assert abs(np.median(hops_batched) - np.median(hops_scalar)) <= 2, (
        np.median(hops_batched), np.median(hops_scalar))


@pytest.mark.slow
def test_scaling_hops_grow_logarithmically():
    m1 = []
    for nsize, seed in ((500, 8), (8000, 9)):
        sorted_ids, n = _network(nsize, seed)
        rng = np.random.default_rng(seed)
        targets = jnp.asarray(K.ids_from_bytes(
            rng.integers(0, 256, (32, 20), dtype=np.uint8)))
        out = simulate_lookups(sorted_ids, n, targets, seed=seed)
        assert np.asarray(out["converged"]).all()
        m1.append(np.median(np.asarray(out["hops"])))
    # bigger network needs ≥ as many hops, but only logarithmically more
    assert m1[1] >= m1[0]
    assert m1[1] - m1[0] <= 6


@pytest.mark.slow
def test_state_limbs_2_bitwise_identical():
    """state_limbs=2 (5-operand merge sorts ranking on the top 64
    distance bits) must be bitwise identical to the exact engine on
    random ids — distinct 160-bit ids tie on 64 bits with probability
    ~2^-58 per merge, so any divergence here is a bug, not a tie."""
    import jax
    import jax.numpy as jnp
    from opendht_tpu.ops.sorted_table import sort_table
    from opendht_tpu.core.search import simulate_lookups

    k1, k2 = jax.random.split(jax.random.PRNGKey(17))
    table = jax.random.bits(k1, (4096, 5), dtype=jnp.uint32)
    targets = jax.random.bits(k2, (128, 5), dtype=jnp.uint32)
    sorted_ids, _, n = sort_table(table)
    a = simulate_lookups(sorted_ids, n, targets, seed=9)
    b = simulate_lookups(sorted_ids, n, targets, seed=9, state_limbs=2)
    for key in ("nodes", "hops", "converged", "dist"):
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]))


def test_guarded_lower_bound_exact_incl_tie64_tables():
    """_guarded_lower_bound's three tiers (64-bit search + one-compare
    correction / full-limb LUT search / full-depth search) must all be
    EXACT vs the reference full-width binary search — on random ids, on
    tables with adjacent top-64 duplicates (the tie64 guard's reason to
    exist), and on heavily clustered ids (LUT-bucket overflow)."""
    import jax
    import jax.numpy as jnp
    from opendht_tpu.ops.sorted_table import (sort_table, build_prefix_lut,
                                              _lower_bound)
    from opendht_tpu.core.search import _guarded_lower_bound

    rng = np.random.default_rng(64)

    def check(ids_np, probes_np, label):
        sorted_ids, _, n = sort_table(jnp.asarray(ids_np))
        lut = build_prefix_lut(sorted_ids, n)
        lower = _guarded_lower_bound(sorted_ids, n, lut)
        got = np.asarray(lower(jnp.asarray(probes_np)))
        want = np.asarray(_lower_bound(sorted_ids, jnp.asarray(probes_np),
                                       n))
        np.testing.assert_array_equal(got, want, err_msg=label)

    base = rng.integers(0, 2**32, size=(2048, 5), dtype=np.uint32)
    # probes: random + exact row hits + rows +/- 1 in the last limb
    probes = rng.integers(0, 2**32, size=(256, 5), dtype=np.uint32)
    probes[:64] = base[rng.integers(0, 2048, 64)]
    probes[64:96] = base[rng.integers(0, 2048, 32)]
    probes[64:96, 4] += 1
    probes[96:128] = base[rng.integers(0, 2048, 32)]
    probes[96:128, 4] -= 1
    check(base, probes, "random")

    dup = base.copy()
    dup[100:140, :2] = dup[100, :2]       # 40 rows share top 64 bits
    check(dup, probes, "tie64")
    dup2 = base.copy()
    dup2[:300] = dup2[0]                  # full duplicate ids
    check(dup2, probes, "full-dup")

    clus = base.copy()
    clus[:1800, 0] = 0x7777AAAA           # LUT bucket overflow
    p2 = probes.copy()
    p2[:128, 0] = 0x7777AAAA
    check(clus, p2, "clustered")


# -- SURVIVOR COMPACTION (core/search.py _lookup_engine): a wave of
# NARROW_MIN_WAVE lookups or more packs its survivors and runs its last
# rounds narrow.  The reference is the same wave cut into sub-waves, each
# with its lookups' global q_index / q_total — ``tp_simulate_lookups`` on
# a q=4 or q=8, t=1 mesh: a lookup's trajectory does not depend on the
# wave it rides in.  Four sub-waves of a NARROW_MIN_WAVE-wide wave are
# UNDER the threshold and run one loop each (narrow_rounds 0).

OUTPUTS = ("nodes", "dist", "hops", "converged")


@pytest.fixture(scope="module")
def cut_network():
    """3,000 ids and 8 × NARROW_MIN_WAVE targets: with α=2, k=8 the
    live share falls 39% → 0.7% → 0.02% → 0.006% → 0 over loop rounds
    5–9, so a wave cuts after round 6."""
    import jax
    from opendht_tpu.core.search import NARROW_MIN_WAVE
    k1, k2 = jax.random.split(jax.random.PRNGKey(3000))
    sorted_ids, _, n = sort_table(jax.random.bits(k1, (3000, 5),
                                                  dtype=jnp.uint32))
    return sorted_ids, n, jax.random.bits(k2, (8 * NARROW_MIN_WAVE, 5),
                                          dtype=jnp.uint32)


def _in_sub_waves(sorted_ids, n, targets, q=4, **kw):
    from opendht_tpu.parallel import make_mesh, tp_simulate_lookups
    return tp_simulate_lookups(make_mesh(q, q=q, t=1), np.asarray(sorted_ids),
                               n, np.asarray(targets), **kw)


def _record_live_count_widths(monkeypatch):
    """Hand ``_lookup_engine`` the ``live_count`` hook it would default to
    (``jnp.sum(~done)``) and return the list of widths it is asked at, one
    entry a loop, filled as the engine is traced."""
    from opendht_tpu.core import search as S
    engine, asked = S._lookup_engine, []

    def hooked(*args, **kwargs):
        def live_count(done):
            asked.append(done.shape[0])
            return jnp.sum(~done)
        return engine(*args, live_count=live_count, **kwargs)

    monkeypatch.setattr(S, "_lookup_engine", hooked)
    return asked


def _assert_same_outputs(out, ref):
    for key in OUTPUTS:
        np.testing.assert_array_equal(np.asarray(out[key]),
                                      np.asarray(ref[key]), err_msg=key)


@pytest.mark.parametrize("state_limbs", [2, 5])
def test_wide_wave_cuts_and_equals_its_sub_waves(cut_network, state_limbs):
    """A wave wide enough to cut equals, bit for bit, the same targets
    run as sub-waves under the width threshold — in the exact mode too,
    whose ``dist`` is the carried planes and is written back with the
    nodes — and says how many rounds it ran narrow."""
    from opendht_tpu.core.search import NARROW_MIN_WAVE
    sorted_ids, n, targets = cut_network
    targets = targets[:NARROW_MIN_WAVE]
    kw = dict(seed=11, alpha=2, state_limbs=state_limbs)
    out = simulate_lookups(sorted_ids, n, targets, **kw)
    assert int(out["narrow_rounds"]) == 1
    assert np.asarray(out["converged"]).all()
    assert np.asarray(out["hops"]).max() == 7
    ref = _in_sub_waves(sorted_ids, n, targets, **kw)
    assert not np.asarray(ref["narrow_rounds"]).any()   # one loop each
    _assert_same_outputs(out, ref)


@pytest.mark.parametrize("width, max_hops, narrow, unconverged", [
    # the budget ends AT the cut: survivors are packed, no round is left
    pytest.param(4096, 6, 0, 28, id="max_hops_at_the_cut"),
    # ... and before the survivors fit: every lookup is live, an eighth
    # of them is packed, nothing runs
    pytest.param(4096, 2, 0, 4096, id="max_hops_before_anyone_fits"),
    # a wave under the threshold keeps one loop
    pytest.param(2048, 48, 0, 0, id="under_the_threshold"),
    # a wave that steps down twice: its 229 survivors fit the second
    # step (512 lanes) as soon as they are packed into the first (4,096),
    # which runs no round
    pytest.param(32768, 48, 3, 0, id="two_steps_down"),
    # ... and the budget ends inside the narrowest loop
    pytest.param(32768, 8, 2, 2, id="max_hops_in_a_narrow_loop"),
])
def test_cut_edge_cases(cut_network, width, max_hops, narrow, unconverged):
    sorted_ids, n, targets = cut_network
    targets = targets[:width]
    kw = dict(seed=11, alpha=2, state_limbs=2, max_hops=max_hops)
    out = simulate_lookups(sorted_ids, n, targets, **kw)
    assert int(out["narrow_rounds"]) == narrow
    assert int((~np.asarray(out["converged"])).sum()) == unconverged
    assert np.asarray(out["hops"]).max() <= max_hops
    # sub-waves of 1,024 or less run one loop; those of the 32,768-wide
    # wave are 4,096 wide and cut once, as the case above proves they may
    _assert_same_outputs(out, _in_sub_waves(
        sorted_ids, n, targets, q=8 if width == 32768 else 4, **kw))


# -- the north star's width (PR 39, dhtbench/configs/northstar-10m.json):
# a wave of 2^20 lookups steps down THREE times (1,048,576 -> 131,072 ->
# 16,384 -> 2,048 lanes).  Here the same cascade at a width the CPU runs
# in half a minute: 64 x NARROW_MIN_WAVE lookups, 262,144 -> 32,768 ->
# 4,096 -> 512.

WIDE_KW = dict(seed=11, alpha=2, state_limbs=2)


@pytest.fixture(scope="module")
def wide_wave(cut_network):
    """``cut_network``'s 3,000 ids under 64 x NARROW_MIN_WAVE targets of
    its own (the fixture holds 8 x), run ONCE through the public entry."""
    import jax
    from opendht_tpu.core.search import NARROW_MIN_WAVE
    sorted_ids, n, _ = cut_network
    targets = jax.random.bits(jax.random.fold_in(jax.random.PRNGKey(3000), 39),
                              (64 * NARROW_MIN_WAVE, 5), dtype=jnp.uint32)
    return sorted_ids, n, targets, simulate_lookups(sorted_ids, n, targets,
                                                    **WIDE_KW)


def test_three_steps_down(wide_wave, monkeypatch):
    """Three cuts — and two tiles — equal, bit for bit, the same targets
    as eight untiled sub-waves of 32,768 — each of which steps down twice,
    which ``two_steps_down`` ties to the uncut loops — and the live count
    is asked at exactly the four widths."""
    import jax
    from opendht_tpu.core import search as S
    from opendht_tpu.core.search import NARROW_DIVISOR, NARROW_MIN_WAVE
    sorted_ids, n, targets, out = wide_wave
    assert targets.shape[0] == 262144
    # two LANE TILES wide at the constant the chip runs with: its six
    # full-width rounds ran tile by tile, the sub-waves' whole
    assert S.lane_tiles(262144) == 2 and int(out["tiled_rounds"]) == 6
    assert int(out["narrow_rounds"]) == 3
    assert np.asarray(out["converged"]).all()
    assert np.asarray(out["hops"]).max() == 9
    ref = _in_sub_waves(sorted_ids, n, targets, q=8, **WIDE_KW)
    assert set(np.asarray(ref["narrow_rounds"]).tolist()) == {2, 3}
    _assert_same_outputs(out, ref)

    asked = _record_live_count_widths(monkeypatch)
    jax.eval_shape(functools.partial(S._simulate_lookups_jit.__wrapped__,
                                     **WIDE_KW), sorted_ids, n, targets)
    widths = [64 * NARROW_MIN_WAVE // NARROW_DIVISOR ** i for i in range(4)]
    assert asked == widths == [262144, 32768, 4096, 512]


def test_a_wide_wave_agrees_with_the_plain_references(wide_wave):
    """Hop statistics of a wave wider than 65,536 against the plain
    reference ``scalar_lookup`` on 48 lanes drawn over the WHOLE index
    range: medians within 2 rounds, the tolerance and the reason
    ``test_hop_parity_with_scalar_reference`` states (the reference
    draws its replies from a generator, the engine from a counter hash:
    parity is statistical); and on the same lanes the closest-8 sets
    against the exact XOR top-8 at sim-10m's 90%."""
    sorted_ids, n, targets, out = wide_wave
    lanes = np.random.default_rng(39).choice(targets.shape[0], 48,
                                             replace=False)
    assert (lanes > 65535).sum() >= 24 and lanes.max() > 200000
    ids_np, t_np = np.asarray(sorted_ids), np.asarray(targets)[lanes]
    hops_scalar = []
    for i, t in enumerate(t_np):
        _, h, conv = scalar_lookup(ids_np, int(n), t, alpha=2,
                                   rng=np.random.default_rng(390 + i))
        assert conv
        hops_scalar.append(h)
    hops_wide = np.asarray(out["hops"])[lanes]
    assert abs(np.median(hops_wide) - np.median(hops_scalar)) <= 2, (
        np.median(hops_wide), np.median(hops_scalar))
    _, true_idx = xor_topk(jnp.asarray(t_np), sorted_ids, k=8)
    nodes = np.asarray(out["nodes"])[lanes]
    agree = sum(set(a.tolist()) == set(b.tolist())
                for a, b in zip(nodes, np.asarray(true_idx)))
    assert agree >= int(np.ceil(0.9 * len(lanes))), agree


def test_the_reply_hash_counter_does_not_wrap_at_the_north_stars_width():
    """``_reply_rows`` counts ``(round * q_total + q) * R + slot`` in
    uint32: no two (round, lookup, slot) of a wave may alias.  At the
    north star's sizes the largest counter is 1.23e9; a wider wave, a
    larger alpha or a longer budget must not wrap it silently."""
    import inspect
    import json
    import os
    from opendht_tpu.core import search as S
    with open(os.path.join(os.path.dirname(__file__), "..", "dhtbench",
                           "configs", "northstar-10m.json")) as f:
        sizes = json.load(f)["sizes"]
    max_hops = inspect.signature(
        S._simulate_lookups_jit.__wrapped__).parameters["max_hops"].default
    assert max_hops == 48 and sizes["concurrent_lookups"] == 2 ** 20
    # round_no runs 0 (the bootstrap) .. max_hops, q < q_total, slot < R
    R = sizes["alpha"] * sizes["k"]
    assert (max_hops + 1) * sizes["concurrent_lookups"] * R < 2 ** 32


# -- LANE TILES (PR 39): a loop wider than ROUND_TILE_LANES runs a round
# over its lanes a tile at a time.  ``wide_wave`` above is two tiles wide
# at the constant the chip runs with; here the tile is 1,024 lanes, so a
# wave the CPU runs in a second is four or eight tiles.

@pytest.mark.parametrize("state_limbs", [2, 5])
def test_a_round_in_lane_tiles_is_the_round_at_once(cut_network, small_tiles,
                                                    state_limbs):
    """Bit for bit the untiled engine's outputs, cut included; the engine
    counts its tiled rounds — the full-width ones — and ``record_wave``
    files them, for a wave wider than a tile only; the tp twin runs a
    chunk wider than a tile in tiles too and keeps its own outputs."""
    from opendht_tpu import telemetry
    sorted_ids, n, targets = cut_network
    targets = targets[:8192]
    kw = dict(seed=11, alpha=2, state_limbs=state_limbs)
    reg = telemetry.get_registry()
    before = reg.snapshot()
    ref = simulate_lookups(sorted_ids, n, targets, **kw)
    assert "tiled_rounds" not in ref
    series = 'dht_search_tiled_rounds{mode="single"}'
    assert series not in telemetry.snapshot_diff(
        before, reg.snapshot())["histograms"]

    small_tiles(1024)
    out = simulate_lookups(sorted_ids, n, targets, **kw)
    _assert_same_outputs(out, ref)
    rounds = int(np.asarray(ref["hops"]).max())
    assert int(out["narrow_rounds"]) == int(ref["narrow_rounds"]) == 3
    assert int(out["tiled_rounds"]) == rounds - 3 == 6
    moved = telemetry.snapshot_diff(before, reg.snapshot())["histograms"]
    assert (moved[series]["count"], moved[series]["sum"]) == (1, 6)

    halves = _in_sub_waves(sorted_ids, n, targets, q=2, **kw)   # 4 tiles each
    assert "tiled_rounds" not in halves
    _assert_same_outputs(halves, ref)


def test_a_tiled_round_counts_once_where_a_closure_reports(
        cut_network, small_tiles, monkeypatch):
    """A gather closure that reports (the tp twin's ``one_pass``): every
    tile of a round reports, and the round counts as one pass once, where
    every tile was one."""
    from opendht_tpu.core import search as S
    sorted_ids, n, targets = cut_network
    gather = S.fused_gather_planar
    monkeypatch.setattr(S, "fused_gather_planar", lambda *a: (
        gather(*a), jnp.int32(1)))
    small_tiles(1024)
    out = simulate_lookups(sorted_ids, n, targets[:4096], seed=11, alpha=2,
                           state_limbs=2)
    rounds = int(np.asarray(out["hops"]).max())
    assert int(out["window_rounds"]) == rounds == 7
    assert int(out["tiled_rounds"]) == 6 and int(out["narrow_rounds"]) == 1


def test_the_tile_stage_is_in_a_tiled_program_only(cut_network, small_tiles):
    """Up to a tile's width the engine lowers to the program it was:
    nothing is cut out or written back, and no operation carries the
    stage's name."""
    from opendht_tpu.core import search as S
    sorted_ids, n, targets = cut_network
    assert S.lane_tiles(S.ROUND_TILE_LANES) == 1
    assert S.lane_tiles(8 * S.ROUND_TILE_LANES) == 8
    assert S.lane_tiles(S.ROUND_TILE_LANES * 3 // 2) == 1   # no whole tiles

    def lowered():
        return S._simulate_lookups_jit.lower(
            sorted_ids, n, targets[:4096], seed=11, alpha=2,
            state_limbs=2).as_text()

    assert "stage_tile" not in lowered()
    small_tiles(1024)
    assert "stage_tile" in lowered()


@pytest.mark.parametrize("n_ids", [0, 5])
def test_cut_with_nobody_live(n_ids):
    """Nobody live at the cut: an empty table (every lookup done before
    the first round) and a five-id one (every lookup finishes in the
    same round, so the live count falls from all to none).  The pack
    finds no survivor, the narrow loop runs no round, the write-back
    drops every lane."""
    import jax
    from opendht_tpu.core.search import NARROW_MIN_WAVE
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    sorted_ids, _, _ = sort_table(jax.random.bits(k1, (8, 5),
                                                  dtype=jnp.uint32))
    targets = jax.random.bits(k2, (NARROW_MIN_WAVE, 5), dtype=jnp.uint32)
    out = simulate_lookups(sorted_ids, n_ids, targets, seed=3,
                           state_limbs=2)
    assert int(out["narrow_rounds"]) == 0
    hops = np.asarray(out["hops"])
    assert (hops == hops[0]).all()
    assert np.asarray(out["converged"]).all() == (n_ids > 0)
    _assert_same_outputs(out, _in_sub_waves(sorted_ids, n_ids, targets,
                                            seed=3, state_limbs=2))


def test_an_explicit_live_count_hook_is_the_engine_without_one(
        cut_network, monkeypatch):
    """PR 38: the live count that the loop conditions and the cut rule
    read is a hook (the tp twin hands in the fullest shard's).  Left out
    it is ``jnp.sum(~done)`` — the goldens below and the lowered text's
    hash in tests/test_sharded.py pin that — and handed in as exactly
    that it gives the same outputs and the same ``narrow_rounds``, asked
    once a loop at that loop's width."""
    import jax
    from opendht_tpu.core import search as S
    from opendht_tpu.core.search import NARROW_DIVISOR, NARROW_MIN_WAVE

    sorted_ids, n, targets = cut_network
    targets = targets[:NARROW_MIN_WAVE]
    kw = dict(seed=11, alpha=2, state_limbs=2)
    out = S._simulate_lookups_jit(sorted_ids, n, targets, **kw)
    asked = _record_live_count_widths(monkeypatch)
    ref = jax.jit(functools.partial(
        S._simulate_lookups_jit.__wrapped__, **kw))(sorted_ids, n, targets)
    assert set(asked) == {NARROW_MIN_WAVE, NARROW_MIN_WAVE // NARROW_DIVISOR}
    assert int(ref["narrow_rounds"]) == int(out["narrow_rounds"]) == 1
    _assert_same_outputs(out, ref)


def test_engine_reply_stream_goldens():
    """The deterministic reply streams are pinned by committed goldens
    (tests/goldens/search_engine.json): the round-6 ROUND-FUSED engine
    (one fused [W·α·k] reply gather per round; block edges positioned
    from the carried candidate distance limb instead of a per-round
    peer gather) must reproduce the round-5 engine's outputs bit for
    bit — as must any future refactor, since wave streaming, survivor
    compaction, and tp-sharding all lean on stream determinism keyed
    by (seed, global query id, round)."""
    import hashlib
    import json
    import os

    with open(os.path.join(os.path.dirname(__file__), "goldens",
                           "search_engine.json")) as f:
        gold = json.load(f)
    rng = np.random.default_rng(1234)
    ids = rng.integers(0, 2**32, size=(4096, 5), dtype=np.uint32)
    targets = jnp.asarray(rng.integers(0, 2**32, size=(96, 5),
                                       dtype=np.uint32))
    sorted_ids, _, n = sort_table(jnp.asarray(ids))
    for tag, kw in (("lut_l5", {}), ("lut_l2", {"state_limbs": 2}),
                    ("exact_l5", {"block_mode": "exact"})):
        out = simulate_lookups(sorted_ids, n, targets, seed=99, **kw)
        h = hashlib.sha256()
        for key in ("nodes", "hops", "converged", "dist"):
            h.update(np.ascontiguousarray(np.asarray(out[key])).tobytes())
        assert h.hexdigest() == gold[tag]["sha256"], (
            tag, np.bincount(np.asarray(out["hops"]), minlength=12)[:12],
            gold[tag]["hops_hist"])
        np.testing.assert_array_equal(np.asarray(out["nodes"])[0],
                                      gold[tag]["nodes_row0"], err_msg=tag)
        assert int(np.asarray(out["converged"]).sum()) \
            == gold[tag]["converged"], tag


def test_lut_block_bounds_exact_up_to_lut_width():
    """_lut_block_bounds must equal the exact prefix-block edges for any
    prefix length <= the LUT width — on clustered tables too (the
    exactness claim is structural, not probabilistic: lut[p] counts
    rows below prefix p) — and clamp to the containing bucket beyond
    the width."""
    import numpy as np
    import jax.numpy as jnp
    from opendht_tpu.ops.sorted_table import sort_table, build_prefix_lut
    from opendht_tpu.core.search import _lut_block_bounds

    rng = np.random.default_rng(55)
    for cluster in (False, True):
        raw = rng.integers(0, 2**32, size=(4096, 5), dtype=np.uint32)
        if cluster:
            raw[:3000, 0] = raw[0, 0]          # one giant top-32 cluster
        s, _p, nv = sort_table(jnp.asarray(raw))
        bits = 16
        lut = build_prefix_lut(s, nv, bits=bits)
        s_np = np.asarray(s)
        top = s_np[:, 0]
        t0 = rng.integers(0, 2**32, size=64, dtype=np.uint32)
        t0[:8] = s_np[:: 512, 0][:8]           # hit real prefixes too
        for L in (0, 1, 7, bits - 1, bits, bits + 3, 40, 160):
            Lc = min(L, bits)
            lo, ub = _lut_block_bounds(
                lut, jnp.asarray(t0), jnp.full((64,), L, jnp.int32))
            lo, ub = np.asarray(lo), np.asarray(ub)
            # oracle: count rows whose top-Lc bits match the target's
            shift = np.uint32(32 - Lc) if Lc else None
            for i in range(64):
                if Lc == 0:
                    want_lo, want_ub = 0, int(nv)
                else:
                    pfx = t0[i] >> shift
                    rows = top >> shift
                    want_lo = int(np.searchsorted(rows, pfx, side="left"))
                    want_ub = int(np.searchsorted(rows, pfx, side="right"))
                    want_ub = min(want_ub, int(nv))
                    want_lo = min(want_lo, int(nv))
                assert lo[i] == want_lo and ub[i] == want_ub, \
                    (cluster, L, i, lo[i], ub[i], want_lo, want_ub)


def _np_mix32(x):
    """``core.search._mix32`` on uint64 lanes masked to 32 bits."""
    m = np.uint64(0xFFFFFFFF)
    x = x & m
    x ^= x >> np.uint64(16)
    x = (x * np.uint64(0x7FEB352D)) & m
    x ^= x >> np.uint64(15)
    x = (x * np.uint64(0x846CA68B)) & m
    x ^= x >> np.uint64(16)
    return x


@pytest.mark.parametrize("block_mode", ["lut", "exact"])
@pytest.mark.parametrize("alpha", [3, 4])
def test_reply_rows_equal_the_w_alpha_k_formula(alpha, block_mode):
    """The slot-major reply rows of a round are, element for element,
    the rows of the ``[W, α, k]`` formula the engine used to compute in
    (PR 27): counter ``(((round·Q + q)·α + a)·k + j) ^ seed``, hash,
    modulo into the peer's block when it holds ≥ k rows, else the slice
    of the fallback window, −1 for a slot that sent no request — here in
    plain numpy over python-int ids, block edges included.  The table is
    small enough that all three cases occur."""
    import bisect
    from opendht_tpu.ops.ids import clz32
    from opendht_tpu.ops.sorted_table import _lower_bound, build_prefix_lut
    from opendht_tpu.core import search as S

    N, W, k, bits, rnd, seed, q_total = 300, 48, 8, 6, 3, 0x9E3779B9, 59
    R = alpha * k
    rng = np.random.default_rng(270 + alpha)
    sorted_ids, n = _network(N, 27)
    n = int(n)
    ids = np.asarray(sorted_ids)[:n]
    tgt = rng.integers(0, 2**32, size=(W, 5), dtype=np.uint32)

    def as_int(limbs):
        return int.from_bytes(np.asarray(limbs, ">u4").tobytes(), "big")

    ids_int = [as_int(r) for r in ids]
    t_int = [as_int(t) for t in tgt]
    pt = np.array([bisect.bisect_left(ids_int, t) for t in t_int], np.int32)
    qidx = np.arange(W, dtype=np.int32) + 5          # global ids, not 0..W
    # peers [W, alpha]: far (random rows), near (next to the target), unsent
    x_rows = rng.integers(0, n, size=(W, alpha)).astype(np.int32)
    near = rng.random((W, alpha)) < 0.4
    x_rows = np.where(near, np.clip(pt[:, None] + rng.integers(
        -2, 3, size=(W, alpha)), 0, n - 1), x_rows).astype(np.int32)
    x_rows[rng.random((W, alpha)) < 0.25] = -1

    # --- the old formula, numpy, logical shape [W, alpha, k] -------------
    lo = np.zeros((W, alpha), np.int64)
    ub = np.zeros((W, alpha), np.int64)
    for q in range(W):
        for a in range(alpha):
            x = x_rows[q, a]
            if block_mode == "exact":
                d = ids_int[max(x, 0)] ^ t_int[q]
                plen = min(160 - d.bit_length() + 1, 160)
                mask = ((1 << plen) - 1) << (160 - plen)
                p_lo = t_int[q] & mask
                lo[q, a] = bisect.bisect_left(ids_int, p_lo)
                ub[q, a] = bisect.bisect_left(
                    ids_int, p_lo + (1 << (160 - plen)))
            else:
                # carried top limb, 0 for an unsent slot; clamped at `bits`
                d0 = int(ids[x, 0] ^ tgt[q, 0]) if x >= 0 else 0
                plen = min(32 - d0.bit_length() + 1, bits)
                top = [int(r[0]) >> (32 - plen) for r in ids]
                lo[q, a] = bisect.bisect_left(top, int(tgt[q, 0]) >> (32 - plen))
                ub[q, a] = bisect.bisect_right(top, int(tgt[q, 0]) >> (32 - plen))
    size = np.maximum(ub - lo, 0)
    qi = qidx.astype(np.uint64)[:, None, None]
    ai = np.arange(alpha, dtype=np.uint64)[None, :, None]
    ji = np.arange(k, dtype=np.uint64)[None, None, :]
    ctr = (((np.uint64(rnd) * np.uint64(q_total) + qi) * np.uint64(alpha)
            + ai) * np.uint64(k) + ji) ^ np.uint64(seed)
    h = _np_mix32(ctr).astype(np.int64)
    blk = lo[..., None] + h % np.maximum(size, 1)[..., None]
    base = np.clip(pt.astype(np.int64) - R // 2, 0, max(n - R, 0))
    fb = np.clip(base[:, None, None] + (ai * np.uint64(k) + ji).astype(
        np.int64), 0, max(n - 1, 0))
    want = np.where(size[..., None] >= k, blk, fb)
    want = np.where((x_rows >= 0)[..., None], want, -1).reshape(W, R)
    sent = x_rows >= 0
    assert (sent & (size >= k)).any() and (sent & (size < k)).any() \
        and (~sent).any()

    # --- the engine's: peer-major in, slot-major out ----------------------
    tj, xj = jnp.asarray(tgt), jnp.asarray(x_rows.T)            # [a, W]
    x_l = [jnp.asarray(ids[np.maximum(x_rows.T, 0), l]) for l in range(5)]
    if block_mode == "exact":
        b = S._common_bits_planar(x_l, [tj[:, l][None, :] for l in range(5)])
        lo_j, ub_j = S._prefix_block_bounds(
            lambda flat: _lower_bound(sorted_ids, flat, n), n,
            jnp.broadcast_to(tj[None], (alpha, W, 5)),
            jnp.clip(b + 1, 0, 160))
    else:
        x_d0 = jnp.where(xj >= 0, x_l[0] ^ tj[:, 0][None, :], 0)
        lo_j, ub_j = S._lut_block_bounds(
            build_prefix_lut(sorted_ids, n, bits=bits), tj[:, 0][None, :],
            clz32(x_d0) + 1)
    np.testing.assert_array_equal(np.asarray(lo_j).T, lo)
    np.testing.assert_array_equal(np.asarray(ub_j).T, ub)
    kw = dict(n=jnp.int32(n), k=k, R=R, q_total=q_total,
              seed_u=jnp.uint32(seed))
    got = S._reply_rows(jnp.asarray(pt), jnp.asarray(qidx), xj,
                        jnp.int32(rnd), lo_j, ub_j, **kw)
    assert got.shape == (R, W)                       # slot-major plane
    np.testing.assert_array_equal(np.asarray(got).T, want)
    # BOOTSTRAP SHAPE: a call for ONE peer at the wave's R computes that
    # peer's k slots and nothing else — the counter's stride and the
    # fallback window are the wave's, not the call's
    one = S._reply_rows(jnp.asarray(pt), jnp.asarray(qidx), xj[:1],
                        jnp.int32(rnd), lo_j[:1], ub_j[:1], **kw)
    assert one.shape == (k, W)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(got)[:k])


@pytest.mark.parametrize("state_limbs", [2, 5])
def test_no_w_alpha_k_tensor_in_the_lowered_round(state_limbs):
    """The reply path stays slot-major: the lowered module holds no
    rank-3 ``[W, alpha, k]`` tensor.  On the TPU that shape pads its
    minor dims (3, 8) to a (4, 128) tile, 21× the bytes, and cost the
    round its second stage (PERF.md §6, PR 27) — an edit that brings it
    back fails here, not only in the benchmark.  W, alpha, k chosen so
    that no other tensor of the module has the shape."""
    from opendht_tpu.core.search import _simulate_lookups_jit

    W, alpha, k = 44, 3, 5
    sorted_ids, n = _network(512, 3)
    targets = jnp.zeros((W, 5), jnp.uint32)
    text = _simulate_lookups_jit.lower(
        sorted_ids, n, targets, seed=1, k=k, alpha=alpha,
        state_limbs=state_limbs).as_text()
    assert "stage_reply_rows" in text                # the round is in there
    assert f"tensor<{alpha * k}x{W}x" in text        # ... slot-major
    assert f"tensor<{W}x{alpha}x{k}x" not in text


# -- BOOTSTRAP SHAPE and the table view (core/search.py _lookup_engine,
# PR 31): the bootstrap round runs at the shape of its one peer, and a
# loop body slices the table only where the slice is the gather's
# staging copy (ops.sorted_table.loop_gather_view).

def _walk_equations(jaxpr, in_loop=False):
    """Walk a jaxpr and every jaxpr in its equations' parameters (jit,
    shard_map, cond branches, loop bodies): yields ``(equation,
    in_loop)``, ``in_loop`` true under any ``while`` or ``scan``."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        inner = in_loop or eqn.primitive.name in ("while", "scan")
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)     # ClosedJaxpr → Jaxpr
                if hasattr(sub, "eqns"):
                    yield from _walk_equations(sub, inner)


def _assert_engine_shape(jaxpr, table_rows, W, k, alpha, staged,
                         window=None, final_fetch=True):
    """Where the table's limb view fits on-chip memory (``staged``) every
    loop body slices it next to its gather — the slice is the gather's
    staging copy — and where it cannot, no ``slice`` / ``dynamic_slice``
    inside a loop body has a table-sized operand
    (``ops.sorted_table.loop_gather_view``).  Either way no gather
    outside the loops issues more than k·W indices (the bootstrap's
    merge, the final id fetch); the loop's is α·k·W — or, in the tp
    twin, α·k·``window``: a shard gathers over its lane window, in a
    pass loop of its own (``parallel.sharded.window_gather``), and only
    the lookup-major final fetch is left outside every loop, which a
    5-limb state does not need (``final_fetch``); the widest gather
    outside the loops is then one of the W probes of the once-a-wave
    positioning (the twin's bootstrap reads the LUT edges of its own
    chunk of the wave: 2·W/t indices, PR 38)."""
    widest = {True: 0, False: 0}
    sliced_in_loop = 0
    for eqn, in_loop in _walk_equations(jaxpr):
        name = eqn.primitive.name
        if in_loop and name in ("slice", "dynamic_slice"):
            sliced_in_loop += table_rows in eqn.invars[0].aval.shape
        if name == "gather":
            n_idx = int(np.prod(eqn.invars[1].aval.shape[:-1]))
            widest[in_loop] = max(widest[in_loop], n_idx)
    assert (sliced_in_loop > 0) == staged
    assert widest[True] == alpha * k * (window or W)
    assert widest[False] == (k * W if final_fetch or not window else W)


# 10M rows: the 2-limb view is 80 MB and is staged; a 25,060,864-row
# shard's is 200 MB and is not (the two cells); a 5-limb state gathers
# from the whole table, which no size slices
ENGINE_SHAPES = [pytest.param(10_000_000, 2, True, id="10M_rows_staged"),
                 pytest.param(25_060_864, 2, False, id="25M_rows_hoisted"),
                 pytest.param(25_060_864, 5, False, id="five_limbs")]


@pytest.mark.parametrize("rows, state_limbs, staged", ENGINE_SHAPES)
def test_the_loop_slices_a_view_that_fits_and_the_bootstrap_is_k_wide(
        rows, state_limbs, staged):
    """One device: the jaxpr of ``_simulate_lookups_jit`` at the cells'
    shapes (abstract operands: nothing is allocated)."""
    import jax
    from opendht_tpu.core.search import _simulate_lookups_jit

    W, k, alpha = 65536, 8, 3
    u32, i32 = jnp.uint32, jnp.int32
    A = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(functools.partial(
        _simulate_lookups_jit, k=k, alpha=alpha, state_limbs=state_limbs))(
        A((rows, 5), u32), A((), i32), A((W, 5), u32), seed=A((), i32),
        lut=A(((1 << 24) + 1,), i32))
    _assert_engine_shape(jaxpr.jaxpr, rows, W, k, alpha, staged)


@pytest.mark.parametrize("rows, state_limbs, staged", ENGINE_SHAPES)
def test_the_loop_slices_a_view_that_fits_in_the_tp_twin(
        rows, state_limbs, staged):
    """Four virtual devices: the same of ``build_tp_lookup``'s ``local``,
    whose table is the shard (the weighted layout, as
    ``sharded_global_sort`` returns it)."""
    import jax
    from opendht_tpu.parallel import make_mesh
    from opendht_tpu.parallel.sharded import build_tp_lookup, window_width

    W, k, alpha = 65536, 8, 3
    fn = build_tp_lookup(make_mesh(4, q=1, t=4), rows, W, k, alpha, 14, 48,
                         state_limbs, True)
    u32, i32 = jnp.uint32, jnp.int32
    A = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(fn)(
        A((4 * rows, 5), u32), A((4, (1 << 22) + 1), i32),
        A(((1 << 24) + 1,), i32), A((), i32), A((4, 2), i32),
        A((W, 5), u32), A((), i32))
    assert window_width(W, 4) == 17408
    _assert_engine_shape(jaxpr.jaxpr, rows, W, k, alpha, staged,
                         window=17408, final_fetch=state_limbs == 2)


@pytest.mark.parametrize("width, alpha", [
    pytest.param(4096, 2, id="cuts"), pytest.param(1024, 3, id="one_loop")])
@pytest.mark.parametrize("state_limbs", [2, 5])
@pytest.mark.parametrize("block_mode", ["lut", "exact"])
def test_one_peer_bootstrap_equals_the_alpha_wide_one(
        cut_network, monkeypatch, block_mode, state_limbs, width, alpha):
    """The bootstrap round at [1, Q] leaves the engine's whole output as
    the α·k-wide bootstrap left it.  The old form is the reference: the
    reply model is handed the one peer padded to α rows with the −1 of
    peers that do not exist — what ``boot`` used to be — so it returns
    [α·k, Q], the gather fetches α·k·Q rows and the merge sorts
    [Q, S + α·k], in a fresh trace of the same engine."""
    import jax
    from opendht_tpu.core import search as S

    sorted_ids, n, targets = cut_network
    kw = dict(seed=11, alpha=alpha, state_limbs=state_limbs,
              block_mode=block_mode)
    out = S._simulate_lookups_jit(sorted_ids, n, targets[:width], **kw)

    reply_rows, widened = S._reply_rows, []

    def alpha_wide(pt, qidx, x_rows, round_no, lo, ub, **kwargs):
        if x_rows.shape[0] == 1:                     # the bootstrap's call
            widened.append(x_rows.shape)
            pad = ((0, alpha - 1), (0, 0))
            x_rows = jnp.pad(x_rows, pad, constant_values=-1)
            lo, ub = jnp.pad(lo, pad), jnp.pad(ub, pad)
        return reply_rows(pt, qidx, x_rows, round_no, lo, ub, **kwargs)

    monkeypatch.setattr(S, "_reply_rows", alpha_wide)
    # (a partial is a new function: jit's trace cache cannot answer with
    # the program traced above)
    ref = jax.jit(functools.partial(
        S._simulate_lookups_jit.__wrapped__, **kw))(
        sorted_ids, n, targets[:width])
    assert widened == [(1, width)]
    assert int(out["narrow_rounds"]) == (1 if width == 4096 else 0)
    assert np.asarray(out["converged"]).all()
    assert int(ref["narrow_rounds"]) == int(out["narrow_rounds"])
    _assert_same_outputs(out, ref)


@pytest.mark.parametrize("mode", ["single", "tp"])
def test_wave_span_carries_mode_width_rounds_only(mode):
    """Under an active root context one wave is ONE ``dht.search.wave``
    span with exactly three attributes — what was timed and counted,
    no estimate riding along — from the single-device entry and from
    its tp twin alike (both go through ``core.search._run_wave``)."""
    from opendht_tpu import tracing

    sorted_ids, n = _network(2048, 21)
    targets = jnp.asarray(K.ids_from_bytes(np.random.default_rng(22)
                          .integers(0, 256, (64, 20), dtype=np.uint8)))
    if mode == "single":
        def run():
            return simulate_lookups(sorted_ids, n, targets, seed=5)
    else:
        from opendht_tpu.parallel import make_mesh, tp_simulate_lookups
        mesh = make_mesh(4, q=1, t=4)

        def run():
            return tp_simulate_lookups(mesh, np.asarray(sorted_ids), n,
                                       targets, seed=5)
    tr = tracing.get_tracer()
    tr.clear()
    was, tr.enabled = tr.enabled, True
    try:
        root = tracing.TraceContext.new_root()
        with tracing.activate(root):
            out = run()
        spans = tr.spans(root.trace_id)
    finally:
        tr.enabled = was
    assert [s["name"] for s in spans] == ["dht.search.wave"]
    assert spans[0]["attrs"] == {
        "mode": mode, "width": 64,
        "rounds": int(np.asarray(out["hops"]).max())}
