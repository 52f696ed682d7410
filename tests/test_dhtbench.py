"""The benchmark's own tests (``dhtbench/tests/``), collected into Tier-1.

They live with the benchmark so that ``python3 -m pytest dhtbench/tests``
runs them alone; this module imports them so that the repo's one test
command counts them too.

ONE CASE IS DROPPED FROM THE IMPORT AND RESTATED HERE, under another name:
``test_sim_cell_at_toy_size_prints_the_contract_line`` pins the per-layer
metrics a CPU rehearsal reports to the one registry metric the cell had
when it was written (``{"sim_round_ms"}``); the cell has three since PR 26,
so its ``[True]`` case fails where it stands (``python3 -m pytest
dhtbench/tests``).  A file the benchmark already has may be edited only by
a ``benchmark`` PR, which mends that one line and then deletes the copy
and the ``del`` below (PERF.md §7).  The copy asserts everything the
original does.

A SECOND CASE, the same way (PR 27):
``test_each_stage_metric_names_a_stage_of_the_program`` pins the metric
files of the ``stage`` source to four and their stages to ``fetch_ids``,
``block_bounds``, ``merge``; ``metrics/sim_reply_rows_ms_per_wave.json``
(the file PR 26 asked for and ISSUE 27 brought) makes them five, so the
original fails where it stands.  The copy here holds every stage metric
to a stage the program names, and names the four it expects.  Since
PR 28 the files are nine: ``host4-100m.wave-65536`` reads the same three
stages and ``owner_merge`` through files of its own.

PR 28's cell ``host4-100m.wave-65536`` is rehearsed here too, on four of
the virtual CPU devices, with the assertions of the sim case; and its
blockwise reference (``dhtbench/reference_blocks.py``) is held to
``reference.XorIndex`` over the whole id set.

PR 32's cell ``sim-10m-churn.wave-65536`` (driver ``sim_churn``) is
rehearsed the same way, its ``check`` is shown to FAIL a table that lost
or resurrected a node, its book (``dhtbench/reference_churn.py``) is held
to a Python set, and the ``stage`` metric files are fourteen: the cell
reads ``fetch_ids`` and the four stages of the churn model and of the
mutable table through files of its own.

PR 39's cell ``northstar-10m.wave-1048576`` (driver ``sim``, UNEDITED) is
rehearsed at the narrowest wave the engine still cuts and with a tile a
quarter of it, so that the metric of the rounds it runs in LANE TILES
reads some, and its manifest entries are held to the configuration's
file: one wave of 2^20 lookups — eight tiles — on one chip.
"""

import json
import os

import numpy as np
import pytest

pytest.register_assert_rewrite("dhtbench.tests.test_dhtbench",
                               "dhtbench.tests.test_stage_source")

from dhtbench import reference, reference_blocks, run    # noqa: E402
from dhtbench.tests.test_dhtbench import *          # noqa: E402,F401,F403
from dhtbench.tests.test_dhtbench import RESULT_KEYS, manifest  # noqa: E402,F401
from dhtbench.tests.test_stage_source import *      # noqa: E402,F401,F403
from dhtbench.tests.test_stage_source import STAGES  # noqa: E402

del test_sim_cell_at_toy_size_prints_the_contract_line      # noqa: F821
del test_each_stage_metric_names_a_stage_of_the_program     # noqa: F821

# the per-layer metrics of the sim cell that read the program's registry,
# and so read something on the CPU too; the trace ones read nothing there
SIM_REGISTRY_METRICS = {"sim_round_ms", "sim_record_ms_per_wave",
                        "sim_dispatch_ms_per_wave",
                        "sim_narrow_rounds_per_wave"}
# PR 29's two: the rounds a wave ran narrow — present in every run, and 0
# at rehearsal size (a 256-lookup wave is under the engine's threshold)
NARROW_ROUNDS_METRICS = {"sim_narrow_rounds_per_wave",
                         "host4_narrow_rounds_per_wave"}


@pytest.mark.parametrize("trace", [False, True])
def test_sim_cell_at_toy_size_reports_every_registry_metric(manifest, trace):  # noqa: F811
    line = run.run_cell("sim-10m.wave-65536", 2 ** 31 + 12345, 1.0, trace,
                        rehearsal={"n_ids": 4096, "wave_targets": 256,
                                   "target_sets": 4})
    line = json.loads(json.dumps(line))
    assert set(line) == RESULT_KEYS         # no breakdown: the CPU has no device plane
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % 256 == 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    if trace:
        assert set(line["metrics"]) == SIM_REGISTRY_METRICS
    else:
        assert set(line["metrics"]) == {"sim_lookups_per_s",
                                        "sim_wave_p90_ms", "setup_s"}
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}
        assert m["value"] > 0 or (name in NARROW_ROUNDS_METRICS
                                  and m["value"] == 0)


# PR 39: the north star's cell reads driver ``sim``'s registry metrics (its
# driver hands them to every cell of its own; ``sim_narrow_rounds_per_wave``
# names its one cell) and what the width added to the program: the rounds
# the engine ran in LANE TILES (registry) and the tiles' copies (stage)
NORTHSTAR = "northstar-10m.wave-1048576"
NORTHSTAR_REGISTRY_METRICS = (SIM_REGISTRY_METRICS
                              - {"sim_narrow_rounds_per_wave"}
                              | {"northstar_tiled_rounds_per_wave"})


@pytest.mark.parametrize("trace", [False, True])
def test_northstar_cell_at_toy_size_tiles_cuts_and_prints_the_contract_line(
        manifest, small_tiles, trace):
    """The cell through ``drivers/sim.py`` at the narrowest wave that
    still steps down (``NARROW_MIN_WAVE`` lookups: 4,096 -> 512 lanes),
    with a tile of 1,024 lanes: its full-width rounds run in four tiles,
    as the chip's run in eight of 131,072, and their count is read."""
    from opendht_tpu.core.search import NARROW_MIN_WAVE as width
    small_tiles(width // 4)
    line = run.run_cell(NORTHSTAR, 2 ** 31 + 39039, 1.0, trace,
                        rehearsal={"n_ids": 4096, "wave_targets": width,
                                   "target_sets": 2})
    line = json.loads(json.dumps(line))
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % width == 0
    if trace:
        assert set(line["metrics"]) == NORTHSTAR_REGISTRY_METRICS
        assert line["metrics"]["northstar_tiled_rounds_per_wave"][
            "value"] >= 4
    else:
        assert set(line["metrics"]) == {m["name"]
                                        for m in manifest["end_to_end"]
                                        if NORTHSTAR in m.get("workloads",
                                                              (NORTHSTAR,))}
        assert {"sim_lookups_per_s", "setup_s"} <= set(line["metrics"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


def test_the_northstar_cell_is_one_wave_of_a_million_on_one_chip(manifest):
    from opendht_tpu.core.search import lane_tiles
    cell, config, driver, files = run.resolve(NORTHSTAR)
    assert driver.__name__ == "dhtbench.drivers.sim" and cell["chips"] == 1
    assert (config["sizes"]["concurrent_lookups"]
            == cell["traffic"]["wave_targets"] == 2 ** 20)
    assert lane_tiles(2 ** 20) == 8 and lane_tiles(2 ** 16) == 1
    # every metric file the harness hands the cell is in the manifest with
    # the cell in its list, and the cell is in no other metric's list
    listed = {m["name"] for m in manifest["per_layer"]
              if NORTHSTAR in m.get("workloads", ())}
    assert listed == set(files) and len(listed) == 14
    assert "sim_narrow_rounds_per_wave" not in listed
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for name, f in files.items():
        assert all(per_layer[name][key] == f[key]
                   for key in ("unit", "better", "layer", "moves"))
    assert NORTHSTAR in [m for m in manifest["end_to_end"]
                         if m["name"] == "sim_lookups_per_s"][0]["workloads"]
    entry = [c for c in manifest["configs"] if c["name"] == config["name"]]
    assert entry[0]["source"] == config["source"] and \
        len(config["source"]) < 200 and \
        entry[0]["reduced"] == config["reduced"] == ["chips"]
    work = [w for w in manifest["workloads"] if w["name"] == NORTHSTAR]
    assert work == [{"name": NORTHSTAR, "config": config["name"],
                     "traffic": "wave-1048576", "chips": 1,
                     "why": cell["why"]}] and len(cell["why"]) <= 200
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= len(manifest["workloads"]) // 2


# the same for the four-chip cell (PR 28): its two envelope spans under
# mode="tp", and the build span its driver reads in set-up
HOST4_REGISTRY_METRICS = {"host4_record_ms_per_wave",
                          "host4_dispatch_ms_per_wave", "host4_table_build_s",
                          "host4_narrow_rounds_per_wave",
                          "host4_window_rounds_per_wave",
                          "host4_home_lanes_per_wave"}


@pytest.mark.parametrize("trace", [False, True])
def test_host4_cell_at_toy_size_prints_the_contract_line(manifest, trace):
    line = run.run_cell("host4-100m.wave-65536", 2 ** 31 + 54321, 1.0, trace,
                        rehearsal={"n_ids": 16384, "wave_targets": 256,
                                   "target_sets": 4})
    line = json.loads(json.dumps(line))
    assert set(line) == RESULT_KEYS         # no breakdown: the CPU has no device plane
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % 256 == 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["count"] >= 4
    if trace:
        assert set(line["metrics"]) == HOST4_REGISTRY_METRICS
    else:
        assert set(line["metrics"]) == {"sim_lookups_per_s",
                                        "sim_wave_p90_ms", "setup_s"}
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}
        assert m["value"] > 0 or (name in NARROW_ROUNDS_METRICS
                                  and m["value"] == 0)


def test_host4_check_fails_a_table_that_is_not_the_seeds(manifest):
    """The ``sorted`` guarantee is checked, not assumed: a built table
    with two rows swapped, one row doubled over another, or a width off
    by one is not ``correct``."""
    import jax.numpy as jnp
    from dhtbench.drivers import sim_tp
    cell, config, driver, _ = run.resolve("host4-100m.wave-65536")
    assert driver is sim_tp
    config = dict(config, sizes=dict(config["sizes"], n_ids=8192))
    st = sim_tp.setup(config, dict(cell["traffic"], wave_targets=64,
                                   target_sets=2), 5, lambda msg: None)
    try:
        assert sim_tp._built_right(st, 8192) is None
        arrays = st.state.arrays
        good = arrays["sorted_ids"]
        swapped = good.at[jnp.array([3, 4])].set(good[jnp.array([4, 3])])
        doubled = good.at[3].set(good[4])
        for bad, why in ((swapped, "not ascending"), (doubled, "checksum")):
            arrays["sorted_ids"] = bad
            assert why in sim_tp._built_right(st, 8192)
        arrays["sorted_ids"] = good
        st.shard_rows = st.shard_rows + np.array([[0, 1], [0, 0], [0, 0],
                                                  [0, 0]])
        assert "expected from" in sim_tp._built_right(st, 8192)
    finally:
        sim_tp.close(st)


@pytest.mark.parametrize("ascending", [False, True])
@pytest.mark.parametrize("blocks", [1, 4, 7])
def test_blockwise_reference_equals_the_whole_table_reference(blocks,
                                                              ascending):
    rng = np.random.default_rng(31)
    ids = rng.integers(0, 2 ** 32, size=(6000, 5), dtype=np.uint32)
    ids[100:140, :2] = ids[100, :2]            # a run that shares 64 bits
    if ascending:           # as a built shard arrives: no argsort is run
        ids = ids[np.lexsort(ids.T[::-1])]
    targets = np.concatenate([
        rng.integers(0, 2 ** 32, size=(12, 5), dtype=np.uint32), ids[100:104]])
    whole = reference.XorIndex(ids)
    edges = np.linspace(0, 6000, blocks + 1).astype(int)
    edges[1:-1] += rng.integers(-50, 50, size=blocks - 1)
    pieces = ((int(a), ids[a:b]) for a, b in zip(edges[:-1], edges[1:]))
    got = reference_blocks.closest_over_blocks(pieces, targets, 8)
    for target, rows in zip(targets, got):
        want = whole.closest(target, 8)
        # equal ids may be picked in either order: compare the distances
        np.testing.assert_array_equal(ids[rows] ^ target, ids[want] ^ target)
    if ascending and blocks > 1:
        # blocks that are ranges of the key space, as the shards of a
        # range-partitioned table are: most targets lie outside a block's
        # range, and are still answered from a handful of candidates —
        # not from the whole block, which at 25M rows a block took eight
        # minutes a run (PERF.md section 6, PR 28)
        block = reference_blocks.BlockIndex(ids[edges[1]:edges[2]])
        outside = [t for t in targets
                   if not (block.key[0] >> np.uint64(32)) <= t[0]
                   <= (block.key[-1] >> np.uint64(32))]
        assert outside
        assert max(len(block.candidates(t, 8)) for t in targets) <= 48
    # a block of fewer than k rows, and an empty one
    tiny = reference_blocks.closest_over_blocks(
        [(0, ids[:3]), (3, ids[3:3]), (3, ids[3:6000])], targets, 8)
    for rows, full in zip(tiny, got):
        np.testing.assert_array_equal(rows, full)


def test_each_stage_metric_file_names_a_stage_of_the_program():
    mdir = os.path.join(run.HERE, "metrics")
    specs = {f[:-len(".json")]: run.load_json(mdir, f)
             for f in sorted(os.listdir(mdir))}
    staged = {name: m["source"] for name, m in specs.items()
              if m["source"]["kind"] == "stage"}
    assert len(staged) == 33
    assert {name: s["stage"] for name, s in staged.items()
            if s["value"] == "stage_ms_per"} == {
        "sim_fetch_ids_ms_per_wave": "fetch_ids",
        "sim_reply_rows_ms_per_wave": "reply_rows",
        "sim_block_bounds_ms_per_wave": "block_bounds",
        "sim_merge_ms_per_wave": "merge",
        "northstar_tile_ms_per_wave": "tile",
        "host4_owner_merge_ms_per_wave": "owner_merge",
        "host4_fetch_ids_ms_per_wave": "fetch_ids",
        "host4_block_bounds_ms_per_wave": "block_bounds",
        "host4_merge_ms_per_wave": "merge",
        "churn_fetch_ids_ms_per_wave": "fetch_ids",
        "churn_expire_ms_per_wave": "expire",
        "churn_delta_window_ms_per_wave": "delta_window",
        "churn_apply_ms_per_wave": "table_apply",
        "churn_compact_ms_per_wave": "table_compact",
        "churn_compact_ms": "table_compact",
        "churn_block_bounds_ms_per_wave": "block_bounds",
        "churn_merge_ms_per_wave": "merge",
        "churn_reply_rows_ms_per_wave": "reply_rows",
        "host4churn_fetch_ids_ms_per_wave": "fetch_ids",
        "host4churn_block_bounds_ms_per_wave": "block_bounds",
        "host4churn_merge_ms_per_wave": "merge",
        "host4churn_owner_merge_ms_per_wave": "owner_merge",
        "host4churn_expire_ms_per_wave": "expire",
        "host4churn_delta_window_ms_per_wave": "delta_window",
        "host4churn_route_ms_per_wave": "table_route",
        "host4churn_apply_ms_per_wave": "table_apply",
        "host4churn_compact_ms_per_wave": "table_compact",
        "host4churn_compact_ms": "table_compact",
        "host4churn_relayout_ms": "table_relayout"}
    # one compaction's time is over the compactions, not over the waves:
    # it does not move with the waves' speed or the window's length
    assert {name for name, s in staged.items() if s.get("per") != "waves"
            and s["value"] == "stage_ms_per"} == {
        "churn_compact_ms", "host4churn_compact_ms", "host4churn_relayout_ms"}
    assert {s["per"] for name, s in staged.items() if s.get("per") != "waves"
            and s["value"] == "stage_ms_per"} == {"compactions"}
    assert {name for name, s in staged.items()
            if s["value"] == "unstaged_share"} \
        == {"sim_unstaged_share", "churn_unstaged_share",
            "host4_unstaged_share", "host4churn_unstaged_share"}
    # every stage a metric names is one the program names: those of the
    # round engine, the tp twin's collective, the churn model's two, the
    # mutable table's two and the sharded mutable table's two more
    from opendht_tpu.core import search
    from opendht_tpu.ops import churn_table
    from opendht_tpu.parallel import sharded
    import inspect
    assert '"owner_merge"' in inspect.getsource(sharded.build_tp_lookup)
    for name in ("expire", "delta_window", "tile"):
        assert f'device_stage("{name}")' in inspect.getsource(search)
    for name in ("table_apply", "table_compact"):
        assert f'device_stage("{name}")' in inspect.getsource(churn_table)
    from opendht_tpu.parallel import churn as sharded_churn
    for name in ("table_route", "table_relayout", "table_apply",
                 "table_compact"):
        assert f'device_stage("{name}")' in inspect.getsource(sharded_churn)
    assert {s["stage"] for s in staged.values() if "stage" in s} \
        <= set(STAGES) | {"owner_merge", "expire", "delta_window",
                          "table_apply", "table_compact", "table_route",
                          "table_relayout", "tile"}
    # the one metric that divides by a stage's time and not the window's
    shares = {name: m["source"] for name, m in specs.items()
              if m["source"]["kind"] == "stage_share"}
    assert {n: s["stage"] for n, s in shares.items()} \
        == {"churn_compact_hbm_share": "table_compact",
            "host4churn_compact_hbm_share": "table_compact"}


# -- PR 32: sim-10m-churn.wave-65536 ------------------------------------------

CHURN_REHEARSAL = {"n_ids": 16384, "wave_targets": 256, "target_sets": 4,
                   "leave_per_tick": 64, "join_per_tick": 64,
                   "delta_rows": 512, "warm_ticks": 5, "schedule_ticks": 600}
# its per-layer metrics that read the program's registry (the trace and
# stage ones read nothing on the CPU)
CHURN_REGISTRY_METRICS = {"churn_expired_peers_per_wave", "churn_tick_ms",
                          "churn_record_ms_per_wave",
                          "churn_dispatch_ms_per_wave",
                          "churn_narrow_rounds_per_wave"}


@pytest.mark.parametrize("trace", [False, True])
def test_churn_cell_at_toy_size_prints_the_contract_line(manifest, trace):
    line = run.run_cell("sim-10m-churn.wave-65536", 2 ** 31 + 32032, 1.0,
                        trace, rehearsal=CHURN_REHEARSAL)
    line = json.loads(json.dumps(line))
    assert set(line) == RESULT_KEYS         # no breakdown: the CPU has no device plane
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % 256 == 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    if trace:
        assert set(line["metrics"]) == CHURN_REGISTRY_METRICS
    else:
        assert set(line["metrics"]) == {"sim_lookups_per_s",
                                        "sim_wave_p90_ms", "setup_s"}
    for name, m in line["metrics"].items():
        # 256 lookups a wave are under the engine's cut: no narrow round
        assert set(m) == {"value", "unit"} and (
            m["value"] > 0 or name == "churn_narrow_rounds_per_wave")


def test_churn_check_fails_a_table_that_lost_or_resurrected_a_node(manifest):
    """The membership guarantee is checked, not assumed: a table whose
    liveness words have lost a departure (a node resurrected), gained
    one (a node lost), or whose delta dropped an arrival is not
    ``correct``; nor is a window without a compaction."""
    import jax.numpy as jnp
    from dhtbench.drivers import sim_churn
    cell, config, driver, _ = run.resolve("sim-10m-churn.wave-65536")
    assert driver is sim_churn
    config = dict(config, sizes={**config["sizes"], **{
        k: v for k, v in CHURN_REHEARSAL.items() if k in config["sizes"]}})
    traffic = {**cell["traffic"], **{
        k: v for k, v in CHURN_REHEARSAL.items() if k in cell["traffic"]}}
    st = sim_churn.setup(config, traffic, 5, lambda msg: None)
    try:
        result = sim_churn.window(st, 0.5)
        result["values"]["compiles_in_window"] = 0
        correct, why = sim_churn.check(st, result)
        assert correct, why
        assert result["values"]["compactions"] >= 1
        assert result["values"]["least_compact_bytes"] == sum(
            [sim_churn.least_compact_bytes(16384, st.table.view.lut.shape[0])]
            * result["values"]["compactions"])
        tbl = st.table
        good = tbl.view
        assert tbl.n_tomb > 0 and tbl.n_delta > 0
        gone = int(np.asarray(good.dead_pos)[0])         # a departed row
        words = np.asarray(good.tomb_bits).copy()
        words[gone >> 5] ^= np.uint32(1 << (gone & 31))
        resurrected = good._replace(tomb_bits=jnp.asarray(words))
        alive = int(np.nonzero(np.asarray(
            sim_churn.live_rows(good)[1])[:tbl.n_base])[0][0])
        words = np.asarray(good.tomb_bits).copy()
        words[alive >> 5] |= np.uint32(1 << (alive & 31))
        lost = good._replace(tomb_bits=jnp.asarray(words))
        dropped = good._replace(n_delta=good.n_delta - 1)
        for bad in (resurrected, lost, dropped):
            tbl.view = bad
            correct, why = sim_churn.check(st, result)
            assert not correct and "membership" in why, why
        tbl.view = good
        assert sim_churn.check(st, result)[0]
        result["values"]["compactions"] = 0
        correct, why = sim_churn.check(st, result)
        assert not correct and "compactions" in why
    finally:
        sim_churn.close(st)


def test_the_churn_book_is_a_plain_set_of_ids():
    from dhtbench import reference_churn
    rng = np.random.default_rng(32)
    book = rng.integers(0, 2 ** 32, size=(500, 5), dtype=np.uint32)
    slots, arrivals = reference_churn.make_schedule(rng, 500, 9, 40)
    leaving = reference_churn.departures(book, slots, arrivals)
    live = {r.tobytes() for r in book}
    for t in range(9):
        assert len(set(slots[t].tolist())) == 40          # no slot twice
        for r in leaving[t]:
            live.remove(r.tobytes())                      # each was alive
        live |= {r.tobytes() for r in arrivals[t]}
        after = reference_churn.book_after(book, slots, arrivals, t + 1)
        assert {r.tobytes() for r in after} == live
    # someone who arrived has departed again
    assert {r.tobytes() for r in leaving.reshape(-1, 5)} \
        & {r.tobytes() for r in arrivals.reshape(-1, 5)}
    # the fingerprint: order-free, and moved by a lost, a doubled or a
    # limb-swapped row
    after = reference_churn.book_after(book, slots, arrivals, 9)
    want = reference_churn.checksum(after).tolist()
    assert reference_churn.checksum(after[::-1]).tolist() == want
    swapped = after.copy()
    swapped[[3, 4], 2] = swapped[[4, 3], 2]
    for bad in (after[1:], np.concatenate([after, after[:1]]), swapped):
        assert reference_churn.checksum(bad).tolist() != want
    # and it is drivers/sim_tp.checksum's arithmetic
    import jax.numpy as jnp
    from dhtbench.drivers.sim_tp import checksum
    assert np.asarray(checksum(jnp.asarray(after),
                               jnp.ones(500, bool))).tolist() == want
    # membership and the exact top-k over the live ids
    live_set = reference_churn.LiveSet(after)
    probe = np.concatenate([after[:5], book[slots[0][:5]]])
    probe[2, 4] ^= 1                                      # 159 bits shared
    assert live_set.holds(probe).tolist() == [
        True, True, False, True, True] + [
        r.tobytes() in live for r in book[slots[0][:5]]]
    target = rng.integers(0, 2 ** 32, size=5, dtype=np.uint32)
    np.testing.assert_array_equal(
        live_set.closest_ids(target, 8),
        after[reference.xor_closest(after, target, 8)])


# -- PR 34: host4-100m-churn.wave-65536 ----------------------------------------

TP_CHURN_REHEARSAL = {"n_ids": 16384, "wave_targets": 256, "target_sets": 4,
                      "leave_per_tick": 64, "join_per_tick": 64,
                      "delta_rows": 128, "warm_ticks": 5,
                      "schedule_ticks": 200}
TP_CHURN_REGISTRY_METRICS = {
    "host4churn_expired_peers_per_wave", "host4churn_tick_ms",
    "host4churn_record_ms_per_wave", "host4churn_dispatch_ms_per_wave",
    "host4churn_narrow_rounds_per_wave",
    "host4churn_window_rounds_per_wave",
    "host4churn_home_lanes_per_wave"}


@pytest.mark.parametrize("trace", [False, True])
def test_tp_churn_cell_at_toy_size_prints_the_contract_line(manifest, trace):
    """The cell on four virtual devices: the sharded table built across
    the mesh, ticked, compacted shard by shard and searched, ends
    ``correct`` (membership and placement range by range, order, the
    sampled closest sets, no departed id returned)."""
    line = run.run_cell("host4-100m-churn.wave-65536", 2 ** 31 + 34034, 1.0,
                        trace, rehearsal=TP_CHURN_REHEARSAL)
    line = json.loads(json.dumps(line))
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % 256 == 0
    if trace:
        assert set(line["metrics"]) == TP_CHURN_REGISTRY_METRICS
    else:
        assert set(line["metrics"]) == {"sim_lookups_per_s",
                                        "sim_wave_p90_ms", "setup_s"}
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and (
            m["value"] > 0 or name == "host4churn_narrow_rounds_per_wave")


def test_every_metric_of_the_tp_churn_cell_is_in_the_manifest(manifest):
    cell = "host4-100m-churn.wave-65536"
    _cell, config, driver, files = run.resolve(cell)
    assert config["driver"] == "sim_tp_churn" and _cell["chips"] == 4
    listed = {m["name"] for m in manifest["per_layer"]
              if cell in m.get("workloads", ())}
    assert listed == set(files) and len(listed) == 23
    assert all(m["cells"] == [cell] for m in files.values())
    for m in manifest["end_to_end"]:
        if m["name"] != "setup_s":
            assert cell in m["workloads"]
    entry = [c for c in manifest["configs"] if c["name"] == config["name"]]
    assert entry[0]["source"] == config["source"] and \
        entry[0]["reduced"] == config["reduced"] == ["chips"]


def test_tp_churn_check_fails_a_table_that_lost_or_misplaced_a_node(manifest):
    """Membership, placement and order are checked, not assumed: a shard
    that lost a departure (a node resurrected), dropped an arrival, or
    holds its rows out of order is not ``correct``; nor is a window
    without a compaction."""
    import jax
    import jax.numpy as jnp
    from dhtbench.drivers import sim_tp_churn
    cell, config, driver, _ = run.resolve("host4-100m-churn.wave-65536")
    assert driver is sim_tp_churn
    config = dict(config, sizes={**config["sizes"], **{
        k: v for k, v in TP_CHURN_REHEARSAL.items() if k in config["sizes"]}})
    traffic = {**cell["traffic"], **{
        k: v for k, v in TP_CHURN_REHEARSAL.items() if k in cell["traffic"]}}
    st = sim_tp_churn.setup(config, traffic, 5, lambda msg: None)
    try:
        result = sim_tp_churn.window(st, 0.5)
        result["values"]["compiles_in_window"] = 0
        correct, why = sim_tp_churn.check(st, result)
        assert correct, why
        v = result["values"]
        assert v["compactions"] >= 1
        lut_entries = st.table.view.arrays["local_lut"].shape[1]
        assert v["least_compact_bytes"] == v["compactions"] * \
            sim_tp_churn.least_compact_bytes(16384, lut_entries, 4)
        assert sim_tp_churn.least_compact_bytes(16384, lut_entries, 4) \
            == 2 * 4096 * 20 + 4 * lut_entries
        tbl = st.table
        good = tbl.table
        assert tbl.n_tomb > 0 and tbl.n_delta > 0

        def place(**leaves):
            return good._replace(**{
                name: jax.device_put(jnp.asarray(value),
                                     getattr(good, name).sharding)
                for name, value in leaves.items()})

        gone = int(np.asarray(good.dead_pos)[0])     # departed, shard 0
        words = np.asarray(good.tomb_bits).copy()
        words[gone >> 5] ^= np.uint32(1 << (gone & 31))
        n_delta = np.asarray(good.n_delta).copy()
        n_delta[1] -= 1
        base = np.asarray(good.base).copy()
        base[[0, 1]] = base[[1, 0]]
        for bad, what in ((place(tomb_bits=words), "checksums"),
                          (place(n_delta=n_delta), "checksums"),
                          (place(base=base), "ascending")):
            tbl._table = bad
            correct, why = sim_tp_churn.check(st, result)
            assert not correct and what in why, why
        tbl._table = good
        assert sim_tp_churn.check(st, result)[0]
        v["compactions"] = 0
        correct, why = sim_tp_churn.check(st, result)
        assert not correct and "compactions" in why
    finally:
        sim_tp_churn.close(st)


def test_the_index_book_and_its_ids():
    """``reference_tp_churn``: ids are a function of an index, the same
    on the host and on the device; the book is indices; the live ids
    near a target answer for the whole live set."""
    import jax.numpy as jnp
    from dhtbench import reference_churn, reference_tp_churn as ref
    from dhtbench.drivers import sim_tp_churn
    from opendht_tpu.parallel.global_sort import dest_shard
    keys = ref.seed_keys(2 ** 31 + 7)
    index = np.arange(20000, dtype=np.uint32)
    ids = ref.ids_of(index, keys)
    np.testing.assert_array_equal(
        np.asarray(sim_tp_churn.ids_of(jnp.asarray(index), keys)), ids)
    assert len({r.tobytes() for r in ids}) == 20000
    assert (ref.seed_keys(3) != keys).any()
    # the owner, written again: the program's splitter
    np.testing.assert_array_equal(ref.key_range(ids[:, 0], 4),
                                  dest_shard(ids[:, 0], 4))
    counts = np.bincount(ref.key_range(ids[:, 0], 4), minlength=4)
    assert abs(counts - 5000).max() < 5 * np.sqrt(20000 * 3 / 16)
    # the book: slots never repeat in a tick, an arrival takes a slot
    rng = np.random.default_rng(34)
    slots = ref.make_slots(rng, 20000 - 360, 9, 40)
    n = 20000 - 360
    book, left = ref.play(n, slots, 9)
    live = set(range(n))
    for t in range(9):
        assert len(set(slots[t].tolist())) == 40
        live -= set(left[t].tolist())
        live |= set(ref.arrivals(n, t, 40).tolist())
    assert set(book.tolist()) == live and len(live) == n
    assert set(left.reshape(-1).tolist()) & set(range(n, n + 360))
    # near a target, the candidates are the whole live set's answer
    alive = ref.ids_of(book, keys)
    whole = reference_churn.LiveSet(alive)
    bits = ref.prefix_bits(n, 8)
    targets = rng.integers(0, 2 ** 32, size=(16, 5), dtype=np.uint32)
    found = np.concatenate([alive[:50], ref.ids_of(left[0][:5], keys)])
    hit = ref.near_buckets(found[:, 0], targets[:, 0], bits)
    near = reference_churn.LiveSet(alive[hit[alive[:, 0] >> 8]])
    assert near.index.ids.shape[0] < n
    assert near.holds(found).tolist() == whole.holds(found).tolist() \
        == [True] * 50 + [False] * 5
    for target in targets:
        np.testing.assert_array_equal(
            ref.closest_ids(near, target, 8, bits),
            whole.closest_ids(target, 8))
    with pytest.raises(RuntimeError):
        ref.closest_ids(reference_churn.LiveSet(alive[:4]), targets[0], 8, 0)
