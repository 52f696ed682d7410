"""Driver ``sim_tp_churn``: the lookup simulator over a table that is
ROW-SHARDED over the chips of one host AND changes — the closed loop of
``drivers/sim_churn.py`` (before every wave one tick of turnover, ticks
and the compactions they trigger inside the window and inside each
wave's time) over the table and the mesh of ``drivers/sim_tp.py``: a
``parallel.churn.ShardedChurnTable`` built across the mesh by
``parallel.sharded_global_sort``, searched through the public
``parallel.tp_simulate_lookups(mesh, state=table.view)``, one wave in
flight.

Nothing of table size is ever on the host, in set-up or in the check.
An id is a function of an INDEX (``reference_tp_churn.ids_of``, written
again below for the device), so the ids are made on each shard from
their indices, a tick's batch is made on the device from the indices
the driver's book hands over (a departure is an index the book holds,
an arrival the next unused one), and the book itself — the plain
reference of who is alive — is an array of indices.  The schedule is a
function of seed and tick, made and placed on the chips in set-up; a
tick hands ``table.apply`` two GLOBAL arrays that are already there,
replicated and not routed (finding each id's shard is the program's
work).  ``check`` holds the table to the book key range by key range
(``placement``, ``membership``), to its order (``sorted``) and the last
wave's answers to the ids alive at that wave.

``setup`` -> state, ``window(state, seconds)`` -> result, ``check(state,
result)`` -> (correct, why), ``close(state)``; see dhtbench/README.md."""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

from dhtbench import reference_churn
from dhtbench import reference_tp_churn as ref
from dhtbench.drivers import sim, sim_tp
from dhtbench.drivers.sim_tp import checksum
from dhtbench.trace_reduce import WINDOW_SPAN
# at import, not in setup: a program without the sharded mutable table
# (the parent of the PR that brought it) fails here, before it reaches
# for the chips
from opendht_tpu.core.table import MAX_STALE_SHARE
from opendht_tpu.ops.churn_table import live_rows
from opendht_tpu.parallel import (make_mesh, sharded_global_sort,
                                  tp_simulate_lookups)
from opendht_tpu.parallel.churn import (SHARD_SPECS, ShardedChurnTable,
                                        one_shard)

LUT_ENTRY_BYTES = 4


def least_compact_bytes(rows_live: int, lut_entries: int, chips: int) -> int:
    """The bytes ONE CHIP's compaction cannot avoid moving: its shard's
    live rows (the network's over the chips) read once and the new base
    written once, 20 B a row, and the shard's LUT written once.  The
    shape function of the merge kernel, per chip as a trace's stage
    time is, for ``host4churn_compact_hbm_share``."""
    return (2 * (int(rows_live) // chips) * sim.ID_BYTES
            + int(lut_entries) * LUT_ENTRY_BYTES)


def ids_of(index, keys):
    """``reference_tp_churn.ids_of`` for the device: ``[..., 5]`` uint32
    ids of uint32 indices."""
    import jax.numpy as jnp
    mix = sim_tp._mix
    return jnp.stack(
        [mix(index ^ jnp.uint32(keys[l, 0]))
         ^ mix(index * jnp.uint32(ref.GOLDEN) + jnp.uint32(keys[l, 1]))
         for l in range(5)], axis=-1)


def _programs(mesh, n_ids: int, keys):
    """The benchmark's own device programs: the ids of each shard's
    indices, a tick's batch from its indices, the read-back of the
    table's shards and the book's fingerprints and near-masks."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P
    n_t = mesh.shape["t"]
    rows = -(-n_ids // n_t)
    everywhere = NamedSharding(mesh, P())

    def make_local():
        index = (lax.axis_index("t") * rows
                 + jnp.arange(rows, dtype=jnp.int32)).astype(jnp.uint32)
        return ids_of(index, keys), index < n_ids

    make_ids = jax.jit(jax.shard_map(
        make_local, mesh=mesh, in_specs=(),
        out_specs=(P("t", None), P("t")), check_vma=False))

    @jax.jit
    def make_tick(left, first_arrival):
        """A tick's two batches, replicated: the ids of the indices that
        leave and of the ``left.shape[0]`` next unused ones."""
        new = first_arrival + jnp.arange(left.shape[0], dtype=jnp.uint32)
        return (lax.with_sharding_constraint(ids_of(left, keys), everywhere),
                lax.with_sharding_constraint(ids_of(new, keys), everywhere))

    def owner(limb0):
        # reference_tp_churn.key_range, on the device
        return ((limb0 >> 8) * jnp.uint32(n_t)) >> 24

    def ascending(table, width):
        at = jnp.arange(table.shape[0] - 1, dtype=jnp.int32)
        le = table[:-1, 4] <= table[1:, 4]
        for limb in (3, 2, 1, 0):
            a, b = table[:-1, limb], table[1:, limb]
            le = (a < b) | ((a == b) & le)
        return jnp.all(le | (at + 1 >= width))

    def read_local(table):
        tbl = one_shard(table)
        ids, live = live_rows(tbl)
        placed = jnp.all(~live | (owner(ids[:, 0]) == lax.axis_index("t")))
        return (jnp.stack([ascending(tbl.base, tbl.n_base),
                           ascending(tbl.delta, tbl.n_delta), placed])[None],
                checksum(ids, live)[None])

    def read_table(table):
        return jax.jit(jax.shard_map(
            read_local, mesh=mesh, in_specs=(SHARD_SPECS,),
            out_specs=(P("t", None), P("t", None)), check_vma=False))(table)

    def book_local(index):
        ids = ids_of(index, keys)
        own = owner(ids[:, 0])
        return lax.psum(jnp.stack([checksum(ids, own == r)
                                   for r in range(n_t)]), "t")

    book_sums = jax.jit(jax.shard_map(
        book_local, mesh=mesh, in_specs=(P("t"),), out_specs=P(),
        check_vma=False))

    @jax.jit
    def near(index, hit):
        """Which of the book's indices make an id in a marked bucket."""
        return hit[ids_of(index, keys)[:, 0] >> 8]

    return SimpleNamespace(make_ids=make_ids, make_tick=make_tick,
                           read_table=read_table, book_sums=book_sums,
                           near=near, book_sharding=NamedSharding(mesh, P("t")),
                           everywhere=everywhere)


def setup(config: dict, traffic: dict, seed: int, log) -> SimpleNamespace:
    import jax
    sizes = config["sizes"]
    n_ids, n_sets = sizes["n_ids"], traffic["target_sets"]
    leave, join = sizes["leave_per_tick"], sizes["join_per_tick"]
    if leave != join:
        raise ValueError("the book keeps the network's size: a tick's "
                         "arrivals take its departures' slots")
    if sizes["max_stale_share"] != MAX_STALE_SHARE:
        raise ValueError(f"the configuration states max_stale_share "
                         f"{sizes['max_stale_share']}, the program's table "
                         f"compacts at {MAX_STALE_SHARE}")
    n_t = sizes["mesh_t"]
    if n_ids % n_t:
        raise ValueError(f"{n_ids} ids do not divide over {n_t} shards")
    mesh = make_mesh(sizes["mesh_q"] * n_t, q=sizes["mesh_q"], t=n_t)
    keys = ref.seed_keys(seed)
    prog = _programs(mesh, n_ids, keys)
    _ids, make_sets, _read = sim_tp._programs(
        mesh, n_ids, traffic["wave_targets"], n_sets)

    # --seed runs past 2**31 and jax keys take 32 bits
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    t0 = time.perf_counter()
    ids, valid = prog.make_ids()
    sets = jax.block_until_ready(make_sets(key))
    state = sharded_global_sort(mesh, ids, valid, donate=True)
    del ids, valid
    if int(state.arrays["n_valid"]) != n_ids:
        raise RuntimeError(f"{int(state.arrays['n_valid'])} valid rows of "
                           f"{n_ids}")
    built_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    tbl = ShardedChurnTable(mesh, state, delta_capacity=sizes["delta_rows"])
    del state
    tbl.compact()                           # warms the compaction's shape
    log(f"sim_tp_churn: {n_ids} ids made on {n_t} shards and sorted across "
        f"the mesh {built_s:.2f}s; table of {n_t} shards x (capacity "
        f"{tbl.view.shard_n} + delta {sizes['delta_rows']}) built and "
        f"compacted once {time.perf_counter() - t0:.2f}s; shard (base, "
        f"width) {np.asarray(tbl.view.arrays['shard_rows']).tolist()}, "
        f"block LUT 2^{tbl.view.block_bits}, local LUT 2^{tbl.view.lut_bits}")

    t0 = time.perf_counter()
    ticks = traffic["schedule_ticks"]
    slots = ref.make_slots(np.random.default_rng([seed, 0xC4]), n_ids, ticks,
                           leave)
    _book, left = ref.play(n_ids, slots, ticks)
    del _book
    schedule = [prog.make_tick(
        jax.device_put(left[t], prog.everywhere),
        np.uint32(n_ids + t * join)) for t in range(ticks)]
    del left
    jax.block_until_ready(schedule)
    log(f"sim_tp_churn: schedule of {ticks} ticks x ({leave} departures, "
        f"{join} arrivals) drawn on the host as indices, made ids and "
        f"placed on the chips {time.perf_counter() - t0:.2f}s")

    st = SimpleNamespace(
        config=config, mesh=mesh, table=tbl, sets=sets, keys=keys, prog=prog,
        slots=slots, schedule=schedule, ticks_done=0,
        base_seed=(seed & 0x3FFFFFFF) + 2, waves_run=0, last=None)

    def tick() -> None:
        """The next tick of the schedule (the table may compact first)."""
        if st.ticks_done >= len(st.schedule):
            raise RuntimeError(
                f"the schedule's {len(st.schedule)} ticks are used up: "
                "raise schedule_ticks in the cell's traffic")
        st.table.apply(*st.schedule[st.ticks_done])
        st.ticks_done += 1

    def wave(i: int):
        """Wave ``i``: its target set in turn, and a reply seed of its own
        (a traced argument of the jit, so a new value compiles nothing)."""
        out = tp_simulate_lookups(
            mesh, targets=sets[i % n_sets], state=st.table.view,
            seed=st.base_seed + i, k=sizes["k"], alpha=sizes["alpha"],
            search_nodes=sizes["search_nodes"],
            state_limbs=sizes["state_limbs"])
        return jax.block_until_ready(out)

    st.tick, st.wave = tick, wave
    t0 = time.perf_counter()
    for _ in range(traffic["warm_ticks"]):  # the window opens mid-period
        tick()
    log(f"sim_tp_churn: {traffic['warm_ticks']} warm-up ticks "
        f"{time.perf_counter() - t0:.3f}s, the first compiling; table "
        f"{tbl.n_tomb} departed of {tbl.n_base}, delta {tbl.n_delta} "
        "(set-up figure)")
    for i in (1, 2):                      # compile, then one warm wave
        t0 = time.perf_counter()
        wave(-i)
        log(f"sim_tp_churn: warm-up wave {i} "
            f"{time.perf_counter() - t0:.3f}s (set-up figure)")
    return st


def window(st, seconds: float) -> dict:
    """``drivers/sim_churn.py``'s loop and result keys over the sharded
    table; the two byte counts are PER CHIP (the total over ``mesh_t``),
    as ``drivers/sim_tp.py``'s, since a trace's times are the mean of
    the chips'."""
    import jax
    tbl, sizes = st.table, st.config["sizes"]
    chips = sizes["mesh_t"]
    wave_ms, tick_ms, outs = [], [], []
    compactions, compact_bytes, compacted = tbl.compactions, 0, set()
    lut_entries = tbl.view.arrays["local_lut"].shape[1]
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        t_start = time.perf_counter()
        t_now = t_start
        while t_now - t_start < seconds:
            before, live = tbl.compactions, tbl.n_live
            st.tick()
            if tbl.compactions != before:
                compact_bytes += (tbl.compactions - before) \
                    * least_compact_bytes(live, lut_entries, chips)
                compacted.add(len(wave_ms))
            t_tick = time.perf_counter()
            out = st.wave(st.waves_run)
            st.waves_run += 1
            t_done = time.perf_counter()
            tick_ms.append((t_tick - t_now) * 1e3)
            wave_ms.append((t_done - t_now) * 1e3)      # tick + wave
            # small per-wave arrays only (drivers/sim.py)
            outs.append((out["converged"], out["hops"],
                         out["expired_peers"]))
            t_now = t_done
        window_s = t_now - t_start
    # reduced after the window: the loop itself fetches nothing
    converged = sum(int(np.asarray(c).sum()) for c, _, _ in outs)
    hops = np.concatenate([np.asarray(h) for _, h, _ in outs])
    expired = [int(np.asarray(e).sum()) for _, _, e in outs]
    attempted = hops.shape[0]
    st.last = (st.waves_run - 1, out, st.ticks_done)
    tick_p50 = float(np.median(tick_ms))
    run_ms = np.subtract(wave_ms, tick_ms)
    run_p50 = float(np.median(run_ms))
    # where the p90 sits: with 65 waves and 3 compactions a window it is
    # the fourth slowest iteration that is no compaction, so what makes
    # a wave a few ms slower decides it (PERF.md section 6, PR 34)
    deepest = [int(np.asarray(h).max()) for _, h, _ in outs]
    by_deepest: dict = {}
    for h, ms in zip(deepest, run_ms):
        by_deepest.setdefault(h, []).append(ms)
    since, last = [], None          # ticks since the window's last compaction
    for i in range(len(wave_ms)):
        last = i if i in compacted else last
        since.append(None if last is None else i - last)
    return {
        "window_s": window_s, "attempted": attempted,
        "failed": attempted - converged,
        "end_to_end": {
            "sim_lookups_per_s": converged / window_s,
            "sim_wave_p90_ms": float(np.percentile(wave_ms, 90))},
        "values": {
            "waves": len(wave_ms), "wave_ms_mean": float(np.mean(wave_ms)),
            "wave_ms_p50": float(np.median(wave_ms)),
            "tick_ms_p50": tick_p50,
            "tick_ms_max": float(np.max(tick_ms)),
            # a stall that is no compaction, as (wave, tick ms, wave ms):
            # the waves, less their tick, over 1.25 x the median of those,
            # and the ticks without a compaction over 10 ms past theirs
            "slow_waves": [(i, round(t, 1), round(w - t, 1))
                           for i, (w, t) in enumerate(zip(wave_ms, tick_ms))
                           if w - t > 1.25 * run_p50
                           or (i not in compacted and t > tick_p50 + 10)][:24],
            "compacted_at": sorted(compacted),
            "wave_ms_by_deepest_hops": {
                h: [len(ms), round(float(np.mean(ms)), 3)]
                for h, ms in sorted(by_deepest.items())},
            # the slowest iterations that are no compaction, as (wave,
            # tick ms, wave ms, deepest hops, ticks since a compaction)
            "tail_waves": [(int(i), round(tick_ms[i], 1),
                            round(float(run_ms[i]), 1), deepest[i], since[i])
                           for i in np.argsort(wave_ms)[::-1]
                           if i not in compacted][:10],
            "hops_min": int(hops.min()), "hops_max": int(hops.max()),
            "hops_mean": float(hops.mean()),
            "hops_histogram": {int(h): int(c) for h, c in
                               zip(*np.unique(hops, return_counts=True))},
            "expired_per_lookup": float(np.sum(expired)) / attempted,
            "compactions": tbl.compactions - compactions,
            "ticks_done": st.ticks_done,
            "table": {"n_base": tbl.n_base, "departed": tbl.n_tomb,
                      "delta": tbl.n_delta, "delta_departed": tbl.n_delta_gone,
                      "shard_rows": np.asarray(
                          tbl.view.arrays["shard_rows"]).tolist()},
            "least_bytes": sim.least_bytes(int(hops.sum()), sizes["alpha"],
                                           sizes["k"]) // chips,
            "least_compact_bytes": compact_bytes}}


def _table_right(st, book) -> "str | None":
    """The guarantees ``sorted``, ``placement`` and ``membership``
    against ``book`` (the live indices, placed over the chips); ``None``
    where they hold, else what broke."""
    tbl = st.table
    flags, shard_sums = (np.asarray(x) for x in st.prog.read_table(tbl.table))
    for what, column in (("base rows not ascending", 0),
                         ("delta rows not ascending", 1),
                         ("a live id outside its shard's key range", 2)):
        if not flags[:, column].all():
            return f"{what}: shards {flags[:, column].tolist()}"
    at = 0
    shard_rows = np.asarray(tbl.view.arrays["shard_rows"]).tolist()
    for i, (base, width) in enumerate(shard_rows):
        if base != at or not 0 <= width <= tbl.view.shard_n:
            return f"shard {i} holds rows {base}+{width}, expected from {at}"
        at += width
    if at != int(tbl.view.arrays["n_valid"]) or at != tbl.n_base:
        return f"the shards hold {at} base rows, the table says {tbl.n_base}"
    book_sums = np.asarray(st.prog.book_sums(book))
    if not np.array_equal(shard_sums, book_sums):
        return (f"after {st.ticks_done} ticks and {tbl.compactions} "
                f"compactions the checksums of the shards' live rows "
                f"{shard_sums.tolist()} are not those of the book's key "
                f"ranges {book_sums.tolist()}")
    return None


def check(st, result: dict):
    """The configuration's guarantees: every lookup converged, hops in
    range, nothing compiled in the window, enough compactions inside it;
    after it the table ordered, every live id on the shard of its key
    range and the shards' live rows the book's, range by range; none of
    the last wave's returned ids a departed one, and a seeded sample of
    its closest-k id sets equal to the numpy XOR top-k over the ids
    alive at that wave at the guaranteed rate."""
    import jax
    g, sizes = st.config["guarantees"], st.config["sizes"]
    v = result["values"]
    if v.get("compiles_in_window"):
        raise RuntimeError(f"{v['compiles_in_window']} executable(s) were "
                           "built inside the measured window")
    if result["failed"]:
        return False, f"{result['failed']} lookups did not converge"
    if not g["hops_min"] <= v["hops_min"] <= v["hops_max"] <= g["hops_max"]:
        return False, f"hops {v['hops_min']}..{v['hops_max']} out of range"
    need = max(1, int(g["compactions_per_s"] * result["window_s"]))
    if v["compactions"] < need:
        return False, (f"{v['compactions']} compactions in a window of "
                       f"{result['window_s']:.1f}s, at least {need} asked")
    i, out, ticks = st.last
    if ticks != st.ticks_done:
        return False, "a tick was applied after the last wave"
    t0 = time.perf_counter()
    book, _left = ref.play(sizes["n_ids"], st.slots, ticks)
    book_placed = jax.device_put(book, st.prog.book_sharding)
    broke = _table_right(st, book_placed)
    if broke:
        return False, "table: " + broke
    table_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    targets = np.asarray(st.sets[i % len(st.sets)])
    nodes = np.asarray(out["nodes"])
    if not (nodes >= 0).all():
        return False, "a lookup returned fewer than k nodes"
    found = np.asarray(out["dist"]) ^ targets[:, None, :]   # ids, no encoding
    rng = np.random.default_rng([st.base_seed, i])
    sample = rng.choice(targets.shape[0], replace=False,
                        size=min(g["sample"], targets.shape[0]))
    bits = ref.prefix_bits(book.shape[0], sizes["k"])
    hit = ref.near_buckets(found[:, :, 0].reshape(-1), targets[sample, 0],
                           bits)
    mask = np.asarray(st.prog.near(
        book_placed, jax.device_put(hit, st.prog.everywhere)))
    live_set = reference_churn.LiveSet(ref.ids_of(book[mask], st.keys))
    stale = int((~live_set.holds(found.reshape(-1, 5))).sum())
    if stale:
        return False, (f"{stale} of the last wave's {found.shape[0]} x "
                       f"{found.shape[1]} returned ids are no live node")
    agree = sum(
        {r.tobytes() for r in found[j]}
        == {r.tobytes() for r in ref.closest_ids(live_set, targets[j],
                                                 sizes["k"], bits)}
        for j in sample)
    floor = int(np.ceil(g["min_exact_agree"] * len(sample)))
    return agree >= floor, (
        f"wave {i} after {ticks} ticks, {v['compactions']} compactions in "
        f"the window: {agree}/{len(sample)} sampled closest-{sizes['k']} id "
        f"sets equal the numpy XOR top-{sizes['k']} over the "
        f"{book.shape[0]} ids alive then (read as the {int(mask.sum())} of "
        f"them near a target or a returned id; floor {floor}), all "
        f"{found.shape[0] * found.shape[1]} returned ids live, hops "
        f"{v['hops_min']}..{v['hops_max']} mean {v['hops_mean']:.2f}, "
        f"expired peers a lookup {v['expired_per_lookup']:.3f}; every shard "
        f"ascending, every live id on its key range's shard, the shards' "
        f"checksums equal the book's range by range ({table_s:.1f}s); "
        f"reference {time.perf_counter() - t0:.1f}s")


def close(st) -> None:
    st.sets = st.table = st.schedule = st.last = st.slots = None
    st.tick = st.wave = None
