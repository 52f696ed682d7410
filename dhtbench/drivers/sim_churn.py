"""Driver ``sim_churn``: the lookup simulator over a network whose
membership changes — a closed loop of (tick, wave): before every wave
one simulated second of turnover is applied to a device-resident table
(``core.table.DeviceChurnTable.apply``: departures found by id and
marked, arrivals merged into a delta; the table compacts by itself),
then a wave of lookups runs over it through the public
``core.search.simulate_lookups``, one wave in flight.  Ticks, and the
compactions they trigger, are inside the timed window and inside each
wave's time.

The driver owns the membership schedule and its own BOOK of who is
alive (``reference_churn``): the schedule is a function of the seed and
the tick's index alone, made in set-up and put on the device, so that
between two waves the driver hands over two arrays that are already
there and computes and fetches nothing.  ``check`` holds the program's
table to the book (an order-free checksum of its live rows) and the
last wave's answers to the live set of that wave.

``setup`` -> state, ``window(state, seconds)`` -> result, ``check(state,
result)`` -> (correct, why), ``close(state)``; see dhtbench/README.md."""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

from dhtbench import reference_churn
from dhtbench.drivers import sim
from dhtbench.drivers.sim_tp import checksum
from dhtbench.trace_reduce import WINDOW_SPAN
# at import, not in setup: a program without the mutable table (the
# parent of the PR that brought it) fails here, before it reaches for
# the chip
from opendht_tpu.core.search import simulate_lookups
from opendht_tpu.core.table import MAX_STALE_SHARE, DeviceChurnTable
from opendht_tpu.ops.churn_table import live_rows

LUT_ENTRY_BYTES = 4


def least_compact_bytes(rows_live: int, lut_entries: int) -> int:
    """The bytes a compaction cannot avoid moving: every live row of the
    base and of the delta read once and the new base written once, 20 B
    a row, and the new base's LUT written once.  The shape function of
    the merge kernel, for ``churn_compact_hbm_share``."""
    return 2 * int(rows_live) * sim.ID_BYTES + int(lut_entries) * LUT_ENTRY_BYTES


def setup(config: dict, traffic: dict, seed: int, log) -> SimpleNamespace:
    import jax
    import jax.numpy as jnp
    from opendht_tpu.ops.sorted_table import sort_table
    sizes = config["sizes"]
    n_ids, n_sets = sizes["n_ids"], traffic["target_sets"]
    n_targets = traffic["wave_targets"]
    leave, join = sizes["leave_per_tick"], sizes["join_per_tick"]
    if leave != join:
        raise ValueError("the book keeps the network's size: a tick's "
                         "arrivals take its departures' slots")
    if sizes["max_stale_share"] != MAX_STALE_SHARE:
        raise ValueError(f"the configuration states max_stale_share "
                         f"{sizes['max_stale_share']}, the program's table "
                         f"compacts at {MAX_STALE_SHARE}")

    @jax.jit
    def make(k_ids, k_targets):
        """ids and every target set in ONE executable, on the device."""
        return (jax.random.bits(k_ids, (n_ids, 5), dtype=jnp.uint32),
                tuple(jax.random.bits(jax.random.fold_in(k_targets, i),
                                      (n_targets, 5), dtype=jnp.uint32)
                      for i in range(n_sets)))

    # --seed runs past 2**31 and jax keys take 32 bits
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    table, sets = make(*jax.random.split(key))
    sorted_ids, _perm, n_valid = jax.block_until_ready(sort_table(table))
    del table
    if int(n_valid) != n_ids:
        raise RuntimeError(f"{int(n_valid)} valid rows of {n_ids}")
    t0 = time.perf_counter()
    book = np.asarray(sorted_ids)           # the network as built
    tbl = DeviceChurnTable(sorted_ids, n_valid,
                           delta_capacity=sizes["delta_rows"])
    del sorted_ids
    tbl.compact()                           # warms the compaction's shape
    log(f"sim_churn: {n_ids} ids sorted, table of capacity "
        f"{tbl.view.capacity} + delta {tbl.view.delta_capacity} built and "
        f"compacted once, {n_sets} sets of {n_targets}, "
        f"{time.perf_counter() - t0:.2f}s")

    t0 = time.perf_counter()
    ticks = traffic["schedule_ticks"]
    slots, arrivals = reference_churn.make_schedule(
        np.random.default_rng([seed, 0xC4]), n_ids, ticks, leave)
    leaving = reference_churn.departures(book, slots, arrivals)
    schedule = [(jax.device_put(leaving[t]), jax.device_put(arrivals[t]))
                for t in range(ticks)]
    del leaving
    jax.block_until_ready(schedule)
    log(f"sim_churn: schedule of {ticks} ticks x ({leave} departures, "
        f"{join} arrivals) made and put on the device, "
        f"{time.perf_counter() - t0:.2f}s")

    st = SimpleNamespace(
        config=config, table=tbl, sets=sets, book=book, slots=slots,
        arrivals=arrivals, schedule=schedule, ticks_done=0,
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
        out = simulate_lookups(
            st.table.view, None, sets[i % n_sets], seed=st.base_seed + i,
            k=sizes["k"], alpha=sizes["alpha"],
            search_nodes=sizes["search_nodes"],
            state_limbs=sizes["state_limbs"])
        return jax.block_until_ready(out)

    st.tick, st.wave = tick, wave
    t0 = time.perf_counter()
    for _ in range(traffic["warm_ticks"]):  # the window opens mid-period
        tick()
    log(f"sim_churn: {traffic['warm_ticks']} warm-up ticks "
        f"{time.perf_counter() - t0:.3f}s, the first compiling; table "
        f"{tbl.n_tomb} departed of {tbl.n_base}, delta {tbl.n_delta} "
        "(set-up figure)")
    for i in (1, 2):                      # compile, then one warm wave
        t0 = time.perf_counter()
        wave(-i)
        log(f"sim_churn: warm-up wave {i} {time.perf_counter() - t0:.3f}s "
            "(set-up figure)")
    return st


def window(st, seconds: float) -> dict:
    import jax
    tbl = st.table
    wave_ms, tick_ms, outs = [], [], []
    compactions, compact_bytes, compacted = tbl.compactions, 0, set()
    lut_entries = tbl.view.lut.shape[0]
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        t_start = time.perf_counter()
        t_now = t_start
        while t_now - t_start < seconds:
            before, live = tbl.compactions, tbl.n_live
            st.tick()
            if tbl.compactions != before:
                compact_bytes += least_compact_bytes(live, lut_entries)
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
    expired = [int(e) for _, _, e in outs]
    attempted = hops.shape[0]
    st.last = (st.waves_run - 1, out, st.ticks_done)
    tick_p50 = float(np.median(tick_ms))
    run_p50 = float(np.median(np.subtract(wave_ms, tick_ms)))
    sizes = st.config["sizes"]
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
            "hops_min": int(hops.min()), "hops_max": int(hops.max()),
            "hops_mean": float(hops.mean()),
            "hops_histogram": {int(h): int(c) for h, c in
                               zip(*np.unique(hops, return_counts=True))},
            "expired_per_lookup": float(np.sum(expired)) / attempted,
            "compactions": tbl.compactions - compactions,
            "ticks_done": st.ticks_done,
            "table": {"n_base": tbl.n_base, "departed": tbl.n_tomb,
                      "delta": tbl.n_delta, "delta_departed": tbl.n_delta_gone},
            "least_bytes": sim.least_bytes(int(hops.sum()), sizes["alpha"],
                                           sizes["k"]),
            "least_compact_bytes": compact_bytes}}


def _membership(st, ticks: int, live_book: np.ndarray) -> "str | None":
    """Guarantee ``membership``; ``None`` where it holds."""
    import jax
    ids, live = live_rows(st.table.view)
    got = np.asarray(jax.jit(checksum)(ids, live))
    want = reference_churn.checksum(live_book)
    if not np.array_equal(got, want):
        return (f"after {ticks} ticks and {st.table.compactions} compactions "
                f"the checksum of the table's live rows {got.tolist()} is "
                f"not the book's {want.tolist()}")
    return None


def check(st, result: dict):
    """The configuration's guarantees: membership (the table's live rows
    are the book's), every lookup converged, hops in range, nothing
    compiled in the window, enough compactions inside it, none of the
    last wave's returned ids a departed one, and a seeded sample of its
    closest-k id sets equal to the numpy XOR top-k over the ids alive at
    that wave at the guaranteed rate."""
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
    live_book = reference_churn.book_after(st.book, st.slots, st.arrivals,
                                           ticks)
    broke = _membership(st, ticks, live_book)
    if broke:
        return False, "membership: " + broke
    member_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    targets = np.asarray(st.sets[i % len(st.sets)])
    nodes = np.asarray(out["nodes"])
    if not (nodes >= 0).all():
        return False, "a lookup returned fewer than k nodes"
    found = np.asarray(out["dist"]) ^ targets[:, None, :]   # ids, no encoding
    live_set = reference_churn.LiveSet(live_book)
    stale = int((~live_set.holds(found.reshape(-1, 5))).sum())
    if stale:
        return False, (f"{stale} of the last wave's {found.shape[0]} x "
                       f"{found.shape[1]} returned ids are no live node")
    rng = np.random.default_rng([st.base_seed, i])
    sample = rng.choice(targets.shape[0], replace=False,
                        size=min(g["sample"], targets.shape[0]))
    agree = sum(
        {r.tobytes() for r in found[j]}
        == {r.tobytes() for r in live_set.closest_ids(targets[j], sizes["k"])}
        for j in sample)
    floor = int(np.ceil(g["min_exact_agree"] * len(sample)))
    return agree >= floor, (
        f"wave {i} after {ticks} ticks, {v['compactions']} compactions in "
        f"the window: {agree}/{len(sample)} sampled closest-{sizes['k']} id "
        f"sets equal the numpy XOR top-{sizes['k']} over the "
        f"{live_book.shape[0]} ids alive then (floor {floor}), all "
        f"{found.shape[0] * found.shape[1]} returned ids live, hops "
        f"{v['hops_min']}..{v['hops_max']} mean {v['hops_mean']:.2f}, "
        f"expired peers a lookup {v['expired_per_lookup']:.3f}; membership "
        f"checksum equals the book's ({member_s:.1f}s); reference "
        f"{time.perf_counter() - t0:.1f}s")


def close(st) -> None:
    st.sets = st.table = st.schedule = st.last = st.book = None
    st.slots = st.arrivals = None
