#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the two deployments the device exists for, once each, through
the entry points a user calls, in ONE process (a chip belongs to one
process), and checks every answer against a plain reference:

- *kernels*: every kernel the served and simulated paths pick **by
  platform** (``select="auto"``, ``merge_pack="auto"``, the donating
  re-jit) compiled here and held to the ``sort`` select or to a numpy
  XOR top-k, over a table with tombstones and a populated delta;
- *simulator* (BASELINE config 3): 10M ids, one 65,536-target wave
  through ``core.search.simulate_lookups``, a sample held to
  ``core.search.scalar_lookup`` and to a numpy XOR top-8;
- *served node*: two ``DhtRunner`` on localhost UDP, the server's IPv4
  table ``bulk_load``-ed with 1M ids (the crawler / bootstrap cache
  deployment), find/get replies held to a numpy XOR top-8, then
  put / get / listen through the runner API, with the program's own
  counters proving the device path served;
- *four chips*: only when ``len(jax.devices()) >= 4`` — the same table
  row-sharded over ``t=4``, bit-identical to one chip, and the served
  node with ``Config(resolve_mesh_t=4)``.

Phases are plain functions of their sizes (``tests/test_chip_smoke.py``
calls them tiny on the CPU); ``main()`` alone fixes the real sizes and
demands the chip.  No phase is wrapped in ``try``/``except``: anything
that raises, and any failed assertion, ends the run non-zero with no
result line.  Seconds printed here are SET-UP figures (compile + first
run, then one warm run) for sizing the time limit — never throughput.

    python chip_smoke.py [--seed N]

On success the last two lines of stdout are the report (versions,
per-phase counts and set-up seconds, compile cache, peak HBM), then the
verdict, which carries the device as JAX reports it and nothing else::

    chip_smoke report: {"versions": {...}, "phases": {...}, ...}
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import contextlib
import faulthandler
import json
import logging
import os
import socket
import sys
import threading
import time
from concurrent.futures import Future

import numpy as np

# sizes main() runs — what the deployments' users would call real
SIM_IDS = 10_000_000          # BASELINE.json configs[2]: 10M-node network
SIM_TARGETS = 65_536          # config 3's wave width
SIM_SAMPLE = 128              # targets held to the scalar + numpy references
SERVED_ROWS = 1_000_000       # crawler / bootstrap cache table
SERVED_REQUESTS = 48          # "a few dozen" find_node / get_values
# the slab a SERVED_ROWS NodeTable grows to (capacity doubles from 1024):
# at the served node's own shapes the kernel phase's executables are the
# ones the served phase then runs, and each is compiled once per process
# (a churn resolve takes over a minute to compile on the chip)
KERNEL_ROWS = 1 << 20
KERNEL_SHAPES = ((1, 8),)     # (Q, k): the per-packet handler's resolve

# Closest-8 agreement floors for the simulator sample.  The lookup is a
# randomized process whose terminal set is not unique: the engine hands
# its α reply slots distinct slices of the (α·k)-row terminal window
# while scalar_lookup draws the slice at random, and on ~2% of targets
# a true closest-8 member lies outside that window, so finding it
# depends on the reply stream.  Measured before this smoke existed
# (CPU, N=1e5 and 1e6, 128 targets each): 94.5% agree with
# scalar_lookup, 97.7-98.4% with the exact top-8.  The floors sit
# ≥ 4 binomial σ below those rates at SIM_SAMPLE.
SIM_MIN_SCALAR_AGREE = 0.85
SIM_MIN_EXACT_AGREE = 0.90

# The run must end within 1200 s.  One that would overrun dies here, with
# every thread's stack on stderr, rather than be killed without a word;
# the four-chip phase (a builder's run, on another machine) gets its own.
WATCHDOG_S = 1150

SYNTH_ADDR = ("10.1.2.3", 4567)   # where the loaded (silent) peers "live"
REPLY_DEADLINE = 600.0            # first answer may sit behind a cold compile
OP_DEADLINE = 180.0


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


# ------------------------------------------------------------------ helpers
def xor_closest(ids: np.ndarray, target: np.ndarray, k: int) -> np.ndarray:
    """The plain reference: indices of the ``k`` rows of ``ids`` [N,5]
    (uint32 big-endian limbs) XOR-closest to ``target`` [5], nearest
    first, by full 160-bit lexicographic order.  A 64-bit pre-filter
    keeps every row whose top two limbs do not exceed the k-th smallest
    (a superset of the answer), then the survivors are ordered on all
    five limbs."""
    d0 = ids[:, 0] ^ target[0]
    d1 = ids[:, 1] ^ target[1]
    key = (d0.astype(np.uint64) << np.uint64(32)) | d1.astype(np.uint64)
    if key.shape[0] > k:
        kth = np.partition(key, k - 1)[k - 1]
        cand = np.nonzero(key <= kth)[0]
    else:
        cand = np.arange(key.shape[0])
    d = ids[cand] ^ target[None, :]
    order = np.lexsort((d[:, 4], d[:, 3], d[:, 2], d[:, 1], d[:, 0]))[:k]
    return cand[order]


class CompileLog:
    """Counts executables built (and persistent-cache hits) through
    ``jax.monitoring`` — the smoke's only view into how much compiling a
    window did.  ``backend_compile_duration`` fires once per executable
    whether XLA compiled it or the persistent cache supplied it."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.executables = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.builds: list = []          # (seconds, jitted function's name)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float, **kw) -> None:
        if event == self._COMPILE:
            with self._lock:
                self.executables += 1
                self.seconds += float(duration_secs)
                self.builds.append((float(duration_secs),
                                    str(kw.get("fun_name", "?"))))

    def _event(self, event: str, **_kw) -> None:
        if event == self._HIT:
            with self._lock:
                self.cache_hits += 1

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def mark(self) -> tuple:
        with self._lock:
            return self.executables, self.seconds, self.cache_hits

    def since(self, mark: tuple, slowest: int = 6) -> dict:
        e, s, h = self.mark()
        with self._lock:
            window = sorted(self.builds[mark[0]:e], reverse=True)[:slowest]
        return {"executables": e - mark[0],
                "compile_s": round(s - mark[1], 3),
                "cache_hits": h - mark[2],
                "slowest": [[name, round(sec, 1)] for sec, name in window]}


class _ErrorTrap(logging.Handler):
    """Collects error-level records of the package's loggers: the served
    path is built to survive a failed warmup, a failed wave launch or a
    side plane going dark — and says so in the log.  The smoke turns
    each of those into a failure."""

    def __init__(self):
        super().__init__(level=logging.ERROR)
        self.records: list = []

    def emit(self, record: logging.LogRecord) -> None:
        self.records.append(f"{record.name}: {record.getMessage()}")


@contextlib.contextmanager
def _trap_errors():
    trap = _ErrorTrap()
    root = logging.getLogger("opendht_tpu")
    root.addHandler(trap)
    try:
        yield trap
    finally:
        root.removeHandler(trap)


@contextlib.contextmanager
def _count_lookup_launches():
    """Count every resolve through ``Snapshot``/``ChurnView.lookup_launch``
    — the one seam the sync and the pipelined resolve share (as
    tests/test_live_node_scale.py does).  Yields ``{(view, Q, k): n}``."""
    from opendht_tpu.core import table as table_mod
    calls: dict = {}
    saved = []
    for cls in (table_mod.Snapshot, table_mod.ChurnView):
        orig = cls.lookup_launch

        def counted(self, queries, *, _orig=orig, _name=cls.__name__, **kw):
            key = (_name, int(np.shape(queries)[0]),
                   int(kw.get("k", table_mod.TARGET_NODES)))
            calls[key] = calls.get(key, 0) + 1
            return _orig(self, queries, **kw)

        saved.append((cls, orig))
        cls.lookup_launch = counted
    try:
        yield calls
    finally:
        for cls, orig in saved:
            cls.lookup_launch = orig


def _on_dht_thread(runner, fn, timeout: float):
    """Run ``fn(dht)`` on the runner's DHT thread and return its result
    (exceptions included) — table state is owned by that thread."""
    fut: Future = Future()

    def op(dht):
        try:
            fut.set_result(fn(dht))
        except BaseException as e:          # noqa: BLE001 — re-raised below
            fut.set_exception(e)

    runner._post_node(op, prio=True)
    return fut.result(timeout)


def _wait_for(pred, timeout: float, what: str, poll: float = 0.01) -> float:
    """Bounded wait; returns the seconds waited, raises on timeout."""
    t0 = time.perf_counter()
    while not pred():
        if time.perf_counter() - t0 > timeout:
            raise TimeoutError(f"{what}: not within {timeout:.0f}s")
        time.sleep(poll)
    return time.perf_counter() - t0


def _series_total(series: dict, name: str, **labels) -> float:
    """Sum of every series of family ``name`` (any node label) whose
    labels include ``labels`` — registry keys are ``name{k="v",...}``."""
    total = 0.0
    for key, v in series.items():
        if key != name and not key.startswith(name + "{"):
            continue
        if all(f'{k}="{val}"' in key for k, val in labels.items()):
            total += v["count"] if isinstance(v, dict) else v
    return total


def _peak_hbm() -> list:
    import jax
    out = []
    for d in jax.devices():
        st = d.memory_stats()
        out.append(None if st is None else int(st.get("peak_bytes_in_use", 0)))
    return out


# ============================================================ phase: kernels
def phase_kernels(*, n_rows: int, seed: int, shapes=KERNEL_SHAPES,
                  batches: int = 8) -> dict:
    """Every kernel chosen BY PLATFORM on the served and simulated
    paths, run as this platform picks it, at each ``(Q, k)`` of
    ``shapes`` (``batches`` query batches per shape, one executable),
    over a table and a churn state laid out as ``core.table`` lays them
    out — tombstones and a populated delta slab, which the served phase
    barely has:

    - ``window_topk(select="auto")`` — on TPU the Pallas
      ``lex_topk_select``, which the t-sharded served resolve runs —
      bit for bit against the ``sort`` select;
    - ``churn_lookup_topk(merge_pack="auto")`` — on TPU the lane-packed
      merge — and ``lookup_topk(donate_queries=True)`` — off the CPU a
      donating re-jit — against the numpy XOR top-k over the live rows.

    (Their plain compiled forms, ``merge_pack=1`` and the non-donating
    jit, were compared bit for bit on the chip when this smoke was
    written, see PERF.md; each costs a minute of compile, the numpy
    reference costs none and is the independent one.)"""
    import jax
    import jax.numpy as jnp
    from opendht_tpu.core.table import DELTA_CAP
    from opendht_tpu.ops.sorted_table import (
        _resolve_merge_pack, churn_lookup_topk, expand_table, lookup_topk,
        sort_table, window_topk)

    t0 = time.perf_counter()
    rng = np.random.default_rng([seed, 1])
    ids = rng.integers(0, 2 ** 32, size=(n_rows, 5), dtype=np.uint32)
    # with a validity mask, as NodeTable.snapshot sorts its slab
    sorted_ids, _perm, n_valid = jax.block_until_ready(
        sort_table(jnp.asarray(ids), jnp.asarray(np.ones(n_rows, bool))))
    expanded = jax.block_until_ready(expand_table(sorted_ids))
    sorted_np = np.asarray(sorted_ids)
    # a churn view's state: tombstones over the base + a small delta slab
    dead = rng.choice(n_rows, size=min(500, n_rows // 8), replace=False)
    tomb = np.zeros((n_rows + 31) // 32, np.uint32)
    for p in dead:
        tomb[p >> 5] |= np.uint32(1) << np.uint32(p & 31)
    tomb = jnp.asarray(tomb)
    d_ids = rng.integers(0, 2 ** 32, size=(DELTA_CAP, 5), dtype=np.uint32)
    d_valid = np.arange(DELTA_CAP) < 300
    d_sorted, _dp, d_n = sort_table(jnp.asarray(d_ids), jnp.asarray(d_valid))
    d_expanded = expand_table(d_sorted, stride=32)
    d_sorted_np = np.asarray(d_sorted)
    alive = np.ones(n_rows, bool)
    alive[dead] = False
    live = np.concatenate([sorted_np[alive], d_ids[d_valid]])

    def same(a, b) -> bool:
        return all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(a, b))

    def oracle(pool, q, k):
        rows = [pool[xor_closest(pool, t, k)] for t in q]
        return np.stack(rows), np.stack(rows) ^ q[:, None, :]

    checked = 0
    for q_n, k in shapes:
        for _ in range(batches):
            q = rng.integers(0, 2 ** 32, size=(q_n, 5), dtype=np.uint32)
            auto = window_topk(sorted_ids, n_valid, jnp.asarray(q), k=k)
            plain = window_topk(sorted_ids, n_valid, jnp.asarray(q), k=k,
                                select="sort")
            assert same(auto, plain), ("window_topk auto != sort", q_n, k)

            dist, enc, _cert = churn_lookup_topk(
                sorted_ids, expanded, n_valid, tomb, d_sorted, d_expanded,
                d_n, jnp.asarray(q), k=k)
            enc = np.asarray(enc)
            got = np.where((enc < n_rows)[..., None],
                           sorted_np[np.clip(enc, 0, n_rows - 1)],
                           d_sorted_np[np.clip(enc - n_rows, 0,
                                               DELTA_CAP - 1)])
            want_ids, want_dist = oracle(live, q, k)
            assert np.array_equal(got, want_ids) and np.array_equal(
                np.asarray(dist), want_dist), ("churn_lookup_topk", q_n, k)

            dist, idx, _cert = lookup_topk(
                sorted_ids, n_valid, jnp.asarray(q), k=k, expanded=expanded,
                donate_queries=True)
            want_ids, want_dist = oracle(sorted_np, q, k)
            assert np.array_equal(sorted_np[np.asarray(idx)], want_ids) \
                and np.array_equal(np.asarray(dist), want_dist), \
                ("lookup_topk(donate_queries=True)", q_n, k)
            checked += 3
    return {"n_rows": n_rows, "shapes": [list(s) for s in shapes],
            "comparisons": checked,
            "window_select": ("pallas" if jax.default_backend() == "tpu"
                              else "sort"),
            "merge_pack": {str(k): _resolve_merge_pack("auto", k)
                           for k in (8, 14)},
            "setup_s": round(time.perf_counter() - t0, 2)}


# ========================================================== phase: simulator
def phase_simulator(*, n_ids: int, n_targets: int, n_sample: int,
                    seed: int) -> dict:
    """BASELINE config 3: ``n_ids`` ids of 160 bits from the seed,
    ``sort_table``, one ``n_targets`` wave through the public
    ``simulate_lookups`` with ``baseline_configs.config3``'s arguments
    (k=8, α=3, search set 14, 2-limb state, prebuilt LUT).  Every
    lookup must converge; a sample is held to ``scalar_lookup`` (the
    repo's independent sequential implementation of the same network
    model) and to the exact numpy top-8."""
    import jax
    import jax.numpy as jnp
    from opendht_tpu.core.search import (SEARCH_NODES, scalar_lookup,
                                         simulate_lookups)
    from opendht_tpu.ops.sorted_table import (build_prefix_lut,
                                              default_lut_bits, sort_table)

    k, alpha = 8, 3
    t0 = time.perf_counter()
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    table = jax.random.bits(k1, (n_ids, 5), dtype=jnp.uint32)
    targets = jax.random.bits(k2, (n_targets, 5), dtype=jnp.uint32)
    sorted_ids, _perm, n_valid = jax.block_until_ready(sort_table(table))
    del table
    lut = jax.block_until_ready(build_prefix_lut(
        sorted_ids, n_valid, bits=default_lut_bits(n_ids)))
    load_s = time.perf_counter() - t0

    def wave():
        return jax.block_until_ready(simulate_lookups(
            sorted_ids, n_valid, targets, seed=seed, k=k, alpha=alpha,
            search_nodes=SEARCH_NODES, lut=lut, state_limbs=2))

    t0 = time.perf_counter()
    out = wave()
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = wave()
    warm_s = time.perf_counter() - t0

    nodes = np.asarray(out["nodes"])
    hops = np.asarray(out["hops"])
    n = int(n_valid)
    assert n == n_ids, (n, n_ids)
    assert nodes.shape == (n_targets, k) and hops.shape == (n_targets,)
    assert np.asarray(out["dist"]).shape == (n_targets, k, 5)
    assert bool(np.asarray(out["converged"]).all()), \
        f"{int((~np.asarray(out['converged'])).sum())} lookups did not converge"
    assert ((nodes >= 0) & (nodes < n)).all(), "node rows out of range"
    assert (hops >= 1).all() and (hops < 48).all(), "hop counts out of range"
    # the engine is deterministic in (seed, query, round)
    assert np.array_equal(nodes, np.asarray(again["nodes"]))
    assert np.array_equal(hops, np.asarray(again["hops"]))

    t0 = time.perf_counter()
    ids_np = np.asarray(sorted_ids)
    tgt_np = np.asarray(targets)
    sample = np.linspace(0, n_targets - 1, num=min(n_sample, n_targets)
                         ).astype(np.int64)
    scalar_hops, scalar_agree, exact_agree = [], 0, 0
    for i in sample:
        s_nodes, s_hops, s_conv = scalar_lookup(
            ids_np, n, tgt_np[i], k=k, alpha=alpha,
            search_nodes=SEARCH_NODES,
            rng=np.random.default_rng([seed, int(i)]))
        assert s_conv, f"scalar_lookup did not converge on target {i}"
        scalar_hops.append(s_hops)
        mine = set(nodes[i].tolist())
        scalar_agree += mine == set(s_nodes)
        exact_agree += mine == set(xor_closest(ids_np[:n], tgt_np[i],
                                               k).tolist())
    del ids_np
    m = len(sample)
    p50_engine = float(np.median(hops[sample]))
    p50_scalar = float(np.median(scalar_hops))
    assert abs(p50_engine - p50_scalar) <= 1, (p50_engine, p50_scalar)
    assert scalar_agree >= SIM_MIN_SCALAR_AGREE * m, (scalar_agree, m)
    assert exact_agree >= SIM_MIN_EXACT_AGREE * m, (exact_agree, m)
    return {"n_ids": n_ids, "n_targets": n_targets, "converged": n_targets,
            "p50_hops": float(np.median(hops)), "max_hops": int(hops.max()),
            "sample": m, "p50_hops_sample": p50_engine,
            "p50_hops_scalar": p50_scalar,
            "closest8_agree_scalar": int(scalar_agree),
            "closest8_agree_exact": int(exact_agree),
            "load_sort_lut_s": round(load_s, 2),
            "first_wave_s": round(first_s, 2), "warm_wave_s": round(warm_s, 3),
            "reference_s": round(time.perf_counter() - t0, 2),
            # handed to the four-chip phase, dropped from the printed
            # result: the key of the ids, not the one-chip table
            "_ids_key": k1,
            "_targets": targets, "_nodes": nodes, "_hops": hops}


# ============================================================= phase: served
def phase_served(*, n_rows: int, n_requests: int, seed: int,
                 compile_log: CompileLog, resolve_mesh_t: int = 0) -> dict:
    """Two ``DhtRunner`` on real localhost UDP.  The server's IPv4 table
    is ``bulk_load``-ed with ``n_rows`` ids and warmed up AFTER the load;
    the client bootstraps to it and asks ``n_requests`` find_node /
    get_values whose node sets must equal the numpy XOR top-8 over the
    same ids; then put, get and listen through the runner API on the
    loaded node.  A search over ``n_rows`` silent synthetic peers never
    converges, so answers and value callbacks are counted under bounded
    waits — completion callbacks are not awaited.

    ``resolve_mesh_t >= 2`` serves the clean-snapshot resolves
    row-sharded over that many devices."""
    from opendht_tpu import native, telemetry
    from opendht_tpu.core import table as table_mod
    from opendht_tpu.core.value import Query, Value
    from opendht_tpu.infohash import InfoHash
    from opendht_tpu.net.engine import WANT4
    from opendht_tpu.ops.ids import ids_from_bytes, ids_to_bytes
    from opendht_tpu.ops.sorted_table import _resolve_merge_pack
    from opendht_tpu.runtime.config import Config, NodeStatus
    from opendht_tpu.runtime.runner import DhtRunner, RunnerConfig
    from opendht_tpu.sockaddr import SockAddr

    assert n_rows > table_mod.HOST_SCAN_MAX_ROWS, \
        "a table the host scan would serve proves nothing about the device"
    k = table_mod.TARGET_NODES
    af = socket.AF_INET
    rng = np.random.default_rng([seed, 2])
    ids = rng.integers(0, 2 ** 32, size=(n_rows, 5), dtype=np.uint32)
    ids_raw = ids_to_bytes(ids)
    sid = rng.bytes(20)
    # k-bucket admission: the loaded ids fill every bucket up to
    # ~log2(n_rows/8) of the server, so a random client would only be
    # cached as a replacement candidate.  One sharing 48 leading bits
    # lands in a bucket no loaded id reaches and is inserted — which is
    # what moves the serving view onto the churn path.
    cid = sid[:6] + bytes([sid[6] ^ 0x80]) + rng.bytes(13)
    server_id, client_id = InfoHash(sid), InfoHash(cid)
    targets = [InfoHash(rng.bytes(20)) for _ in range(n_requests)]
    tgt_limbs = ids_from_bytes(b"".join(bytes(t) for t in targets))
    key, hot_key = InfoHash(rng.bytes(20)), InfoHash(rng.bytes(20))

    def reference(i: int, live: np.ndarray) -> list:
        """numpy XOR top-k of target ``i`` over ``live`` [n,5] limbs, as
        the 20-byte ids a reply carries — without the requester itself
        (net/engine.py deserialize_nodes drops its own id)."""
        rows = xor_closest(live, tgt_limbs[i], k)
        found = [r.tobytes() for r in ids_to_bytes(live[rows])]
        return [b for b in found if b != cid]

    reg = telemetry.get_registry()
    before = reg.snapshot()
    out: dict = {"n_rows": n_rows, "resolve_mesh_t": resolve_mesh_t}
    server, client = DhtRunner(), DhtRunner()
    with _trap_errors() as errors, _count_lookup_launches() as launches:
        try:
            # ---- server: stock config but for its id (and the mesh) ----
            server.run(0, RunnerConfig(dht_config=Config(
                node_id=server_id, resolve_mesh_t=resolve_mesh_t)))

            def load_warm_resolve(dht):
                """ONE op on the DHT thread, so no scheduler job runs in
                between: the node's own first search (a few seconds
                after start) asks the closest loaded peers, they stay
                silent, each expiry tombstones a row — and a snapshot
                with pending churn no longer resolves sharded."""
                table = dht.tables[af]
                t_load = time.perf_counter()
                table.bulk_load(ids, dht.scheduler.time(),
                                addrs=SockAddr(*SYNTH_ADDR))
                t_warm = time.perf_counter()
                dht.warmup()        # AFTER the load: this is what compiles
                t_res = time.perf_counter()
                # the per-packet handler's own call, one target at a time
                res = [dht.find_closest_nodes(t, af) for t in targets]
                sharded = bool(table.last_resolve_sharded)
                return (table, [[bytes(nd.id) for nd in nodes]
                                for nodes in res],
                        sharded, dht.resolve_mesh_t(), table.churn_pending,
                        (t_warm - t_load, t_res - t_warm,
                         time.perf_counter() - t_res))

            table, clean, sharded, mesh_t, pending, secs = _on_dht_thread(
                server, load_warm_resolve, 2 * REPLY_DEADLINE)
            out["load_s"], out["warmup_s"], out["clean_resolve_s"] = (
                round(x, 2) for x in secs)
            log(f"served: {n_rows} rows loaded in {out['load_s']}s, "
                f"warmup {out['warmup_s']}s")
            assert len(table) == n_rows and table._snap is not None
            assert pending == 0, "snapshot was not clean before the client"
            for i in range(n_requests):
                assert clean[i] == reference(i, ids), \
                    f"clean-snapshot resolve {i} != numpy XOR top-{k}"
            assert mesh_t == max(1, resolve_mesh_t), (mesh_t, resolve_mesh_t)
            assert sharded == (resolve_mesh_t > 1), (sharded, resolve_mesh_t)
            out["clean_resolve"] = {"answers": n_requests, "sharded": sharded,
                                    "resolve_mesh_t": mesh_t}
            if sharded:
                out["shards"] = _shard_report(
                    table._snap._tp_state[2]["sorted_ids"], resolve_mesh_t)

            # ---- client bootstraps; the server inserts it -> churn view
            window = compile_log.mark()
            t_window = time.perf_counter()
            client.run(0, RunnerConfig(dht_config=Config(node_id=client_id)))
            client.bootstrap("127.0.0.1", server.get_bound_port())
            _wait_for(lambda: client.get_status(af) is NodeStatus.CONNECTED,
                      OP_DEADLINE, "client connecting to the server")
            _wait_for(lambda: table.row_of(client_id) is not None,
                      OP_DEADLINE, "server inserting the client")
            assert table.churn_pending >= 1, \
                "client insert did not enter the churn view"

            answers: dict = {}

            def ask(indices):
                def op(dht):
                    node = dht.engine.cache.get_node(
                        server_id,
                        SockAddr("127.0.0.1", server.get_bound_port()),
                        dht.scheduler.time(), confirm=True)
                    for i in indices:
                        def done(_req, ans, i=i):
                            answers[i] = [bytes(nd.id) for nd in ans.nodes4]
                        if i % 2:
                            dht.engine.send_find_node(
                                node, targets[i], want=WANT4, on_done=done)
                        else:
                            dht.engine.send_get_values(
                                node, targets[i], Query(), want=WANT4,
                                on_done=done)
                _on_dht_thread(client, op, OP_DEADLINE)

            def ask_until_answered(indices, deadline: float) -> int:
                """A request lives 3 attempts of 1 s; a server busy
                compiling outlasts that, so re-ask what is unanswered
                until the bounded wait runs out."""
                t_end = time.perf_counter() + deadline
                rounds = 0
                while True:
                    missing = [i for i in indices if i not in answers]
                    if not missing:
                        return rounds
                    assert time.perf_counter() < t_end, \
                        f"{len(missing)} requests unanswered in {deadline:.0f}s"
                    ask(missing)
                    rounds += 1
                    with contextlib.suppress(TimeoutError):
                        _wait_for(lambda: all(i in answers for i in missing),
                                  4.0, "replies")

            # the first request after the insert waits behind whatever
            # churn-view shape the node has yet to compile (warmup ran on
            # the clean snapshot): set-up time
            t0 = time.perf_counter()
            out["first_reply_rounds"] = ask_until_answered([0], REPLY_DEADLINE)
            out["first_reply_s"] = round(time.perf_counter() - t0, 2)
            t0 = time.perf_counter()
            out["burst_rounds"] = ask_until_answered(
                range(n_requests), REPLY_DEADLINE)
            out["burst_s"] = round(time.perf_counter() - t0, 2)
            # what the server could answer from: the loaded ids and the
            # client, less the rows it has itself expired by now (its
            # own first search asks silent peers — see above)
            dead = _on_dht_thread(
                server, lambda dht: {r.tobytes() for r in ids_to_bytes(
                    table._ids[table._valid & table._expired])}, OP_DEADLINE)
            keep = np.fromiter((r.tobytes() not in dead for r in ids_raw),
                               bool, n_rows) if dead else np.ones(n_rows, bool)
            live = np.concatenate([ids[keep], ids_from_bytes(cid)])
            for i in range(n_requests):
                assert answers[i] == reference(i, live), \
                    f"reply {i} != numpy XOR top-{k} over the same ids"
            out["expired_rows"] = len(dead)
            out["replies"] = n_requests
            log(f"served: {n_requests} find/get replies equal the numpy "
                f"reference (first after {out['first_reply_s']}s)")

            # ---- put / get / listen through the runner API ------------
            got: list = []
            heard: list = []
            t0 = time.perf_counter()
            server.put(key, Value(b"chip-smoke-1", value_id=1))
            server.get(key, lambda vals: got.extend(vals) or True)
            _wait_for(lambda: any(v.data == b"chip-smoke-1" for v in got),
                      OP_DEADLINE, "get reading the put value back")
            token = server.listen(
                key, lambda vals, expired: heard.extend(vals) or True)
            assert token.result(OP_DEADLINE), "listen was shed at admission"
            server.put(key, Value(b"chip-smoke-2", value_id=2))
            _wait_for(lambda: any(v.data == b"chip-smoke-2" for v in heard),
                      OP_DEADLINE, "listen receiving the second put")
            out["put_get_listen_s"] = round(time.perf_counter() - t0, 2)

            # ---- a hot key, so the cache has something to probe: stored
            # through the persistence path (no announce, no listener —
            # only PURE gets are cache-eligible), then read until the
            # keyspace tick calls it hot and a later get is served by it
            server.import_values([(bytes(hot_key), [
                (int(time.time()), Value(b"hot", value_id=3).get_packed())])])
            hot: list = []
            t0 = time.perf_counter()
            hot_gets = 0
            # registry series are process-wide and keyed by node id: a
            # second server on the same seed continues the first's count
            hits0 = server.get_cache()["hits"]
            while server.get_cache()["hits"] == hits0:
                assert time.perf_counter() - t0 < OP_DEADLINE, \
                    "no hot-cache hit: " + json.dumps(server.get_cache())
                server.get(hot_key, lambda vals: hot.extend(vals) or True)
                hot_gets += 1
                time.sleep(0.005)
            assert any(v.data == b"hot" for v in hot)
            out["hot_gets_until_hit"] = hot_gets
            out["hot_s"] = round(time.perf_counter() - t0, 2)
            out["served_window"] = dict(
                compile_log.since(window),
                wall_s=round(time.perf_counter() - t_window, 2))

            # ---- the program's own counters ---------------------------
            snap_ok = _on_dht_thread(
                server, lambda dht: (table._snap is not None
                                     and table._snap.version == table._version),
                OP_DEADLINE)
            assert snap_ok, "snapshot version != table version"
            cache, keyspace, listeners = (server.get_cache(),
                                          server.get_keyspace(),
                                          server.get_listeners())
            out["udp_engine"] = "native" if server._udp is not None \
                else "python"
            out["native_available"] = bool(native.available())
        finally:
            client.join()
            server.join()

    diff = telemetry.snapshot_diff(before, reg.snapshot())
    n_launch = sum(launches.values())
    churn_launch = sum(v for (view, _q, _k), v in launches.items()
                       if view == "ChurnView")
    assert n_launch >= n_requests + 1, (n_launch, n_requests)
    assert churn_launch >= n_requests, \
        "requests after the insert did not resolve on the churn view"
    pack = _resolve_merge_pack("auto", k)
    assert _series_total(diff["counters"], "dht_churn_lookups_total",
                         pack=pack) >= n_requests
    assert _series_total(reg.snapshot()["counters"],
                         "dht_churn_merge_pack_resolved_total",
                         pack=pack) >= 1
    assert _series_total(diff["counters"],
                         "dht_ingest_wave_failures_total") == 0
    assert _series_total(diff["counters"], "dht_ingest_waves_total") >= 1
    # side planes: not dark, and each launched on the device
    assert cache["enabled"] and cache["hits"] > hits0, cache
    assert keyspace["enabled"] and keyspace["observed_total"] >= 1, keyspace
    assert listeners["enabled"] and not listeners["dark"], listeners
    match_launches = _series_total(diff["histograms"],
                                   "dht_listener_match_seconds")
    assert match_launches >= 1, "listener table never launched its match"
    assert not errors.records, errors.records
    out.update({
        "lookup_launches": {f"{v}:Q{q}:k{kk}": c
                            for (v, q, kk), c in sorted(launches.items())},
        "lookup_launch_shapes": len(launches),
        "churn_merge_pack": pack,
        "ingest_waves": int(_series_total(diff["counters"],
                                          "dht_ingest_waves_total")),
        "cache_hits": int(cache["hits"] - hits0),
        "keyspace_observed": int(keyspace["observed_total"]),
        "listener_match_launches": int(match_launches),
        "_clean_answers": clean,
    })
    return out


def _shard_report(arr, t: int) -> list:
    """Nothing here had run on more than one real device: each of the
    ``t`` devices must hold exactly one N/t shard of ``arr``.  Returns
    per-device ``bytes_in_use`` (None where the backend reports no
    memory stats)."""
    shards = arr.addressable_shards
    assert len(shards) == t, (len(shards), t)
    assert len({s.device for s in shards}) == t, "shards share a device"
    report = []
    for s in shards:
        assert s.data.shape[0] * t == arr.shape[0], (s.data.shape, arr.shape)
        st = s.device.memory_stats()
        report.append(None if st is None else int(st["bytes_in_use"]))
    return report


# ========================================================= phase: four chips
def phase_four_chips(*, sim: dict, served: dict, n_rows: int,
                     n_requests: int, seed: int,
                     compile_log: CompileLog) -> dict:
    """The simulator's ids row-sharded over ``make_mesh(4, q=1, t=4)``:
    made again from the same key, each shard's rows on its own chip,
    and sorted ACROSS the mesh by ``parallel.sharded_global_sort`` — the
    one-chip table is not held beside the shards.
    ``parallel.tp_simulate_lookups`` on that state must be bit-identical
    in ``nodes`` and ``hops`` to the one-chip wave ``sim`` holds (what
    ``__graft_entry__.dryrun_multichip`` pins on a CPU mesh).  Then the
    served node with ``Config(resolve_mesh_t=4)``: the same answers as
    ``served`` (t=1) gave on the same seed, resolved sharded on the
    clean snapshot, one N/4 shard per device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    from opendht_tpu.core.search import SEARCH_NODES
    from opendht_tpu.parallel import (make_mesh, sharded_global_sort,
                                      tp_simulate_lookups)

    t = 4
    mesh = make_mesh(t, q=1, t=t)
    t0 = time.perf_counter()
    # the same bits as phase_simulator's table (threefry is partitionable:
    # the values do not depend on the sharding), each row made in place
    ids = jax.jit(
        lambda key: jax.random.bits(key, (sim["n_ids"], 5), dtype=jnp.uint32),
        out_shardings=NamedSharding(mesh, PartitionSpec("t", None)))(
            sim["_ids_key"])
    state = sharded_global_sort(mesh, ids, donate=True)
    del ids
    widths = np.asarray(state.arrays["shard_rows"])[:, 1].tolist()
    assert sum(widths) == sim["n_ids"], (widths, sim["n_ids"])
    shard_bytes = _shard_report(state.arrays["sorted_ids"], t)

    def wave():
        return jax.block_until_ready(tp_simulate_lookups(
            mesh, targets=sim["_targets"], state=state, seed=seed, k=8,
            alpha=3, search_nodes=SEARCH_NODES, state_limbs=2))

    out = wave()
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    wave()
    warm_s = time.perf_counter() - t0
    assert np.array_equal(np.asarray(out["nodes"]), sim["_nodes"]), \
        "t=4 nodes differ from one chip"
    assert np.array_equal(np.asarray(out["hops"]), sim["_hops"]), \
        "t=4 hops differ from one chip"
    assert bool(np.asarray(out["converged"]).all())
    log(f"four chips: t={t} simulator wave bit-identical to one chip")

    served4 = phase_served(n_rows=n_rows, n_requests=n_requests, seed=seed,
                           compile_log=compile_log, resolve_mesh_t=t)
    assert served4["_clean_answers"] == served["_clean_answers"], \
        "t=4 served answers differ from t=1"
    assert served4["clean_resolve"] == {"answers": n_requests,
                                        "sharded": True, "resolve_mesh_t": t}
    return {"t": t, "sim_bit_identical": True,
            "sim_shard_rows": int(state.shard_n),
            "sim_shard_widths": widths,
            "sim_shard_bytes_in_use": shard_bytes,
            "sim_first_wave_s": round(first_s, 2),
            "sim_warm_wave_s": round(warm_s, 3), "served": served4}


# ===================================================================== main
def _public(d: dict) -> dict:
    """A phase's result without its hand-over arrays (``_`` keys)."""
    return {k: (_public(v) if isinstance(v, dict) else v)
            for k, v in d.items() if not k.startswith("_")}


def print_result(device: dict, report: dict) -> None:
    """The end of a run in which every phase passed: the report on one
    line, then — the LAST line of stdout, read by whoever runs the smoke
    — one JSON object with exactly ``ok`` and ``device``
    (``platform``, ``kind``, ``count`` as JAX reports them)."""
    print("chip_smoke report: " + json.dumps(report))
    print(json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}}), flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0,
                   help="every id, target and node id derives from it")
    args = p.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({dev.device_kind}); nothing was run", file=sys.stderr)
        return 1
    # a device_kind the benchmark's peaks table (dhtbench/peaks.json)
    # does not hold raises here, before any work
    from dhtbench.run import peaks_for
    peaks_for(dev.device_kind)
    import jaxlib
    import libtpu
    from opendht_tpu.compile_cache import ensure_compile_cache

    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    cache_dir = ensure_compile_cache()
    compile_log = CompileLog()
    n_dev = len(jax.devices())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev}
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu.__version__}
    log(f"device {device} versions {versions} compile cache {cache_dir}")

    def run_phase(name: str, run) -> dict:
        mark, t0 = compile_log.mark(), time.perf_counter()
        out = run()
        out["phase_wall_s"] = round(time.perf_counter() - t0, 2)
        out["phase_compiles"] = compile_log.since(mark)
        log(f"phase {name} ok (set-up figures, not throughput): "
            + json.dumps(_public(out)))
        return out

    phases = {
        "kernels": run_phase("kernels", lambda: phase_kernels(
            n_rows=KERNEL_ROWS, seed=args.seed)),
        "simulator": run_phase("simulator", lambda: phase_simulator(
            n_ids=SIM_IDS, n_targets=SIM_TARGETS, n_sample=SIM_SAMPLE,
            seed=args.seed)),
        "served": run_phase("served", lambda: phase_served(
            n_rows=SERVED_ROWS, n_requests=SERVED_REQUESTS, seed=args.seed,
            compile_log=compile_log)),
    }
    # facts only a TPU can show
    assert phases["kernels"]["merge_pack"]["8"] == 16, phases["kernels"]
    assert phases["served"]["churn_merge_pack"] == 16

    if n_dev >= 4:
        faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
        four = run_phase("four_chips", lambda: phase_four_chips(
            sim=phases["simulator"], served=phases["served"],
            n_rows=SERVED_ROWS, n_requests=SERVED_REQUESTS, seed=args.seed,
            compile_log=compile_log))
        assert all(b for b in four["sim_shard_bytes_in_use"]), four
        assert all(b for b in four["served"]["shards"]), four
        phases["four_chips"] = four
    else:
        phases["four_chips"] = {"skipped": f"{n_dev} device(s); needs 4"}

    faulthandler.cancel_dump_traceback_later()
    cached = [e.stat().st_size for e in os.scandir(cache_dir)
              if e.is_file()] if os.path.isdir(cache_dir) else []
    print_result(device, {
        "versions": versions, "seed": args.seed,
        "peak_key": dev.device_kind,
        "compile_cache": dict(compile_log.since((0, 0.0, 0)),
                              dir=cache_dir, files=len(cached),
                              bytes=sum(cached)),
        "peak_hbm_bytes": _peak_hbm(),
        "wall_s": round(time.perf_counter() - _T0, 1),
        "phases": _public(phases)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
