"""Driver ``served``: one ``DhtRunner`` on real localhost UDP, stock
``Config`` but for its node id, its IPv4 table ``bulk_load``-ed with ids
from the seed and warmed up AFTER the load — the set-up of
``chip_smoke.phase_served`` — answering the peers of
``drivers/served_peers.py``, which run in a child process without jax.

The peers' ids share the node's first 48 bits, so they land in a k-bucket
no loaded id reaches and the node inserts them: the whole window is then
served from the churn view, the state a live node is always in.  Random
ids would be kept out by k-bucket admission.

``setup`` -> state, ``window(state, seconds)`` -> result, ``check(state,
result)`` -> (correct, why), ``close(state)``; see dhtbench/README.md."""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
from concurrent.futures import Future
from types import SimpleNamespace

import numpy as np

from dhtbench import reference
from dhtbench.trace_reduce import WINDOW_SPAN

SYNTH_ADDR = ("10.1.2.3", 4567)   # where the loaded (silent) peers "live"
COLD_DEADLINE = 900.0             # a first answer may sit behind cold compiles
OP_DEADLINE = 180.0


def on_dht_thread(runner, fn, timeout: float):
    """Run ``fn(dht)`` on the runner's DHT thread, which owns the table."""
    fut: Future = Future()

    def op(dht):
        try:
            fut.set_result(fn(dht))
        except BaseException as e:          # noqa: BLE001 — re-raised by result()
            fut.set_exception(e)

    runner._post_node(op, prio=True)
    return fut.result(timeout)


class ResolveSpans:
    """The benchmark's own span around every resolve, from its own file:
    wraps ``Snapshot``/``ChurnView.lookup_launch`` — the one seam the sync
    and the pipelined resolve share — and times launch to consumed result.
    ``calls``: ``{(view, Q, k): n}``; ``seconds``: their summed wall time."""

    def __init__(self):
        from opendht_tpu.core import table as table_mod
        self.calls: dict = {}
        self.seconds = 0.0
        self._saved = []
        for cls in (table_mod.Snapshot, table_mod.ChurnView):
            orig = cls.lookup_launch
            self._saved.append((cls, orig))
            cls.lookup_launch = self._wrap(orig, cls.__name__,
                                           table_mod.TARGET_NODES)

    def _wrap(self, orig, view: str, default_k: int):
        spans = self

        def timed(self, queries, **kw):
            t0 = time.perf_counter()
            key = (view, int(np.shape(queries)[0]), int(kw.get("k", default_k)))
            spans.calls[key] = spans.calls.get(key, 0) + 1
            pending = orig(self, queries, **kw)
            launched = time.perf_counter() - t0
            finalize = pending._finalize
            if finalize is None:            # already resolved on the host
                spans.seconds += launched
                return pending

            def consumed():
                t1 = time.perf_counter()
                try:
                    return finalize()
                finally:
                    spans.seconds += launched + time.perf_counter() - t1

            pending._finalize = consumed
            return pending
        return timed

    def mark(self) -> tuple:
        return dict(self.calls), self.seconds

    def since(self, mark: tuple) -> tuple:
        calls = {k: n - mark[0].get(k, 0) for k, n in self.calls.items()
                 if n - mark[0].get(k, 0)}
        return calls, self.seconds - mark[1]

    def close(self) -> None:
        for cls, orig in self._saved:
            cls.lookup_launch = orig


def peer_id(server_id: bytes, tail: bytes) -> bytes:
    """Shares the node's first 48 bits and differs at the 49th, as
    ``chip_smoke.phase_served`` builds its client's: all peers land in ONE
    k-bucket (which holds 8), so the node's bucket maintenance resolves one
    target at a time, the shape the handler has already warmed."""
    return server_id[:6] + bytes([server_id[6] ^ 0x80]) + tail[:13]


def live_ids(state) -> np.ndarray:
    """The loaded ids the node has not expired, read on its own thread."""
    def read(dht):
        table = state.table
        return table._ids[table._valid & table._expired].copy()

    def keys(rows):                        # one 20-byte key per [5] uint32 row
        return np.ascontiguousarray(rows).view(np.dtype((np.void, 20))).ravel()

    dead = on_dht_thread(state.server, read, OP_DEADLINE)
    if not len(dead):
        return state.ids
    return state.ids[~np.isin(keys(state.ids), keys(dead))]


def ask_child(state, command: str) -> dict:
    state.child.stdin.write(command + "\n")
    state.child.stdin.flush()
    line = state.child.stdout.readline()
    if not line:
        raise RuntimeError(f"the generator died on {command!r} "
                           f"(exit {state.child.poll()})")
    out = json.loads(line)
    if "error" in out:
        raise RuntimeError(out["error"])
    return out


def setup(config: dict, traffic: dict, seed: int, log) -> SimpleNamespace:
    from opendht_tpu.core import table as table_mod
    from opendht_tpu.infohash import InfoHash
    from opendht_tpu.runtime.config import Config
    from opendht_tpu.runtime.runner import DhtRunner, RunnerConfig
    from opendht_tpu.sockaddr import SockAddr

    sizes = config["sizes"]
    n_rows = sizes["n_rows"]
    if n_rows <= table_mod.HOST_SCAN_MAX_ROWS:
        raise ValueError("a table the host scan would serve proves nothing "
                         "about the device")
    rng = np.random.default_rng([seed, 2])
    ids = rng.integers(0, 2 ** 32, size=(n_rows, 5), dtype=np.uint32)
    sid = rng.bytes(20)
    peers = [peer_id(sid, rng.bytes(13)) for _ in range(traffic["peers"])]
    af = socket.AF_INET

    state = SimpleNamespace(config=config, traffic=traffic, ids=ids,
                            peers=peers, k=table_mod.TARGET_NODES,
                            server=DhtRunner(), spans=ResolveSpans(),
                            child=None, table=None, live_start=None)
    try:
        # stock configuration but for the node id: rate limiter on,
        # request lifetime as upstream
        state.server.run(0, RunnerConfig(dht_config=Config(
            node_id=InfoHash(sid))))

        def load_and_warm(dht):
            """ONE op on the DHT thread, so no scheduler job runs between
            the load and the warm-up (chip_smoke.phase_served)."""
            table = dht.tables[af]
            t0 = time.perf_counter()
            table.bulk_load(ids, dht.scheduler.time(),
                            addrs=SockAddr(*SYNTH_ADDR))
            t1 = time.perf_counter()
            dht.warmup()            # AFTER the load: this is what compiles
            return table, t1 - t0, time.perf_counter() - t1

        state.table, load_s, warm_s = on_dht_thread(
            state.server, load_and_warm, COLD_DEADLINE)
        log(f"served: {n_rows} rows loaded in {load_s:.2f}s, warmup() on the "
            f"clean snapshot {warm_s:.2f}s")

        state.child = subprocess.Popen(
            [sys.executable, "-m", "dhtbench.drivers.served_peers",
             json.dumps({
                 "server_ip": "127.0.0.1",
                 "server_port": state.server.get_bound_port(),
                 "peer_ids": [p.hex() for p in peers],
                 "bind_ips": [f"127.0.0.{2 + i}" for i in range(len(peers))],
                 "seed": seed, "sample": config["guarantees"]["sample"],
                 "resend_s": traffic["resend_s"],
                 "lifetime_s": traffic["lifetime_s"]})],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))))
        intro = ask_child(state, f"intro {COLD_DEADLINE}")
        if not all(intro["per_peer"]):
            raise RuntimeError(f"peers left unanswered: {intro['per_peer']}")
        missing = on_dht_thread(state.server, lambda dht: [
            i for i, p in enumerate(peers)
            if state.table.row_of(InfoHash(p)) is None], OP_DEADLINE)
        if missing or state.table.churn_pending < 1:
            raise RuntimeError(f"peers {missing} were not inserted: the node "
                               "would not serve from the churn view")
        # every shape the window can reach, now on the churn view: the
        # handler's (Q=1, k=8) and the node's own searches' (Q=1, k=14)
        t0 = time.perf_counter()
        on_dht_thread(state.server, lambda dht: dht.warmup(), COLD_DEADLINE)
        log(f"served: {len(peers)} peers introduced in "
            f"{intro['window_s']:.2f}s ({intro['resent']} re-sends), warmup() "
            f"on the churn view {time.perf_counter() - t0:.2f}s")
        warm = ask_child(state, f"run {traffic['warm_s']}")

        def side_jobs(dht):
            """The node's periodic jobs that launch on the device, once
            each, so that their first (compiling) pass is not in the
            window: the keyspace sketch's tick (every 2 s once requests
            were observed) and bucket maintenance (5-25 s apart)."""
            dht.keyspace.tick()
            dht._bucket_maintenance(af)

        on_dht_thread(state.server, side_jobs, COLD_DEADLINE)
        log(f"served: warm-up traffic {warm['answered']} answered, "
            f"{warm['failed']} failed in {warm['window_s']:.2f}s; launches "
            f"so far {_shapes(state.spans.calls)}; udp engine "
            f"{'native' if state.server._udp is not None else 'python'}")
    except BaseException:
        close(state)
        raise
    return state


def _shapes(calls: dict) -> dict:
    return {f"{v}:Q{q}:k{k}": n for (v, q, k), n in sorted(calls.items())}


def window(state, seconds: float) -> dict:
    import jax
    state.live_start = live_ids(state)
    mark = state.spans.mark()
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        res = ask_child(state, f"run {seconds}")
    calls, resolve_s = state.spans.since(mark)
    state.live_end = live_ids(state)
    lat = np.asarray(res["latency_ms"])
    late = np.asarray(res["send_late_ms"])
    attempted = res["answered"] + res["failed"]
    state.sample = res["sample"]
    n_calls = sum(calls.values())
    return {
        "window_s": res["window_s"], "attempted": attempted,
        "failed": res["failed"],
        "end_to_end": {
            "served_ops_per_s": res["answered"] / res["window_s"],
            "served_p95_ms": float(np.percentile(lat, 95))
            if len(lat) else None},
        "values": {
            "requests": attempted, "answered": res["answered"],
            "latency_p50_ms": float(np.median(lat)) if len(lat) else None,
            "resolve_ms": 1e3 * resolve_s / n_calls if n_calls else None,
            "resolves": n_calls, "launches": _shapes(calls),
            "resent": res["resent"],
            "in_flight_at_end": res["in_flight_at_end"],
            "generator_send_late_ms_p50": float(np.median(late)),
            "generator_send_late_ms_max": float(late.max()),
            "generator_busy_share": res["generator_busy_share"],
            "per_peer": res["per_peer"],
            "node_queries_to_peers": res["server_queries"],
            "expired_rows_start": len(state.ids) - len(state.live_start),
            "expired_rows_end": len(state.ids) - len(state.live_end)}}


def check(state, result: dict):
    """Every sampled reply is the XOR top-k of what the node could know
    while it answered (``reference.reply_is_right``)."""
    if not state.sample:
        return False, "no reply to check"
    live_start = {r.tobytes() for r in
                  state.live_start.astype(">u4")}
    wrong = [t for i, t, ids in state.sample
             if not reference.reply_is_right(
                 bytes.fromhex(t), [bytes.fromhex(h) for h in ids],
                 state.peers[i], live_start=live_start,
                 live_end=state.live_end, peers=state.peers, k=state.k)]
    v = result["values"]
    return not wrong, (
        f"{len(state.sample) - len(wrong)}/{len(state.sample)} sampled "
        f"replies equal the numpy XOR top-{state.k}; rows expired before/"
        f"after the window {v['expired_rows_start']}/{v['expired_rows_end']}"
        + (f"; wrong targets {wrong[:3]}" if wrong else ""))


def close(state) -> None:
    """Stop the generator and the node, and wait for both."""
    child = state.child
    if child is not None and child.poll() is None:
        try:
            child.stdin.write("quit\n")
            child.stdin.flush()
            child.wait(10)
        except (OSError, subprocess.TimeoutExpired):
            child.kill()
            child.wait()
    if child is not None:
        child.stdin.close()
        child.stdout.close()
    state.child = None
    if state.server is not None:
        state.server.join()
        state.server = None
    state.spans.close()
