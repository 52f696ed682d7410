"""Unified telemetry (ISSUE-3): registry primitives, Prometheus golden,
span timers, scheduler stale-heap compaction, request-lifecycle counters
over the loopback engine harness, the stats islands (get_nodes_stats /
get_node_message_stats), and kernel bit-identity with telemetry on/off."""

import json
import math
import os
import re
import socket

import numpy as np
import pytest

from opendht_tpu import telemetry
from opendht_tpu.infohash import InfoHash
from opendht_tpu.net import EngineCallbacks, NetworkEngine
from opendht_tpu.scheduler import Scheduler
from opendht_tpu.sockaddr import SockAddr
from opendht_tpu.testing.telemetry_smoke import parse_exposition

pytestmark = pytest.mark.quick  # sub-minute smoke tier: -m quick

GOLDENS = os.path.join(os.path.dirname(__file__), "goldens")


# ------------------------------------------------------------ primitives
def test_counter_gauge_label_series():
    reg = telemetry.MetricsRegistry()
    reg.counter("a_total", type="x").inc()
    reg.counter("a_total", type="x").inc(2)
    reg.counter("a_total", type="y").inc()
    reg.gauge("g").set(3)
    reg.gauge("g").inc(2)
    snap = reg.snapshot()
    assert snap["counters"] == {'a_total{type="x"}': 3,
                                'a_total{type="y"}': 1}
    assert snap["gauges"] == {"g": 5}


def test_metric_kind_clash_raises():
    reg = telemetry.MetricsRegistry()
    reg.counter("x_total")
    with pytest.raises(ValueError):
        reg.gauge("x_total")


def test_histogram_buckets_and_quantiles():
    reg = telemetry.MetricsRegistry()
    h = reg.histogram("h_seconds")
    # exact powers of two land in the bucket whose upper bound they are
    h.observe(0.25)
    d = h.to_dict()
    assert d["buckets"] == [[0.25, 1]]
    h.observe_many([0.1] * 99)            # bulk path, same series
    assert h.count == 100
    # ~all mass in (0.0625, 0.125]; p50 interpolates inside it
    assert 0.0625 < h.quantile(0.5) <= 0.125
    assert h.quantile(0.99) <= 0.25
    # zero / negative observations are counted, bucketed lowest
    h.observe(0.0)
    assert h.count == 101


def test_histogram_bulk_matches_scalar():
    a = telemetry.MetricsRegistry().histogram("a")
    b = telemetry.MetricsRegistry().histogram("b")
    vals = [1e-9, 0.001, 0.5, 1.0, 7.0, 1e6, 0.0]
    for v in vals:
        a.observe(v)
    b.observe_many(vals)
    assert a.to_dict() == b.to_dict()


def test_span_times_and_observes():
    reg = telemetry.MetricsRegistry()
    with reg.span("s_seconds", op="t") as sp:
        pass
    assert sp.elapsed >= 0.0
    assert reg.histogram("s_seconds", op="t").count == 1
    # record=False: timing still returned, histogram untouched
    with reg.span("s_seconds", record=False, op="t") as sp2:
        pass
    assert sp2.elapsed >= 0.0
    assert reg.histogram("s_seconds", op="t").count == 1


def test_prometheus_escaping_and_validity():
    reg = telemetry.MetricsRegistry()
    reg.counter("esc_total", path='a"b\\c\nd').inc()
    text = reg.prometheus()
    assert '\\"' in text and "\\\\" in text and "\\n" in text
    parse_exposition(text)                 # grammar-valid


# ---------------------------------------------------------------- golden
def _golden_registry():
    reg = telemetry.MetricsRegistry()
    reg.counter("dht_demo_requests_total", type="ping").inc(3)
    reg.counter("dht_demo_requests_total", type="get").inc()
    reg.gauge("dht_demo_queue_depth").set(7)
    reg.gauge("dht_demo_load", family="ipv4").set(0.5)
    h = reg.histogram("dht_demo_rtt_seconds", type="get")
    for v in (0.0005, 0.003, 0.004, 0.25, 1.5):
        h.observe(v)
    return reg


def test_prometheus_exposition_golden():
    """The text exposition format is a wire contract (scraped by real
    Prometheus servers): pin it byte-for-byte."""
    text = _golden_registry().prometheus()
    path = os.path.join(GOLDENS, "prometheus_stats.txt")
    with open(path) as f:
        assert text == f.read()
    parse_exposition(text)


def test_snapshot_prometheus_same_registry():
    reg = _golden_registry()
    snap = reg.snapshot()
    series = parse_exposition(reg.prometheus())
    for k, v in snap["counters"].items():
        assert series[k] == v
    for k, v in snap["gauges"].items():
        assert series[k] == v
    for k, d in snap["histograms"].items():
        # name{labels} → name_count{labels} (the exposition suffixes the
        # family name, not the labeled series)
        base, _, lbl = k.partition("{")
        suffix = ("{" + lbl) if lbl else ""
        assert series[base + "_count" + suffix] == d["count"]
        assert math.isclose(series[base + "_sum" + suffix], d["sum"])
    json.dumps(snap)


# ------------------------------------------------- scheduler (satellite 3)
def test_scheduler_stale_tracking_and_compaction():
    reg = telemetry.get_registry()
    comp = reg.counter("dht_scheduler_heap_compactions_total")
    c0 = comp.value
    clock = [0.0]
    s = Scheduler(clock=lambda: clock[0])
    # live survivor at the HEAD: the run()-entry drain stops at it, so
    # the 500 stale entries behind it are only removable by compaction
    keep = s.add(1.0, lambda: None)
    jobs = [s.add(1000.0 + i, lambda: None) for i in range(500)]
    for j in jobs:
        j.cancel()
    assert s.stale_entries == 500
    assert len(s._heap) == 501
    s.run()
    # compaction: cancelled entries dropped, live job kept, counted
    assert len(s._heap) == 1 and not s._heap[0][2].cancelled
    assert s.stale_entries == 0
    assert comp.value == c0 + 1
    assert reg.gauge("dht_scheduler_stale_entries").value == 0
    assert not keep.cancelled


def test_scheduler_edit_counts_stale():
    clock = [0.0]
    s = Scheduler(clock=lambda: clock[0])
    j = s.add(100.0, lambda: None)
    j2 = s.edit(j, 200.0)
    assert s.stale_entries == 1                   # old entry left behind
    assert j2 is not None and not j2.cancelled


def test_scheduler_cancel_heavy_heap_bounded():
    """Regression (ISSUE-3 satellite): a cancel-heavy workload must not
    grow the heap unboundedly under lazy deletion."""
    clock = [0.0]
    s = Scheduler(clock=lambda: clock[0])
    for i in range(10_000):
        s.add(5000.0 + i, lambda: None).cancel()
        if i % 100 == 0:
            s.run()
    s.run()
    assert len(s._heap) <= 2 * 100 + 1


def test_scheduler_tick_lag_observed():
    reg = telemetry.get_registry()
    h = reg.histogram("dht_scheduler_tick_lag_seconds")
    n0, s0 = h.count, h.sum
    clock = [0.0]
    s = Scheduler(clock=lambda: clock[0])
    fired = []
    s.add(1.0, lambda: fired.append(1))
    clock[0] = 3.0
    s.run()
    assert fired == [1]
    assert h.count == n0 + 1
    assert h.sum - s0 == pytest.approx(2.0)


# ----------------------------------------- engine lifecycle (tentpole+sat 4)
class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _Net:
    """Minimal two-engine in-memory switch (same shape as the
    test_net_engine harness)."""

    def __init__(self):
        self.clock = _FakeClock()
        self.endpoints = {}
        self.queue = []

    def make_engine(self, name, port, callbacks=None, **kw):
        sched = Scheduler(clock=self.clock)
        addr = SockAddr("10.0.0.%d" % port, 4000 + port)
        eng = NetworkEngine(
            InfoHash.get(name), 0,
            lambda data, dst, a=addr: self.queue.append((data, a, dst)) or 0,
            sched, callbacks or EngineCallbacks(), **kw)
        self.endpoints[addr] = eng
        return eng, addr

    def pump(self, steps=50):
        for _ in range(steps):
            moved = False
            while self.queue:
                data, src, dst = self.queue.pop(0)
                eng = self.endpoints.get(dst)
                if eng is not None:
                    eng.process_message(data, src)
                moved = True
            for eng in self.endpoints.values():
                eng.scheduler.run()
            if not moved and not self.queue:
                break


def _counter_value(name, **labels):
    return telemetry.get_registry().counter(name, **labels).value


def test_request_lifecycle_counters_and_message_stats():
    """Scripted exchange: every RPC type once; asserts BOTH the
    MessageStats island (get_node_message_stats in/out + reset-on-read)
    and the registry mirrors/lifecycle series advanced together.  The
    registry deltas go through ``snapshot_diff`` (ISSUE-4 satellite)
    instead of hand-rolled before/after subtraction."""
    from opendht_tpu.core.value import Query, Value

    reg = telemetry.get_registry()
    before = reg.snapshot()

    net = _Net()
    a, addr_a = net.make_engine("alice", 1)
    b, addr_b = net.make_engine("bob", 2)
    node_b = a.cache.get_node(b.myid, addr_b, 0.0, confirm=True)

    done = []
    a.send_ping(node_b, on_done=lambda r, ans: done.append("ping"))
    a.send_find_node(node_b, InfoHash.get("t"),
                     on_done=lambda r, ans: done.append("find"))
    a.send_get_values(node_b, InfoHash.get("k"), Query(),
                      on_done=lambda r, ans: done.append("get"))
    a.send_listen(node_b, InfoHash.get("k"), Query(), b"token", None,
                  socket_cb=lambda n, m: None)
    a.send_announce_value(node_b, InfoHash.get("k"), Value(b"v"), None,
                          b"token")
    a.send_refresh_value(node_b, InfoHash.get("k"), 1, b"token")
    net.pump()
    assert "ping" in done and "find" in done and "get" in done

    # the island: [ping, find, get, listen, put], reset on read
    assert b.get_node_message_stats(incoming=True) == [1, 1, 1, 1, 1]
    assert b.get_node_message_stats(incoming=True) == [0, 0, 0, 0, 0]
    assert a.get_node_message_stats(incoming=False) == [1, 1, 1, 1, 1]
    assert b.in_stats.refresh == 0          # reset cleared it too

    # the registry mirrors advanced with the island (no reset: the
    # registry is cumulative — Prometheus counters never rewind)
    d = telemetry.snapshot_diff(before, reg.snapshot())
    assert d["counters"]['dht_net_requests_sent_total{type="ping"}'] == 1
    assert d["counters"][
        'dht_net_requests_completed_total{type="ping"}'] == 1
    assert d["counters"][
        'dht_net_messages_total{direction="in",type="ping"}'] == 1
    assert d["counters"][
        'dht_net_messages_total{direction="out",type="put"}'] == 1
    assert d["histograms"]['dht_net_rtt_seconds{type="ping"}']["count"] == 1


def test_request_expiry_and_timeout_counters():
    reg = telemetry.get_registry()
    exp0 = _counter_value("dht_net_requests_expired_total", type="ping")
    to0 = reg.counter("dht_net_request_timeouts_total").value

    net = _Net()
    a, _ = net.make_engine("alice", 1)
    dead = SockAddr("10.0.0.99", 4099)      # nothing listens there
    node = a.cache.get_node(InfoHash.get("ghost"), dead, 0.0, confirm=True)
    expired = []
    a.send_ping(node, on_expired=lambda r, over: expired.append(over))
    for _ in range(8):                      # 3 attempts × 1 s + expiry
        net.clock.t += 1.0
        a.scheduler.run()
    assert True in expired
    assert _counter_value("dht_net_requests_expired_total",
                          type="ping") == exp0 + 1
    # 2 retries after the first attempt
    assert reg.counter("dht_net_request_timeouts_total").value == to0 + 2


def test_rate_limit_drop_counter():
    drops = telemetry.get_registry().counter("dht_net_ratelimit_drops_total")
    d0 = drops.value
    net = _Net()
    a, addr_a = net.make_engine("alice", 1)
    b, _ = net.make_engine("bob", 2, max_req_per_sec=8)  # per-IP = 1/s
    sent = []
    a._send_fn = lambda data, dst: sent.append(data) or 0
    node_b = a.cache.get_node(b.myid, SockAddr("10.0.0.2", 4002), 0.0,
                              confirm=True)
    for _ in range(10):
        a.send_ping(node_b)
    for pkt in sent:
        b.process_message(pkt, addr_a)
    assert drops.value > d0


# -------------------------------------- stats islands tests (satellite 4)
def _mk_dht(**kw):
    from opendht_tpu.runtime.config import Config
    from opendht_tpu.runtime.dht import Dht
    clock = _FakeClock()
    clock.t = 100_000.0
    sched = Scheduler(clock=clock)
    dht = Dht(lambda data, addr: 0, Config(node_id=InfoHash.get("self")),
              sched, has_v4=True, has_v6=False, **kw)
    return dht, clock


def test_get_nodes_stats_field_by_field():
    """(satellite 4) the island checked against a hand-populated table:
    good / dubious / incoming / cached / table_depth / searches /
    node_cache_size each verified independently."""
    from opendht_tpu.core.table import NODE_GOOD_TIME

    dht, clock = _mk_dht()
    af = socket.AF_INET
    table = dht.tables[af]
    now = dht.scheduler.time()

    # 3 good nodes (replied now)
    good_ids = [InfoHash.get("good%d" % i) for i in range(3)]
    for i, h in enumerate(good_ids):
        table.insert(h, SockAddr("10.1.0.%d" % (i + 1), 4000), now,
                     confirm=2)
    # 2 dubious (heard of, never replied)
    for i in range(2):
        table.insert(InfoHash.get("dub%d" % i),
                     SockAddr("10.2.0.%d" % (i + 1), 4000), now, confirm=0)
    # 1 stale: replied long ago -> falls out of the good window
    table.insert(InfoHash.get("old"), SockAddr("10.3.0.1", 4000),
                 now - NODE_GOOD_TIME - 10, confirm=2)
    # 1 incoming: good AND seen (query) after its last reply
    table.insert(good_ids[0], SockAddr("10.1.0.1", 4000), now + 1,
                 confirm=1)

    st = dht.get_nodes_stats(af)
    assert st.good_nodes == 3
    assert st.dubious_nodes == 3            # 2 hearsay + 1 stale replier
    assert st.incoming_nodes == 1
    assert st.get_known_nodes() == 6
    assert st.cached_nodes == 0
    assert st.searches == 0
    assert st.node_cache_size == 0

    # table_depth = deepest occupied bucket + 1
    occ = table.bucket_occupancy()
    expect_depth = int(np.nonzero(occ)[0][-1] + 1)
    assert st.table_depth == expect_depth
    assert st.get_network_size_estimation() == 8 * 2 ** expect_depth

    # a search and an engine-cache node move their gauges
    dht.get(InfoHash.get("needle"), lambda vals: True, lambda ok, ns: None)
    dht.engine.cache.get_node(InfoHash.get("peer"),
                              SockAddr("10.9.0.1", 4000), now, confirm=True)
    st2 = dht.get_nodes_stats(af)
    assert st2.searches == 1
    assert st2.node_cache_size >= 1      # the search interns peers too

    # the dict the proxy's GET / serves carries every field
    d = st2.to_dict()
    for key in ("good", "dubious", "cached", "incoming", "searches",
                "node_cache", "table_depth", "network_size_estimation"):
        assert key in d

    # empty family: all-zero stats, no crash
    st6 = dht.get_nodes_stats(socket.AF_INET6)
    assert st6.good_nodes == 0 and st6.get_known_nodes() == 0


# ------------------------------------------- kernel bit-identity (tentpole)
def _toy_table(seed, N=2048, Q=64):
    rng = np.random.default_rng(seed)
    raw = rng.integers(0, 2 ** 32, (N, 5), dtype=np.uint32)
    ids = raw[np.lexsort([raw[:, i] for i in range(4, -1, -1)])]
    return ids, rng.integers(0, 2 ** 32, (Q, 5), dtype=np.uint32)


def test_simulate_lookups_bitidentical_with_telemetry():
    """Telemetry enabled vs disabled must not change a single bit of the
    search engine's output (host-side envelope only), while the wave
    histograms advance only when enabled."""
    from opendht_tpu.core.search import simulate_lookups

    ids, targets = _toy_table(5)
    N, Q = len(ids), len(targets)

    reg = telemetry.get_registry()
    wave = reg.histogram("dht_search_wave_seconds")
    width = reg.histogram("dht_search_wave_width", mode="single")
    hops_h = reg.histogram("dht_search_hops", mode="single")
    n_wave, n_width, n_hops = wave.count, width.count, hops_h.count

    reg.enabled = True
    out_on = simulate_lookups(ids, N, targets, seed=3)
    assert width.count == n_width + 1
    assert hops_h.count == n_hops + Q
    try:
        reg.enabled = False
        out_off = simulate_lookups(ids, N, targets, seed=3)
        assert width.count == n_width + 1      # no new observations
    finally:
        reg.enabled = True
    for k in ("nodes", "dist", "hops", "converged"):
        assert np.array_equal(np.asarray(out_on[k]),
                              np.asarray(out_off[k])), k


def test_wave_envelope_spans_dispatch_and_record():
    """One wave = one observation in each of the three envelope
    histograms, dispatch + record within the caller's wall time; none
    with the registry disabled, outputs equal."""
    import time
    from opendht_tpu.core.search import simulate_lookups

    ids, targets = _toy_table(6)
    reg = telemetry.get_registry()
    simulate_lookups(ids, len(ids), targets, seed=4)        # compile
    names = ("dht_search_wave_seconds", "dht_search_dispatch_seconds",
             "dht_search_record_seconds")
    hists = [reg.histogram(n, mode="single") for n in names]
    before = [(h.count, h.sum) for h in hists]
    t0 = time.perf_counter()
    out_on = simulate_lookups(ids, len(ids), targets, seed=4)
    wall = time.perf_counter() - t0
    took = [h.sum - s for h, (_, s) in zip(hists, before)]
    assert [h.count - c for h, (c, _) in zip(hists, before)] == [1, 1, 1]
    wave, dispatch, record = took
    assert 0 < dispatch <= wave and record > 0
    assert wave + record <= wall
    try:
        reg.enabled = False
        counts = [h.count for h in hists]
        out_off = simulate_lookups(ids, len(ids), targets, seed=4)
        assert [h.count for h in hists] == counts
    finally:
        reg.enabled = True
    for k in ("nodes", "dist", "hops", "converged"):
        assert np.array_equal(np.asarray(out_on[k]),
                              np.asarray(out_off[k])), k


# ------------------------------------------- device stages, named in the IR
ROUND_STAGES = ["select", "block_bounds", "reply_rows", "fetch_ids",
                "merge", "converge"]


@pytest.fixture(scope="module")
def staged_engine():
    """(lowered text, compiled text) of the wave engine at a toy size."""
    import jax.numpy as jnp
    from opendht_tpu.core.search import _simulate_lookups_jit
    from opendht_tpu.ops.sorted_table import build_prefix_lut, default_lut_bits
    ids, targets = _toy_table(7, N=4096, Q=32)
    lut = build_prefix_lut(jnp.asarray(ids), len(ids),
                           bits=default_lut_bits(len(ids)))
    lowered = _simulate_lookups_jit.lower(
        ids, len(ids), targets, seed=1, k=8, alpha=3, search_nodes=14,
        lut=lut, state_limbs=2)
    return lowered.as_text(), lowered.compile().as_text()


@pytest.fixture(scope="module")
def staged_tp_engine():
    """The same for the table-sharded twin on a t=4 CPU mesh."""
    import jax.numpy as jnp
    from opendht_tpu.parallel import make_mesh, shard_table_state
    from opendht_tpu.parallel.sharded import build_tp_lookup
    mesh = make_mesh(4, q=1, t=4)
    ids, targets = _toy_table(8, N=4096, Q=32)
    state = shard_table_state(mesh, ids, len(ids))
    a = state.arrays
    lowered = build_tp_lookup(mesh, state.shard_n, 32, 8, 3, 14, 48, 2).lower(
        a["sorted_ids"], a["local_lut"], a["block_lut"], a["n_valid"],
        targets, jnp.asarray(1, jnp.int32))
    return lowered.as_text(), lowered.compile().as_text()


@pytest.mark.parametrize("stage", ROUND_STAGES)
def test_stage_is_a_symbol_of_the_lowered_module(staged_engine, stage):
    """The name is in the IR proper — a function symbol, printed with
    debug info off — so it is part of the compile cache's key, which a
    named_scope (a debug location) is not."""
    lowered, _ = staged_engine
    assert "loc(" not in lowered                    # no debug info printed
    assert re.search(r"func\.func private @stage_%s(_\d+)?\(" % stage,
                     lowered)


@pytest.mark.parametrize("stage", ROUND_STAGES)
def test_stage_names_the_operations_of_the_compiled_round(staged_engine,
                                                          stage):
    """Every stage shows in the optimized module's op_names under the
    round loop, and XLA inlined the inner jits: no call is left."""
    _, compiled = staged_engine
    assert re.search(r'op_name="[^"]*/while/body/(?:[^"/]+/)*jit\(stage_%s\)/'
                     % stage, compiled)
    assert not re.search(r"[ =]call\(", compiled)


@pytest.mark.parametrize("stage", ROUND_STAGES + ["owner_merge"])
def test_stage_names_reach_the_four_way_sharded_round(staged_tp_engine,
                                                      stage):
    """The tp twin runs the same engine under shard_map and inherits its
    stages; the round's lane exchange is a stage of its own (PR 38: the
    all-gather of the chunks' index and the all-reduce of the parts,
    cut back to the chunk), each still one operation of the loop body
    that carries the stage's name, no call left."""
    lowered, compiled = staged_tp_engine
    assert re.search(r"func\.func private @stage_%s(_\d+)?\(" % stage,
                     lowered)
    assert re.search(r'op_name="[^"]*/while/body/(?:[^"/]+/)*jit\(stage_%s\)/'
                     % stage, compiled)
    assert not re.search(r"[ =]call\(", compiled)
    for collective in ("all-gather", "all-reduce"):
        assert re.search(collective + r'[^\n]*op_name="[^"]*/while/body/'
                         r'(?:[^"/]+/)*jit\(stage_owner_merge\)/', compiled)


def test_device_stage_names_the_jit_and_nothing_else():
    import jax.numpy as jnp
    double = telemetry.device_stage("merge")(lambda x: x * 2)
    assert double.__name__ == "stage_merge"
    assert int(double(jnp.int32(21))) == 42


# ------------------------------------------------ monitor (satellite 2)
def test_monitor_parse_alerts():
    from opendht_tpu.testing.network_monitor import parse_alerts
    assert parse_alerts(["p95=2.5", "50=1"]) == {95.0: 2.5, 50.0: 1.0}
    assert parse_alerts([]) == {}
    with pytest.raises(ValueError):
        parse_alerts(["p95"])
    with pytest.raises(ValueError):
        parse_alerts(["p101=4"])


# --------------------------------------------------- proxy route (tentpole)
class _StubRunner:
    """The minimum surface DhtProxyServer touches for GET / + /stats."""

    def get_node_id(self):
        return InfoHash.get("stub-node")

    def get_id(self):
        return InfoHash()

    def get_node_stats(self, af):
        raise RuntimeError("no table")

    def get_metrics(self):
        return telemetry.get_registry().snapshot()


def test_proxy_stats_prometheus_route():
    import urllib.request
    from opendht_tpu.proxy.server import DhtProxyServer

    telemetry.get_registry().counter("dht_test_probe_total").inc()
    srv = DhtProxyServer(_StubRunner(), 0)
    try:
        with urllib.request.urlopen(
                "http://127.0.0.1:%d/stats" % srv.port, timeout=10) as r:
            assert r.status == 200
            assert "text/plain" in r.headers.get("Content-Type", "")
            text = r.read().decode()
        series = parse_exposition(text)
        assert series["dht_test_probe_total"] >= 1
        assert series["dht_proxy_requests_total"] >= 1
        assert "dht_proxy_listen_count" in series
        # the JSON STATS island still serves (reference STATS / route)
        req = urllib.request.Request(
            "http://127.0.0.1:%d/" % srv.port, method="STATS")
        with urllib.request.urlopen(req, timeout=10) as r:
            obj = json.loads(r.read())
        assert "requestRate" in obj
    finally:
        srv.stop()


# ------------------------------------------- snapshot_diff edges (round 17)
def test_snapshot_diff_series_only_in_after():
    """A series born between the snapshots diffs against zero — the
    case every overhead driver hits on its first instrumented rep
    (round-17 satellite: snapshot_diff was load-bearing for the paired
    drivers but only exercised indirectly)."""
    reg = telemetry.MetricsRegistry()
    before = reg.snapshot()
    reg.counter("sd_new_total", op="x").inc(7)
    reg.histogram("sd_new_seconds").observe(0.25)
    d = telemetry.snapshot_diff(before, reg.snapshot())
    assert d["counters"]['sd_new_total{op="x"}'] == 7
    assert d["histograms"]["sd_new_seconds"] == {"count": 1, "sum": 0.25}


def test_snapshot_diff_bucket_set_growth():
    """Observations landing in a bucket the ``before`` snapshot never
    had must still produce the right count/sum delta (the diff reads
    count/sum, never assumes matching bucket sets)."""
    reg = telemetry.MetricsRegistry()
    h = reg.histogram("sd_grow_seconds")
    h.observe(0.5)
    before = reg.snapshot()
    h.observe(1e6)          # a brand-new (far) bucket
    h.observe(1e6)
    d = telemetry.snapshot_diff(before, reg.snapshot())
    got = d["histograms"]["sd_grow_seconds"]
    assert got["count"] == 2
    assert got["sum"] == pytest.approx(2e6)
    # bucket sets genuinely differ between the snapshots
    nb = len(reg.snapshot()["histograms"]["sd_grow_seconds"]["buckets"])
    assert nb == 2


def test_snapshot_diff_labeled_series_mismatch():
    """Label sets that exist on only ONE side stay distinct series:
    present-only-in-after diffs against zero, present-only-in-before
    (a registry reset mid-run) surfaces as a NEGATIVE delta rather
    than silently vanishing — the overhead drivers would misattribute
    a whole mode otherwise."""
    reg = telemetry.MetricsRegistry()
    reg.counter("sd_lab_total", mode="a").inc(3)
    before = reg.snapshot()
    reg.reset()                        # zero IN PLACE (test helper)
    reg.counter("sd_lab_total", mode="b").inc(5)
    d = telemetry.snapshot_diff(before, reg.snapshot())
    assert d["counters"]['sd_lab_total{mode="b"}'] == 5
    assert d["counters"]['sd_lab_total{mode="a"}'] == -3
    # zero-delta series are dropped entirely
    reg2 = telemetry.MetricsRegistry()
    reg2.counter("sd_zero_total").inc(2)
    snap = reg2.snapshot()
    d2 = telemetry.snapshot_diff(snap, snap)
    assert d2 == {"counters": {}, "gauges": {}, "histograms": {}}
