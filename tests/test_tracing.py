"""Distributed tracing + flight recorder (ISSUE-4): context/wire
primitives, bounded ring, sampling, Chrome/Perfetto export, kernel
bit-identity with tracing on/off, request-lifecycle spans over the
loopback engine harness, cross-node span assembly over a real UDP
cluster, the proxy ``GET /trace`` route, and ``snapshot_diff``."""

import json

import numpy as np
import pytest

from opendht_tpu import telemetry, tracing
from opendht_tpu.infohash import InfoHash
from opendht_tpu.net import EngineCallbacks, NetworkEngine
from opendht_tpu.scheduler import Scheduler
from opendht_tpu.sockaddr import SockAddr
from opendht_tpu.testing.trace_assembler import (_wait_connected,
                                                 assemble_trace, check_tree,
                                                 collect_spans)

pytestmark = pytest.mark.quick


# ------------------------------------------------------------- primitives
def test_context_wire_roundtrip():
    ctx = tracing.TraceContext.new_root()
    assert ctx.sampled
    back = tracing.decode_wire(ctx.to_wire())
    assert (back.trace_id, back.span_id, back.flags) == \
        (ctx.trace_id, ctx.span_id, ctx.flags)
    child = ctx.child()
    assert child.trace_id == ctx.trace_id
    assert child.span_id != ctx.span_id
    assert not tracing.TraceContext.new_root(sampled=False).sampled


def test_decode_wire_bounded():
    good = {"i": b"\x01" * 16, "s": b"\x02" * 8, "f": 3}
    assert tracing.decode_wire(good) is not None
    for bad in (None, 7, "x", b"\x00" * 26, [1], {},
                {"i": b"\x01" * 16}, {"s": b"\x02" * 8},
                {"i": b"\x01" * 16, "s": b"\x02" * 8, "f": []},
                {"i": b"\x01" * 1000000, "s": b"\x02" * 8},
                {"i": b"\x00" * 16, "s": b"\x02" * 8}):
        assert tracing.decode_wire(bad) is None, repr(bad)[:40]


def test_ring_bounded_and_oldest_evicted():
    tr = tracing.Tracer(capacity=32, node="n")
    for i in range(100):
        tr.event("e", i=i)
    recs = tr.records()
    assert len(recs) == 32
    assert min(r["attrs"]["i"] for r in recs) == 68   # oldest evicted
    tr.clear()
    assert not tr.records()


def test_span_nesting_and_ambient_context():
    tr = tracing.Tracer(node="n")
    assert tracing.current() is None
    with tr.span("outer", kind="client") as outer:
        assert tracing.current() is outer.ctx
        with tr.span("inner", parent=tracing.current()) as inner:
            assert inner.ctx.trace_id == outer.ctx.trace_id
    assert tracing.current() is None
    spans = tr.spans(outer.ctx.trace_id)
    assert {s["name"] for s in spans} == {"outer", "inner"}
    by = {s["name"]: s for s in spans}
    assert by["inner"]["parent_id"] == by["outer"]["span_id"]
    assert by["outer"]["parent_id"] is None
    assert by["inner"]["start"] >= by["outer"]["start"]


def test_sampling_disabled_and_rate_limited():
    tr = tracing.Tracer(node="n")
    tr.enabled = False
    assert not tr.span("x")
    assert tr.record("x", 0.0, 1.0) is None
    tr.event("x")
    assert not tr.records()
    tr.enabled = True
    tr.set_sample_rate(0.0)
    assert not tr.span("x")                   # roots rejected
    parent = tracing.TraceContext.new_root()
    assert tr.span("x", parent=parent)        # children follow the flag
    tr.set_sample_rate(None)
    assert tr.span("x")
    # unsampled parent → no child span
    cold = tracing.TraceContext.new_root(sampled=False)
    assert not tr.span("x", parent=cold)


def test_run_with_and_activate():
    ctx = tracing.TraceContext.new_root()
    got = tracing.run_with(ctx, tracing.current)
    assert got is ctx and tracing.current() is None
    with tracing.activate(ctx):
        with tracing.activate(None):          # explicit clearing
            assert tracing.current() is None
        assert tracing.current() is ctx


# ----------------------------------------------------------- chrome export
def test_chrome_trace_fields_and_roundtrip():
    tr = tracing.Tracer(node="node-a")
    with tr.span("dht.op.get", kind="client", op="get") as sp:
        tr.record("dht.search.wave", sp.start, 0.001, parent=sp.ctx,
                  node="node-b", width=64)
    tr.event("request_timeout", type="get", tid=7)
    dump = tracing.to_chrome_trace(tr.records())
    back = json.loads(json.dumps(dump))
    evs = back["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == 2
    for e in xs:
        for field in ("name", "pid", "tid", "ts", "dur", "args"):
            assert field in e
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["dur"] >= 0
    # one pid per node, named via metadata
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert names == {"node-a", "node-b"}
    assert {e["pid"] for e in xs} == {1, 2}
    # the instant event
    assert any(e["ph"] == "i" and e["name"] == "request_timeout"
               for e in evs)


# ------------------------------------------- kernel bit-identity (tentpole)
def test_simulate_lookups_bitidentical_with_tracing():
    """Tracing on (ambient sampled context active) vs tracer disabled
    must not change a single bit of the search engine's output — the
    wave span is recorded from the host envelope AFTER the
    compiled computation.  Untraced waves (no ambient context) record
    NOTHING, so bench loops cannot churn the flight-recorder ring."""
    from opendht_tpu.core.search import simulate_lookups

    rng = np.random.default_rng(11)
    N, Q = 2048, 64
    raw = rng.integers(0, 2 ** 32, (N, 5), dtype=np.uint32)
    ids = raw[np.lexsort([raw[:, i] for i in range(4, -1, -1)])]
    targets = rng.integers(0, 2 ** 32, (Q, 5), dtype=np.uint32)

    tr = tracing.get_tracer()
    tr.clear()
    tr.enabled = True
    root = tracing.TraceContext.new_root()
    with tracing.activate(root):
        out_on = simulate_lookups(ids, N, targets, seed=3)
    waves = [s for s in tr.spans(root.trace_id)
             if s["name"] == "dht.search.wave"]
    assert len(waves) == 1
    assert waves[0]["attrs"]["width"] == Q
    assert waves[0]["parent_id"] == root.span_hex
    # one span a wave: what it records was timed; the deepest lookup's
    # rounds ride it as an attribute
    assert waves[0]["attrs"]["rounds"] == int(np.asarray(out_on["hops"]).max())
    assert [s["name"] for s in tr.spans(root.trace_id)] == ["dht.search.wave"]
    # enabled tracer, no ambient context: ring stays untouched
    n_spans = len(tr.records())
    out_plain = simulate_lookups(ids, N, targets, seed=3)
    assert len(tr.records()) == n_spans
    try:
        tr.enabled = False
        with tracing.activate(tracing.TraceContext.new_root()):
            out_off = simulate_lookups(ids, N, targets, seed=3)
        assert len(tr.records()) == n_spans       # nothing recorded
    finally:
        tr.enabled = True
    for k in ("nodes", "dist", "hops", "converged"):
        a = np.asarray(out_on[k])
        assert np.array_equal(a, np.asarray(out_off[k])), k
        assert np.array_equal(a, np.asarray(out_plain[k])), k


# ------------------------------------ engine lifecycle over loopback harness
class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _Net:
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


def test_rpc_spans_client_server_pair():
    tr = tracing.get_tracer()
    tr.clear()
    net = _Net()
    a, _ = net.make_engine("alice", 1)
    b, addr_b = net.make_engine("bob", 2)
    node_b = a.cache.get_node(b.myid, addr_b, 0.0, confirm=True)
    root = tracing.TraceContext.new_root()
    done = []
    with tracing.activate(root):
        a.send_ping(node_b, on_done=lambda r, m: done.append(1))
    net.pump()
    assert done
    spans = tr.spans(root.trace_id)
    by = {s["name"]: s for s in spans}
    assert set(by) == {"dht.rpc.ping", "dht.server.ping"}
    client, server = by["dht.rpc.ping"], by["dht.server.ping"]
    assert client["parent_id"] == root.span_hex
    assert server["parent_id"] == client["span_id"]
    assert client["kind"] == "client" and server["kind"] == "server"
    assert client["node"] == str(a.myid) and server["node"] == str(b.myid)
    assert client["attrs"]["outcome"] == "completed"
    # client span covers the whole RTT: it cannot end before the server
    # span started (same process clock)
    assert client["dur"] >= server["dur"] * 0.5


def test_expired_request_closes_span_and_records_event():
    tr = tracing.get_tracer()
    tr.clear()
    net = _Net()
    a, _ = net.make_engine("alice", 1)
    ghost = a.cache.get_node(InfoHash.get("ghost"),
                             SockAddr("10.0.0.99", 4099), 0.0, confirm=True)
    root = tracing.TraceContext.new_root()
    with tracing.activate(root):
        a.send_ping(ghost)
    for _ in range(8):
        net.clock.t += 1.0
        a.scheduler.run()
    spans = tr.spans(root.trace_id)
    assert len(spans) == 1
    assert spans[0]["attrs"]["outcome"] == "expired"
    assert spans[0]["attrs"]["attempts"] >= 3
    evs = {e["ev"] for e in tr.events()}
    assert "request_expired" in evs
    assert "request_timeout" in evs


def test_untraced_traffic_records_nothing():
    tr = tracing.get_tracer()
    tr.clear()
    net = _Net()
    a, _ = net.make_engine("alice", 1)
    b, addr_b = net.make_engine("bob", 2)
    node_b = a.cache.get_node(b.myid, addr_b, 0.0, confirm=True)
    a.send_ping(node_b)
    net.pump()
    assert not tr.spans()


# ------------------------------------------------ cross-node assembly (sat)


def test_cross_node_span_assembly_udp_cluster():
    """Boot a real-UDP cluster, run one traced put+get, assert the
    assembled tree: client op spans → per-hop rpc spans → remote server
    spans, monotone timestamps, ≥3 contributing nodes, and the Chrome
    dump round-trips with the exact Perfetto fields."""
    from opendht_tpu.core.value import Value
    from opendht_tpu.testing.dhtcluster import NodeCluster

    tr = tracing.get_tracer()
    tr.clear()
    net = NodeCluster()
    try:
        net.resize(5)
        assert _wait_connected(net.nodes)
        key = InfoHash.get("traced-op")
        root = tracing.TraceContext.new_root()
        with tracing.activate(root):
            assert net.nodes[-1].put_sync(key, Value(b"t"), timeout=20.0)
            vals = net.nodes[-1].get_sync(key, timeout=20.0)
        assert any(v.data == b"t" for v in vals)

        tree = assemble_trace(net.nodes, root.trace_id)
        assert tree["trace_id"] == root.trace_hex
        assert tree["spans"] >= 5
        contributing = [n for n in tree["nodes"] if n]
        assert len(contributing) >= 3, contributing
        assert check_tree(tree) == []
        # the roots under the user's ambient context are the two op spans
        root_ops = sorted(r["name"] for r in tree["roots"]
                          if r["name"].startswith("dht.op."))
        assert root_ops == ["dht.op.get", "dht.op.put"]
        for r in tree["roots"]:
            if r["name"].startswith("dht.op."):
                assert r["parent_id"] == root.span_hex
                assert r["attrs"]["ok"] is True
        # every node's own get_trace view feeds the same assembly
        assert collect_spans([net.nodes[0]], root.trace_id)

        # chrome dump round-trip with the exact Perfetto fields
        dump = tracing.to_chrome_trace(
            collect_spans(net.nodes, root.trace_id))
        back = json.loads(json.dumps(dump))
        xs = [e for e in back["traceEvents"] if e["ph"] == "X"]
        assert len(xs) == tree["spans"]
        for e in xs:
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
            assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert len({e["pid"] for e in xs}) >= 3       # one pid per node
    finally:
        net.close()


def test_reused_search_does_not_leak_finished_trace():
    """Review regression: a Search reused by a later UNTRACED op must
    drop the earlier op's context — otherwise the new op's RPCs record
    into (and wire-propagate) a trace that already ended."""
    import socket as _socket
    from opendht_tpu.runtime.config import Config
    from opendht_tpu.runtime.dht import Dht

    clock = _FakeClock()
    clock.t = 100_000.0
    dht = Dht(lambda data, addr: 0, Config(node_id=InfoHash.get("self")),
              Scheduler(clock=clock), has_v4=True, has_v6=False)
    key = InfoHash.get("reused")
    root = tracing.TraceContext.new_root()
    with tracing.activate(root):
        dht.get(key, lambda vals: True, lambda ok, ns: None)
    sr = dht.searches[_socket.AF_INET][key]
    assert sr.trace_ctx is root
    dht.get(key, lambda vals: True, lambda ok, ns: None)   # untraced
    assert dht.searches[_socket.AF_INET][key] is sr        # reused
    assert sr.trace_ctx is None                            # cleared


def test_scanner_topology_snapshot():
    """ISSUE-4 satellite: dhtscanner's per-node snapshot is JSON-able
    and carries routing/bucket/storage/flight-recorder sections."""
    from opendht_tpu.core.value import Value
    from opendht_tpu.testing.dhtcluster import NodeCluster
    from opendht_tpu.tools.dhtscanner import topology_snapshot

    net = NodeCluster()
    try:
        net.resize(3)
        assert _wait_connected(net.nodes)
        assert net.nodes[1].put_sync(InfoHash.get("snap"), Value(b"x"),
                                     timeout=20.0)
        snap = topology_snapshot(net.nodes[0])
        json.dumps(snap)
        assert len(snap["node_id"]) == 40
        assert snap["known_nodes"] >= 2
        assert sum(snap["bucket_fill"]) >= 2
        assert snap["routing"]["ipv4"]["good"] >= 0
        assert "keys" in snap["storage"]
        assert isinstance(snap["events"], list)
        # round-10 maintenance stats ride the snapshot for soak-diffing
        assert isinstance(snap["maintenance"], dict)
        assert all(k.startswith("dht_maintenance_")
                   for k in snap["maintenance"])
    finally:
        net.close()


# --------------------------------------------------------- proxy route
class _StubRunner:
    def get_node_id(self):
        return InfoHash.get("stub-node")

    def get_id(self):
        return InfoHash()

    def get_node_stats(self, af):
        raise RuntimeError("no table")

    def get_metrics(self):
        return telemetry.get_registry().snapshot()


def test_proxy_trace_routes():
    import urllib.request
    from opendht_tpu.proxy.server import DhtProxyServer

    tr = tracing.get_tracer()
    tr.clear()
    with tr.span("dht.op.get", kind="client") as sp:
        pass
    trace_hex = sp.ctx.trace_hex
    tr.event("probe_event", x=1)
    srv = DhtProxyServer(_StubRunner(), 0)
    try:
        base = "http://127.0.0.1:%d" % srv.port
        with urllib.request.urlopen(base + "/trace", timeout=10) as r:
            dump = json.loads(r.read())
        assert any(e["ev"] == "probe_event" for e in dump["events"])
        assert dump["capacity"] == tr.capacity
        with urllib.request.urlopen(base + "/trace/" + trace_hex,
                                    timeout=10) as r:
            obj = json.loads(r.read())
        assert obj["trace_id"] == trace_hex
        assert [s["name"] for s in obj["spans"]] == ["dht.op.get"]
        with urllib.request.urlopen(
                base + "/trace/" + trace_hex + "?fmt=chrome",
                timeout=10) as r:
            chrome = json.loads(r.read())
        assert any(e.get("ph") == "X" for e in chrome["traceEvents"])
    finally:
        srv.stop()


# --------------------------------------------------------- dhtnode REPL
def test_repl_trace_and_dump_commands(monkeypatch, tmp_path):
    """The `trace`/`dump` REPL commands (the reference's dumpTables
    surface): trace listing, one-trace tree, chrome file export, and
    the flight-recorder dump — driven through cmd_loop on a live
    runner, no identity needed."""
    import builtins
    import contextlib
    import io

    from opendht_tpu.runtime.runner import DhtRunner
    from opendht_tpu.tools.dhtnode import cmd_loop

    tr = tracing.get_tracer()
    tr.clear()
    with tr.span("dht.op.get", kind="client", node="repl-node") as sp:
        pass
    tr.event("request_expired", type="ping", tid=9)
    chrome_path = tmp_path / "trace.json"

    node = DhtRunner()
    node.run(0)
    try:
        script = iter(["trace", "trace %s" % sp.ctx.trace_hex,
                       "trace chrome %s" % chrome_path, "dump 5", "x"])
        monkeypatch.setattr(builtins, "input",
                            lambda prompt="": next(script))
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            cmd_loop(node, None)
        text = out.getvalue()
    finally:
        node.join()
    assert sp.ctx.trace_hex in text                 # listing shows the id
    assert '"dht.op.get"' in text                   # tree dump
    assert "trace events" in text                   # chrome export line
    assert "request_expired" in text                # flight recorder
    assert "ring capacity" in text
    chrome = json.loads(chrome_path.read_text())
    assert any(e.get("ph") == "X" and e["name"] == "dht.op.get"
               for e in chrome["traceEvents"])


# ------------------------------------------------------- snapshot_diff (sat)
def test_snapshot_diff():
    reg = telemetry.MetricsRegistry()
    reg.counter("c_total", op="a").inc(2)
    reg.gauge("g").set(5)
    reg.histogram("h_seconds").observe(0.5)
    before = reg.snapshot()
    reg.counter("c_total", op="a").inc(3)
    reg.counter("c_total", op="b").inc()          # new series
    reg.gauge("g").set(4)
    reg.histogram("h_seconds").observe(0.25)
    after = reg.snapshot()
    d = telemetry.snapshot_diff(before, after)
    assert d["counters"] == {'c_total{op="a"}': 3, 'c_total{op="b"}': 1}
    assert d["gauges"] == {"g": -1}
    assert d["histograms"]["h_seconds"]["count"] == 1
    assert d["histograms"]["h_seconds"]["sum"] == pytest.approx(0.25)
    # no movement → empty sections
    d2 = telemetry.snapshot_diff(after, after)
    assert d2 == {"counters": {}, "gauges": {}, "histograms": {}}


# ------------------------------------- name-filtered dump x eviction (sat)
def test_dump_name_filter_matches_posthoc_under_eviction():
    """ISSUE-10 satellite: a name-filtered dump taken MID-FLOOD (the
    ring actively evicting) must equal the unfiltered dump filtered
    post-hoc — the filter is a read-side projection and can never see
    records eviction already dropped, nor retain extras."""
    tr = tracing.Tracer(capacity=64, node="evict-test")
    # flood 10x capacity with two interleaved event names plus spans
    for i in range(320):
        tr.event("keep_me" if i % 3 == 0 else "drop_me", i=i)
        if i % 7 == 0:
            tr.record("keep_me.span", float(i), 0.001)
    full = tr.dump()
    filt = tr.dump(name="keep_me")
    want_ev = [e for e in full["events"] if "keep_me" in e["ev"]]
    want_sp = [s for s in full["spans"] if "keep_me" in s["name"]]
    assert [e["seq"] for e in filt["events"]] == [e["seq"] for e in want_ev]
    assert [s["seq"] for s in filt["spans"]] == [s["seq"] for s in want_sp]
    # eviction really happened: the oldest retained seq is deep into
    # the flood, and the filtered view starts no earlier
    total = 320 + len(range(0, 320, 7))
    oldest = min(r["seq"] for r in tr.records())
    assert oldest >= total - 64
    assert filt["events"][0]["seq"] >= oldest
    # monotone order preserved through filtering
    seqs = [e["seq"] for e in filt["events"]]
    assert seqs == sorted(seqs)


def test_trace_hex_strict_and_spans_guard():
    """ISSUE-10 satellite: _trace_hex returns None for malformed ids
    (non-hex, oversized, empty) and Tracer.spans() with a malformed id
    returns [] — never the whole ring (the old char-strip
    normalization made bogus ids look like valid zero-padded ones)."""
    from opendht_tpu.tracing import _trace_hex
    assert _trace_hex(None) is None
    assert _trace_hex("zz") is None
    assert _trace_hex("") is None
    assert _trace_hex("a" * 33) is None
    assert _trace_hex("0x" + "g" * 4) is None
    # int(s, 16) would accept digit-group underscores and sign
    # prefixes — these are malformed, not well-formed-unknown (review
    # finding)
    assert _trace_hex("a_b") is None
    assert _trace_hex("+ab") is None
    assert _trace_hex("-1") is None
    # well-formed ids normalize to 32 hex digits
    assert _trace_hex("ab") == "ab".rjust(32, "0")
    assert _trace_hex("0xAB") == "ab".rjust(32, "0")
    assert _trace_hex(0xAB) == "%032x" % 0xAB
    ctx = tracing.TraceContext.new_root()
    assert _trace_hex(ctx) == ctx.trace_hex
    tr = tracing.Tracer(capacity=16)
    tr.record("a-span", 0.0, 0.001)
    assert len(tr.spans()) == 1                 # unfiltered: everything
    assert tr.spans("not-hex!") == []           # malformed: nothing
    assert tr.spans("f" * 32) == []             # well-formed unknown
    got = tr.spans(tr.records()[0]["trace_id"])
    assert len(got) == 1                        # well-formed known
