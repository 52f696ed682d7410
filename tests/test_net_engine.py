"""Network engine tests: wire-format layout, request lifecycle with
retries, the full RPC matrix over a two-engine loopback harness,
fragmentation/reassembly, rate limiting, martian filtering, and compact
node blobs (reference contracts: src/network_engine.cpp,
parsed_message.h, request.h, node_cache.cpp)."""

import socket

import msgpack
import pytest

from opendht_tpu.core.value import Query, Value
from opendht_tpu.infohash import InfoHash
from opendht_tpu.net import (
    EngineCallbacks, MessageType, NetworkEngine, Node, NodeCache,
    ParsedMessage, RequestAnswer,
)
from opendht_tpu.net.engine import (
    MAX_PACKET_VALUE_SIZE, MTU, SEND_NODES, is_martian,
)
from opendht_tpu.net.parsed_message import pack_tid, unpack_tid
from opendht_tpu.scheduler import Scheduler
from opendht_tpu.sockaddr import SockAddr

pytestmark = pytest.mark.quick  # sub-minute smoke tier: -m quick


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class Net:
    """Two (or more) engines wired through an in-memory packet switch."""

    def __init__(self):
        self.clock = FakeClock()
        self.endpoints = {}           # SockAddr -> engine
        self.queue = []
        self.drop = lambda data, src, dst: False

    def make_engine(self, name, port, callbacks=None, network=0):
        sched = Scheduler(clock=self.clock)
        addr = SockAddr("10.0.0.%d" % port, 4000 + port)
        holder = {}
        eng = NetworkEngine(
            InfoHash.get(name), network,
            lambda data, dst: self.queue.append((data, holder["addr"], dst)) or 0,
            sched, callbacks or EngineCallbacks())
        holder["addr"] = addr
        self.endpoints[addr] = eng
        return eng

    def pump(self, steps=50):
        """Deliver queued packets and run schedulers until quiescent."""
        for _ in range(steps):
            progressed = False
            while self.queue:
                data, src, dst = self.queue.pop(0)
                eng = self.endpoints.get(dst)
                if eng is None:
                    continue
                if not self.drop(data, src, dst):
                    eng.process_message(data, src)
                progressed = True
            for eng in self.endpoints.values():
                eng.scheduler.run()
            if not progressed and not self.queue:
                break

    def advance(self, dt):
        self.clock.t += dt
        for eng in self.endpoints.values():
            eng.scheduler.run()


@pytest.fixture()
def net():
    return Net()


def make_pair(net, cbs_a=None, cbs_b=None):
    a = net.make_engine("alice", 1, cbs_a)
    b = net.make_engine("bob", 2, cbs_b)
    addr_a = next(ad for ad, e in net.endpoints.items() if e is a)
    addr_b = next(ad for ad, e in net.endpoints.items() if e is b)
    node_b_for_a = a.cache.get_node(b.myid, addr_b, 0.0, confirm=True)
    node_a_for_b = b.cache.get_node(a.myid, addr_a, 0.0, confirm=True)
    return a, b, node_b_for_a, node_a_for_b


# ------------------------------------------------------------- wire format
def test_ping_wire_layout(net):
    sent = []
    eng = net.make_engine("alice", 1)
    eng._send_fn = lambda data, dst: sent.append(data) or 0
    node = Node(InfoHash.get("bob"), SockAddr("10.0.0.9", 1234))
    eng.send_ping(node)
    obj = msgpack.unpackb(sent[0], raw=False, strict_map_key=False)
    # exact top-level/arg layout (network_engine.cpp:677-695)
    assert list(obj) == ["a", "q", "t", "y", "v"]
    assert obj["a"] == {"id": bytes(eng.myid)}
    assert obj["q"] == "ping" and obj["y"] == "q" and obj["v"] == "RNG1"
    assert len(obj["t"]) == 4


def test_netid_in_header_and_filtering(net):
    sent = []
    eng = net.make_engine("alice", 1, network=7)
    eng._send_fn = lambda data, dst: sent.append(data) or 0
    node = Node(InfoHash.get("bob"), SockAddr("10.0.0.9", 1234))
    eng.send_ping(node)
    obj = msgpack.unpackb(sent[0], raw=False)
    assert obj["n"] == 7
    # a mismatched-network packet is dropped silently
    other = net.make_engine("carol", 2, network=0)
    got = []
    other.cb.on_ping = lambda n: got.append(n) or RequestAnswer()
    other.process_message(sent[0], SockAddr("10.0.0.1", 4001))
    assert got == []


def test_tid_roundtrip():
    assert unpack_tid(pack_tid(0xDEADBEEF)) == 0xDEADBEEF
    assert unpack_tid(12345) == 12345
    with pytest.raises(ValueError):
        unpack_tid(b"\x01\x02")


def test_martian_filter():
    assert is_martian(SockAddr("10.0.0.1", 0))            # port 0
    assert is_martian(SockAddr("0.1.2.3", 80))            # 0.x
    assert is_martian(SockAddr("224.0.0.1", 80))          # multicast
    assert not is_martian(SockAddr("8.8.8.8", 80))
    assert is_martian(SockAddr("ff02::1", 80))            # v6 multicast
    assert is_martian(SockAddr("fe80::1", 80))            # link-local
    assert is_martian(SockAddr("::", 80))
    assert not is_martian(SockAddr("2001:db8::1", 80))


# ------------------------------------------------------------ rpc round-trips
def test_ping_pong_roundtrip(net):
    a, b, node_b, _ = make_pair(net)
    done = []
    a.send_ping(node_b, on_done=lambda req, ans: done.append(req))
    net.pump()
    assert len(done) == 1
    assert done[0].completed
    assert node_b.reply_time == net.clock.t
    # bob learned about alice through the exchange
    assert b.cache.size(socket.AF_INET) >= 1


def test_find_node_returns_sorted_truncated_nodes(net):
    target = InfoHash.get("target")

    def on_find(node, t, want):
        ans = RequestAnswer()
        # hand back 20 candidate nodes; engine must sort by XOR and cut to 8
        ans.nodes4 = [Node(InfoHash.get(f"n{i}"), SockAddr("10.0.1.%d" % i, 100 + i))
                      for i in range(1, 21)]
        return ans

    cbs = EngineCallbacks(on_find_node=on_find)
    a, b, node_b, _ = make_pair(net, cbs_b=cbs)
    got = []
    a.send_find_node(node_b, target, want=1,
                     on_done=lambda req, ans: got.append(ans))
    net.pump()
    assert len(got) == 1
    ids = [n.id for n in got[0].nodes4]
    assert len(ids) == SEND_NODES
    dists = [bytes(target.xor(i)) for i in ids]
    assert dists == sorted(dists)


def test_served_request_is_timed_without_a_sampled_sender(net):
    """Every incoming request lands in dht_server_request_seconds{type},
    wire context or none; the dht.server.* tracer span still needs one.
    A reply is no request and is not timed."""
    from opendht_tpu import telemetry, tracing
    reg, tr = telemetry.get_registry(), tracing.get_tracer()
    find = reg.histogram("dht_server_request_seconds", type="find")
    a, b, node_b, _ = make_pair(net, cbs_b=EngineCallbacks(
        on_find_node=lambda node, t, want: RequestAnswer()))
    seen = []
    process = b.process_message
    b.process_message = lambda data, src: (
        seen.append(ParsedMessage.from_bytes(data).trace_ctx),
        process(data, src))
    count, spans = find.count, len(tr.spans())
    try:
        tr.enabled = False              # the sender attaches no context
        a.send_find_node(node_b, InfoHash.get("target"), want=1)
        net.pump()
    finally:
        tr.enabled = True
    assert seen == [None]
    assert find.count == count + 1 and find.sum > 0
    assert len(tr.spans()) == spans
    assert (("type", "reply"),) not in reg.series(
        "dht_server_request_seconds")


def test_get_values_inline_and_token(net):
    val = Value(b"payload", value_id=42)

    def on_get(node, h, want, query):
        return RequestAnswer(ntoken=b"tok123", values=[val])

    a, b, node_b, _ = make_pair(net, cbs_b=EngineCallbacks(on_get_values=on_get))
    got = []
    a.send_get_values(node_b, InfoHash.get("key"), Query(),
                      on_done=lambda req, ans: got.append(ans))
    net.pump()
    assert len(got) == 1
    assert got[0].ntoken == b"tok123"
    assert got[0].values == [val]


def test_get_values_field_projection(net):
    val = Value(b"payload", type_id=5, value_id=42)
    val.seq = 9

    def on_get(node, h, want, query):
        return RequestAnswer(values=[val])

    a, b, node_b, _ = make_pair(net, cbs_b=EngineCallbacks(on_get_values=on_get))
    got = []
    a.send_get_values(node_b, InfoHash.get("key"), Query("SELECT id, seq"),
                      on_done=lambda req, ans: got.append(ans))
    net.pump()
    assert len(got) == 1 and not got[0].values
    fields = got[0].fields
    assert len(fields) == 1
    from opendht_tpu.core.value import Field
    assert fields[0].index[Field.ID].value == 42
    assert fields[0].index[Field.SEQ_NUM].value == 9


def test_announce_value_roundtrip_and_large_value_fragmentation(net):
    stored = []

    def on_announce(node, h, token, values, created):
        stored.extend(values)
        return RequestAnswer()

    a, b, node_b, _ = make_pair(net, cbs_b=EngineCallbacks(on_announce=on_announce))
    big = Value(b"\xab" * (4 * MTU), value_id=77)   # forces ValueData parts
    acked = []
    a.send_announce_value(node_b, InfoHash.get("key"), big, None, b"tok",
                          on_done=lambda req, ans: acked.append(ans.vid))
    net.pump()
    assert len(stored) == 1
    assert stored[0].id == 77 and stored[0].data == big.data
    assert acked == [77]


def test_small_value_stays_in_one_packet(net):
    captured = []
    a = net.make_engine("alice", 1)
    a._send_fn = lambda data, dst: captured.append(data) or 0
    node = Node(InfoHash.get("bob"), SockAddr("10.0.0.9", 1234))
    small = Value(b"x" * 100, value_id=5)
    a.send_announce_value(node, InfoHash.get("k"), small, None, b"t")
    assert len(captured) == 1                      # no part packets
    obj = msgpack.unpackb(captured[0], raw=False)
    assert isinstance(obj["a"]["values"][0], dict)  # inline value


def test_listen_push_channel(net):
    """listen opens a per-node socket; pushes and id-updates arrive on it."""
    listens = []

    def on_listen(node, h, token, sid, query):
        listens.append((node, sid))
        return RequestAnswer()

    a, b, node_b, node_a = make_pair(net, cbs_b=EngineCallbacks(on_listen=on_listen))
    pushes = []

    def socket_cb(node, msg):
        pushes.append(msg)

    req = a.send_listen(node_b, InfoHash.get("room"), Query(), b"tok", None,
                        socket_cb=socket_cb)
    net.pump()
    assert len(listens) == 1
    peer_node, sid = listens[0]
    assert sid == req.socket_id

    # bob pushes a value over the socket
    v = Value(b"new", value_id=3)
    b.tell_listener(node_a, sid, InfoHash.get("room"), -1, b"tok", [], [], [v],
                    Query())
    net.pump()
    assert len(pushes) == 1 and pushes[0].values == [v]

    # refreshed / expired id lists
    b.tell_listener_refreshed(node_a, sid, InfoHash.get("room"), b"tok", [3])
    b.tell_listener_expired(node_a, sid, InfoHash.get("room"), b"tok", [3])
    net.pump()
    assert pushes[1].refreshed_values == [3]
    assert pushes[2].expired_values == [3]


def test_error_reply_reaches_on_error(net):
    """A 401 on announce routes to the on_error callback
    (network_engine.cpp:536-553)."""
    from opendht_tpu.net.engine import DhtProtocolException

    def on_announce(node, h, token, values, created):
        raise DhtProtocolException(DhtProtocolException.UNAUTHORIZED,
                                   DhtProtocolException.PUT_WRONG_TOKEN)

    errors = []
    cbs_a = EngineCallbacks()
    cbs_a.on_error = lambda req, e: errors.append(e.code)
    a, b, node_b, _ = make_pair(net, cbs_a=cbs_a,
                                cbs_b=EngineCallbacks(on_announce=on_announce))
    a.send_announce_value(node_b, InfoHash.get("k"), Value(b"v", value_id=1),
                          None, b"bad")
    net.pump()
    assert errors == [401]


# ------------------------------------------------------- request lifecycle
def test_request_retries_then_expires(net):
    a = net.make_engine("alice", 1)
    sent = []
    a._send_fn = lambda data, dst: sent.append(data) or 0   # black hole
    node = Node(InfoHash.get("bob"), SockAddr("10.0.0.9", 1234))
    expiries = []
    req = a.send_ping(node, on_expired=lambda r, done: expiries.append(done))
    assert len(sent) == 1
    for _ in range(5):
        net.advance(1.1)
    assert len(sent) == 3                 # MAX_ATTEMPT_COUNT
    assert req.expired
    assert expiries == [False, True]      # early hint + final
    assert node.expired


def test_reply_to_expired_request_ignored(net):
    a, b, node_b, _ = make_pair(net)
    done = []
    # drop everything for a while
    held = []
    net.drop = lambda data, src, dst: held.append((data, src, dst)) or True
    a.send_ping(node_b, on_done=lambda r, ans: done.append(1))
    for _ in range(5):
        net.advance(1.1)
    net.drop = lambda data, src, dst: False
    # deliver the stale ping now; bob answers; alice must not fire on_done
    for data, src, dst in held:
        net.endpoints[dst].process_message(data, src)
    net.pump()
    assert done == []


# ---------------------------------------------------------- rx protections
def test_rate_limit_drops_request_floods(net):
    hits = []
    cbs = EngineCallbacks(on_ping=lambda n: hits.append(1) or RequestAnswer())
    b = net.make_engine("bob", 2, cbs)
    src = SockAddr("10.0.0.1", 4001)
    ping = msgpack.packb({"a": {"id": bytes(InfoHash.get("alice"))},
                          "q": "ping", "t": pack_tid(1), "y": "q",
                          "v": "RNG1"}, use_bin_type=True)
    for _ in range(400):
        b.process_message(ping, src)
    # per-IP cap is 200/s
    assert len(hits) == 200


def test_blacklist_and_self_message_dropped(net):
    a, b, node_b, node_a = make_pair(net)
    hits = []
    b.cb.on_ping = lambda n: hits.append(1) or RequestAnswer()
    b.blacklist_node(node_a)
    a.send_ping(node_b)
    net.pump()
    assert hits == []
    # message with b's own id is ignored
    self_ping = msgpack.packb({"a": {"id": bytes(b.myid)}, "q": "ping",
                               "t": pack_tid(9), "y": "q", "v": "RNG1"},
                              use_bin_type=True)
    b.process_message(self_ping, SockAddr("10.0.0.50", 999))
    assert hits == []


def test_stalled_fragment_reassembly_times_out(net):
    stored = []
    a, b, node_b, _ = make_pair(
        net, cbs_b=EngineCallbacks(
            on_announce=lambda n, h, t, v, c: stored.extend(v) or RequestAnswer()))
    big = Value(b"\xcd" * (4 * MTU), value_id=9)
    # drop all ValueData part packets
    net.drop = lambda data, src, dst: msgpack.unpackb(
        data, raw=False, strict_map_key=False).get("y") == "v"
    a.send_announce_value(node_b, InfoHash.get("k"), big, None, b"tok")
    net.pump(steps=2)
    assert len(b._partials) == 1
    net.advance(11.0)             # > RX_MAX_PACKET_TIME
    assert len(b._partials) == 0
    assert stored == []


# ----------------------------------------------------------------- NodeCache
def test_node_cache_interning_and_closest():
    cache = NodeCache()
    nodes = []
    for i in range(64):
        nid = InfoHash.get(f"node{i}")
        nodes.append(cache.get_node(nid, SockAddr("10.1.0.%d" % (i + 1), 100),
                                    now=0.0, confirm=True))
    # interning: same id gives the same object
    again = cache.get_node(nodes[0].id, nodes[0].addr, 0.0, confirm=False)
    assert again is nodes[0]

    target = InfoHash.get("target")
    # Oracle: the reference's greedy frontier walk (node_cache.cpp:41-74).
    # Note this is deliberately NOT the exact global top-k — XOR distance
    # is non-monotone along lexicographic order within one side, and the
    # reference accepts the approximation for cache refill.
    keys = sorted(bytes(n.id) for n in nodes)
    tkey = bytes(target)
    lo = __import__("bisect").bisect_left(keys, tkey) - 1
    hi = lo + 1
    expect = []
    while len(expect) < 8 and (lo >= 0 or hi < len(keys)):
        if lo < 0:
            expect.append(keys[hi]); hi += 1
        elif hi >= len(keys):
            expect.append(keys[lo]); lo -= 1
        elif bytes(target.xor(InfoHash(keys[lo]))) < bytes(target.xor(InfoHash(keys[hi]))):
            expect.append(keys[lo]); lo -= 1
        else:
            expect.append(keys[hi]); hi += 1
    got = cache.get_cached_nodes(target, socket.AF_INET, 8)
    assert [bytes(n.id) for n in got] == expect
    # every returned node is among the 2*count lexicographic neighbors —
    # the walk's locality guarantee
    window = set(keys[max(0, lo - 16):hi + 16])
    assert all(bytes(n.id) in window for n in got)

    # expired nodes are skipped
    got[0].set_expired()
    dead_id = got[0].id
    got2 = cache.get_cached_nodes(target, socket.AF_INET, 8)
    assert dead_id not in [n.id for n in got2]
    assert len(got2) == 8          # backfilled from the next frontier


def test_node_cache_weak_refs():
    cache = NodeCache()
    n = cache.get_node(InfoHash.get("x"), SockAddr("10.1.0.1", 100), 0.0, True)
    assert cache.size(socket.AF_INET) == 1
    del n
    import gc
    gc.collect()
    assert cache.lookup(InfoHash.get("x"), socket.AF_INET) is None
