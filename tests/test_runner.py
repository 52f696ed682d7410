"""DhtRunner integration tests over real localhost UDP sockets —
the analog of the reference tests/dhtrunnertester.cpp (2 real nodes,
bootstrap, blocking get sees put :30-57) plus the listen test the
reference left as a TODO (:60-62), and a signed-put through identities."""

import time

import pytest

from opendht_tpu.core.value import Value
from opendht_tpu.infohash import InfoHash
from opendht_tpu.runtime.config import NodeStatus
from opendht_tpu.runtime.runner import DhtRunner, RunnerConfig


def wait_for(pred, timeout=20.0, step=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(step)
    return pred()


@pytest.fixture
def two_nodes():
    a, b = DhtRunner(), DhtRunner()
    a.run(0)
    b.run(0)
    b.bootstrap("127.0.0.1", a.get_bound_port())
    yield a, b
    a.join()
    b.join()


def test_ipv6_dual_stack_put_get():
    """Dual-stack runners bootstrap over ::1 and serve values on the v6
    family (every table/search is duplicated per family, dht.h:370-381)."""
    import socket
    a, b = DhtRunner(), DhtRunner()
    a.run(0, ipv6=True)
    b.run(0, ipv6=True)
    def v6_up(r):
        return (r._sock6 is not None
                or (r._udp is not None and r._udp.has_v6))
    if not (v6_up(a) and v6_up(b)):
        a.join(); b.join()
        pytest.skip("no IPv6 loopback available")
    try:
        b.bootstrap("::1", a.get_bound_port())
        assert wait_for(lambda: b.get_status(socket.AF_INET6)
                        is NodeStatus.CONNECTED)
        key = InfoHash.get("v6key")
        assert b.put_sync(key, Value(b"over-six"), timeout=20.0)
        vals = a.get_sync(key, timeout=20.0)
        assert any(v.data == b"over-six" for v in vals)
    finally:
        a.join()
        b.join()


def test_ipv6_python_fallback_put_get():
    """v6 with the native engine DISABLED: the Python-socket fallback
    path must keep serving dual-stack on its own (the round-5 review's
    'Python fallback preserved' clause — the native v6 path is covered
    by test_native.py and test_ipv6_dual_stack_put_get)."""
    import socket
    a, b = DhtRunner(), DhtRunner()
    a.run(0, RunnerConfig(native_engine=False), ipv6=True)
    b.run(0, RunnerConfig(native_engine=False), ipv6=True)
    assert a._udp is None and b._udp is None     # really on Python sockets
    if a._sock6 is None or b._sock6 is None:
        a.join(); b.join()
        pytest.skip("no IPv6 loopback available")
    try:
        b.bootstrap("::1", a.get_bound_port())
        assert wait_for(lambda: b.get_status(socket.AF_INET6)
                        is NodeStatus.CONNECTED)
        key = InfoHash.get("v6-python-fallback")
        assert b.put_sync(key, Value(b"six sans native"), timeout=20.0)
        vals = a.get_sync(key, timeout=20.0)
        assert any(v.data == b"six sans native" for v in vals)
    finally:
        a.join()
        b.join()


def test_bootstrap_connects(two_nodes):
    a, b = two_nodes
    assert a.get_bound_port() > 0 and b.get_bound_port() > 0
    assert wait_for(lambda: a.get_status() is NodeStatus.CONNECTED
                    and b.get_status() is NodeStatus.CONNECTED), \
        f"never connected: a={a.get_status()} b={b.get_status()}"


def test_put_get(two_nodes):
    a, b = two_nodes
    assert wait_for(lambda: b.get_status() is NodeStatus.CONNECTED)
    key = InfoHash.get("testkey")
    assert b.put_sync(key, Value(b"yo"), timeout=20.0)
    vals = a.get_sync(key, timeout=20.0)
    assert any(v.data == b"yo" for v in vals)


def test_listen(two_nodes):
    a, b = two_nodes
    assert wait_for(lambda: a.get_status() is NodeStatus.CONNECTED
                    and b.get_status() is NodeStatus.CONNECTED)
    key = InfoHash.get("listenkey")
    heard = []
    token_fut = a.listen(key, lambda vals, expired:
                         heard.extend(v.data for v in vals
                                      if not expired) or True)
    token_fut.result(10.0)
    b.put(key, Value(b"pushed value"))
    assert wait_for(lambda: b"pushed value" in heard, 20.0), \
        "listener never heard the remote put"
    a.cancel_listen(key, token_fut)


def test_many_nodes_converge():
    runners = []
    try:
        seed = DhtRunner()
        seed.run(0)
        runners.append(seed)
        for _ in range(4):
            r = DhtRunner()
            r.run(0)
            r.bootstrap("127.0.0.1", seed.get_bound_port())
            runners.append(r)
        assert wait_for(lambda: all(r.get_status() is NodeStatus.CONNECTED
                                    for r in runners), 30.0)
        key = InfoHash.get("multi")
        assert runners[2].put_sync(key, Value(b"over the mesh"), timeout=20.0)
        vals = runners[4].get_sync(key, timeout=20.0)
        assert any(v.data == b"over the mesh" for v in vals)
        stats = runners[0].get_node_stats()
        assert stats.good_nodes >= 1
    finally:
        for r in runners:
            r.join()


def test_identity_signed_put():
    # the one runner test that NEEDS the crypto wheel; importing it here
    # (not at module top) keeps the rest of this file runnable in
    # minimal containers, like the identity-less runner itself
    crypto = pytest.importorskip("opendht_tpu.crypto")
    ida = crypto.generate_identity("runner-a", key_length=1024)
    idb = crypto.generate_identity("runner-b", key_length=1024)
    a, b = DhtRunner(), DhtRunner()
    try:
        a.run(0, RunnerConfig(identity=ida))
        b.run(0, RunnerConfig(identity=idb))
        b.bootstrap("127.0.0.1", a.get_bound_port())
        assert wait_for(lambda: b.get_status() is NodeStatus.CONNECTED)
        key = InfoHash.get("signed-runner")
        import concurrent.futures
        fut = concurrent.futures.Future()
        b.put_signed(key, Value(b"signed over udp"),
                     lambda ok, ns: fut.done() or fut.set_result(ok))
        assert fut.result(30.0)
        vals = a.get_sync(key, timeout=20.0)
        assert any(v.data == b"signed over udp" and v.check_signature()
                   for v in vals)
    finally:
        a.join()
        b.join()


def test_join_idempotent():
    r = DhtRunner()
    r.run(0)
    r.join()
    r.join()
    assert not r.is_running()


def test_prio_ops_cannot_starve_normal_ops():
    """Starvation regression (round 12): sustained prio traffic — every
    pump finds the prio queue non-empty again — must not indefinitely
    defer normal ops.  Before the fix, ``_loop``'s elif skipped the
    normal queue whenever prio ops were pending, so a prio source that
    re-arms each pump (bootstrap ping storms, stats polls) deferred
    every get/put/listen forever.  The fairness bound: each pump drains
    prio first, then the eligible normal backlog."""
    r = DhtRunner()
    r.run(0, RunnerConfig(threaded=False))
    try:
        order = []
        r._post(lambda dht: order.append("normal"))

        def rearm(dht):
            order.append("prio")
            r._post(rearm, prio=True)     # the queue is never observed empty

        r._post(rearm, prio=True)
        for _ in range(4):
            r.loop()
        assert "normal" in order, \
            "normal op starved behind sustained prio traffic"
        # prio keeps strict precedence within its pump
        assert order.index("prio") < order.index("normal")
    finally:
        r.join()


def test_normal_ops_still_gated_while_bootstrapping():
    """The fairness fix must not weaken the reference's gating: while a
    bootstrap attempt is in flight (disconnected + bootstrapping),
    normal ops stay queued; prio ops run (dhtrunner.cpp:393-398)."""
    r = DhtRunner()
    r.run(0, RunnerConfig(threaded=False))
    try:
        r._bootstraping = True            # simulate the bootstrap thread
        ran = []
        r._post(lambda dht: ran.append("normal"))
        r._post(lambda dht: ran.append("prio"), prio=True)
        r.loop()
        assert ran == ["prio"], ran
        r._bootstraping = False
        r.loop()
        assert ran == ["prio", "normal"], ran
    finally:
        r.join()
