"""Drive the CLI tool bodies end to end: the dhtnode REPL dispatch
(g/l/p/pp/cpp/s/e/q?/il/ii/info/ll/cc/stt/pst/log), the dhtchat
mainline, and the dhtscanner mainline — previously covered only by
manual smoke runs (↔ reference tools/dhtnode.cpp:104-460,
dhtchat.cpp, dhtscanner.cpp)."""

import builtins
import contextlib
import io
import re
import time

import pytest

from opendht_tpu import crypto
from opendht_tpu.core.value import Value
from opendht_tpu.infohash import InfoHash
from opendht_tpu.runtime.config import Config, NodeStatus
from opendht_tpu.runtime.runner import DhtRunner, RunnerConfig
from opendht_tpu.tools.dhtnode import cmd_loop


def wait_for(pred, timeout=20.0, step=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(step)
    return pred()


@pytest.fixture(scope="module")
def net():
    """peer ↔ node, both with identities (for s/e ops)."""
    ident_a = crypto.generate_identity("repl-peer", key_length=1024)
    ident_b = crypto.generate_identity("repl-node", key_length=1024)
    peer = DhtRunner()
    node = DhtRunner()
    peer.run(0, RunnerConfig(dht_config=Config(), identity=ident_a))
    node.run(0, RunnerConfig(dht_config=Config(), identity=ident_b))
    node.bootstrap("127.0.0.1", peer.get_bound_port())
    assert wait_for(lambda: peer.get_status() is NodeStatus.CONNECTED
                    and node.get_status() is NodeStatus.CONNECTED)
    yield peer, node
    peer.join()
    node.join()


def repl(node, script, monkeypatch, until=""):
    """Run cmd_loop feeding `script` lines; returns captured stdout.
    ``until``: text an asynchronous command (``q?``) prints from the DHT
    thread — waited for (bounded) while stdout is still redirected, so
    the late print lands here and not in the middle of pytest's own
    progress line."""
    lines = iter(script)

    def fake_input(prompt=""):
        try:
            return next(lines)
        except StopIteration:
            raise EOFError

    monkeypatch.setattr(builtins, "input", fake_input)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        cmd_loop(node, None)
        if until:
            wait_for(lambda: until in out.getvalue(), timeout=10.0)
    return out.getvalue()


def test_repl_core_ops(net, monkeypatch):
    peer, node = net
    out = repl(node, [
        "h",
        "info",
        "p repl-key hello from repl",
        "g repl-key",
        "pp perm-key permanent payload",
        "s signed-key signed payload",
        "q? repl-key select id",
        "ll",
        "cc",
        "bogus-op",
        "g",                      # missing argument
        "x",
    ], monkeypatch, until="fields:")
    assert "Put: True" in out
    assert "hello from repl" in out and re.search(r"Get: \d+ value", out)
    assert "PutSigned: True" in out
    assert "Node id:" in out or "id:" in out          # info output
    assert "connectivity change signalled" in out
    assert "unknown op 'bogus-op'" in out
    assert "missing argument" in out
    # pp printed the value id for cpp
    m = re.search(r"Put: True \(id ([0-9a-f]+)\)\nPutSigned", out)
    assert "Put: True (id " in out

    # the permanent put is cancellable in a second session
    vid = re.findall(r"Put: True \(id ([0-9a-f]+)\)", out)[-1]
    out2 = repl(node, ["cpp perm-key %s" % vid, "x"], monkeypatch)
    assert "cancelled" in out2


def test_repl_listen_and_cancel(net, monkeypatch):
    peer, node = net
    out = repl(node, ["l listen-key", "x"], monkeypatch)
    m = re.search(r"listening, token (\d+)", out)
    assert m, out
    token = m.group(1)
    # push a value from the peer; then cancel by token in a new session
    assert peer.put_sync(InfoHash.get("listen-key"), Value(b"heard"),
                         timeout=20.0)
    out2 = repl(node, ["cl %s" % token, "x"], monkeypatch)
    # the listen token map is per-cmd_loop call, so cl in a fresh session
    # reports the friendly error rather than cancelling
    assert "error" in out2 or "cancelled" in out2


def test_repl_encrypted_put(net, monkeypatch):
    peer, node = net
    # encrypt to our own identity: the cert is known locally and the
    # value round-trips through the DHT encrypted
    my_id = node.get_id().hex()
    out = repl(node, ["e enc-key %s secret text" % my_id, "x"], monkeypatch)
    assert "PutEncrypted: True" in out, out


def test_repl_index_ops(net, monkeypatch):
    peer, node = net
    out = repl(node, [
        "il myindex somefield 7",
        "ii myindex somefield",
        "x",
    ], monkeypatch)
    assert "Index insert: True" in out, out
    assert "Lookup: True" in out, out


def test_repl_proxy_ops(net, monkeypatch):
    peer, node = net
    from opendht_tpu.proxy import DhtProxyServer
    server = DhtProxyServer(peer, port=0)
    try:
        out = repl(node, [
            "stt 0",
            "stp",
            "pst 127.0.0.1:%d" % server.port,
            "p via-proxy proxied payload",
            "g via-proxy",
            "psp",
            "x",
        ], monkeypatch)
        assert re.search(r"proxy server on port \d+", out)
        assert "proxy server stopped" in out
        assert "backend switched to proxy" in out
        assert "Put: True" in out
        assert "proxied payload" in out
        assert "backend switched to UDP" in out
    finally:
        server.stop()


def test_repl_ingest_state(net, monkeypatch):
    """The round-12 `ingest` command surfaces the wave builder's
    coalescing health (queue depth, occupancy, time-in-queue, sheds)."""
    peer, node = net
    out = repl(node, [
        "p ingest-repl-key some value",    # drive at least one wave
        "ingest",
        "x",
    ], monkeypatch)
    assert "batching on" in out
    assert re.search(r"queue \d+/\d+", out)
    assert re.search(r"waves \d+  occupancy mean", out)
    assert re.search(r"time-in-queue p50 .* sheds \d+", out)


def test_repl_cache_state(net, monkeypatch):
    """The round-16 `cache` command surfaces the hot-value cache
    (occupancy, hit ratio, replica-k) and the `json` form dumps the
    full GET /cache snapshot."""
    peer, node = net
    out = repl(node, ["cache", "cache json", "x"], monkeypatch)
    assert re.search(r"occupancy \d+/\d+  hit ratio", out)
    assert re.search(r"replica k 8->16 on \d+ hot key\(s\)", out)
    assert '"enabled": true' in out        # the json dump


def test_repl_log_toggle(net, monkeypatch):
    peer, node = net
    out = repl(node, ["log", "log off", "x"], monkeypatch)
    assert "logging on" in out and "logging off" in out


def test_dhtchat_mainline(net, monkeypatch):
    peer, node = net
    from opendht_tpu.core.default_types import ImMessage
    from opendht_tpu.tools import dhtchat

    heard = []
    room = InfoHash.get("room:testroom")
    peer.listen(room, lambda vals, expired: heard.extend(
        v for v in vals if not expired) or True)
    time.sleep(0.5)

    lines = ["hello over dht"]

    def fake_input(prompt=""):
        if lines:
            return lines.pop(0)
        # give the signed put time to announce before quitting (main
        # joins the node immediately after the empty line)
        wait_for(lambda: any(b"hello over dht" in v.data for v in heard),
                 timeout=20.0)
        return ""

    monkeypatch.setattr(builtins, "input", fake_input)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = dhtchat.main(["-b", "127.0.0.1:%d" % peer.get_bound_port(),
                           "testroom"])
    assert rc == 0
    assert "Joined room testroom" in out.getvalue()
    assert wait_for(lambda: any(
        b"hello over dht" in v.data for v in heard
        if not v.is_encrypted()), timeout=20.0), heard


def test_dhtscanner_mainline(net, monkeypatch):
    peer, node = net
    from opendht_tpu.tools import dhtscanner
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = dhtscanner.main(["-b", "127.0.0.1:%d" % peer.get_bound_port(),
                              "--rounds", "2"])
    assert rc == 0
    text = out.getvalue()
    assert "nodes discovered" in text
    assert "network size estimation" in text
