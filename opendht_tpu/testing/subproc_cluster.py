"""Out-of-process cluster tier: a whole DHT cluster in a CHILD process,
remote-controlled over a msgpack-stdin RPC channel.

Analog of the reference's ``DhtNetworkSubProcess`` (reference
python/tools/dht/network.py:42-281), which spawns clusters in separate
processes (there: via NSPopen into a netns) and drives them with
line-commands over stdin.  The TPU build keeps the process boundary —
it is what makes concurrency bugs in runner/engine visible instead of
GIL-masked, and lets a test kill an entire cluster with one signal —
but upgrades the control channel to length-delimited msgpack request/
response frames (the project wire codec) instead of ad-hoc text.

Protocol (child stdin → request, child stdout → response, stderr free
for logs):  each frame is one msgpack map ``{"op": str, ...}`` /
``{"ok": bool, ...}``.  Ops:

  launch {n}            → {ok, ports: [int], ids: [bytes]}
  resize {n}            → {ok, n}
  bootstrap {host,port} → {ok}   (every node dials the address —
                                  interconnects clusters across processes)
  put {key, value}      → {ok, stored: bool}
  get {key}             → {ok, values: [bytes]}
  ids {}                → {ok, ids: [bytes]}
  stats {}              → {ok, n, msgs: int}
  quit {}               → {ok} then child exits

The child runs on the CPU backend (``JAX_PLATFORMS=cpu`` in its
environment): its tables are small and resolve on the host, and a chip
belongs to one process — the parent, or the next child, may need it.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time
from typing import Optional

import msgpack


# ---------------------------------------------------------------------------
# parent side
# ---------------------------------------------------------------------------

class ClusterSubProcess:
    """Parent-side handle: spawn, RPC, and (ungracefully) kill a child
    process hosting a whole cluster of live UDP DHT nodes."""

    def __init__(self, n_nodes: int = 0, *, timeout: float = 60.0,
                 argv_prefix: tuple = ()):
        """``argv_prefix``: argv prepended to the child command — e.g.
        ``("ip", "netns", "exec", ns)`` runs the whole cluster inside a
        network namespace (the real-kernel tier, testing/netns_net.py)."""
        self.timeout = timeout
        env = dict(os.environ)
        # set, not defaulted: on a machine with a chip the inherited
        # value names the TPU first, and a child that took the chip
        # would lock out its parent and every sibling
        env["JAX_PLATFORMS"] = "cpu"
        self.proc = subprocess.Popen(
            [*argv_prefix, sys.executable, "-m",
             "opendht_tpu.testing.subproc_cluster", "--child"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, env=env)
        self._unpacker = msgpack.Unpacker(raw=True)
        self.ports: list[int] = []
        self.ids: list[bytes] = []
        if n_nodes:
            self.launch(n_nodes)

    # -- framing -----------------------------------------------------------
    def _call(self, op: str, **kw) -> dict:
        import selectors
        req = {"op": op, **kw}
        self.proc.stdin.write(msgpack.packb(req, use_bin_type=True))
        self.proc.stdin.flush()
        deadline = time.monotonic() + self.timeout
        sel = selectors.DefaultSelector()
        sel.register(self.proc.stdout, selectors.EVENT_READ)
        try:
            while True:
                for msg in self._unpacker:
                    out = {k.decode(): v for k, v in msg.items()}
                    if not out.get("ok"):
                        raise RuntimeError(
                            f"child {op} failed: {out.get('error')!r}")
                    return out
                # poll with a bounded wait so a hung-but-alive child
                # raises TimeoutError instead of blocking read1 forever
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TimeoutError(
                        f"child {op} timed out after {self.timeout}s")
                if not sel.select(timeout=min(left, 1.0)):
                    continue
                chunk = self.proc.stdout.read1(65536)
                if not chunk:
                    raise RuntimeError(
                        f"child died mid-{op} (rc={self.proc.poll()})")
                self._unpacker.feed(chunk)
        finally:
            sel.close()

    # -- cluster ops -------------------------------------------------------
    def launch(self, n: int) -> list[int]:
        out = self._call("launch", n=n)
        self.ports = list(out["ports"])
        self.ids = list(out["ids"])
        return self.ports

    def resize(self, n: int) -> None:
        self._call("resize", n=n)

    def bootstrap(self, host: str, port: int) -> None:
        self._call("bootstrap", host=host, port=port)

    def put(self, key: bytes, value: bytes) -> bool:
        return bool(self._call("put", key=key, value=value)["stored"])

    def get(self, key: bytes) -> list[bytes]:
        return list(self._call("get", key=key)["values"])

    def node_ids(self) -> list[bytes]:
        return list(self._call("ids")["ids"])

    def stats(self) -> dict:
        return self._call("stats")

    # -- lifecycle ---------------------------------------------------------
    def quit(self) -> None:
        """Graceful shutdown: nodes join, child exits 0."""
        try:
            self._call("quit")
        except Exception:
            pass
        self.proc.wait(timeout=self.timeout)

    def kill(self) -> None:
        """Simulate whole-cluster failure: SIGKILL, no goodbyes — every
        node in the child vanishes without expiring its peers' routing
        entries (↔ the reference churn scenarios killing NSPopen
        clusters)."""
        self.proc.send_signal(signal.SIGKILL)
        self.proc.wait(timeout=self.timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        if self.proc.poll() is None:
            self.quit()


# ---------------------------------------------------------------------------
# child side
# ---------------------------------------------------------------------------

def _child_main() -> int:
    # the platform is fixed by the parent through the environment
    # (ClusterSubProcess.__init__): importing this module has already
    # touched the backend, so a pin here would be too late
    from ..infohash import InfoHash
    from ..core.value import Value
    from .dhtcluster import NodeCluster

    # Warm the device lookup kernels BEFORE any node exchanges packets:
    # the first find_closest triggers several jit compiles (sort /
    # expand / lookup, a few seconds on CPU) and a compile stall inside
    # the packet path drops every in-flight request — observed as the
    # first put of a fresh child hanging until search expiry.
    from ..core.table import NodeTable
    _warm = NodeTable(InfoHash.get("warmup-self"))
    _warm.insert(InfoHash.get("warmup-peer"), None)
    _warm.find_closest([InfoHash.get("warmup-target")])
    del _warm

    cluster = NodeCluster()
    stdin = sys.stdin.buffer
    stdout = sys.stdout.buffer
    unpacker = msgpack.Unpacker(raw=True)

    def reply(**kw):
        stdout.write(msgpack.packb({"ok": True, **kw}, use_bin_type=True))
        stdout.flush()

    def fail(err):
        import traceback
        text = ("".join(traceback.format_exception(err)).strip()
                if isinstance(err, BaseException) else str(err))
        stdout.write(msgpack.packb({"ok": False, "error": text},
                                   use_bin_type=True))
        stdout.flush()

    while True:
        chunk = stdin.read1(65536)
        if not chunk:
            break
        unpacker.feed(chunk)
        for msg in unpacker:
            req = {k.decode(): v for k, v in msg.items()}
            op = req.get("op", b"").decode() \
                if isinstance(req.get("op"), bytes) else req.get("op")
            try:
                if op == "launch":
                    cluster.resize(int(req["n"]))
                    reply(ports=[n.get_bound_port() for n in cluster.nodes],
                          ids=[bytes(n.get_node_id())
                               for n in cluster.nodes])
                elif op == "resize":
                    cluster.resize(int(req["n"]))
                    reply(n=len(cluster.nodes))
                elif op == "bootstrap":
                    host = req["host"]
                    host = host.decode() if isinstance(host, bytes) else host
                    for n in cluster.nodes:
                        n.bootstrap(host, int(req["port"]))
                    reply()
                elif op == "put":
                    ok = cluster.nodes[0].put_sync(
                        InfoHash(req["key"]), Value(req["value"]),
                        timeout=30.0)
                    reply(stored=bool(ok))
                elif op == "get":
                    vals = cluster.nodes[0].get_sync(
                        InfoHash(req["key"]), timeout=30.0) or []
                    reply(values=[bytes(v.data) for v in vals])
                elif op == "ids":
                    reply(ids=[bytes(n.get_node_id())
                               for n in cluster.nodes])
                elif op == "stats":
                    msgs = 0
                    for n in cluster.nodes:
                        st = n.get_node_message_stats()
                        msgs += sum(st) if st else 0
                    reply(n=len(cluster.nodes), msgs=msgs)
                elif op == "quit":
                    reply()
                    cluster.resize(0)
                    return 0
                else:
                    fail(f"unknown op {op!r}")
            except Exception as e:                      # keep serving
                fail(e)
    cluster.resize(0)
    return 0


if __name__ == "__main__":
    if "--child" in sys.argv:
        sys.exit(_child_main())
    print(__doc__)
