"""The served cells' load generator: Kademlia peers on plain UDP sockets, in
a process of its own that never imports jax (stdlib, msgpack, numpy), so it
neither holds the chip nor shares the server's interpreter lock.

Started by ``drivers/served.py`` as ``python3 -m dhtbench.drivers.served_peers
'<json parameters>'``; takes one command per line on stdin and answers each
with one JSON line on stdout:

    intro <deadline_s>   every peer asks until it has one reply (the node
                         inserts it; a cold node compiles meanwhile)
    run <seconds>        the traffic of the cell, for that long
    quit

Traffic (parameters ``loop``): ``closed`` — every peer keeps ONE ``find``
request outstanding for a fresh uniformly random 160-bit target and sends the
next when the reply arrives.  A request without reply is re-sent after
``resend_s`` and given up after ``lifetime_s`` (upstream's 1 s and 3 s): it
then counts as failed, and as the lifetime in the latencies.  The peers answer
the node's own pings and finds (no nodes, a token), as live peers would.

Packets are the ``a/q/t/y/v`` map ``NetworkEngine._header`` builds for
``send_find_node(..., want=WANT4)``; a reply's ``r.n4`` holds 26-byte entries
(20 of id, 4 of address, 2 of port), nearest first.
"""

from __future__ import annotations

import json
import random
import select
import socket
import sys
import time

import msgpack

AGENT = "RNG1"
NODE_ENTRY = 26           # n4: 20 B id + 4 B IPv4 + 2 B port
ID_LEN = 20


def pack_find(my_id: bytes, target: bytes, tid: int) -> bytes:
    return msgpack.packb(
        {"a": {"id": my_id, "target": target, "w": [int(socket.AF_INET)]},
         "q": "find", "t": tid.to_bytes(4, "big"), "y": "q", "v": AGENT},
        use_bin_type=True)


def pack_reply(my_id: bytes, tid: bytes, to_ip: bytes, nodes: bool) -> bytes:
    body = {"id": my_id, "sa": to_ip}
    if nodes:           # a find/get reply; one without a token gets us blacklisted
        body["n4"] = b""
        body["token"] = my_id + my_id[:12]
    return msgpack.packb({"r": body, "t": tid, "y": "r", "v": AGENT},
                         use_bin_type=True)


def parse(data: bytes) -> dict:
    """``{"kind": "reply", "tid", "ids": [20-byte ids]}`` for a reply,
    ``{"kind": "query", "q", "tid": bytes}`` for a request of the node's,
    ``{"kind": "other"}`` for anything else (errors included)."""
    msg = msgpack.unpackb(data, raw=False, strict_map_key=False)
    if not isinstance(msg, dict) or "t" not in msg:
        return {"kind": "other"}
    tid = msg["t"]
    if isinstance(msg.get("r"), dict):
        n4 = msg["r"].get("n4", b"")
        return {"kind": "reply",
                "tid": int.from_bytes(tid, "big") if isinstance(tid, bytes)
                else int(tid),
                "ids": [n4[i:i + ID_LEN]
                        for i in range(0, len(n4) - NODE_ENTRY + 1,
                                       NODE_ENTRY)]}
    if msg.get("y") == "q" and "q" in msg:
        return {"kind": "query", "q": msg["q"], "tid": tid}
    return {"kind": "other"}


class Peers:
    def __init__(self, p: dict):
        self.p = p
        self.server = (p["server_ip"], p["server_port"])
        self.ids = [bytes.fromhex(h) for h in p["peer_ids"]]
        self.rng = random.Random(p["seed"])
        self.socks = []
        for i in range(len(self.ids)):
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.bind((p["bind_ips"][i], 0))
            s.setblocking(False)
            self.socks.append(s)
        self.index = {s.fileno(): i for i, s in enumerate(self.socks)}
        self.tid = 0
        self.server_queries: dict = {}

    def close(self) -> None:
        for s in self.socks:
            s.close()

    def _send_new(self, i: int, now: float) -> dict:
        self.tid = (self.tid % 0xFFFFFFF0) + 1
        target = self.rng.randbytes(ID_LEN)
        req = {"tid": self.tid, "target": target, "first": now, "last": now,
               "packet": pack_find(self.ids[i], target, self.tid)}
        self.socks[i].sendto(req["packet"], self.server)
        return req

    def _receive(self, i: int):
        """One datagram of peer ``i``: a parsed reply, or ``None`` after
        answering (or ignoring) anything else."""
        try:
            data, addr = self.socks[i].recvfrom(4096)
        except BlockingIOError:
            return None
        try:
            msg = parse(data)
        except Exception:                 # noqa: BLE001 — any bad datagram
            return None
        if msg["kind"] == "query":
            q = msg["q"]
            self.server_queries[q] = self.server_queries.get(q, 0) + 1
            self.socks[i].sendto(
                pack_reply(self.ids[i], msg["tid"],
                           socket.inet_aton(addr[0]), nodes=q != "ping"),
                addr)
            return None
        return msg if msg["kind"] == "reply" else None

    def run(self, seconds: float, *, until_each_answered: bool = False
            ) -> dict:
        """The closed loop.  With ``until_each_answered`` (the
        introduction) a peer stops after its first reply and never gives
        up before ``seconds``."""
        resend, lifetime = self.p["resend_s"], self.p["lifetime_s"]
        n = len(self.socks)
        out = [None] * n                  # the outstanding request of each peer
        answered = [0] * n
        lat_ms, late_ms, replies, failed, resent = [], [], [], 0, 0
        due = [time.perf_counter()] * n   # when the peer's next send was due
        t0 = time.perf_counter()
        t_end = t0 + seconds
        busy = 0.0
        self.server_queries = {}
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            if until_each_answered and all(answered):
                break
            for i in range(n):
                if out[i] is None:
                    if until_each_answered and answered[i]:
                        continue
                    late_ms.append((now - due[i]) * 1e3)
                    out[i] = self._send_new(i, now)
                elif now - out[i]["first"] >= lifetime \
                        and not until_each_answered:
                    failed += 1
                    lat_ms.append(lifetime * 1e3)
                    out[i], due[i] = None, now
                elif now - out[i]["last"] >= resend:
                    out[i]["last"] = now
                    resent += 1
                    self.socks[i].sendto(out[i]["packet"], self.server)
            pending = [r for r in out if r is not None]
            wake = min([t_end] + [min(r["last"] + resend, r["first"] + (
                seconds if until_each_answered else lifetime))
                for r in pending])
            t_sel = time.perf_counter()
            busy += t_sel - now
            ready, _, _ = select.select(self.socks, [], [],
                                        max(0.0, wake - t_sel))
            t_woke = time.perf_counter()
            for s in ready:
                i = self.index[s.fileno()]
                while True:
                    msg = self._receive(i)
                    if msg is None:
                        break
                    req = out[i]
                    if req is None or msg["tid"] != req["tid"]:
                        continue          # a late duplicate
                    now = time.perf_counter()
                    lat_ms.append((now - req["first"]) * 1e3)
                    replies.append((i, req["target"], msg["ids"]))
                    answered[i] += 1
                    out[i], due[i] = None, now
            busy += time.perf_counter() - t_woke
        window_s = time.perf_counter() - t0
        sample = self.rng.sample(range(len(replies)),
                                 min(self.p["sample"], len(replies)))
        return {
            "window_s": window_s, "answered": len(replies), "failed": failed,
            "in_flight_at_end": sum(r is not None for r in out),
            "resent": resent, "latency_ms": lat_ms, "send_late_ms": late_ms,
            "generator_busy_share": busy / window_s if window_s else 0.0,
            "server_queries": self.server_queries,
            "per_peer": answered,
            "sample": [[replies[j][0], replies[j][1].hex(),
                        [b.hex() for b in replies[j][2]]] for j in sample]}


def main() -> int:
    peers = Peers(json.loads(sys.argv[1]))
    try:
        for line in sys.stdin:
            cmd = line.split()
            if not cmd or cmd[0] == "quit":
                break
            if cmd[0] == "intro":
                res = peers.run(float(cmd[1]), until_each_answered=True)
            elif cmd[0] == "run":
                res = peers.run(float(cmd[1]))
            else:
                res = {"error": f"unknown command {cmd[0]!r}"}
            print(json.dumps(res), flush=True)
    finally:
        peers.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
