"""Live node at device scale (round-4 verdict ask #3).

A real ``Dht`` node with a table PAST the host-scan threshold
(core/table.py HOST_SCAN_MAX_ROWS) must serve protocol requests through
the device snapshot path — engine → Dht → NodeTable →
Snapshot.lookup_launch (the round-20 launch/consume seam every resolve,
sync or pipelined, funnels through) — and this is asserted, not
assumed: every closest-node resolve during
the burst is counted through the snapshot/churn view, and the snapshot
version must match the table's.  ``chip_smoke.phase_served`` is the same
stack at 1M rows on the chip; this test runs it at 8K rows over real
localhost UDP.
"""

import secrets
import select
import socket
import threading
import time

import numpy as np
import pytest

from opendht_tpu.core import table as table_mod
from opendht_tpu.core.value import Query
from opendht_tpu.infohash import InfoHash
from opendht_tpu.net.engine import EngineCallbacks, NetworkEngine
from opendht_tpu.runtime.config import Config
from opendht_tpu.runtime.dht import Dht
from opendht_tpu.scheduler import Scheduler
from opendht_tpu.sockaddr import SockAddr

N_ROWS = 8192            # > HOST_SCAN_MAX_ROWS → every lookup is device
N_BURST = 12


def test_live_node_serves_burst_through_device_path(monkeypatch):
    assert N_ROWS > table_mod.HOST_SCAN_MAX_ROWS

    ssock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    ssock.bind(("127.0.0.1", 0))
    sport = ssock.getsockname()[1]
    ssock.setblocking(False)
    dht = Dht(lambda data, dst: ssock.sendto(data, (str(dst.ip), dst.port))
              and 0, Config(max_req_per_sec=1_000_000), has_v6=False)
    table = dht.tables[socket.AF_INET]
    rng = np.random.default_rng(3)
    table.bulk_load(rng.integers(0, 2 ** 32, size=(N_ROWS, 5),
                                 dtype=np.uint32),
                    dht.scheduler.time(), addrs=SockAddr("10.9.9.9", 999))
    dht.warmup()
    assert table._snap is not None

    calls = {"n": 0}
    # lookup_launch is the one seam both the sync and the pipelined
    # resolve forms share (lookup() itself delegates to it) — counting
    # here covers the device path whatever ingest_pipeline_depth is
    for cls in (table_mod.Snapshot, table_mod.ChurnView):
        orig = cls.lookup_launch

        def counted(self, queries, *, _orig=orig, **kw):
            calls["n"] += 1
            return _orig(self, queries, **kw)

        monkeypatch.setattr(cls, "lookup_launch", counted)

    stop = threading.Event()

    def serve():
        while not stop.is_set():
            r, _, _ = select.select([ssock], [], [], 0.02)
            if not r:
                continue
            try:
                data, addr = ssock.recvfrom(64 * 1024)
            except OSError:
                continue
            dht.periodic(data, SockAddr(addr[0], addr[1]))

    th = threading.Thread(target=serve, daemon=True)
    th.start()
    try:
        csock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        csock.bind(("127.0.0.1", 0))
        csock.setblocking(False)
        ceng = NetworkEngine(InfoHash.get("client"), 0,
                             lambda data, dst: csock.sendto(
                                 data, (str(dst.ip), dst.port)) and 0,
                             Scheduler(), EngineCallbacks())
        node = ceng.cache.get_node(dht.myid, SockAddr("127.0.0.1", sport),
                                   time.monotonic(), confirm=True)
        done = []
        calls["n"] = 0
        for i in range(N_BURST):
            tgt = InfoHash.get(b"burst-" + secrets.token_bytes(8))
            if i % 2:
                ceng.send_find_node(node, tgt, want=1,
                                    on_done=lambda r, a: done.append(a))
            else:
                ceng.send_get_values(node, tgt, Query(), want=1,
                                     on_done=lambda r, a: done.append(a))
        deadline = time.monotonic() + 90
        while len(done) < N_BURST and time.monotonic() < deadline:
            ceng.scheduler.run()
            r, _, _ = select.select([csock], [], [], 0.02)
            if r:
                try:
                    data, addr = csock.recvfrom(64 * 1024)
                except OSError:
                    continue
                ceng.process_message(data, SockAddr(addr[0], addr[1]))
        csock.close()
    finally:
        stop.set()
        th.join()
        ssock.close()

    assert len(done) == N_BURST
    # every reply resolved its closest set on the DEVICE path
    assert calls["n"] >= N_BURST
    assert table._snap is not None
    assert table._snap.version == table._version
    # replies actually carry closest nodes from the loaded table
    assert all(len(a.nodes4) == 8 for a in done)
