"""Native C++ engine tests: scalar-kernel parity with the Python
InfoHash reference, sorted-walk vs full-scan agreement, and the UDP
engine's loopback datagram path + ingress guards."""

import time

import numpy as np
import pytest

from opendht_tpu.infohash import InfoHash
from opendht_tpu import native

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native toolchain unavailable")


def _rand_ids(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, size=(n, 20), dtype=np.uint8)


# ------------------------------------------------------------ scalar parity

def test_xor_cmp_matches_python():
    ids = _rand_ids(64, 1)
    s = InfoHash(bytes(ids[0]))
    for i in range(1, 31, 3):
        a, b = InfoHash(bytes(ids[i])), InfoHash(bytes(ids[i + 1]))
        assert native.xor_cmp(bytes(s), bytes(a), bytes(b)) == \
            s.xor_cmp(a, b)
    assert native.xor_cmp(bytes(s), bytes(ids[5]), bytes(ids[5])) == 0


def test_common_bits_matches_python():
    ids = _rand_ids(32, 2)
    for i in range(0, 30, 2):
        a, b = InfoHash(bytes(ids[i])), InfoHash(bytes(ids[i + 1]))
        assert native.common_bits(bytes(a), bytes(b)) == \
            InfoHash.common_bits(a, b)
    a = InfoHash(bytes(ids[0]))
    assert native.common_bits(bytes(a), bytes(a)) == 160


# ------------------------------------------------------------- table lookup

def test_sorted_walk_equals_full_scan():
    ids = _rand_ids(500, 3)
    queries = _rand_ids(40, 4)
    sorted_ids, perm = native.sort_ids(ids)
    walk = native.sorted_closest(sorted_ids, queries, k=8, window=64)
    scan = native.scan_closest(ids, queries, k=8)
    # map walk's sorted indices back to original rows
    walk_rows = np.where(walk >= 0, perm[np.clip(walk, 0, None)], -1)
    assert np.array_equal(walk_rows, scan)


def test_sorted_walk_matches_device_kernel():
    """Native outward walk == JAX full-scan oracle (ops/xor_topk)."""
    import jax.numpy as jnp
    from opendht_tpu.ops.ids import ids_from_bytes
    from opendht_tpu.ops.xor_topk import xor_topk

    ids = _rand_ids(300, 5)
    queries = _rand_ids(17, 6)
    sorted_ids, perm = native.sort_ids(ids)
    walk = native.sorted_closest(sorted_ids, queries, k=8)
    walk_rows = np.where(walk >= 0, perm[np.clip(walk, 0, None)], -1)

    _, idx = xor_topk(jnp.asarray(ids_from_bytes(queries)),
                      jnp.asarray(ids_from_bytes(ids)), k=8)
    assert np.array_equal(walk_rows, np.asarray(idx))


def test_clustered_table_certificate_fallback():
    """Adversarially clustered ids (hundreds sharing a prefix) defeat a
    fixed window; the native certificate must trigger the full-scan
    fallback so results stay exact even with a tiny window."""
    ids = _rand_ids(300, 9)
    ids[:200, :6] = 0xAB                 # 200 ids share a 48-bit prefix
    queries = _rand_ids(25, 10)
    queries[:10, :6] = 0xAB              # some queries land in the cluster
    sorted_ids, perm = native.sort_ids(ids)
    walk = native.sorted_closest(sorted_ids, queries, k=8, window=16)
    scan = native.scan_closest(ids, queries, k=8)
    walk_rows = np.where(walk >= 0, perm[np.clip(walk, 0, None)], -1)
    # fallback results are original-row indices already mapped via the
    # sorted table; map both sides to distances for comparison
    def dist(i, q):
        return bytes(a ^ b for a, b in zip(ids[i], queries[q]))
    for qi in range(queries.shape[0]):
        got = sorted(dist(i, qi) for i in walk_rows[qi])
        want = sorted(dist(i, qi) for i in scan[qi])
        assert got == want, qi


def test_small_table_padding():
    ids = _rand_ids(3, 7)
    queries = _rand_ids(2, 8)
    sorted_ids, perm = native.sort_ids(ids)
    out = native.sorted_closest(sorted_ids, queries, k=8)
    assert (out[:, :3] >= 0).all() and (out[:, 3:] == -1).all()


# --------------------------------------------------------------- UDP engine

def test_udp_loopback_roundtrip():
    with native.UdpEngine(0) as a, native.UdpEngine(0) as b:
        assert a.port > 0 and b.port > 0
        assert a.send(b"ping-payload", ("127.0.0.1", b.port)) == 0
        deadline = time.monotonic() + 5.0
        pkts = []
        while not pkts and time.monotonic() < deadline:
            pkts = b.poll()
            time.sleep(0.01)
        assert pkts, "packet never arrived"
        rx_time, data, (host, port) = pkts[0]
        assert data == b"ping-payload"
        assert host == "127.0.0.1" and port == a.port
        assert rx_time > 0
        st = b.stats()
        assert st["rx"] == 1 and st["queued"] == 0


def test_udp_rate_limit_drops():
    with native.UdpEngine(0) as a, \
            native.UdpEngine(0, per_ip_rps=10, global_rps=10,
                             exempt_loopback=False) as b:
        for i in range(50):
            a.send(b"x%d" % i, ("127.0.0.1", b.port))
        time.sleep(0.5)
        got = len(b.poll(max_pkts=100))
        st = b.stats()
        assert got <= 10
        assert st["dropped_rate"] >= 30


def test_udp_loopback_exempt_from_limits():
    """Default engines never rate-limit 127.0.0.1 sources (local
    clusters share that IP)."""
    with native.UdpEngine(0) as a, \
            native.UdpEngine(0, per_ip_rps=5, global_rps=5) as b:
        for i in range(40):
            a.send(b"y%d" % i, ("127.0.0.1", b.port))
        deadline = time.monotonic() + 5.0
        got = []
        while len(got) < 40 and time.monotonic() < deadline:
            got.extend(b.poll(max_pkts=64))
            time.sleep(0.01)
        assert len(got) == 40
        assert b.stats()["dropped_rate"] == 0


def test_udp_v6_loopback_exempt_from_limits():
    """::1 joins the 127/8 rate-limit exemption (local v6 clusters share
    that source the same way v4 ones share 127.0.0.1)."""
    with native.UdpEngine(0) as a, \
            native.UdpEngine(0, per_ip_rps=5, global_rps=5) as b:
        if not (a.has_v6 and b.has_v6):
            pytest.skip("no IPv6 on this host")
        for i in range(40):
            a.send(b"z%d" % i, ("::1", b.port))
        deadline = time.monotonic() + 5.0
        got = []
        while len(got) < 40 and time.monotonic() < deadline:
            got.extend(b.poll(max_pkts=64))
            time.sleep(0.01)
        assert len(got) == 40
        assert b.stats()["dropped_rate"] == 0


def test_udp_batch_poll():
    with native.UdpEngine(0) as a, native.UdpEngine(0) as b:
        for i in range(20):
            a.send(("msg-%02d" % i).encode(), ("127.0.0.1", b.port))
        deadline = time.monotonic() + 5.0
        got = []
        while len(got) < 20 and time.monotonic() < deadline:
            got.extend(b.poll(max_pkts=64))
            time.sleep(0.01)
        assert len(got) == 20
        assert [p[1] for p in got] == \
            [("msg-%02d" % i).encode() for i in range(20)]


def test_helpers_raise_without_lib(monkeypatch):
    # On hosts without a toolchain get_lib() returns None; module-level
    # helpers must raise the actionable RuntimeError, not AttributeError.
    import pytest
    from opendht_tpu.native import wrappers
    monkeypatch.setattr(wrappers, "get_lib", lambda: None)
    with pytest.raises(RuntimeError, match="native library unavailable"):
        wrappers.common_bits(b"\0" * 20, b"\0" * 20)
    with pytest.raises(RuntimeError, match="native library unavailable"):
        wrappers.UdpEngine(0)


def test_concurrent_first_builds_all_load_the_library(tmp_path):
    """Six processes that find an empty cache at once (the workers of a
    test run under a fresh ``HOME``) each build and rename a file of
    their own: every one ends with a library it can load.  With one
    shared temporary name the first to finish took the others' file from
    under them, and a whole test file skipped as ``unavailable``."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, OPENDHT_TPU_CACHE=str(tmp_path))
    code = ("from opendht_tpu.native import build; "
            "print(build.available())")
    procs = [subprocess.Popen([sys.executable, "-c", code], env=env,
                              stdout=subprocess.PIPE, text=True)
             for _ in range(6)]
    assert [p.communicate(timeout=300)[0].strip() for p in procs] \
        == ["True"] * 6
    assert [f for f in os.listdir(tmp_path) if f.endswith(".tmp")] == []


def test_a_failed_build_leaves_no_temporary(tmp_path, monkeypatch):
    """A compiler that dies after it began to write: no library, and the
    process's own temporary is gone with it."""
    import os
    import subprocess
    from opendht_tpu.native import build
    monkeypatch.setenv("OPENDHT_TPU_CACHE", str(tmp_path))

    def dies(cmd, **_kw):
        open(cmd[cmd.index("-o") + 1], "wb").close()
        raise subprocess.CalledProcessError(1, cmd, stderr=b"half way")

    monkeypatch.setattr(build.subprocess, "run", dies)
    assert build._build() is None
    assert os.listdir(tmp_path) == []


def test_udp_v6_roundtrip():
    with native.UdpEngine(0) as a, native.UdpEngine(0) as b:
        if not (a.has_v6 and b.has_v6):
            pytest.skip("no IPv6 on this host")
        a.send(b"over six", ("::1", b.port))
        deadline = time.monotonic() + 5.0
        got = []
        while not got and time.monotonic() < deadline:
            got.extend(b.poll())
            time.sleep(0.01)
        assert got and got[0][1] == b"over six"
        assert got[0][2] == ("::1", a.port)


def test_udp_dual_stack_same_port():
    with native.UdpEngine(0) as a, native.UdpEngine(0) as b:
        if not b.has_v6:
            pytest.skip("no IPv6 on this host")
        a.send(b"via four", ("127.0.0.1", b.port))
        a.send(b"via six", ("::1", b.port))
        deadline = time.monotonic() + 5.0
        got = []
        while len(got) < 2 and time.monotonic() < deadline:
            got.extend(b.poll())
            time.sleep(0.01)
        assert {p[1] for p in got} == {b"via four", b"via six"}
        fams = {(":" in p[2][0]) for p in got}
        assert fams == {True, False}


def test_udp_v6_disabled():
    with native.UdpEngine(0, ipv6=False) as e:
        assert not e.has_v6
        assert e.send(b"x", ("::1", 1)) != 0     # EAFNOSUPPORT
