"""The benchmark's own tests — CPU, seconds not minutes:

    python3 -m pytest dhtbench/tests -q

None loads the TPU library, starts a cluster or describes a topology."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from dhtbench import reference, run, trace_reduce
from dhtbench.drivers import served_peers
from dhtbench.sources import registry as registry_source

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return run.load_json(ROOT, "BENCHMARK.json")


def test_manifest_names_and_units(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["dhtbench"]
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in names
    for w in manifest["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for c in manifest["configs"]:
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])


def test_every_cell_resolves_by_name(manifest):
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    seen = set()
    for w in manifest["workloads"]:
        cell, config, driver, metrics = run.resolve(w["name"])
        assert cell["config"] == w["config"] == config["name"]
        assert cell["chips"] == w["chips"]
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        declared = configs[w["config"]]
        assert declared["file"] == f"dhtbench/configs/{w['config']}.json"
        assert declared["source"] == config["source"]
        assert declared["reduced"] == config["reduced"]
        for fn in ("setup", "window", "check", "close"):
            assert callable(getattr(driver, fn))
        reported = [n for n, m in end_to_end.items()
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in reported and len(reported) >= 2
        assert metrics, "a cell reports at least one per-layer metric"
        for name, m in metrics.items():
            seen.add(name)
            assert m["layer"] and m["moves"] in reported, (name, m["moves"])
            assert os.path.exists(os.path.join(
                run.HERE, "sources", m["source"]["kind"] + ".py")), name
            mirror = per_layer[name]
            assert {k: mirror[k] for k in ("unit", "better", "layer",
                                           "moves")} \
                == {k: m[k] for k in ("unit", "better", "layer", "moves")}
            assert w["name"] in mirror.get("workloads", [w["name"]])
    assert seen == set(per_layer), "BENCHMARK.json and metrics/ differ"


RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("trace", [False, True])
def test_sim_cell_at_toy_size_prints_the_contract_line(manifest, trace):
    line = run.run_cell("sim-10m.wave-65536", 2 ** 31 + 12345, 1.0, trace,
                        rehearsal={"n_ids": 4096, "wave_targets": 256,
                                   "target_sets": 4})
    line = json.loads(json.dumps(line))
    assert set(line) == RESULT_KEYS         # no breakdown: the CPU has no device plane
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % 256 == 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    if trace:
        assert set(line["metrics"]) == {"sim_round_ms"}   # the trace ones read nothing here
    else:
        assert set(line["metrics"]) == {"sim_lookups_per_s",
                                        "sim_wave_p90_ms", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


def test_without_a_chip_nothing_runs_and_nothing_is_printed(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "sim-10m.wave-65536", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_unknown_device_kind_raises():
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.peaks_for("TPU v9 imaginary")


def _bytes(rows: np.ndarray) -> list:
    return [r.astype(">u4").tobytes() for r in rows]


def test_reply_check():
    rng = np.random.default_rng(7)
    ids = rng.integers(0, 2 ** 32, size=(5000, 5), dtype=np.uint32)
    peers = _bytes(rng.integers(0, 2 ** 32, size=(3, 5), dtype=np.uint32))
    target = rng.bytes(20)
    pool = np.concatenate([ids, reference.limbs(b"".join(peers[1:]))])
    top = _bytes(pool[reference.xor_closest(pool, reference.limbs(target)[0], 9)])
    live = set(_bytes(ids))
    ask = dict(live_start=live, live_end=ids, peers=peers, k=8)
    assert reference.reply_is_right(target, top[:8], peers[0], **ask)
    # one wrong id: the 9th closest in place of the 8th
    assert not reference.reply_is_right(target, top[:7] + [top[8]],
                                        peers[0], **ask)
    # out of order, short, or naming the asker
    assert not reference.reply_is_right(target, top[1::-1] + top[2:8],
                                        peers[0], **ask)
    assert not reference.reply_is_right(target, top[:7], peers[0], **ask)
    assert not reference.reply_is_right(target, top[:7] + [peers[0]],
                                        peers[0], **ask)
    # answered before a row expired: the row is gone at the end, and right
    loaded = [b for b in top[:8] if b not in peers]
    gone = loaded[0]
    after = ids[np.array([b != gone for b in _bytes(ids)])]
    assert reference.reply_is_right(target, top[:8], peers[0],
                                    **dict(ask, live_end=after))
    # answered after it expired: the 9th moves up, and that is right too
    assert reference.reply_is_right(
        target, [b for b in top if b != gone][:8], peers[0],
        **dict(ask, live_end=after))
    # but a row that was never live is not
    assert not reference.reply_is_right(
        target, top[:8], peers[0], **dict(ask, live_start=live - {gone},
                                          live_end=after))


def test_wire_format_both_ways():
    from opendht_tpu.net.engine import NetworkEngine
    from opendht_tpu.net.parsed_message import MessageType, ParsedMessage
    my_id, target = bytes(range(20)), bytes(range(20, 40))
    msg = ParsedMessage.from_bytes(served_peers.pack_find(my_id, target, 77))
    assert msg.type is MessageType.FIND_NODE and msg.tid == 77
    assert bytes(msg.id) == my_id and bytes(msg.target) == target
    assert msg.want == 1                                    # WANT4
    # a reply as the program builds it, through the benchmark's parser
    sent = []
    engine = NetworkEngine.__new__(NetworkEngine)
    engine.myid, engine.is_client, engine.network = my_id, False, 0
    engine._send = lambda data, addr: sent.append(data)
    from opendht_tpu.core.value import Query
    from opendht_tpu.sockaddr import SockAddr
    nodes = b"".join(bytes([i]) * 20 + bytes([10, 1, 2, 3, 0x11, 0xD7])
                     for i in range(8))
    engine.send_nodes_values(SockAddr("127.0.0.2", 4000), 77, nodes, b"", [],
                             Query(), b"tok")
    got = served_peers.parse(sent[0])
    assert got == {"kind": "reply", "tid": 77,
                   "ids": [bytes([i]) * 20 for i in range(8)]}
    # and the peers' own answers to the node parse as replies
    pong = ParsedMessage.from_bytes(served_peers.pack_reply(
        my_id, (5).to_bytes(4, "big"), bytes([127, 0, 0, 1]), nodes=True))
    assert pong.type is MessageType.REPLY and pong.tid == 5 and pong.token


def test_trace_reduce_on_hand_made_intervals():
    ms = 1e6                                 # the trace's clock is in ns
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_f", 10 * ms, 40 * ms),
                            ("jit_f", 60 * ms, 90 * ms)],
            "XLA Ops": [("%while.1 = s32[8] while(...)", 10 * ms, 40 * ms),
                        ("%fusion.2 = u32[4,2]{1,0} fusion(...), kind=kLoop",
                         12 * ms, 30 * ms),
                        ("%fusion.2 = u32[4,2]{1,0} fusion(...), kind=kLoop",
                         60 * ms, 90 * ms)],
            "Async XLA Ops": [("%copy-start", 0, 100 * ms)]},   # not busy time
        "/host:CPU": {"python": [
            ("dhtbench.window", 0, 100 * ms),
            ("wave", 6 * ms, 94 * ms), ("record_wave", 41 * ms, 59 * ms)]},
        "/device:CUSTOM:Megascale Trace": {"x": [("y", 0, 100 * ms)]}}
    r = trace_reduce.reduce(planes)
    assert r["chips"] == 1
    assert r["busy_s"] == pytest.approx(0.060)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["idle_share"] == pytest.approx(0.40)
    ops = dict(r["device_ops"])
    assert ops["fusion.2 u32[4,2] fusion kLoop"] == pytest.approx(0.048)
    assert ops["while.1 s32[8] while"] == pytest.approx(0.012)   # self time
    gaps = dict(r["idle_gaps"])
    assert gaps["host:record_wave"] == pytest.approx(0.020)
    assert gaps["host:dhtbench.window"] == pytest.approx(0.020)  # 0-10, 90-100
    assert trace_reduce.merge([(5, 7), (1, 3), (2, 6)]) == [(1, 7)]
    assert trace_reduce.reduce({"/host:CPU": {}}) is None


def test_registry_source_reads_deltas_and_means():
    diff = {"counters": {'drops{node="a"}': 3, 'drops{node="b"}': 2},
            "histograms": {'round_seconds{mode="single"}':
                           {"count": 4, "sum": 0.084},
                           'round_seconds{mode="tp"}': {"count": 1, "sum": 9}}}
    after = {"counters": {'drops{node="a"}': 3, 'drops{node="b"}': 2,
                          "quiet": 5}}
    ctx = {"registry": diff, "registry_after": after}
    read = registry_source.read
    assert read({"series": "drops", "stat": "delta"}, ctx) == 5.0
    assert read({"series": "quiet", "stat": "delta"}, ctx) == 0.0
    assert read({"series": "absent", "stat": "delta"}, ctx) is None
    assert read({"series": "round_seconds", "labels": {"mode": "single"},
                 "stat": "mean", "scale": 1000}, ctx) == pytest.approx(21.0)
    assert read({"series": "absent", "stat": "mean"}, ctx) is None


def test_the_generator_never_imports_jax():
    code = ("import sys, dhtbench.drivers.served_peers; "
            "sys.exit(any(m == 'jax' or m.startswith('jax.') "
            "for m in sys.modules))")
    assert subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          timeout=60).returncode == 0


def test_xor_index_equals_the_plain_reference():
    rng = np.random.default_rng(11)
    ids = rng.integers(0, 2 ** 32, size=(20000, 5), dtype=np.uint32)
    ids[:64, :2] = ids[64, :2]          # a run of rows equal in their top 64 bits
    index = reference.XorIndex(ids)
    targets = rng.integers(0, 2 ** 32, size=(40, 5), dtype=np.uint32)
    targets[0] = ids[7]
    targets[1, :2] = ids[64, :2]
    for t in targets:
        assert index.closest(t, 8).tolist() \
            == reference.xor_closest(ids, t, 8).tolist()
