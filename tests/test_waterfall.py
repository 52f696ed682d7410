"""Per-op latency waterfall (round 19, opendht_tpu/waterfall.py): the
always-on stage profiler, the per-op sum≈end-to-end decomposition pin,
exemplar-stamped hot buckets, the degrade-only stage_budget health
signal, and the dhtmon/REPL/export surfaces."""

from __future__ import annotations

import json
import re
import socket as _socket
import time

import numpy as np

from opendht_tpu import health, telemetry, waterfall
from opendht_tpu.infohash import InfoHash
from opendht_tpu.runtime import Config, Dht
from opendht_tpu.runtime.live_search import SEARCH_NODES
from opendht_tpu.scheduler import Scheduler
from opendht_tpu.sockaddr import SockAddr
from opendht_tpu.waterfall import (DEFAULT_STAGE_BUDGETS, STAGE_ALIASES,
                                   STAGES, StageProfiler, WaterfallConfig)

AF = _socket.AF_INET

#: per-op decomposition tolerance (the acceptance-criteria pin): the
#: recorded stages are non-overlapping sub-intervals of the op's
#: admission→scatter wall-clock, so their sum can never exceed it, and
#: the unattributed remainder — the wave-assembly glue (grouping loop,
#: target-array build, metric writes), all host-side — must stay a
#: small fraction of the op (floored for CPU scheduling jitter)
SUM_TOL_FRAC = 0.5
SUM_TOL_FLOOR_S = 0.100


def _profiler(**cfg_kw) -> StageProfiler:
    return StageProfiler(WaterfallConfig(**cfg_kw),
                         reg=telemetry.MetricsRegistry())


def make_dht(clock, n_nodes=12, **cfg_kw):
    """The wave-builder test harness: v4-only Dht on a virtual clock
    with a populated table and a swallow-everything transport."""
    cfg = Config(**cfg_kw)
    dht = Dht(lambda data, addr: 0, config=cfg,
              scheduler=Scheduler(clock=lambda: clock["t"]),
              has_v6=False)
    rng = np.random.default_rng(1234)
    table = dht.tables[AF]
    added = 0
    while added < n_nodes:
        h = InfoHash(bytes(rng.integers(0, 256, 20, dtype=np.uint8)))
        if table.insert(h, SockAddr("10.9.0.%d" % (added + 1), 4500),
                        now=clock["t"], confirm=2) is not None:
            added += 1
    return dht


# ========================================================== unit: profiler
def test_observe_disabled_is_noop():
    p = _profiler(enabled=False)
    p.observe("queue_wait", 1.0)
    p.record_op("get", {"queue_wait": 1.0}, 1.0)
    assert p.snapshot()["stages"]["queue_wait"]["count"] == 0
    assert p.ops() == []
    assert not p.enabled


def test_exemplar_rides_the_landing_bucket():
    p = _profiler()
    tid = "ab" * 16
    p.observe("device_launch", 0.004, exemplar=tid)
    d = p.snapshot()["stages"]["device_launch"]
    assert d["count"] == 1
    assert d["exemplars"], "hot bucket lost its exemplar"
    le, value, got = d["exemplars"][0]
    assert value == 0.004 and got == tid and le >= 0.004


def test_first_launch_true_exactly_once_per_group():
    p = _profiler()
    assert p.first_launch((AF, 8))
    assert not p.first_launch((AF, 8))
    assert p.first_launch((AF, 16))      # a new group shape compiles again
    assert not p.first_launch((AF, 16))


def test_record_op_ring_bounded():
    p = _profiler(op_ring=4)
    for i in range(10):
        p.record_op("get", {"queue_wait": 0.001}, 0.002, trace_id="%02x" % i)
    ops = p.ops()
    assert len(ops) == 4
    assert [o["trace_id"] for o in ops] == ["06", "07", "08", "09"]
    assert all("t" in o for o in ops)


def test_folded_flamegraph_lines():
    p = _profiler()
    p.observe("queue_wait", 0.001)
    p.observe("device_launch", 0.005)    # alias lands in device_wait
    out = p.folded()
    assert out.endswith("\n")
    for ln in out.strip().splitlines():
        assert re.fullmatch(r"dht;op;[a-z_]+ \d+", ln), ln
    assert "dht;op;queue_wait 1000" in out
    # folded emits canonical stages only — the round-22 alias resolves
    assert "dht;op;device_wait 5000" in out
    assert "device_launch" not in out
    assert _profiler().folded() == ""    # nothing observed, nothing folded


def test_stage_budget_windowed_worst_ratio():
    p = _profiler()
    assert p.stage_budget() is None          # nothing observed
    for _ in range(5):
        p.observe("queue_wait", 0.001)       # well under the 20 ms budget
    r = p.stage_budget()
    assert r is not None and r < 1.0
    # the window consumed those samples: a quiet interval is unknown,
    # not a replay of boot history
    assert p.stage_budget() is None
    for _ in range(5):
        p.observe("queue_wait", 10 * DEFAULT_STAGE_BUDGETS["queue_wait"])
    assert p.stage_budget() > 1.0
    # below the min-event floor the signal stays unknown (one slow
    # wave at boot is not a trend)
    p.observe("queue_wait", 1.0)
    assert p.stage_budget() is None


def test_stage_budget_excludes_device_compile():
    p = _profiler()
    for _ in range(8):
        p.observe("device_compile", 500.0)   # way past any budget
    assert p.stage_budget() is None


def test_configure_rebounds_ring_and_budgets():
    p = _profiler()
    p.record_op("get", {}, 0.001)
    p.configure(WaterfallConfig(op_ring=2, budgets={"queue_wait": 9.0}))
    assert p.budgets["queue_wait"] == 9.0
    assert p.budgets["rpc_wait"] == DEFAULT_STAGE_BUDGETS["rpc_wait"]
    for i in range(5):
        p.record_op("get", {}, 0.001)
    assert len(p.ops()) == 2


# ================================================= integration: wave path
def test_wave_stages_advance_and_ops_sum_to_end_to_end():
    """One coalesced wave through the live wave builder: queue_wait /
    device stage / scatter_back all advance on the GLOBAL profiler,
    and every per-op record's stage sum ≈ its end-to-end wall-clock
    within the pinned tolerance (rpc_wait excluded by construction —
    it overlaps the device stages)."""
    wf = waterfall.get_profiler()
    wf.configure(WaterfallConfig())
    base = {s: wf._h[s].count for s in STAGES}
    t0 = time.time()

    clock = {"t": 5000.0}
    dht = make_dht(clock, ingest_fill_target=4, ingest_deadline=5.0)
    for i in range(4):
        dht.get(InfoHash.get(f"wf-sum-{i}"))
    dht.scheduler.run()

    assert wf._h["queue_wait"].count >= base["queue_wait"] + 4
    dev = (wf._h["device_compile"].count + wf._h["device_wait"].count
           - base["device_compile"] - base["device_wait"])
    assert dev >= 1
    assert wf._h["scatter_back"].count >= base["scatter_back"] + 1

    # the GLOBAL op ring may already be full from earlier tests, so
    # the 4 new records are asserted by wall-clock stamp, not length
    recs = wf.ops()[-4:]
    assert len(recs) == 4 and all(o["t"] >= t0 for o in recs), recs
    assert all(o["kind"] == "refill" for o in recs), recs
    for o in recs:
        s = sum(o["stages"].values())
        assert "rpc_wait" not in o["stages"]
        assert s <= o["end_to_end"] + 1e-6, (s, o)
        gap = o["end_to_end"] - s
        assert gap <= max(SUM_TOL_FLOOR_S,
                          SUM_TOL_FRAC * o["end_to_end"]), o


def test_wave_compile_execute_split_per_group():
    """The FIRST timed launch of a (family, k) group lands in
    device_compile; the second identical wave lands in
    device_launch."""
    wf = waterfall.get_profiler()
    wf.configure(WaterfallConfig())
    wf._compiled.clear()
    c0 = wf._h["device_compile"].count
    l0 = wf._h["device_launch"].count
    clock = {"t": 6000.0}
    dht = make_dht(clock, ingest_fill_target=2, ingest_deadline=5.0)
    for i in range(2):
        dht.get(InfoHash.get(f"wf-split-a{i}"))
    dht.scheduler.run()
    assert wf._h["device_compile"].count == c0 + 1
    assert wf._h["device_launch"].count == l0
    for i in range(2):
        dht.get(InfoHash.get(f"wf-split-b{i}"))
    dht.scheduler.run()
    assert wf._h["device_compile"].count == c0 + 1
    assert wf._h["device_launch"].count == l0 + 1


def test_results_bit_identical_profiler_on_vs_off():
    """The profiler only observes: the wave's resolved node rows are
    identical with it enabled and disabled."""
    wf = waterfall.get_profiler()
    targets = [InfoHash.get(f"wf-ident-{i}") for i in range(5)]

    def run_wave(enabled: bool):
        wf.configure(WaterfallConfig(enabled=enabled))
        clock = {"t": 7000.0}
        dht = make_dht(clock, ingest_fill_target=5, ingest_deadline=5.0)
        got = []
        for t in targets:
            dht.wave_builder.submit(t, AF, SEARCH_NODES,
                                    lambda nodes: got.append(nodes))
        dht.scheduler.run()
        return [[n.id for n in row] for row in got]

    try:
        on = run_wave(True)
        off = run_wave(False)
    finally:
        wf.configure(WaterfallConfig())
    assert on == off


def test_config_plumbs_through_dht():
    """Config.waterfall reconfigures the process-global profiler at
    node construction (last node wins, like the shared registry)."""
    wf = waterfall.get_profiler()
    clock = {"t": 8000.0}
    try:
        make_dht(clock, waterfall=WaterfallConfig(enabled=False,
                                                  op_ring=7))
        assert wf is waterfall.get_profiler()
        assert not wf.enabled
        assert wf._ops.maxlen == 7
    finally:
        wf.configure(WaterfallConfig())


# ====================================================== health + export
def test_stage_budget_health_signal_registered_degrade_only():
    assert health.DEFAULT_SIGNAL_THRESHOLDS["stage_budget"] == (1.0, 2.0)
    assert "stage_budget" in health.HealthConfig().degrade_only
    clock = {"t": 9000.0}
    dht = make_dht(clock, n_nodes=4)
    nh = health.NodeHealth(dht)
    assert "stage_budget" in nh.evaluator.providers
    # unknown (None) when the window has no new samples — never trips
    wf = waterfall.get_profiler()
    wf.stage_budget()                        # consume any prior window
    assert nh.evaluator.providers["stage_budget"]() is None


def test_profiler_publishes_budget_gauges_on_its_registry():
    """The stage budgets export as gauges from construction (and track
    a reconfigure) on the profiler's OWN registry."""
    reg = telemetry.MetricsRegistry()
    p = StageProfiler(reg=reg)
    g = reg.snapshot()["gauges"]
    for stage in STAGES:
        key = 'dht_stage_budget_seconds{stage="%s"}' % stage
        assert key in g, sorted(g)
        assert g[key] == p.budgets[stage]
    p.configure(WaterfallConfig(budgets={"queue_wait": 0.5}))
    g = reg.snapshot()["gauges"]
    assert g['dht_stage_budget_seconds{stage="queue_wait"}'] == 0.5


def test_snapshot_shape_and_quantiles():
    p = _profiler()
    for v in (0.001, 0.002, 0.004, 0.008):
        p.observe("rpc_wait", v)
    doc = json.loads(json.dumps(p.snapshot()))   # JSON-able
    assert doc["enabled"] is True
    # canonical stages plus the one-release alias mirror (round 22)
    assert set(doc["stages"]) == set(STAGES) | set(STAGE_ALIASES)
    assert doc["stages"]["device_launch"]["alias_of"] == "device_wait"
    rw = doc["stages"]["rpc_wait"]
    assert rw["count"] == 4
    assert rw["p50"] is not None and rw["p99"] >= rw["p50"]
    assert doc["budgets"]["rpc_wait"] == DEFAULT_STAGE_BUDGETS["rpc_wait"]


# ============================================================ dhtmon gate
def test_dhtmon_stage_p95_reader_handles_both_label_orders():
    from opendht_tpu.tools.dhtmon import _stage_p95s
    series = {}
    for le, n in (("0.001", 2), ("0.01", 8), ("+Inf", 8)):
        series['dht_stage_seconds_bucket{le="%s",stage="queue_wait"}'
               % le] = float(n)
    for le, n in (("0.05", 3), ("+Inf", 4)):
        series['dht_stage_seconds_bucket{stage="rpc_wait",le="%s"}'
               % le] = float(n)
    series["dht_op_seconds_bucket{le=\"1\"}"] = 9.0     # ignored
    p = _stage_p95s(series)
    assert set(p) == {"queue_wait", "rpc_wait"}
    assert 0.001 < p["queue_wait"] <= 0.01
    assert p["rpc_wait"] <= 0.05


def test_dhtmon_max_stage_spec_validation():
    from opendht_tpu.tools import dhtmon
    assert dhtmon.main(["--nodes", "127.0.0.1:1", "--max-stage",
                        "bogus=1.0"]) == 2
    assert dhtmon.main(["--nodes", "127.0.0.1:1", "--max-stage",
                        "queue_wait"]) == 2
    assert dhtmon.main(["--nodes", "127.0.0.1:1", "--max-stage",
                        "queue_wait=notanumber"]) == 2


# ===================================================== scanner sections
def test_scanner_snapshot_has_waterfall_and_chaos_sections():
    """dhtscanner --json surfaces the per-op waterfall and the chaos
    counters (round-19 satellite): the ``waterfall`` section IS the
    node's get_profile() doc, the ``chaos`` section filters the
    ``dht_chaos_*`` counters off get_metrics()."""
    import json as _json

    from opendht_tpu.runtime.runner import DhtRunner
    from opendht_tpu.tools.dhtscanner import topology_snapshot

    r = DhtRunner()
    try:
        r.run(0)
        snap = topology_snapshot(r)
        wfs = snap["waterfall"]
        assert wfs["enabled"] is True
        assert set(wfs["stages"]) == set(STAGES) | set(STAGE_ALIASES)
        chaos = snap["chaos"]
        assert isinstance(chaos, dict)
        assert all(k.startswith("dht_chaos_") for k in chaos)
        _json.dumps(snap)                     # the --json surface
    finally:
        r.join()
