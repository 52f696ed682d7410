"""Kernel cost ledger (ISSUE-6): cost-model determinism, budget
round-trip vs live lowering, kernel bit-identity with the ledger
enabled, export surfaces (registry gauges + Prometheus exposition +
wave-span attrs), and the perf gate's injected-regression failure."""

import importlib.util
import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp                                      # noqa: E402

from opendht_tpu import profiling, telemetry, tracing        # noqa: E402
from opendht_tpu.testing.telemetry_smoke import parse_exposition  # noqa: E402

pytestmark = pytest.mark.quick

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGETS = os.path.join(ROOT, "perf_budgets.json")

#: the cheap representative subset most tests lower (the budgets
#: round-trip test lowers everything, once, into the shared cache)
SUBSET = ["expanded_topk", "fused_gather_planar", "maintenance_sweep",
          "simulate_lookups"]


def _load_ci_module(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "ci", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ledger():
    led = profiling.get_ledger()
    led.enabled = True
    led.compute(SUBSET)
    yield led
    led.enabled = True


# ------------------------------------------------------------ determinism
def test_cost_model_deterministic(ledger):
    """Two lowerings of the same kernel at the same canonical shape
    agree exactly — the property that makes the budgets committable."""
    a = ledger.compute(["expanded_topk"])["expanded_topk"]
    b = ledger.compute(["expanded_topk"], force=True)["expanded_topk"]
    for field in ("flops", "bytes_accessed", "argument_bytes",
                  "output_bytes", "temp_bytes"):
        assert a[field] == b[field], field
    assert a["shape"] == b["shape"]


def test_every_spec_lowers(ledger):
    """No registered kernel spec may rot: every entry lowers without an
    error record (the gate fails CI on the same condition)."""
    out = ledger.compute(SUBSET)
    assert all("error" not in e for e in out.values()), out


# --------------------------------------------------- budgets + perf gate
def test_budgets_roundtrip_against_live_lowering():
    """The committed perf_budgets.json must round-trip against a live
    lowering on this host — exactly what ci/perf_gate.py enforces in
    CI, invoked through its real entry point."""
    assert os.path.exists(BUDGETS), "perf_budgets.json not committed"
    perf_gate = _load_ci_module("perf_gate")
    assert perf_gate.main(["--budgets", BUDGETS]) == 0


def test_budgets_carry_open_accelerator_bounds():
    """The three OPEN on-chip bounds ride the budget file as open
    entries with their settling commands pre-wired (ROADMAP item 3)."""
    with open(BUDGETS) as f:
        budgets = json.load(f)
    ob = budgets["open_bounds"]
    for key in ("wave_p50_ms_1024", "churny_static_ratio",
                "maintenance_sweep_config4"):
        assert ob[key]["open"] is True
        assert "settle" in ob[key] and ob[key]["settle"]
    assert set(budgets["kernels"]) == set(profiling.KERNEL_SPECS)


def test_perf_gate_fails_on_injected_cost_regression(tmp_path, capsys):
    """Doubling one kernel's budgeted HBM traffic (equivalently: the
    live kernel halving under an unchanged budget — the direction a
    real regression moves the live side) must fail the gate with a
    diff naming the kernel and field."""
    with open(BUDGETS) as f:
        budgets = json.load(f)
    budgets["kernels"]["expanded_topk"]["bytes_accessed"] /= 2.0
    p = tmp_path / "perf_budgets.json"
    p.write_text(json.dumps(budgets))
    perf_gate = _load_ci_module("perf_gate")
    assert perf_gate.main(["--budgets", str(p)]) == 1
    err = capsys.readouterr().err
    assert "expanded_topk.bytes_accessed" in err


def test_perf_gate_fails_on_shape_drift(tmp_path):
    """A silently moved canonical shape must not re-base the budget —
    the gate demands a deliberate --update instead."""
    with open(BUDGETS) as f:
        budgets = json.load(f)
    budgets["kernels"]["maintenance_sweep"]["shape"]["N"] += 1
    p = tmp_path / "perf_budgets.json"
    p.write_text(json.dumps(budgets))
    perf_gate = _load_ci_module("perf_gate")
    assert perf_gate.main(["--budgets", str(p)]) == 1


def test_perf_gate_timing_ceilings_warn_not_fail(tmp_path, capsys):
    """Wall-clock smoke records breaching their soft ceiling WARN and
    the gate still passes — shared-runner timing informs, cost gates."""
    rec_dir = tmp_path / "records"
    rec_dir.mkdir()
    (rec_dir / "exp_round_r6.json").write_text(
        json.dumps({"fused_ms_per_round": 1e9}))
    perf_gate = _load_ci_module("perf_gate")
    assert perf_gate.main(["--budgets", BUDGETS,
                           "--records", str(rec_dir)]) == 0
    out = capsys.readouterr().out
    assert "perf_gate WARN" in out and "fused_ms_per_round" in out


# -------------------------------------------------- kernel bit-identity
def test_kernels_bit_identical_with_ledger_enabled(ledger):
    """The shipping kernels' outputs must be byte-for-byte unchanged by
    computing + exporting the ledger and running the record_wave hook
    with a traced wave — the ledger observes, never participates."""
    from opendht_tpu.core.search import simulate_lookups
    from opendht_tpu.ops.sorted_table import (sort_table, expand_table,
                                              expanded_topk)
    ids = jax.random.bits(jax.random.PRNGKey(42), (2048, 5),
                          dtype=jnp.uint32)
    targets = jax.random.bits(jax.random.PRNGKey(43), (64, 5),
                              dtype=jnp.uint32)
    sorted_ids, _p, n_valid = sort_table(ids)
    expanded = expand_table(sorted_ids)

    ledger.enabled = False
    base_topk = jax.block_until_ready(
        expanded_topk(sorted_ids, expanded, n_valid, targets, k=8))
    base_wave = jax.block_until_ready(
        simulate_lookups(sorted_ids, n_valid, targets, alpha=3, k=8))

    ledger.enabled = True
    ledger.compute(SUBSET)
    ledger.export_to_registry()
    tr = tracing.get_tracer()
    with tracing.activate(tracing.TraceContext.new_root()):
        led_wave = jax.block_until_ready(
            simulate_lookups(sorted_ids, n_valid, targets, alpha=3, k=8))
    led_topk = jax.block_until_ready(
        expanded_topk(sorted_ids, expanded, n_valid, targets, k=8))

    for a, b in zip(jax.tree_util.tree_leaves(base_topk),
                    jax.tree_util.tree_leaves(led_topk)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for key in ("nodes", "dist", "hops", "converged"):
        assert np.array_equal(np.asarray(base_wave[key]),
                              np.asarray(led_wave[key])), key
    # and the traced wave actually carried the device-cost attrs
    waves = [s for s in tr.spans() if s["name"] == "dht.search.wave"]
    assert waves and "est_device_bytes" in waves[-1]["attrs"]


# ------------------------------------------------------- export surfaces
def test_export_gauges_and_exposition(ledger):
    reg = telemetry.MetricsRegistry()
    n = ledger.export_to_registry(reg)
    assert n >= len(SUBSET)
    snap = reg.snapshot()
    key = 'dht_kernel_bytes_accessed{kernel="expanded_topk"}'
    entry = ledger.compute(["expanded_topk"])["expanded_topk"]
    assert snap["gauges"][key] == entry["bytes_accessed"]
    series = parse_exposition(reg.prometheus())
    assert series[key] == entry["bytes_accessed"]
    assert 'dht_kernel_flops{kernel="maintenance_sweep"}' in series


def test_maybe_export_is_gated(monkeypatch):
    """A process that never computed the ledger (and didn't arm
    OPENDHT_TPU_LEDGER) must pay nothing on a metrics scrape."""
    monkeypatch.delenv("OPENDHT_TPU_LEDGER", raising=False)
    led = profiling.get_ledger()
    led.enabled = False            # simulate the never-computed state
    try:
        reg = telemetry.MetricsRegistry()
        assert profiling.maybe_export(reg) == 0
        assert not reg.snapshot()["gauges"]
    finally:
        led.enabled = True


def test_measure_and_roofline(ledger):
    out = ledger.measure(["fused_gather_planar"], reps=1)
    e = out["fused_gather_planar"]
    assert e["measured_s"] > 0
    rl = e["roofline"]
    assert rl["bound"] in ("memory", "compute")
    assert rl["hbm_pct_of_peak"] >= 0
    # the roofline identity: pct == 100 * bytes / (t * peak)
    peaks = profiling.platform_peaks()
    expect = 100.0 * e["bytes_accessed"] / e["measured_s"] \
        / peaks["hbm_bytes_per_s"]
    assert rl["hbm_pct_of_peak"] == pytest.approx(expect, rel=1e-3)


def test_wave_attrs_scaling_and_gating(ledger):
    entry = ledger.compute(["simulate_lookups"])["simulate_lookups"]
    w_c = entry["shape"]["W"]
    attrs = profiling.wave_attrs(2 * w_c, 3, 0.5)
    assert attrs["est_device_bytes"] == int(entry["bytes_accessed"] * 6)
    assert attrs["est_device_flops"] == int(entry["flops"] * 6)
    assert "est_hbm_pct_of_peak" in attrs
    ledger.enabled = False
    try:
        assert profiling.wave_attrs(2 * w_c, 3, 0.5) == {}
    finally:
        ledger.enabled = True
    # zero-round waves (empty table fast exit) attach nothing
    assert profiling.wave_attrs(w_c, 0, 0.5) == {}


def test_snapshot_folds_live_series(ledger):
    """The paired PR-3 histogram's p50 rides the snapshot next to the
    canonical cost, linking cost model to shipping latency."""
    reg = telemetry.get_registry()
    reg.histogram("dht_maintenance_sweep_seconds").observe(0.004)
    snap = ledger.snapshot()
    e = snap["maintenance_sweep"]
    assert e["series"] == "dht_maintenance_sweep_seconds"
    assert e["live_count"] >= 1 and e["live_p50_s"] > 0
