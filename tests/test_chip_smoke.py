"""chip_smoke.py on the CPU: its phase functions at toy sizes on the
8-device virtual mesh (the chip run is the same code at the sizes
``main()`` fixes), and the pieces that decide whether a chip run is
believed — ``main()`` refusing to run without a TPU, the compile-cache
placement, and the peaks table refusing a device it does not know.
"""

import json
import os

import jax
import pytest

import chip_smoke
from dhtbench.run import peaks_for
from opendht_tpu import compile_cache

SEED = 0


@pytest.fixture(scope="module")
def compile_log():
    log = chip_smoke.CompileLog()
    yield log
    log.close()


@pytest.fixture(scope="module")
def sim():
    return chip_smoke.phase_simulator(n_ids=50_000, n_targets=1024,
                                      n_sample=64, seed=SEED)


@pytest.fixture(scope="module")
def served(compile_log):
    return chip_smoke.phase_served(n_rows=8192, n_requests=12, seed=SEED,
                                   compile_log=compile_log)


def test_phase_kernels_platform_choices_match_references():
    out = chip_smoke.phase_kernels(n_rows=20_000, seed=SEED,
                                   shapes=((1, 8), (37, 14)), batches=2)
    assert out["comparisons"] == 12
    # off-TPU the platform-chosen forms ARE the plain ones
    assert out["window_select"] == "sort"
    json.dumps(chip_smoke._public(out))
    assert out["merge_pack"] == {"8": 1, "14": 1}


def test_phase_simulator(sim):
    assert sim["converged"] == sim["n_targets"] == 1024
    assert abs(sim["p50_hops_sample"] - sim["p50_hops_scalar"]) <= 1
    assert sim["closest8_agree_exact"] >= 0.9 * sim["sample"]
    json.dumps(chip_smoke._public(sim))        # the printed part is JSON


def test_phase_served(served):
    assert served["replies"] == 12
    assert served["clean_resolve"] == {"answers": 12, "sharded": False,
                                       "resolve_mesh_t": 1}
    # every request after the client's insert resolved on the churn view
    assert served["lookup_launches"]["ChurnView:Q1:k8"] >= 12
    assert served["cache_hits"] >= 1
    assert served["listener_match_launches"] >= 1
    assert served["keyspace_observed"] >= 1
    assert served["served_window"]["executables"] >= 1
    json.dumps(chip_smoke._public(served))


def test_phase_four_chips(sim, served, compile_log):
    assert len(jax.devices()) >= 4
    four = chip_smoke.phase_four_chips(
        sim=sim, served=served, n_rows=8192, n_requests=12, seed=SEED,
        compile_log=compile_log)
    assert four["sim_bit_identical"] and four["t"] == 4
    # shards of unequal width (a range partition by key), each within
    # its capacity, all of the ids between them
    assert sum(four["sim_shard_widths"]) == sim["n_ids"]
    assert max(four["sim_shard_widths"]) <= four["sim_shard_rows"]
    assert four["served"]["clean_resolve"]["sharded"]
    assert four["served"]["clean_resolve"]["resolve_mesh_t"] == 4
    assert len(four["served"]["shards"]) == 4
    json.dumps(chip_smoke._public(four))


def test_main_refuses_without_a_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""                  # no result line, no work
    assert "needs a TPU" in captured.err


def test_last_line_is_the_verdict_and_nothing_else(capsys):
    """Whoever runs the smoke reads the LAST stdout line and takes no
    other key than these: the report travels on the line before."""
    chip_smoke.print_result(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        {"versions": {"jax": jax.__version__}, "phases": {"served": {}}})
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 2
    assert json.loads(lines[0].removeprefix("chip_smoke report: "))["phases"]
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert type(last["device"]["count"]) is int


def test_xor_closest_matches_python_ints():
    import numpy as np
    rng = np.random.default_rng(5)
    ids = rng.integers(0, 2 ** 32, size=(500, 5), dtype=np.uint32)
    ids[7, :2] = ids[3, :2]                    # a top-64-bit tie
    target = ids[3] ^ np.array([0, 0, 1, 2, 3], np.uint32)

    def as_int(row):
        return int.from_bytes(b"".join(int(x).to_bytes(4, "big")
                                       for x in row), "big")
    want = sorted(range(500), key=lambda i: as_int(ids[i]) ^ as_int(target))
    assert chip_smoke.xor_closest(ids, target, 8).tolist() == want[:8]


# ------------------------------------------------------------ compile cache
def test_compile_cache_leaves_a_set_variable_alone(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/x")
    assert compile_cache.ensure_compile_cache() == "/x"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_is_one_fixed_dir_in_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    root = os.path.dirname(os.path.abspath(chip_smoke.__file__))
    try:
        first = compile_cache.ensure_compile_cache()
        assert compile_cache.ensure_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert os.path.dirname(first) == root
    with open(os.path.join(root, ".gitignore")) as f:
        assert os.path.basename(first) + "/" in f.read().split()


# -------------------------------------------------------------------- peaks
class _Dev:
    def __init__(self, kind, platform="tpu"):
        self.device_kind, self.platform = kind, platform


def test_peaks_unknown_device_kind_raises(monkeypatch, capsys):
    """A TPU whose ``device_kind`` the benchmark's table
    (dhtbench/peaks.json, the repo's one peaks table) does not hold
    stops ``main()`` before any work."""
    for kind in ("TPU v9", ""):
        monkeypatch.setattr(jax, "devices", lambda *a, k=kind: [_Dev(k)])
        with pytest.raises(KeyError, match="no peaks for device_kind"):
            chip_smoke.main([])
    assert capsys.readouterr().out == ""


def test_peaks_row_for_the_kind_the_chip_reported():
    row = peaks_for("TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["bf16_flop_per_s"] == 197e12
    with pytest.raises(KeyError):           # no CPU row: a CPU run has
        peaks_for("cpu")                    # no device metric to divide
