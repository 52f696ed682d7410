"""Load-aware resharding acceptance driver (ISSUE-17, round 21).

The t-sharded table splits the sorted id space into ~equal ROW slices
(parallel/partition.py), which a Zipf-skewed workload defeats: the
shards owning the hot keys serve most of the traffic while the rest
idle.  Round 21 closes the loop — the keyspace observatory's 256-bin
load histogram feeds ``solve_shard_boundaries`` /
``solve_shard_edges`` (blended with row counts by
``rebalance_load_weight``) and the node hot-swaps the shard state at
the solved traffic-weighted boundaries (row movement + per-shard LUT
rebuild, never a re-sort).

This driver measures exactly that trade at ``t ∈ {2, 4}`` under a
Zipf(1.1) stream whose hot keys concentrate in the low ring:

  before    the histogram folded at the UNIFORM ring split — the
            max/mean per-shard load the seed layout serves
  after     the SAME histogram refolded at the solved edges
            (λ = 0.9) — what the ``dht_shard_imbalance`` gauge
            converges to after the swap
  swap_ms   wall-clock of the serving-path state rebuild
            (core/table.py ``Snapshot._shard_state`` with a layout:
            host row movement + declarative placement), the cost a
            swap adds to the NEXT wave
  build_ms  the tp engine-state rebuild (``shard_table_state`` with
            boundaries: row movement + the weighted per-shard LUT
            rebuild launch — the ``reshard_state_build`` cost-gate
            kernel)

Bit-identity is asserted in the same run, both halves of the
acceptance pin: the weighted engine state drives
``tp_simulate_lookups`` to the single-device engine's exact outputs,
and the Snapshot serving path answers identically unsharded /
uniform-sharded / layout-sharded — INCLUDING a wave launched before
the swap and consumed after it (the round-20 pipeline's in-flight
case).

``--capture reshard_balance`` writes captures/reshard_balance.json;
README/PARITY quote the t=4 imbalance drop under
``<!-- capture:reshard_balance -->`` (ci/check_docs.py enforces the
quotes both directions).  ``--smoke`` is the CI form: small table,
asserts before > 2.0 and after < 1.3 at t=4, both bit-identity pins,
and a generous swap-latency band via the perf gate's timing records.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)          # driver_common
import driver_common as dc         # noqa: E402  (puts the repo root on sys.path)

ZIPF_A = 1.1
LOAD_WEIGHT = 0.9
#: hot pool keys land spread over this many low-ring bins, so the
#: uniform split concentrates them on shard 0 at t<=4 (256/t bins per
#: shard) while the solver still has within-range structure to cut
HOT_BINS = 32
HOT_RANKS = 96


def _zipf_hist(pool_n: int, total: int, seed: int = 41) -> np.ndarray:
    """The 256-bin load histogram of a Zipf(1.1) stream over a pool
    whose top-ranked keys live in the low ring (bins 0..HOT_BINS-1) —
    the shape the keyspace observatory hands the rebalance tick."""
    rng = np.random.default_rng(seed)
    top_byte = rng.integers(0, 256, size=pool_n).astype(np.int64)
    top_byte[:HOT_RANKS] = np.arange(HOT_RANKS) % HOT_BINS
    ranks = np.arange(1, pool_n + 1)
    p = 1.0 / ranks ** ZIPF_A
    p /= p.sum()
    draws = rng.choice(pool_n, size=total, p=p)
    return np.bincount(top_byte[draws], minlength=256).astype(np.int64)


def _bin_rows(sorted_ids, n: int) -> np.ndarray:
    top = np.asarray(sorted_ids[:, 0]).astype(np.int64)
    edges_v = np.arange(1, 256, dtype=np.int64) << 24
    counts = np.searchsorted(top[:n], edges_v, side="left")
    return np.diff(np.concatenate([[0], counts, [n]]))


def _measure_t(t: int, hist, sorted_ids, perm, n_valid, queries,
               reps: int) -> dict:
    import jax
    import jax.numpy as jnp
    from opendht_tpu.core.search import simulate_lookups
    from opendht_tpu.core.table import Snapshot
    from opendht_tpu.keyspace import bin_edges_uniform, fold_bins, _imbalance
    from opendht_tpu.parallel.partition import (
        shard_table_state, solve_shard_boundaries, solve_shard_edges)
    from opendht_tpu.parallel.sharded import make_mesh, tp_simulate_lookups
    from opendht_tpu.reshard import ReshardLayout

    n = int(n_valid)
    loads_before = fold_bins(hist, bin_edges_uniform(t))
    imb_before = _imbalance(loads_before)
    edges = solve_shard_edges(hist, t, load_weight=LOAD_WEIGHT)
    loads_after = fold_bins(hist, list(edges))
    imb_after = _imbalance(loads_after)

    mesh = make_mesh(t, q=1, t=t)
    bnd = solve_shard_boundaries(_bin_rows(sorted_ids, n), hist, t,
                                 load_weight=LOAD_WEIGHT)

    # ---- engine-state bit-identity (tp twin vs single device) + the
    # weighted LUT-rebuild launch cost
    ref = simulate_lookups(sorted_ids, n_valid, jnp.asarray(queries),
                           seed=9)
    build_ms = []
    state = None
    for _ in range(max(reps, 1) + 1):           # first rep warms compile
        t0 = time.perf_counter()
        state = shard_table_state(mesh, np.asarray(sorted_ids), n_valid,
                                  boundaries=bnd)
        jax.block_until_ready(state.arrays["local_lut"])
        build_ms.append((time.perf_counter() - t0) * 1e3)
    out = tp_simulate_lookups(mesh, targets=queries, seed=9, state=state)
    bit_identical = all(
        np.array_equal(np.asarray(out[k2]), np.asarray(ref[k2]))
        for k2 in ("nodes", "hops", "converged", "dist"))

    # ---- serving-path identity across the swap (in-flight pinned) +
    # the swap's host cost (row movement + placement)
    lay = ReshardLayout(gen=1, t=t, edges=tuple(float(e) for e in edges),
                        bin_loads=np.asarray(hist, np.int64),
                        load_weight=LOAD_WEIGHT)
    snap = Snapshot(sorted_ids, np.asarray(perm), n_valid, 1, ("k", 0))
    ref_rows, ref_dist = snap.lookup(queries)
    pl_old = snap.lookup_launch(queries, mesh=mesh)          # pre-swap wave
    pl_new = snap.lookup_launch(queries, mesh=mesh, layout=lay)  # the swap
    inflight_identical = True
    for pl in (pl_old, pl_new):
        rows_i, dist_i = pl.consume()
        inflight_identical &= (np.array_equal(rows_i, ref_rows)
                               and np.array_equal(dist_i, ref_dist))
    swap_ms = []
    for _ in range(max(reps, 1)):
        snap._tp_state = None                   # force the rebuild
        snap._reshard_rows = None
        t0 = time.perf_counter()
        placed, _ph = snap._shard_state(mesh, lay)
        jax.block_until_ready(placed["sorted_ids"])
        swap_ms.append((time.perf_counter() - t0) * 1e3)

    return {
        "imbalance_before": round(float(imb_before), 4),
        "imbalance_after": round(float(imb_after), 4),
        "loads_before": [round(float(x), 1) for x in loads_before],
        "loads_after": [round(float(x), 1) for x in loads_after],
        "boundaries": [int(x) for x in bnd],
        "uniform_rows": [-(-n * i // t) for i in range(1, t)],
        "swap_ms": round(float(np.median(swap_ms)), 3),
        "build_ms": round(float(np.median(build_ms[1:])), 3),
        "bit_identical": bool(bit_identical),
        "inflight_identical": bool(inflight_identical),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("-N", type=int, default=16384, help="table rows")
    p.add_argument("-Q", type=int, default=64, help="lookup batch")
    p.add_argument("--draws", type=int, default=120000,
                   help="Zipf stream length")
    p.add_argument("--pool", type=int, default=256, help="Zipf key pool")
    p.add_argument("--reps", type=int, default=9,
                   help="swap-timing reps (median)")
    p.add_argument("--capture", default="",
                   help="write captures/<name>.json")
    p.add_argument("--smoke", action="store_true",
                   help="CI form: small table, acceptance asserts + "
                        "generous swap-latency band")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from opendht_tpu.ops.sorted_table import sort_table

    n_rows, q_n, draws, reps = ((4096, 16, 40000, 3) if args.smoke
                                else (args.N, args.Q, args.draws,
                                      args.reps))
    hist = _zipf_hist(args.pool, draws)
    rng = np.random.default_rng(43)
    ids = rng.integers(0, 2 ** 32, size=(n_rows, 5), dtype=np.uint32)
    sorted_ids, perm, n_valid = sort_table(jnp.asarray(ids))
    queries = rng.integers(0, 2 ** 32, size=(q_n, 5), dtype=np.uint32)

    results = {}
    for t in (2, 4):
        if len(jax.devices()) < t:
            print("exp_reshard_r17: skipping t=%d (%d devices)"
                  % (t, len(jax.devices())))
            continue
        results["t%d" % t] = r = _measure_t(
            t, hist, sorted_ids, perm, n_valid, queries, reps)
        print("t=%d: imbalance %.2f -> %.2f (swap %.2f ms, state build "
              "%.2f ms, bit_identical=%s, inflight=%s)"
              % (t, r["imbalance_before"], r["imbalance_after"],
                 r["swap_ms"], r["build_ms"], r["bit_identical"],
                 r["inflight_identical"]))

    rec = {
        "driver": "exp_reshard_r17",
        "N": n_rows, "Q": q_n, "zipf_a": ZIPF_A, "draws": draws,
        "pool": args.pool, "load_weight": LOAD_WEIGHT,
    }
    rec.update(results)
    if "t4" in results:
        r4 = results["t4"]
        rec["swap_ms"] = r4["swap_ms"]
        # capture headline: the t=4 rebalance factor under the Zipf
        # flood
        rec["metric"] = (
            "load-aware resharding: max/mean shard load imbalance of a "
            "Zipf(%.1f) stream folded at the uniform t=4 split vs the "
            "solved traffic-weighted edges (lambda=%.1f), N=%d, "
            "platform=cpu; value = before/after rebalance factor"
            % (ZIPF_A, LOAD_WEIGHT, n_rows))
        rec["unit"] = "x imbalance reduction, t=4 (cpu)"
        rec["value"] = round(
            r4["imbalance_before"] / r4["imbalance_after"], 2)
    dc.emit(dict(rec))

    for key, r in results.items():
        assert r["bit_identical"], \
            "%s: weighted state diverged from the single-device engine" \
            % key
        assert r["inflight_identical"], \
            "%s: an in-flight wave was remapped across the swap" % key
    if args.smoke or args.capture:
        r4 = results.get("t4")
        assert r4 is not None, \
            "t=4 needs >=4 devices (CI sets " \
            "--xla_force_host_platform_device_count=8)"
        assert r4["imbalance_before"] > 2.0, \
            "Zipf flood read balanced on the uniform split: %r" % (r4,)
        assert r4["imbalance_after"] < 1.3, \
            "solved boundaries left the load imbalanced: %r" % (r4,)

    if args.capture:
        dc.write_capture(args.capture, rec)
    return 0


if __name__ == "__main__":
    sys.exit(main())
