"""Round-18 chaos-plane driver: a >=50k-simulated-node swarm stepping a
scripted join/leave storm plus an asymmetric partition-and-heal
entirely on device — ONE ``ops/swarm.py swarm_step`` launch per tick —
with the lookup-success and replica-coverage invariants asserted
degraded during the cut and RESTORED after healing, deterministic under
the fixed seed (the ISSUE-13 acceptance scenario).

Full mode commits ``captures/swarm_storm.json`` (per-tick invariant
timeline + wall-clock per tick on this host); ``--smoke`` runs the same
arc at S=4096 for CI (and feeds the perf gate's timing_soft record).

Usage::

    python benchmarks/exp_chaos_r18.py                # full: S=50000
    python benchmarks/exp_chaos_r18.py --smoke        # CI arc at S=4096
"""

from __future__ import annotations

import argparse
import sys
import time

from driver_common import emit, write_capture  # noqa: E402 (sys.path)


def storm_plan():
    """The ISSUE-13 acceptance arc: join/leave storm, then an
    ASYMMETRIC partition (g0→g1 blocked, g1→g0 open — the one-way
    routing failure a symmetric cut never exercises) that heals when
    its phase ends."""
    from opendht_tpu import chaos
    return chaos.FaultPlan([
        chaos.Phase("storm", start=1.0, duration=3.0,
                    storm=chaos.Storm(leave_rate=0.10, join_rate=0.10)),
        chaos.Phase("refill", start=4.0, duration=3.0,
                    storm=chaos.Storm(join_rate=0.5)),
        chaos.Phase("split", start=8.0, duration=6.0,
                    partition=chaos.Partition(block=[("g0", "g1")])),
    ], seed=3)


def run_arc(n_nodes: int, *, n_keys: int, sweep: int, ticks: int,
            seed: int = 5):
    from opendht_tpu.ops.swarm import SwarmSim

    sim = SwarmSim(storm_plan(), n_nodes=n_nodes, n_keys=n_keys,
                   n_groups=2, seed=seed, sweep_sample=sweep,
                   repub_every=2)
    rows = []
    for i in range(ticks):
        t0 = time.perf_counter()
        m = sim.tick()
        tick_ms = (time.perf_counter() - t0) * 1e3
        m.update(sim.probe())
        m["tick_ms"] = round(tick_ms, 3)
        rows.append(m)
    return rows


def check_arc(rows) -> None:
    assert rows[0]["verdict"] == "healthy", rows[0]
    cut = rows[9:13]
    assert any(r["verdict"] != "healthy" for r in cut), \
        "partition never degraded the invariants"
    last = rows[-1]
    assert last["verdict"] == "healthy", last
    assert last["lookup_success"] >= 0.95, last
    assert last["replica_coverage"] >= 0.95, last
    assert sum(r["n_leave"] for r in rows) > 0
    assert sum(r["n_join"] for r in rows) > 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("-S", "--nodes", type=int, default=50_000)
    p.add_argument("-K", "--keys", type=int, default=64)
    p.add_argument("-M", "--sweep", type=int, default=32)
    p.add_argument("--ticks", type=int, default=22)
    p.add_argument("--seed", type=int, default=5)
    p.add_argument("--smoke", action="store_true",
                   help="CI arc at S=4096 (no capture write)")
    args = p.parse_args(argv)

    import jax

    if args.smoke:
        rows = run_arc(4096, n_keys=48, sweep=args.sweep, ticks=args.ticks,
                       seed=args.seed)
        check_arc(rows)
        # determinism: the same seed replays the identical storm
        rows2 = run_arc(4096, n_keys=48, sweep=args.sweep,
                        ticks=args.ticks, seed=args.seed)
        strip = [{k: v for k, v in r.items() if k != "tick_ms"}
                 for r in rows]
        strip2 = [{k: v for k, v in r.items() if k != "tick_ms"}
                  for r in rows2]
        assert strip == strip2, "swarm storm not deterministic under seed"
        emit({"mode": "smoke", "n_nodes": 4096,
              "swarm_tick_ms": round(
                  sorted(r["tick_ms"] for r in rows)[len(rows) // 2], 3),
              "final_lookup_success": rows[-1]["lookup_success"],
              "final_replica_coverage": rows[-1]["replica_coverage"]})
        print("exp_chaos_r18 --smoke: OK (deterministic, invariants "
              "restored after heal)")
        return 0

    rows = run_arc(args.nodes, n_keys=args.keys, sweep=args.sweep,
                   ticks=args.ticks, seed=args.seed)
    check_arc(rows)
    ticks_ms = sorted(r["tick_ms"] for r in rows)
    cut = rows[9:13]
    rec = {
        "driver": "exp_chaos_r18",
        "platform": jax.devices()[0].platform,
        # headline row of the capture
        "metric": ("p50 swarm_step wall-clock per tick, %d-node storm"
                   % args.nodes),
        "unit": "ms",
        "value": round(ticks_ms[len(ticks_ms) // 2], 3),
        "n_nodes": args.nodes,
        "n_keys": args.keys,
        "sweep_sample": args.sweep,
        "ticks": args.ticks,
        "seed": args.seed,
        "tick_ms_p50": round(ticks_ms[len(ticks_ms) // 2], 3),
        "tick_ms_max": round(ticks_ms[-1], 3),
        "min_success_during_cut": min(r["lookup_success"] for r in cut),
        "min_coverage_during_cut": min(r["replica_coverage"]
                                       for r in cut),
        "final_lookup_success": rows[-1]["lookup_success"],
        "final_replica_coverage": rows[-1]["replica_coverage"],
        "model_err_mean": round(sum(r["model_err"] for r in rows)
                                / len(rows), 2),
        "timeline": [{k: r[k] for k in
                      ("n_alive", "lookup_success", "replica_coverage",
                       "verdict")} for r in rows],
    }
    emit({k: v for k, v in rec.items() if k != "timeline"})
    write_capture("swarm_storm", rec)
    return 0


if __name__ == "__main__":
    sys.exit(main())
