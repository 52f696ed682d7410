"""Tracing on-cost on the 8192-wave search round (round 9 tentpole).

The ISSUE-4 acceptance gate: with distributed tracing sampled-on (a
root trace context active around the wave — the recipe PARITY gives
for settling the OPEN bounds), the 8192-wave iterative-search round
must cost < 3% over the tracer-disabled run — inside the band
`captures/telemetry_overhead.json` established — and with sampling
OFF (tracer enabled but no context active, the production idle state)
the cost must be unmeasurable (< 0.5%).  The instrumentation is
host-side only: the wave span is recorded from the envelope's
already-measured elapsed AFTER the compiled computation returns, so
the expectation is noise-level; this driver measures both modes and
commits the result as ``captures/trace_overhead.json``.

Methodology: all modes run the SAME compiled executable, interleaved
over ``--reps`` trips with the mode ORDER ROTATING per rep (a fixed
order aliases against periodic background load on shared hosts), and
the committed pair is the MEDIAN OF PER-REP PAIRED differences —
each rep holds all three modes inside a ~3 s window, so pairing
cancels load drift on any longer timescale, where per-mode aggregates
on this host ride a ~±0.8% neighbor-noise floor.  Telemetry stays ON
in every mode (its cost is the r8 capture's number); only the tracer
toggles.  Mode deltas go through ``telemetry.snapshot_diff``
to assert the instrumentation actually fired (sampled mode) or stayed
silent (disabled mode).

Usage::

    python benchmarks/exp_trace_r9.py --save        # writes capture
    python benchmarks/exp_trace_r9.py --smoke       # CI band check
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import driver_common as dc         # noqa: E402  (puts the repo root on sys.path)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("-N", type=int, default=0,
                   help="table rows (default: 1M on accelerator, 128K cpu)")
    p.add_argument("-W", type=int, default=8192, help="wave width")
    p.add_argument("--reps", type=int, default=15,
                   help="timed trips per mode (interleaved)")
    p.add_argument("--save", action="store_true",
                   help="write captures/trace_overhead.json")
    p.add_argument("--smoke", action="store_true",
                   help="assert sampled overhead < 10%% (generous CI "
                        "band; the committed capture documents the "
                        "tight numbers)")
    args = p.parse_args(argv)

    import jax
    from opendht_tpu import telemetry, tracing
    from opendht_tpu.core.search import simulate_lookups
    from opendht_tpu.ops.sorted_table import (build_prefix_lut, sort_table,
                                              default_lut_bits)

    on_accel = jax.devices()[0].platform != "cpu"
    N = args.N or (1_000_000 if on_accel else 131_072)
    W = args.W

    key = jax.random.PRNGKey(9)
    k1, k2 = jax.random.split(key)
    table = jax.random.bits(k1, (N, 5), dtype=jax.numpy.uint32)
    targets = jax.random.bits(k2, (W, 5), dtype=jax.numpy.uint32)
    sorted_ids, _perm, n_valid = jax.block_until_ready(sort_table(table))
    lut = jax.block_until_ready(build_prefix_lut(
        sorted_ids, n_valid, bits=default_lut_bits(N)))
    del table

    tr = tracing.get_tracer()
    reg = telemetry.get_registry()
    reg.enabled = True                      # telemetry ON in every mode

    # wave spans are context-gated (core/search.py record_wave): the
    # sampled mode activates a fresh root per trip — the full traced
    # path, activation included — while "unsampled" is the production
    # idle state (tracer enabled, no ambient context, nothing records)
    def set_mode(mode: str) -> None:
        tr.enabled = mode != "off"

    def trip(mode: str) -> float:
        set_mode(mode)
        ctx = (tracing.TraceContext.new_root() if mode == "sampled"
               else None)
        t0 = time.perf_counter()
        with tracing.activate(ctx):
            out = simulate_lookups(sorted_ids, n_valid, targets,
                                   alpha=3, k=8, lut=lut, state_limbs=2)
            jax.block_until_ready(out)
        return time.perf_counter() - t0

    # shared warmup: one executable serves all modes
    for mode in ("sampled", "unsampled", "off"):
        trip(mode)

    # instrumentation sanity via snapshot_diff + the ring
    tr.clear()
    before = reg.snapshot()
    trip("sampled")
    d = telemetry.snapshot_diff(before, reg.snapshot())
    waves = [s for s in tr.spans() if s["name"] == "dht.search.wave"]
    assert waves, "sampled mode recorded no wave span"
    assert any(k.startswith("dht_search_wave_seconds")
               for k in d["histograms"]), "telemetry envelope silent"
    tr.clear()
    trip("unsampled")
    assert not tr.spans(), "unsampled mode recorded spans"

    # mode order ROTATES per rep: a fixed order aliases against periodic
    # background load on shared hosts (one run measured the do-less
    # "unsampled" mode 9% dearer than "sampled" purely from load landing
    # on the same slot every rep); rotation decorrelates it
    times: dict = {"off": [], "unsampled": [], "sampled": []}
    order = ["off", "unsampled", "sampled"]
    for i in range(args.reps):
        for mode in order[i % 3:] + order[:i % 3]:
            times[mode].append(trip(mode))
    set_mode("sampled")

    # headline pair = MEDIAN OF PER-REP PAIRED relative differences:
    # each rep runs all three modes within a ~3 s window, so the paired
    # per-rep delta cancels background-load drift on any longer
    # timescale — per-mode aggregate medians/mins on this shared host
    # ride a ~±0.8% neighbor-noise floor and repeatedly measured the
    # do-less "unsampled" mode ABOVE "sampled" (physically impossible
    # as signal).  Per-mode medians stay in the record so that floor
    # is visible next to the paired estimate.
    on_pct = float(np.median([(s - o) / o for s, o in
                              zip(times["sampled"], times["off"])])) * 100
    off_pct = float(np.median([(u - o) / o for u, o in
                               zip(times["unsampled"], times["off"])])) * 100
    med = {m: float(np.median(v) * 1e3) for m, v in times.items()}
    rec = {
        "name": "trace_overhead",
        "value": round(on_pct, 3),
        "unit": "percent",
        "sampling_off_pct": round(off_pct, 3),
        "wave": W, "N": N, "reps": args.reps,
        "wave_ms_sampled": round(med["sampled"], 3),
        "wave_ms_unsampled": round(med["unsampled"], 3),
        "wave_ms_disabled": round(med["off"], 3),
        "platform": jax.devices()[0].platform,
        "note": "8192-wave search round, median of per-rep paired "
                "deltas over rotation-interleaved trips (per-mode "
                "medians also recorded): traced (root context active, "
                "wave span recorded) / enabled-but-untraced vs "
                "tracer disabled (host-side envelope only; same "
                "executable; telemetry on in all modes)",
    }
    dc.emit(rec)

    if args.save:
        dc.write_capture("trace_overhead", rec)

    if args.smoke and on_pct >= 10.0:
        print("trace overhead %.2f%% exceeds the 10%% smoke band"
              % on_pct, file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
