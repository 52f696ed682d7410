"""Shared scaffolding for the ``benchmarks/exp_*`` drivers.

Every driver used to hand-roll the same four things — the repo-root
``sys.path`` insert, JSON record printing, ``captures/<name>.json``
writing, and chain-slope reporting — seven copies that drifted
independently (the round-10 driver wrote captures with a trailing
newline, the round-8 one without; half the drivers could not feed the
CI perf gate because their records never hit disk).  This module is
the one copy, and it adds the two hooks the kernel cost ledger's CI
gate rides on:

- :func:`emit` — print one JSON record AND (when
  ``$OPENDHT_TPU_SMOKE_RECORD_DIR`` is set, as ``ci/run_ci.sh`` does)
  merge it into ``<dir>/<driver>.json`` so ``ci/perf_gate.py`` can
  soft-check the smoke timings after the suite ran — one schema for
  every driver's records.
- :func:`profile_ctx` — optional programmatic ``jax.profiler.trace``
  capture around a measured region (``--profile DIR`` via
  :func:`add_profile_arg`), the device-timeline complement to the
  ledger's cost model: host spans (telemetry), wire spans (tracing)
  and XLA device traces then align in one Perfetto load.

Importing this module puts the repo root on ``sys.path`` (the drivers
live in ``benchmarks/`` which is inserted by each driver's two-line
header), so ``from opendht_tpu import ...`` works however the driver
is launched — CLI, heredoc, or ``spec_from_file_location`` — and
places the persistent compile cache (``opendht_tpu.compile_cache``):
every driver imports this before its first compile.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from opendht_tpu.compile_cache import ensure_compile_cache  # noqa: E402

ensure_compile_cache()

CAPTURES = os.path.join(ROOT, "captures")


def driver_name(fallback: str = "driver") -> str:
    """The emitting driver's module name (``exp_round_r6`` …) — the
    smoke-record key ``perf_gate``'s ``timing_soft`` entries name.

    Resolved by walking the call stack for the nearest frame that lives
    in this benchmarks/ directory, NOT from ``__main__``: ci/run_ci.sh
    invokes the drivers via ``python - <<PY`` + spec_from_file_location,
    where ``__main__.__file__`` is ``<stdin>`` and the record would
    land under a name no ``timing_soft`` entry ever matches."""
    here = os.path.dirname(os.path.abspath(__file__))
    f = sys._getframe(1)
    while f is not None:
        fn = f.f_code.co_filename
        base = os.path.basename(fn)
        if (fn and base != "driver_common.py" and not fn.startswith("<")
                and os.path.dirname(os.path.abspath(fn)) == here):
            return os.path.splitext(base)[0]
        f = f.f_back
    main = sys.modules.get("__main__")
    mf = getattr(main, "__file__", None)
    if mf and not mf.startswith("<"):
        return os.path.splitext(os.path.basename(mf))[0]
    return fallback


def emit(rec: dict, name: str | None = None) -> dict:
    """Print ``rec`` as one JSON line (the drivers' existing contract)
    and merge it into the smoke-record file when the CI record dir is
    armed.  Records carrying a ``stage`` key accumulate under a
    ``stages`` map keyed by stage name (so profile_search's six slope
    records all survive in one document); stage-less records merge at
    the top level.  ``perf_gate.check_timing`` looks fields up in both
    places."""
    print(json.dumps(rec), flush=True)
    rec_dir = os.environ.get("OPENDHT_TPU_SMOKE_RECORD_DIR")
    if rec_dir:
        try:
            os.makedirs(rec_dir, exist_ok=True)
            path = os.path.join(rec_dir, (name or driver_name()) + ".json")
            merged = {}
            if os.path.exists(path):
                with open(path) as f:
                    merged = json.load(f)
            if "stage" in rec:
                merged.setdefault("stages", {})[str(rec["stage"])] = rec
            else:
                merged.update(rec)
            with open(path, "w") as f:
                json.dump(merged, f, indent=1, sort_keys=True)
        except Exception:
            pass                # records are advisory; never kill a bench
    return rec


def write_capture(name: str, rec: dict) -> str:
    """Write ``captures/<name>.json`` (the check_docs-enforced artifact
    form: indent=1 + trailing newline, the one the round-10 driver
    settled on)."""
    os.makedirs(CAPTURES, exist_ok=True)
    path = os.path.join(CAPTURES, name + ".json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
        f.write("\n")
    print(f"capture written: {path}")
    return path


def slope_record(stage: str, dt_s: float, **extra) -> dict:
    """One chain-slope measurement as the record schema every driver
    prints: stage name + ms, rounded the way the docs quote them."""
    rec = {"stage": stage, "ms": round(dt_s * 1e3, 3)}
    rec.update(extra)
    return rec


def add_paired_delta_args(parser, reps: int = 15) -> None:
    """The shared CLI surface of :func:`paired_delta` (round 19): every
    overhead driver grew its own ``--reps`` copy; ``--stages`` prints
    the per-stage latency waterfall next to the headline delta so 'the
    overhead moved' and 'WHERE the time goes' come from one run."""
    parser.add_argument("--reps", type=int, default=reps,
                        help="timed trips per mode (interleaved)")
    parser.add_argument("--stages", action="store_true",
                        help="print the per-stage waterfall decomposition "
                             "(dht_stage_seconds p50/p95 + budgets) next "
                             "to the paired delta")


def paired_delta(trip, reps: int, modes=("off", "on")) -> dict:
    """The round-9 paired-delta overhead methodology, extracted to ONE
    copy (round 19 — five drivers had drifted hand-rolled loops): both
    modes run the SAME compiled executable via ``trip(mode) -> seconds``,
    one shared warmup pass per mode, then ``reps`` trips per mode
    interleaved with the mode order rotating per rep (pairing cancels
    background-load drift on shared hosts).  Returns::

        {"on_pct":  median of per-rep (instrumented-baseline)/baseline,
         "med_ms":  {mode: median trip ms},   # the noise floor, visible
         "times":   {mode: [seconds, ...]}}

    ``modes[0]`` is the baseline, ``modes[1]`` the instrumented mode."""
    import numpy as np

    order = list(modes)
    times = {m: [] for m in order}
    for m in order:                          # shared warmup
        trip(m)
    for i in range(reps):
        for m in order[i % len(order):] + order[:i % len(order)]:
            times[m].append(trip(m))
    base, instr = order[0], order[1]
    on_pct = float(np.median(
        [(s - o) / o for s, o in zip(times[instr], times[base])])) * 100
    return {
        "on_pct": on_pct,
        "med_ms": {m: float(np.median(v) * 1e3)
                   for m, v in times.items()},
        "times": times,
    }


def print_stage_waterfall(snapshot: dict) -> None:
    """Human-readable per-stage table off a ``StageProfiler.snapshot()``
    — what ``--stages`` (see :func:`add_paired_delta_args`) prints."""
    print("%-16s %8s %10s %10s %10s" % ("stage", "count", "p50 ms",
                                        "p95 ms", "budget ms"))
    budgets = snapshot.get("budgets", {})
    for stage, d in snapshot.get("stages", {}).items():
        if not d.get("count"):
            continue
        fmt = lambda v: "-" if v is None else "%.3f" % (v * 1e3)  # noqa: E731
        print("%-16s %8d %10s %10s %10.1f"
              % (stage, d["count"], fmt(d.get("p50")), fmt(d.get("p95")),
                 budgets.get(stage, 0.0) * 1e3))


def add_profile_arg(parser) -> None:
    parser.add_argument(
        "--profile", default="", metavar="DIR",
        help="wrap the measured region in a programmatic "
             "jax.profiler.trace capture written to DIR (load in "
             "ui.perfetto.dev; aligns with the telemetry span "
             "TraceAnnotations and the ledger's cost model)")


@contextlib.contextmanager
def profile_ctx(profile_dir: str):
    """``with profile_ctx(args.profile): <measured region>`` — a no-op
    when the flag is empty or the profiler is unavailable (minimal
    containers), a full XLA device-trace capture otherwise."""
    if not profile_dir:
        yield
        return
    try:
        import jax
        prof = jax.profiler.trace(profile_dir)
    except Exception as e:
        print(f"profiler capture unavailable ({e}); running unprofiled")
        yield
        return
    with prof:
        yield
    print(f"jax.profiler trace written to {profile_dir}")
