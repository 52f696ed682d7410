"""CI perf-regression gate over the kernel cost ledger (ROADMAP item 3).

Five rounds of kernel perf (920× → 46× → 121× → 131× → 213× vs the
scalar baseline) previously had no gate: a refactor could double a
kernel's HBM traffic and every tier-1 test would stay green.  This gate
closes that hole with the only perf signal that is DETERMINISTIC on a
shared CPU runner — the XLA cost model of each shipped kernel lowered
at its canonical shape (opendht_tpu/profiling.py KERNEL_SPECS):

- **Hard gate** (exit 1): per-kernel ``flops`` / ``bytes_accessed`` /
  ``argument_bytes`` / ``output_bytes`` vs the committed
  ``perf_budgets.json``, inside a per-field relative tolerance that
  absorbs XLA version drift (the cost model's constants move a few
  percent across releases; a real regression moves 2×).  A canonical
  SHAPE change is also hard — a silently moved shape would re-base the
  budget without review (run ``--update`` deliberately instead).
- **Soft warnings** (never fail): ``temp_bytes`` (XLA scheduling
  dependent — buffer assignment legitimately reshuffles across
  versions) and the wall-clock ``timing_soft`` ceilings checked against
  the smoke records the CI drivers drop in
  ``$OPENDHT_TPU_SMOKE_RECORD_DIR`` (benchmarks/driver_common.py) —
  shared runners flake, so timing informs, cost gates.
- **Open accelerator bounds**: the three OPEN on-chip numbers
  (≤8 ms 1024-wave p50, churny/static ≥0.6×, the config-4 maintenance
  sweep) ride along as ``open: true`` entries with their committed
  settling commands — the next accelerator session flips them to
  enforced values here instead of re-plumbing a gate.

Usage::

    python ci/perf_gate.py              # gate against perf_budgets.json
    python ci/perf_gate.py --update     # re-base budgets from live lowering
    python ci/perf_gate.py --records /tmp/odt-smoke   # + timing soft-warn
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BUDGETS = os.path.join(ROOT, "perf_budgets.json")

#: default relative tolerance per hard-gated field.  flops/bytes move a
#: few percent with XLA version drift (constant folding, fusion
#: decisions); argument/output bytes are pure shape math and barely
#: move.  A regression of interest (2×-class) clears every band.
DEFAULT_TOL = {
    "flops": 0.25,
    "bytes_accessed": 0.25,
    "argument_bytes": 0.05,
    "output_bytes": 0.05,
}
SOFT_TOL = {"temp_bytes": 0.60}


def _load_budgets(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _check_field(failures, warnings, name, field, budget, observed, tol,
                 soft=False):
    if budget == 0 and observed == 0:
        return
    lo, hi = budget * (1 - tol), budget * (1 + tol)
    if lo <= observed <= hi:
        return
    ratio = observed / budget if budget else float("inf")
    msg = (f"{name}.{field}: observed {observed:.6g} vs budget "
           f"{budget:.6g} ({ratio:.2f}x, tolerance ±{tol:.0%})")
    (warnings if soft else failures).append(msg)


def check_costs(budgets: dict, ledger: dict, failures: list,
                warnings: list) -> None:
    tol = dict(DEFAULT_TOL, **budgets.get("tolerance", {}))
    stol = dict(SOFT_TOL, **budgets.get("soft_tolerance", {}))
    for name, b in sorted(budgets.get("kernels", {}).items()):
        e = ledger.get(name)
        if e is None:
            failures.append(f"{name}: budgeted kernel missing from the "
                            f"ledger (KERNEL_SPECS) — removing a shipped "
                            f"kernel needs a deliberate --update")
            continue
        if "error" in e:
            failures.append(f"{name}: ledger failed to lower: {e['error']}")
            continue
        if e.get("shape") != b.get("shape"):
            failures.append(
                f"{name}: canonical shape drifted — budget {b.get('shape')}"
                f" vs ledger {e.get('shape')}; re-base with --update if "
                f"intentional")
            continue
        for field, t in tol.items():
            _check_field(failures, warnings, name, field,
                         float(b.get(field, 0.0)), float(e.get(field, 0.0)),
                         t)
        for field, t in stol.items():
            _check_field(failures, warnings, name, field,
                         float(b.get(field, 0.0)), float(e.get(field, 0.0)),
                         t, soft=True)
    for name in sorted(ledger):
        if name not in budgets.get("kernels", {}) \
                and "error" not in ledger[name]:
            failures.append(f"{name}: shipped kernel has no budget entry — "
                            f"run ci/perf_gate.py --update and commit "
                            f"perf_budgets.json")


def check_timing(budgets: dict, records_dir: str, warnings: list) -> None:
    """Wall-clock ceilings from the CI smoke records — soft by design:
    shared CPU runners stall unpredictably, so a breach WARNS with the
    number while the deterministic cost gate above decides pass/fail."""
    if not records_dir or not os.path.isdir(records_dir):
        return
    recs = {}
    for p in glob.glob(os.path.join(records_dir, "*.json")):
        try:
            with open(p) as f:
                recs[os.path.splitext(os.path.basename(p))[0]] = json.load(f)
        except Exception:
            continue
    for key, spec in sorted(budgets.get("timing_soft", {}).items()):
        rec = recs.get(spec["record"])
        if rec is None:
            # a supplied records dir missing a budgeted record means a
            # driver stopped emitting (or was renamed) — say so, or the
            # ceiling silently becomes dead config
            warnings.append(
                f"timing[{key}]: no {spec['record']}.json in "
                f"{records_dir} — the ceiling was not checked (driver "
                f"renamed or not run?)")
            continue
        # stage records accumulate under "stages" (driver_common.emit);
        # a budgeted field may live top-level or in any stage record
        val = rec.get(spec["field"])
        if val is None:
            for srec in rec.get("stages", {}).values():
                val = srec.get(spec["field"])
                if val is not None:
                    break
        if val is None:
            warnings.append(
                f"timing[{key}]: {spec['record']}.json carries no "
                f"{spec['field']!r} field — the ceiling was not checked "
                f"(field renamed?)")
            continue
        if float(val) > float(spec["max"]):
            warnings.append(
                f"timing[{key}]: {spec['record']}.{spec['field']} = "
                f"{val} exceeds the soft ceiling {spec['max']} "
                f"{spec.get('unit', '')} — wall-clock only, not failing "
                f"({spec.get('note', '')})".rstrip())


def print_open_bounds(budgets: dict) -> None:
    ob = budgets.get("open_bounds", {})
    if not ob:
        return
    print("perf_gate: %d OPEN accelerator bound(s) awaiting settlement "
          "(not gated until an accelerator run commits them):" % len(ob))
    for key, b in sorted(ob.items()):
        print(f"  - {key}: target {b['target']} on "
              f"{b['metric']}\n    settle: {b['settle']}")


def compute_ledger(kernels=None) -> dict:
    import jax
    jax.config.update("jax_platforms", "cpu")   # deterministic CI platform
    if jax.default_backend() != "cpu":
        # config updates are a no-op once a backend is initialized: an
        # in-process caller that already touched an accelerator would
        # lower there and fail every cpu budget with confusing
        # tolerance diffs — fail loudly with the fix instead
        raise SystemExit(
            "perf_gate: jax backend is %r but perf_budgets.json is "
            "cpu-lowered — run in a fresh process with JAX_PLATFORMS=cpu"
            % jax.default_backend())
    from opendht_tpu import profiling
    return profiling.get_ledger().compute(kernels)


def update_budgets(path: str, ledger: dict, merge: bool = False) -> None:
    """Re-base the budget file from the live ledger, preserving the
    curated sections (tolerances, open bounds, timing ceilings).
    ``merge=True`` (a ``--kernels`` subset re-base) updates only the
    named entries and keeps every other committed budget — a subset
    must never silently delete the rest of the file."""
    old = _load_budgets(path) if os.path.exists(path) else {}
    kernels = dict(old.get("kernels", {})) if merge else {}
    for name, e in sorted(ledger.items()):
        if "error" in e:
            raise SystemExit(f"--update refused: {name} failed to lower "
                             f"({e['error']})")
        kernels[name] = {
            "shape": e["shape"],
            "flops": e["flops"],
            "bytes_accessed": e["bytes_accessed"],
            "argument_bytes": e["argument_bytes"],
            "output_bytes": e["output_bytes"],
            "temp_bytes": e["temp_bytes"],
        }
    out = {
        "_note": ("XLA cost-model budgets per kernel per canonical shape "
                  "(opendht_tpu/profiling.py KERNEL_SPECS), lowered on "
                  "cpu.  Gated by ci/perf_gate.py in ci/run_ci.sh; "
                  "re-base deliberately with ci/perf_gate.py --update."),
        "platform": "cpu",
        "tolerance": old.get("tolerance", DEFAULT_TOL),
        "soft_tolerance": old.get("soft_tolerance", SOFT_TOL),
        "kernels": kernels,
        "open_bounds": old.get("open_bounds", {}),
        "timing_soft": old.get("timing_soft", {}),
    }
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"perf_gate: budgets re-based for {len(kernels)} kernels -> "
          f"{path}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--budgets", default=BUDGETS)
    p.add_argument("--update", action="store_true",
                   help="re-base perf_budgets.json from live lowering "
                        "(deliberate re-baseline; review the diff)")
    p.add_argument("--kernels", default="",
                   help="comma-separated subset (default: all)")
    p.add_argument("--records",
                   default=os.environ.get("OPENDHT_TPU_SMOKE_RECORD_DIR",
                                          ""),
                   help="smoke-record dir for the timing soft-warn pass")
    args = p.parse_args(argv)

    names = [k for k in args.kernels.split(",") if k] or None
    ledger = compute_ledger(names)

    if args.update:
        update_budgets(args.budgets, ledger, merge=bool(names))
        return 0

    if not os.path.exists(args.budgets):
        print(f"perf_gate: {args.budgets} missing — run "
              f"'python ci/perf_gate.py --update' and commit it",
              file=sys.stderr)
        return 1
    budgets = _load_budgets(args.budgets)
    if names:
        budgets = dict(budgets,
                       kernels={k: v for k, v in budgets["kernels"].items()
                                if k in names})

    failures: list = []
    warnings: list = []
    check_costs(budgets, ledger, failures, warnings)
    check_timing(budgets, args.records, warnings)

    for w in warnings:
        print("perf_gate WARN:", w)
    print_open_bounds(budgets)
    if failures:
        print("perf_gate: COST-MODEL REGRESSION vs perf_budgets.json:",
              file=sys.stderr)
        for fmsg in failures:
            print(" -", fmsg, file=sys.stderr)
        print("(if the change is intentional, re-base with "
              "'python ci/perf_gate.py --update' and commit the diff)",
              file=sys.stderr)
        return 1
    print("perf_gate: %d kernel budgets within tolerance (%d soft "
          "warnings)" % (len(budgets.get("kernels", {})), len(warnings)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
