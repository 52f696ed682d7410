"""dhtbench — one run of one cell of BENCHMARK.json.

    python3 -m dhtbench.run --workload <cell> --seed N --seconds S --trace 0|1

Everything that belongs to one cell is a file found by name, never a
table in this module (see dhtbench/README.md):

    workloads/<cell>.json -> configs/<config>.json -> drivers/<driver>.py
    metrics/*.json (those that name the cell or its driver) -> sources/<kind>.py

The run demands a TPU with the chips the cell asks for, makes its data
from ``--seed``, warms every shape up (``setup_s``: process start to the
first timed operation), measures for ``--seconds``, checks the answers
against ``reference.py`` and prints ONE JSON object as its last line:
``correct, attempted, failed, metrics, device`` (``breakdown`` too when
traced).  ``--trace 0`` reports the cell's end-to-end metrics; ``--trace
1`` runs the window under the JAX profiler and reports its per-layer
metrics instead.  Everything else worth reading is on earlier lines.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()       # setup_s counts from here: before jax loads

import argparse                 # noqa: E402
import importlib                # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402
import threading                # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".dhtbench_trace")   # fixed, git-ignored
# a traced window is short: the trace is large and the tracer slows the
# host, so per-layer numbers are read over this many seconds at most
TRACE_SECONDS = 3.0


def log(msg: str) -> None:
    print(f"[dhtbench +{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


def load_json(*parts: str):
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


class CompileLog:
    """Executables built (and persistent-cache hits) through
    ``jax.monitoring``, as ``chip_smoke.CompileLog`` counts them:
    ``backend_compile_duration`` fires once per executable whether XLA
    compiled it or the persistent cache supplied it."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.cache_hits = 0
        self.builds: list = []          # (seconds, jitted function's name)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float, **kw) -> None:
        if event == self._COMPILE:
            with self._lock:
                self.builds.append((float(duration_secs),
                                    str(kw.get("fun_name", "?"))))

    def _event(self, event: str, **_kw) -> None:
        if event == self._HIT:
            with self._lock:
                self.cache_hits += 1

    def mark(self) -> tuple:
        with self._lock:
            return len(self.builds), self.cache_hits

    def since(self, mark: tuple = (0, 0), slowest: int = 5) -> dict:
        with self._lock:
            built = self.builds[mark[0]:]
            hits = self.cache_hits - mark[1]
        return {"executables": len(built), "cache_hits": hits,
                "compile_s": sum(s for s, _ in built),
                "slowest": [[n, round(s, 2)] for s, n in
                            sorted(built, reverse=True)[:slowest]]}


def resolve(workload: str):
    """The cell's files, by name: ``(cell, config, driver module, metric
    files that apply)``."""
    cell = load_json(HERE, "workloads", workload + ".json")
    config = load_json(HERE, "configs", cell["config"] + ".json")
    driver = importlib.import_module("dhtbench.drivers." + config["driver"])
    metrics = {}
    mdir = os.path.join(HERE, "metrics")
    for fname in sorted(os.listdir(mdir)):
        if fname.endswith(".json"):
            m = load_json(mdir, fname)
            if workload in m.get("cells", ()) \
                    or m.get("driver") == config["driver"]:
                metrics[fname[:-len(".json")]] = m
    return cell, config, driver, metrics


def peaks_for(device_kind: str) -> dict:
    """The published peaks of this device; one that is not in the table is
    an error, not a default."""
    table = load_json(HERE, "peaks.json")["peaks"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"dhtbench/peaks.json (has {sorted(table)})")
    return table[device_kind]


def read_metric(spec: dict, ctx: dict):
    source = importlib.import_module(
        "dhtbench.sources." + spec["source"]["kind"])
    return source.read(spec["source"], ctx)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             rehearsal: "dict | None" = None) -> dict:
    """One run; returns the result line as a dict.  ``rehearsal`` (a
    test's or a builder's own call, never the command line) overrides the
    configuration's sizes and the cell's traffic by key and lifts the
    demand for a TPU: such a run proves the control flow and gives no
    number worth keeping."""
    import jax
    cell, config, driver, metric_files = resolve(workload)
    manifest = load_json(ROOT, "BENCHMARK.json")
    chips = cell["chips"]
    devices = jax.devices()
    dev = devices[0]
    if rehearsal is None and (dev.platform != "tpu" or len(devices) < chips):
        raise SystemExit(
            f"dhtbench: {workload} needs {chips} TPU chip(s), JAX found "
            f"{len(devices)} x {dev.platform!r} ({dev.device_kind}); "
            "nothing was run")
    peaks = peaks_for(dev.device_kind) if rehearsal is None else None
    if rehearsal:
        config = dict(config, sizes={**config["sizes"], **{
            k: v for k, v in rehearsal.items() if k in config["sizes"]}})
        cell = dict(cell, traffic={**cell["traffic"], **{
            k: v for k, v in rehearsal.items() if k in cell["traffic"]}})

    from opendht_tpu import telemetry
    from opendht_tpu.compile_cache import ensure_compile_cache
    cache_dir = ensure_compile_cache()
    compiles = CompileLog()
    log(f"cell {workload} seed {seed} seconds {seconds} trace {int(trace)}; "
        f"device {dev.platform} {dev.device_kind!r} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")

    state = driver.setup(config, cell["traffic"], seed, log)
    try:
        log(f"set-up compiles {compiles.since()}")
        registry = telemetry.get_registry()
        before = registry.snapshot()
        mark = compiles.mark()
        setup_s = time.perf_counter() - _T0
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(TRACE_DIR)
            try:
                result = driver.window(state, min(seconds, TRACE_SECONDS))
            finally:
                jax.profiler.stop_trace()
        else:
            result = driver.window(state, seconds)
        in_window = compiles.since(mark)
        after = registry.snapshot()
        moved = telemetry.snapshot_diff(before, after)
        log(f"window {result['window_s']:.3f}s: attempted "
            f"{result['attempted']} failed {result['failed']}; end to end "
            f"{result['end_to_end']}; values {result['values']}; "
            f"compiles in window {in_window}")
        log("registry over the window: " + json.dumps(
            {"counters": moved["counters"], "histogram_means": {
                k: [h["count"], h["sum"] / h["count"]]
                for k, h in moved["histograms"].items()}})[:3000])
        result["values"]["compiles_in_window"] = in_window["executables"]
        correct, why = driver.check(state, result)
        log(f"check: correct {correct} — {why}")
    finally:
        driver.close(state)

    reduced = None
    if trace:
        from dhtbench import trace_reduce
        reduced = trace_reduce.reduce(trace_reduce.load(TRACE_DIR))
        if reduced is None and rehearsal is None:
            raise RuntimeError("the trace holds no device operation")
    peak_bytes = max((int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                      for d in devices[:chips]), default=0)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}

    metrics: dict = {}
    if trace:
        ctx = {"values": result["values"], "registry": moved,
               "registry_after": after, "trace": reduced, "peaks": peaks}
        for name, spec in metric_files.items():
            value = read_metric(spec, ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": spec["unit"]}
        if reduced is not None:
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
    else:
        values = dict(result["end_to_end"], setup_s=setup_s)
        for m in manifest["end_to_end"]:
            if workload in m.get("workloads", (workload,)):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    line = {"correct": bool(correct), "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": device}
    if reduced is not None:
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    log(f"setup_s {setup_s:.3f}; peak HBM {peak_bytes} bytes; "
        f"compiles over the run {compiles.since()}")
    return line


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
