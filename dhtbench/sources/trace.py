"""Source kind ``trace``: the reduced device trace of the window
(``trace_reduce.reduce``).  ``{"kind": "trace", "value": ...}`` with

- ``idle_share``: 100 × (1 − busy / window);
- ``busy_ms_per`` / ``idle_ms_per``: device busy (idle) milliseconds of the
  window over the driver's count ``per`` (waves, requests);
- ``hbm_share``: 100 × the driver's byte count ``bytes`` (from its shape
  function) / the chip's peak HBM bytes/s / device busy seconds;
- ``op_ms_per``: self time of the operations whose trace name matches the
  regular expression ``op``, milliseconds over ``per``.

Reads nothing where no device plane was traced (a CPU rehearsal)."""

import re


def read(spec: dict, ctx: dict):
    trace = ctx["trace"]
    if trace is None:
        return None
    what = spec["value"]
    if what == "idle_share":
        return 100.0 * trace["idle_share"]
    if what == "hbm_share":
        moved = ctx["values"].get(spec["bytes"])
        if moved is None or ctx["peaks"] is None:
            return None
        least_s = moved / ctx["peaks"]["hbm_bytes_per_s"]
        return 100.0 * least_s / trace["busy_s"]
    per = ctx["values"].get(spec["per"])
    if not per:
        return None
    if what == "busy_ms_per":
        return 1e3 * trace["busy_s"] / per
    if what == "idle_ms_per":
        return 1e3 * (trace["window_s"] - trace["busy_s"]) / per
    if what == "op_ms_per":
        pattern = re.compile(spec["op"])
        return 1e3 * sum(sec for name, sec in trace["ops"].items()
                         if pattern.search(name)) / per
    raise ValueError(f"trace source: unknown value {what!r}")
