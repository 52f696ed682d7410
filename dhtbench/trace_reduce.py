"""From a profiler trace (``.xplane.pb``) to device busy time, idle share,
the operations that took most device time and the longest idle gaps.

What a v5e trace holds (looked at by hand, PERF.md §5): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event
per run of an executable), ``XLA Ops`` (every HLO operation, nested: a
``while`` spans its body's operations) and ``Async XLA Ops`` (copies in
flight, overlapping the others); and one plane ``/host:CPU`` whose line
named after the interpreter (``python3``) holds the ``TraceAnnotation``
spans and every Python call, beside one line per runtime thread.
All planes share one clock, nanoseconds from the start of the trace.

The arithmetic is on plain tuples so that it can be checked on a
hand-made list (``dhtbench/tests``); only :func:`load` touches JAX.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "dhtbench.window"      # a driver wraps its timed window in it
BUSY_LINES = ("XLA Modules", "XLA Ops")
OPS_LINE = "XLA Ops"
TOP = 10


def load(path: str) -> dict:
    """``{plane: {line: [(name, start_ns, end_ns), ...]}}`` of the newest
    ``.xplane.pb`` under ``path`` (or of ``path`` itself)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    planes: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns),
                 float(ev.start_ns) + float(ev.duration_ns))
                for ev in line.events)
    return planes


def merge(intervals) -> list:
    """Union of ``(start, end)`` intervals as a sorted list of disjoint ones."""
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy, lo: float, hi: float) -> list:
    """The parts of ``[lo, hi]`` that the disjoint sorted ``busy`` leaves."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events) -> dict:
    """Seconds by name of the ``(name, start, end)`` events of ONE line,
    each less the time of the events nested inside it (a ``while`` is
    charged what its body's operations do not cover)."""
    total: dict = {}
    stack: list = []                     # [name, start, end, nested_ns]

    def close():
        name, start, end, child = stack.pop()
        total[name] = total.get(name, 0.0) + max(end - start - child, 0.0)
        if stack:
            stack[-1][3] += end - start

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][2]:
            close()
        stack.append([name, start, end, 0.0])
    while stack:
        close()
    return {name: ns / 1e9 for name, ns in total.items()}


def short_op(name: str) -> str:
    """``%fusion.164 = u32[1572864,2]{0,1:T(2,128)S(1)} fusion(...), kind=kCustom``
    -> ``fusion.164 u32[1572864,2] fusion kCustom``; other names unchanged."""
    head = name.split(" = ", 1)
    if len(head) != 2:
        return name[:120]
    shape = re.match(r"\(?(\w+\[[\d,]*\])", head[1])
    opcode = re.search(r"[}\])] (\S+?)\(", head[1])
    kind = re.search(r"kind=(\w+)", head[1])
    parts = [head[0].lstrip("%"), shape.group(1) if shape else "",
             opcode.group(1) if opcode else "", kind.group(1) if kind else ""]
    return " ".join(p for p in parts if p)


def _host_spans(planes: dict) -> list:
    return [ev for plane, lines in planes.items() if plane.startswith("/host:")
            for evs in lines.values() for ev in evs]


def blame(idle, spans) -> list:
    """What the host was doing in each idle gap (sorted, disjoint): the
    shortest host span that covers at least half of the gap, which is the
    innermost call that explains it.  One sweep over both lists."""
    spans = sorted(spans, key=lambda ev: ev[1])
    names, active, j = [], [], 0
    for lo, hi in idle:
        while j < len(spans) and spans[j][1] < hi:
            active.append(spans[j])
            j += 1
        active = [ev for ev in active if ev[2] > lo]
        covering = [(e - s, name) for name, s, e in active
                    if min(e, hi) - max(s, lo) >= 0.5 * (hi - lo)]
        names.append("host:" + (min(covering)[1] if covering
                                else "no_span")[:100])
    return names


def reduce(planes: dict, window_span: str = WINDOW_SPAN) -> "dict | None":
    """Busy and idle over the traced window, averaged over the device
    planes that ran anything; ``None`` where no device plane did (a CPU
    rehearsal).  The window is the host span ``window_span`` where the
    trace has it, else first device event to last."""
    devices = {name: lines for name, lines in planes.items()
               if name.startswith("/device:") and "CUSTOM" not in name
               and any(lines.get(ln) for ln in BUSY_LINES)}
    if not devices:
        return None
    spans = _host_spans(planes)
    marks = [(s, e) for name, s, e in spans if name == window_span]
    busy_by_plane = {name: merge((s, e) for ln in BUSY_LINES
                                 for _n, s, e in lines.get(ln, ()))
                     for name, lines in devices.items()}
    if marks:
        lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    else:
        lo = min(b[0][0] for b in busy_by_plane.values())
        hi = max(b[-1][1] for b in busy_by_plane.values())
    busy_s, ops, idle = [], {}, {}
    for name, lines in devices.items():
        busy = clip(busy_by_plane[name], lo, hi)
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        in_window = [ev for ev in lines.get(OPS_LINE, ())
                     if ev[2] > lo and ev[1] < hi]
        for op, sec in self_times(in_window).items():
            ops[op] = ops.get(op, 0.0) + sec / len(devices)
        idle_gaps = gaps(busy, lo, hi)
        for gap, who in zip(idle_gaps, blame(idle_gaps, spans)):
            idle[who] = idle.get(who, 0.0) + (gap[1] - gap[0]) / 1e9 / len(devices)
    window_s = (hi - lo) / 1e9
    mean_busy = sum(busy_s) / len(busy_s)

    def top(d, label=lambda n: n):
        return [[label(n), sec] for n, sec in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": mean_busy, "window_s": window_s,
            "idle_share": 1.0 - mean_busy / window_s,
            "chips": len(devices), "ops": ops,
            "device_ops": top(ops, short_op), "idle_gaps": top(idle)}
