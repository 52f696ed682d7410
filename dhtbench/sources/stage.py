"""Source kind ``stage``: device self time by the stages the PROGRAM names
(``opendht_tpu.telemetry.device_stage``).

The program runs each part of a lookup round as an inner jit named
``stage_<name>``, so every HLO operation's ``op_name`` reads
``jit(_simulate_lookups_jit)/while/body/jit(stage_merge)/sort``.  A v5e
trace keeps that string as the stat ``tf_op`` (``<op_name>:<op type>``) —
not on the events of a device plane's ``XLA Ops`` line but on their
``XEventMetadata``, which ``jax.profiler.ProfileData`` does not surface
(looked at by hand, PERF.md §5).  So :func:`read_planes` reads the file's
protobuf bytes itself, once: every line's events with their times, each
joined to its metadata — name and ``tf_op`` — by ``metadata_id``.

An operation belongs to the INNERMOST ``jit(stage_<name>)`` component of
its ``op_name``, else to no stage: the prefix is all the reader knows of
the program.  A fusion carries the ``op_name`` of one of its instructions:
where XLA fused across two stages, the whole fusion is charged to that one
(PERF.md §5 lists them).  Self time as ``trace_reduce.self_times`` charges
it: a ``while`` gets only what its body's operations do not cover, so the
stages and the unstaged rest add up to the busy time of the line.

``{"kind": "stage", "value": ...}`` with

- ``stage_ms_per``: self time of the operations of stage ``stage``,
  milliseconds over the driver's count ``per`` (waves);
- ``unstaged_share``: 100 × busy self time under no stage / busy self time.

Opens the newest ``.xplane.pb`` of ``run.TRACE_DIR`` itself (the reduced
trace in ``ctx`` keeps names and times only), reads the window from the
``dhtbench.window`` host span, and logs the time of every stage once a
run.  Reads nothing where the trace names no stage (the parent of the PR
that brought them) or holds no device plane (a CPU rehearsal).  The
arithmetic is on plain tuples and bytes (``dhtbench/tests``).

By hand, on any trace of the program (a four-chip one too):
``python3 -m dhtbench.sources.stage <trace dir or .xplane.pb> [count]``
prints the same table, per ``count`` (waves).
"""

from __future__ import annotations

import glob
import os
import sys

from dhtbench import trace_reduce

PREFIX = "stage_"           # telemetry.device_stage names its jits so
OP_NAME_STAT = "tf_op"      # on XEventMetadata: "<HLO op_name>:<op type>"
UNSTAGED = ""


def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, the bytes for a length-delimited or fixed-width field."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"protobuf wire type {wire}")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode()


def read_planes(xspace: bytes) -> dict:
    """``{plane: {line: [(name, op_name, start_ns, end_ns), ...]}}`` from
    the bytes of an ``XSpace`` in one pass: ``name`` is the event
    metadata's (on a device plane the HLO instruction's text), ``op_name``
    its ``tf_op`` stat less the op type (``""`` without one).  Field
    numbers are those of ``xplane.proto``: XSpace.planes 1; XPlane.name 2,
    .lines 3, .event_metadata 4, .stat_metadata 5 (maps: key 1, value 2);
    XLine.name 2, .timestamp_ns 3, .events 4; XEvent.metadata_id 1,
    .offset_ps 2, .duration_ps 3; XEventMetadata.name 2, .stats 5;
    XStatMetadata.name 2; XStat.metadata_id 1, .str_value 5, .ref_value 7
    (a stat_metadata id)."""
    out = {}
    for number, plane in _fields(memoryview(xspace)):
        if number != 1:
            continue
        plane_name, lines, metadata, stat_names = "", [], {}, {}
        for number, value in _fields(plane):
            if number == 2:
                plane_name = _text(value)
            elif number == 3:
                lines.append(value)
            elif number in (4, 5):
                entry = dict(_fields(value))
                (metadata if number == 4 else stat_names)[entry[1]] = entry[2]
        stat_names = {key: _text(dict(_fields(value)).get(2, b""))
                      for key, value in stat_names.items()}
        labels = {}                 # metadata id -> (name, op_name)
        for key, value in metadata.items():
            name, op_name = "", UNSTAGED
            for number, field in _fields(value):
                if number == 2:
                    name = _text(field)
                elif number == 5:
                    stat = dict(_fields(field))
                    if stat_names.get(stat.get(1)) == OP_NAME_STAT:
                        op_name = (_text(stat[5]) if 5 in stat else
                                   stat_names.get(stat.get(7), "")
                                   ).rsplit(":", 1)[0]
            labels[key] = (name, op_name)
        by_line = out.setdefault(plane_name, {})
        for line in lines:
            line_name, t0_ns, events = "", 0, []
            for number, value in _fields(line):
                if number == 2:
                    line_name = _text(value)
                elif number == 3:
                    t0_ns = value
                elif number == 4:
                    event = dict(_fields(value))
                    events.append((event.get(1), event.get(2, 0),
                                   event.get(3, 0)))
            by_line.setdefault(line_name, []).extend(
                (*labels.get(key, ("", UNSTAGED)), t0_ns + offset / 1e3,
                 t0_ns + (offset + duration) / 1e3)
                for key, offset, duration in events)
    return out


def load(path: str) -> "tuple | None":
    """``(window, {device plane: [(op_name, start_ns, end_ns), ...]})`` of
    the newest ``.xplane.pb`` under ``path`` (or of ``path`` itself): the
    events of each device plane's ``XLA Ops`` line labelled by their HLO
    ``op_name``, and the ``(lo, hi)`` of the window span (``None`` without
    one).  ``None`` without a trace."""
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            return None
        path = found[-1]
    with open(path, "rb") as f:
        planes = read_planes(f.read())
    marks = [(start, end) for plane, lines in planes.items()
             if plane.startswith("/host:") for events in lines.values()
             for name, _op, start, end in events
             if name == trace_reduce.WINDOW_SPAN]
    window = ((min(s for s, _ in marks), max(e for _, e in marks))
              if marks else None)
    ops = {plane: [event[1:] for event in lines[trace_reduce.OPS_LINE]]
           for plane, lines in planes.items()
           if plane.startswith("/device:") and "CUSTOM" not in plane
           and lines.get(trace_reduce.OPS_LINE)}
    return window, ops


def stage_of(op_name: str) -> str:
    """The stage of the innermost ``jit(stage_<name>)`` component of
    ``op_name``; ``UNSTAGED`` where there is none."""
    head = "jit(" + PREFIX
    for part in reversed(op_name.split("/")):
        if part.startswith(head) and part.endswith(")") \
                and len(part) > len(head) + 1:
            return part[len(head):-1]
    return UNSTAGED


def by_stage(events, window=None) -> dict:
    """Seconds of self time by stage (``UNSTAGED`` for the rest) of the
    ``(op_name, start, end)`` events of ONE line, clipped to ``window``."""
    if window is not None:
        lo, hi = window
        events = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                  if min(e, hi) > max(s, lo)]
    return trace_reduce.self_times(
        [(stage_of(name), s, e) for name, s, e in events])


def mean_times(loaded) -> "dict | None":
    """``{stage: seconds}`` inside the window, the mean over the device
    planes; ``None`` where no operation of the trace is under a stage."""
    window, planes = loaded
    times = {}
    for events in planes.values():
        for stage, sec in by_stage(events, window).items():
            times[stage] = times.get(stage, 0.0) + sec / len(planes)
    return times if set(times) - {UNSTAGED} else None


def report(times: dict, per: int) -> None:
    """The time of every stage, largest first, then the unstaged rest."""
    total = sum(times.values())
    print(f"[dhtbench stage] device self time by stage, mean of the "
          f"device planes ({per} waves, busy {total:.4f}s):", flush=True)
    for stage in (*sorted(set(times) - {UNSTAGED}, key=times.get,
                          reverse=True), UNSTAGED):
        sec = times.get(stage, 0.0)
        print(f"[dhtbench stage]   {stage or '(no stage)':<13}"
              f"{sec:9.4f}s {1e3 * sec / per:9.3f} ms/wave "
              f"{100 * sec / total if total else 0:6.2f}%", flush=True)


def stage_times(ctx: dict) -> "dict | None":
    """:func:`mean_times` of the run's trace — worked out once a run and
    kept in ``ctx``."""
    if "stage_times" not in ctx:
        from dhtbench import run
        loaded = load(run.TRACE_DIR) if ctx.get("trace") else None
        times = mean_times(loaded) if loaded else None
        if times:
            report(times, ctx["values"].get("waves") or 1)
        ctx["stage_times"] = times
    return ctx["stage_times"]


def read(spec: dict, ctx: dict):
    times = stage_times(ctx)
    busy = sum(times.values()) if times else 0.0
    if not busy:
        return None
    what = spec["value"]
    if what == "unstaged_share":
        return 100.0 * times.get(UNSTAGED, 0.0) / busy
    if what == "stage_ms_per":
        per = ctx["values"].get(spec["per"])
        if not per or spec["stage"] not in times:
            return None
        return 1e3 * times[spec["stage"]] / per
    raise ValueError(f"stage source: unknown value {what!r}")


if __name__ == "__main__":
    found = load(sys.argv[1])
    staged = mean_times(found) if found else None
    if not staged:
        sys.exit(f"no device operation under a {PREFIX}* jit in {sys.argv[1]}")
    report(staged, int(sys.argv[2]) if len(sys.argv) > 2 else 1)
