"""The ``stage`` source: the arithmetic on hand-made events, what it reads
from a context, and what it reads where there is nothing to read — CPU,
seconds:

    python3 -m pytest dhtbench/tests/test_stage_source.py -q
"""

from __future__ import annotations

import os

import pytest

from dhtbench import run
from dhtbench.sources import stage
from dhtbench.trace_reduce import OPS_LINE, WINDOW_SPAN

# the stages the program names today (core/search.py, parallel/sharded.py);
# the reader knows none of them, only the prefix
STAGES = ("select", "block_bounds", "reply_rows", "fetch_ids", "merge",
          "converge", "owner_merge")
ENGINE = "jit(_simulate_lookups_jit)"
MS = 1e6                                  # the trace's clock is in ns


def test_stage_arithmetic_on_hand_made_events():
    body = ENGINE + "/while/body/"
    events = [
        # a while spans its body's operations and keeps 2 ms of its own
        (ENGINE + "/while", 10 * MS, 40 * MS),
        (body + "jit(stage_fetch_ids)/jit(_take)/gather", 10 * MS, 22 * MS),
        # under two jit(...) components that are stages: the innermost
        (body + "jit(stage_fetch_ids)/jit(stage_owner_merge)/psum", 22 * MS, 26 * MS),
        # a nested while inside a stage: body and loop both the stage's
        (body + "jit(stage_block_bounds)/while", 26 * MS, 32 * MS),
        (body + "jit(stage_block_bounds)/while/body/jit(_take)/gather",
         27 * MS, 31 * MS),
        (body + "jit(stage_merge)/sort", 32 * MS, 38 * MS),
        # under none: the engine's own work outside the stages
        (ENGINE + "/transpose", 40 * MS, 45 * MS),
        ("", 45 * MS, 46 * MS),                      # an event with no op_name
        # outside the window: clipped, or dropped
        (ENGINE + "/jit(stage_merge)/sort", 48 * MS, 60 * MS),
        (ENGINE + "/jit(stage_merge)/sort", 70 * MS, 80 * MS)]
    times = stage.by_stage(events, window=(0.0, 50 * MS))
    assert times["fetch_ids"] == pytest.approx(0.012)
    assert times["owner_merge"] == pytest.approx(0.004)
    assert times["block_bounds"] == pytest.approx(0.006)
    assert times["merge"] == pytest.approx(0.006 + 0.002)
    assert times[stage.UNSTAGED] == pytest.approx(0.002 + 0.005 + 0.001)
    # the stages and the rest add up to the busy time of the line
    assert sum(times.values()) == pytest.approx(0.038)
    # without a window every event counts whole
    assert stage.by_stage(events)["merge"] == pytest.approx(0.028)


@pytest.mark.parametrize("name", STAGES)
def test_an_operation_belongs_to_its_innermost_stage(name):
    other = STAGES[(STAGES.index(name) + 1) % len(STAGES)]
    inner, outer = f"jit(stage_{name})", f"jit(stage_{other})"
    assert stage.stage_of(f"{ENGINE}/while/body/{inner}/add") == name
    assert stage.stage_of(f"{ENGINE}/{outer}/{inner}/jit(_where)/select_n") \
        == name
    assert stage.stage_of(f"{ENGINE}/{inner}") == name
    # a jit that is no stage (one of the same name without the prefix
    # too), a scope that only looks like one, the bare prefix, and a bare
    # instruction name
    assert stage.stage_of(f"{ENGINE}/while/body/jit(_take)/gather") \
        == stage.UNSTAGED
    assert stage.stage_of(f"{ENGINE}/jit({name})/add") == stage.UNSTAGED
    assert stage.stage_of(f"{ENGINE}/stage_{name}/add") == stage.UNSTAGED
    assert stage.stage_of(f"{ENGINE}/jit(stage_)/add") == stage.UNSTAGED
    assert stage.stage_of("gather") == stage.UNSTAGED


def _ctx(times, waves=4):
    return {"stage_times": times, "values": {"waves": waves},
            "trace": {"busy_s": 1.0}}


def test_what_the_source_reads_from_the_stage_times():
    times = {"fetch_ids": 0.240, "merge": 0.080, stage.UNSTAGED: 0.080}
    per_wave = {"kind": "stage", "value": "stage_ms_per", "per": "waves"}
    assert stage.read(dict(per_wave, stage="fetch_ids"), _ctx(times)) \
        == pytest.approx(60.0)
    assert stage.read(dict(per_wave, stage="merge"), _ctx(times)) \
        == pytest.approx(20.0)
    assert stage.read({"kind": "stage", "value": "unstaged_share"},
                      _ctx(times)) == pytest.approx(20.0)
    # a stage the trace holds nothing of, a count the driver did not give
    assert stage.read(dict(per_wave, stage="owner_merge"), _ctx(times)) is None
    assert stage.read(dict(per_wave, stage="merge"), _ctx(times, 0)) is None
    with pytest.raises(ValueError):
        stage.read({"kind": "stage", "value": "nonsense"}, _ctx(times))


def _varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _msg(*fields) -> bytes:
    """A protobuf message from ``(number, int | bytes | str)`` fields."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


T0_NS = 10 ** 15


def _plane(name: str, line: str, metadata: dict, stat_names: dict,
           events=()) -> bytes:
    """An ``XPlane``: ``{event metadata name: [stat, ...]}`` (ids from
    300 up, in order), ``{stat id: name}``, and one line of ``(metadata
    id, offset_ps, duration_ps)`` events — written BEFORE the two maps, as
    the profiler writes it."""
    return _msg(
        (1, 7), (2, name),
        (3, _msg((2, line), (3, T0_NS),
                 *[(4, _msg((1, i), (2, off), (3, dur)))
                   for i, off, dur in events])),
        *[(4, _msg((1, i), (2, _msg((1, i), (2, event), *[(5, st) for st in
                                                          stats]))))
          for i, (event, stats) in enumerate(metadata.items(), 300)],
        *[(5, _msg((1, i), (2, _msg((1, i), (2, n)))))
          for i, n in stat_names.items()])


GATHER = "%fusion.164 = u32[1572864,2]{0,1:T(2,128)} fusion(...)"
SORT = "%sort.192 = s32[65536,38] sort(...)"
COPY = "%copy.1 = u32[8] copy(...)"


def _xspace() -> bytes:
    stat_names = {3: "flops", 4: "tf_op",
                  9: "jit(f)/jit(stage_merge)/sort:Sort"}
    device = _plane("/device:TPU:0", OPS_LINE, {
        # tf_op as a string, with the op type after the colon or none
        GATHER: [_msg((1, 3), (3, 2 ** 40)),
                 _msg((1, 4), (5, "jit(f)/while/body/jit(stage_fetch_ids)"
                                  "/gather:"))],
        # tf_op as a reference into the stat names, a double stat before it
        SORT: [_msg((1, 3)) + b"\x11" + bytes(8), _msg((1, 4), (7, 9))],
        # no tf_op: the event gets no op_name
        COPY: [_msg((1, 3), (4, 5))]}, stat_names,
        # two events of one metadata, one of each other, one of none
        events=[(300, 0, 4_000_000), (301, 4_000_000, 2_000_000),
                (300, 6_000_000, 4_000_500), (302, 10_000_500, 1_000_000),
                (999, 11_000_500, 1_000_000)])
    host = _plane("/host:CPU", "python3", {WINDOW_SPAN: []}, stat_names,
                  events=[(300, 1_000_000, 10_000_000)])
    return _msg((1, device), (4, "host"), (1, host))


def test_planes_from_the_bytes_of_a_trace():
    planes = stage.read_planes(_xspace())
    fetch = "jit(f)/while/body/jit(stage_fetch_ids)/gather"
    assert planes["/device:TPU:0"] == {OPS_LINE: [
        (GATHER, fetch, T0_NS + 0.0, T0_NS + 4000.0),
        (SORT, "jit(f)/jit(stage_merge)/sort", T0_NS + 4000.0, T0_NS + 6000.0),
        (GATHER, fetch, T0_NS + 6000.0, T0_NS + 10000.5),
        (COPY, "", T0_NS + 10000.5, T0_NS + 11000.5),
        ("", "", T0_NS + 11000.5, T0_NS + 12000.5)]}
    assert planes["/host:CPU"] == {"python3": [
        (WINDOW_SPAN, "", T0_NS + 1000.0, T0_NS + 11000.0)]}
    assert stage.read_planes(b"") == {}
    with pytest.raises(ValueError):
        stage.read_planes(b"\x0b")                 # a group: not in xplane


def test_load_labels_the_ops_line_and_finds_the_window(tmp_path):
    assert stage.load(str(tmp_path)) is None
    path = tmp_path / "plugins" / "profile" / "t" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_xspace())
    for where in (str(tmp_path), str(path)):
        window, planes = stage.load(where)
        assert window == (T0_NS + 1000.0, T0_NS + 11000.0)
        assert list(planes) == ["/device:TPU:0"]
        assert len(planes["/device:TPU:0"]) == 5
    # inside the window: 3 + 4.0005 us of fetch_ids, 2 of merge, 0.9995 of none
    times = stage.mean_times((window, planes))
    assert times == pytest.approx({"fetch_ids": 7.0005e-6, "merge": 2e-6,
                                   stage.UNSTAGED: 0.9995e-6})
    # a trace that names no stage (the parent of the PR that brought them)
    bare = {"/device:TPU:0": [(COPY, 0.0, 5.0), ("jit(f)/jit(merge)/sort",
                                                 5.0, 9.0)]}
    assert stage.mean_times((None, bare)) is None
    assert stage.mean_times((None, {})) is None


def test_the_table_lists_every_stage_largest_first(capsys):
    stage.report({"merge": 0.08, "fetch_ids": 0.24, stage.UNSTAGED: 0.08}, 4)
    rows = [line.split()[2] for line in capsys.readouterr().out.splitlines()]
    assert rows[1:] == ["fetch_ids", "merge", "(no"]


@pytest.mark.parametrize("value", ["stage_ms_per", "unstaged_share"])
def test_nothing_to_read_reads_nothing(value, monkeypatch, tmp_path):
    spec = {"kind": "stage", "value": value, "stage": "merge", "per": "waves"}
    # a CPU rehearsal: no device plane was traced
    assert stage.read(spec, {"values": {"waves": 3}, "trace": None}) is None
    # a trace directory without a trace
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path))
    traced = {"values": {"waves": 3}, "trace": {"busy_s": 1.0}}
    assert stage.read(spec, dict(traced)) is None
    # a trace of a program that names no stages
    (tmp_path / "parent.xplane.pb").write_bytes(
        _xspace().replace(b"jit(stage_", b"jit(other_"))
    ctx = dict(traced)
    assert stage.read(spec, ctx) is None and ctx["stage_times"] is None
    # and the same trace with them
    (tmp_path / "parent.xplane.pb").write_bytes(_xspace())
    assert stage.read(spec, dict(traced)) > 0


def test_a_profiler_trace_of_the_cpu_has_a_window_and_no_device_plane(
        tmp_path):
    import jax
    import jax.numpy as jnp
    from opendht_tpu import telemetry
    double = jax.jit(telemetry.device_stage("merge")(lambda x: x * 2))
    double(jnp.arange(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            double(jnp.arange(8)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    window, planes = stage.load(str(tmp_path))
    assert window is not None and window[1] > window[0]
    assert planes == {}


def test_each_stage_metric_names_a_stage_of_the_program():
    mdir = os.path.join(run.HERE, "metrics")
    specs = [run.load_json(mdir, f) for f in sorted(os.listdir(mdir))]
    staged = [m["source"] for m in specs if m["source"]["kind"] == "stage"]
    assert len(staged) == 4
    assert {s["stage"] for s in staged if s["value"] == "stage_ms_per"} \
        == {"fetch_ids", "block_bounds", "merge"} <= set(STAGES)
