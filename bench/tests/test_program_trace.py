"""The readers of what the program marks in a trace: its spans
(``sim.pick_bucket``, ``sim.chunk_launch``), the tick phase of each
device operation (``harness.program_trace``), and the forecast's row
counters.

A hand-made record whose numbers are worked out below; a trace written
by the profiler on the CPU, for the program spans; a program from before
the spans and scopes, on which the readers read nothing; and a small
slice of a real trace of ``paper-baseline.google-c8`` on a TPU v5e."""
import json
import types
from pathlib import Path

import jax
import pytest

from harness import cell, program_trace, trace

FIXTURE = Path(__file__).with_name("fixtures") / "trace_baseline_c8_phases.json"
DEV = "/device:TPU:0"

# one device, a window of 0..10,000 ns.  Chunk 1 is a loop (100..2,100)
# holding four phased operations; a boundary with a bucket pick; chunk 2
# (2,900..3,800, holding a nested shape op); a boundary without a pick;
# chunk 3's forecast op, and an op the window's end clips to 200 ns.
OPS = [
    ("%while.1 = (s32[]) while(%t), body=%b", 100, 2000, None),
    ("%fusion.2 = f32[8] fusion(%p), kind=kLoop", 200, 500, "forecast"),
    ("%fusion.3 = f32[8] fusion(%p), kind=kLoop", 800, 300, "admit"),
    ("%fusion.4 = f32[8] fusion(%p), kind=kLoop", 1200, 400, "oom"),
    ("%fusion.5 = f32[8] fusion(%p), kind=kLoop", 1700, 200, "metrics"),
    ("%fusion.6 = f32[8] fusion(%p), kind=kLoop", 2900, 900, "elastic"),
    ("%fusion.7 = f32[8] fusion(%p), kind=kLoop", 3000, 200, "shape"),
    ("%fusion.8 = f32[8] fusion(%p), kind=kLoop", 4450, 5000, "forecast"),
    ("%fusion.9 = f32[8] fusion(%p), kind=kLoop", 9800, 500, "arrivals"),
]
HAND = {
    "devices": {DEV: [(n, s, d) for n, s, d, _ in OPS]},
    "host": [("bench.traced", 0, 10_000), ("bench.bucket_pick", 2480, 140),
             ("bench.launch", 2640, 170), ("bench.done_read", 2820, 1100),
             ("bench.launch", 4290, 120)],
}
PROGRAM = {
    "program": [("sim.pick_bucket", 2500, 100, {"bucket": 8, "ready": 7, "members": 8}),
                ("sim.chunk_launch", 2650, 150, {"bucket": 8, "ticks": 32, "members": 8, "n": 2}),
                ("sim.chunk_wait", 2830, 1080, {"n": 2}),
                ("sim.chunk_launch", 4300, 100, {"bucket": 8, "ticks": 32, "members": 8, "n": 3})],
    "phase": {DEV: [ph for *_, ph in OPS]},
}
# busy: 100..2,100 + 2,900..3,800 + 4,450..9,450 + 9,800..10,000 = 8,100.
# Self time: the loop 2,000 - 500 - 300 - 400 - 200 = 600 (no phase);
# fusion.6 900 - 200 = 700; fusion.9 200 after clipping.
SELF = {None: 600, "forecast": 5500, "admit": 300, "oom": 400, "metrics": 200,
        "elastic": 700, "shape": 200, "arrivals": 200}


def _ctx(rec, prog, ticks=None):
    window = types.SimpleNamespace(chunks=[])
    ctx = cell.Context(rec, window, ticks or {}, {}, None, 32)
    ctx.program_trace = prog
    return ctx


def test_phase_self_times_by_hand():
    got = program_trace.phase_ns(HAND, PROGRAM, DEV)
    assert got == SELF
    assert sum(got.values()) == trace.busy_ns(HAND, DEV) == 8100


def test_phase_readers_by_hand():
    ctx = _ctx(HAND, PROGRAM)
    assert cell.reader("forecast_busy_pct")(ctx) == pytest.approx(100 * 5500 / 8100)
    # oom 400 + admit 300 + elastic 700
    assert cell.reader("placement_busy_pct")(ctx) == pytest.approx(100 * 1400 / 8100)
    shares = program_trace.phase_shares(ctx)
    assert sum(shares.values()) == pytest.approx(100.0)
    assert shares[None] == pytest.approx(100 * 600 / 8100)
    assert any(n.startswith("tick phases") for n in ctx.notes)
    # the one unscoped operation: the loop, by its self time
    assert "top unscoped operations, seconds: [['%while.1 while', 6e-07]]" in ctx.notes


def test_nested_phases_by_hand():
    """An operation with no phase takes that of the operation it runs
    inside, or else the one phase every phased operation inside it
    shares; a loop over several phases keeps none."""
    events = [("%while.1", 0, 100), ("%a", 10, 20), ("%while.2", 40, 50),
              ("%b", 45, 10), ("%c", 60, 10), ("%d", 200, 10)]
    phases = [None, "forecast", None, None, "forecast", None]
    # while.2 holds c (forecast): forecast; b runs inside while.2: forecast;
    # while.1 holds a, while.2, b, c, all forecast once resolved: forecast
    assert program_trace.nested(events, phases) == ["forecast"] * 5 + [None]
    mixed = [None, "forecast", None, None, "shape", None]
    # while.1 holds forecast and shape: none; while.2 holds shape alone
    assert program_trace.nested(events, mixed) == [
        None, "forecast", "shape", "shape", "shape", None]


def test_an_unscoped_loop_of_one_phase_counts_to_it():
    """On the hand-made record with chunk 3's forecast operation run
    inside an unscoped loop (4,400..9,500) that holds an unscoped copy
    (9,460..9,500): the loop and the copy count to ``forecast``, and the
    context notes the share that came from nesting."""
    rec = dict(HAND, devices={DEV: HAND["devices"][DEV][:7] + [
        ("%while.10 = (s32[]) while(%t), body=%c", 4400, 5100),
        HAND["devices"][DEV][7],
        ("%copy.11 = f32[8] copy(%p)", 9460, 40),
        HAND["devices"][DEV][8]]})
    prog = dict(PROGRAM, phase={DEV: PROGRAM["phase"][DEV][:7] + [None, "forecast", None,
                                                                  "arrivals"]})
    # busy 8,100 + 50 (4,400..4,450) + 50 (9,450..9,500) = 8,200; the loop's
    # self time 5,100 - 5,000 - 40 = 60, the copy's 40
    got = program_trace.phase_ns(rec, prog, DEV)
    assert got == dict(SELF, forecast=5600)
    ctx = _ctx(rec, prog)
    assert cell.reader("forecast_busy_pct")(ctx) == pytest.approx(100 * 5600 / 8200)
    note = next(n for n in ctx.notes if n.startswith("tick phases"))
    assert note.endswith(f"phase from nesting {100 * 100 / 8200:.4f}")


def test_forecast_rows_useful_by_hand():
    # two members, two ticks; the vmapped loop computed 2 x 16 rows a tick
    ticks = {"forecast_rows": [[4, 2], [6, 0]], "forecast_rows_done": [[16, 16], [16, 0]]}
    ctx = _ctx(HAND, PROGRAM, ticks)
    assert cell.reader("forecast_rows_useful_pct")(ctx) == pytest.approx(100 * 12 / 64)
    # a run that traced no device reads nothing
    assert cell.reader("forecast_rows_useful_pct")(_ctx({"devices": {}, "host": []},
                                                        PROGRAM, ticks)) is None


def test_a_program_without_spans_or_scopes_reads_nothing():
    old = {"program": [], "phase": {DEV: [None] * len(OPS)}}
    ctx = _ctx(HAND, old)
    for name in ("forecast_busy_pct", "placement_busy_pct"):
        assert cell.reader(name)(ctx) is None, name


def test_load_reads_the_program_spans_of_a_cpu_trace(tmp_path, monkeypatch):
    """The program's spans on the profiler's host plane, with their args;
    ``of`` takes the newest trace only where its device operations are
    those the record holds (a CPU trace has none), and raises otherwise."""
    from repro.obs import span
    jax.profiler.start_trace(str(tmp_path / "t"))
    try:
        with span("sim.pick_bucket", args={"bucket": 8, "ready": 5, "members": 2}):
            pass
        with span("sim.chunk_launch", args={"bucket": 8, "ticks": 32, "members": 2, "n": 1}):
            jax.numpy.ones(4).block_until_ready()
        with span("bench.launch"):
            pass
    finally:
        jax.profiler.stop_trace()
    path = next((tmp_path / "t").rglob("*.xplane.pb"))
    got = program_trace.load(path)
    assert [(n, a) for n, _, _, a in got["program"]] == [
        ("sim.pick_bucket", {"bucket": 8, "ready": 5, "members": 2}),
        ("sim.chunk_launch", {"bucket": 8, "ticks": 32, "members": 2, "n": 1})]
    assert got["phase"] == {} and got["ops"] == {}
    monkeypatch.setattr(program_trace, "OUT", tmp_path)
    fresh = cell.Context({"devices": {}, "host": []}, types.SimpleNamespace(chunks=[]),
                         {}, {}, None, 32)
    assert program_trace.of(fresh) is None            # no device traced
    ctx = cell.Context(HAND, types.SimpleNamespace(chunks=[]), {}, {}, None, 32)
    with pytest.raises(RuntimeError, match="is not the trace this run read"):
        program_trace.of(ctx)


def test_a_traced_run_without_the_protobuf_module_fails(tmp_path, monkeypatch):
    """Without the XSpace module the phase readers raise, rather than
    leave their metrics out of the result."""
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(program_trace, "OUT", tmp_path)
    monkeypatch.setattr(program_trace, "_xplane_pb2", lambda: None)
    ctx = cell.Context(HAND, types.SimpleNamespace(chunks=[]), {}, {}, None, 32)
    with pytest.raises(RuntimeError, match="no XSpace protobuf module"):
        cell.reader("placement_busy_pct")(ctx)


def test_phase_of_reads_any_string_value():
    op = "jit(tick_chunk)/vmap()/while/body/closed_call/tick/admit/while/body/add"
    assert program_trace.phase_of([3, op]) == "admit"
    assert program_trace.phase_of(["jit(tick_chunk)/tick/leap/while"]) == "leap"
    # the innermost phase: the forecast nests in the shaping
    assert program_trace.phase_of(
        ["jit(tick_chunk)/while/body/tick/shape/forecast/while/body/dot"]) == "forecast"
    assert program_trace.phase_of(["jit(tick_chunk)/tick/shape/gather"]) == "shape"
    # a scope that is not a phase, or no scope: none
    assert program_trace.phase_of(["jit(tick_chunk)/tick/iota"]) is None
    assert program_trace.phase_of(["reduce_window_sum", 1, None]) is None


def test_device_phases_from_event_metadata(tmp_path):
    """A TPU puts an operation's op_name in its event metadata's stats (as
    a string, or as a reference to a stat metadata's name): each gives
    the phase, in the line's order."""
    pb2 = program_trace._xplane_pb2()
    if pb2 is None:
        pytest.skip("no XSpace protobuf module installed")
    space = pb2.XSpace()
    plane = space.planes.add(id=1, name=DEV)
    for sid, name in ((1, "tf_op"), (2, "program_id"),
                      (3, "jit(tick_chunk)/while/body/closed_call/tick/oom/select")):
        plane.stat_metadata[sid].id, plane.stat_metadata[sid].name = sid, name
    for mid, name in ((7, "%fusion.1 = f32[8] fusion(%p)"), (8, "%while.2 = while(%t)"),
                      (9, "%fusion.3 = f32[8] fusion(%q)")):
        plane.event_metadata[mid].id, plane.event_metadata[mid].name = mid, name
    plane.event_metadata[7].stats.add(metadata_id=1,
                                      str_value="jit(tick_chunk)/tick/shape/forecast/mul")
    plane.event_metadata[8].stats.add(metadata_id=2, int64_value=4)
    plane.event_metadata[9].stats.add(metadata_id=1, ref_value=3)
    line = plane.lines.add(id=1, name=trace.OPS_LINE, timestamp_ns=1000)
    for mid, off in ((7, 5000), (8, 9000), (9, 12000), (7, 30000)):
        line.events.add(metadata_id=mid, offset_ps=off, duration_ps=1000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    got = program_trace.load(path)
    assert got["phase"] == {DEV: ["forecast", None, "oom", "forecast"]}
    assert got["ops"] == {DEV: (4, 1005)}
    assert got["program"] == []


@pytest.fixture(scope="module")
def recorded():
    if not FIXTURE.exists():
        pytest.skip("no recorded trace fixture")
    return json.loads(FIXTURE.read_text())


def test_recorded_phases_add_up_to_busy_time(recorded):
    """On a real trace, self times by phase add up to the busy time (the
    operations nest, they do not overlap partly), the phases the
    baseline's tick runs are all there, and the program's spans hold the
    window's chunk launch."""
    rec = {"devices": {d: [tuple(e) for e in evs] for d, evs in recorded["devices"].items()},
           "host": [tuple(e) for e in recorded["host"]]}
    prog = {"program": [tuple(e) for e in recorded["program"]], "phase": recorded["phase"]}
    for dev in rec["devices"]:
        got = program_trace.phase_ns(rec, prog, dev)
        busy = trace.busy_ns(rec, dev)
        assert abs(sum(got.values()) - busy) <= 1e-3 * busy
        assert {"arrivals", "completions", "monitor", "oom", "admit", "elastic",
                "metrics"} <= set(got)
    lo, hi = trace.window(rec)
    launches = [a for name, s, _, a in prog["program"]
                if name == "sim.chunk_launch" and lo <= s < hi]
    assert len(launches) == 1
    assert set(launches[0]) == {"bucket", "ticks", "members", "n"}
