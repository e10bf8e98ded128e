"""The reduction from a profiler trace to the per-layer numbers.

Two records: a hand-made one whose numbers are worked out below, and a
small slice of a real trace of ``paper-gp.google-c8`` on a TPU v5e,
recorded by ``harness.trace.load`` and committed as a fixture, checked
against a second, independent sweep over the same events."""
import json
from pathlib import Path

import pytest

from harness import trace

FIXTURE = Path(__file__).with_name("fixtures") / "trace_gp_c8.json"

# one device; host spans from 100 to 1000 ns
HAND = {
    "devices": {"/device:TPU:0": [
        ("%fusion.1 = f32[8] fusion(%p), kind=kLoop", 50, 100),      # clipped to 100..150
        ("%fusion.2 = f32[8] fusion(%p), kind=kLoop", 120, 100),     # 120..220, overlaps
        ("%custom-call.3 = f32[16] custom-call(%x), custom_call_target=\"gram\"", 300, 50),
        ("%custom-call.3 = f32[16] custom-call(%x), custom_call_target=\"gram\"", 310, 20),  # nested
        ("%while.4 = pred[] reduce(%y)", 600, 200),
        ("%fusion.9 = f32[8] fusion(%p), kind=kLoop", 1500, 10),     # outside the window
    ]},
    "host": [("bench.bucket_pick", 100, 20), ("bench.launch", 130, 60),
             ("bench.done_read", 220, 700), ("bench.bucket_pick", 950, 50)],
}


def test_window_busy_and_gaps_by_hand():
    assert trace.window(HAND) == (100, 1000)
    # busy: 100..220 (120) + 300..350 (50) + 600..800 (200) = 370
    assert trace.busy_ns(HAND, "/device:TPU:0") == 370
    assert trace.gaps(HAND, "/device:TPU:0") == [(220, 300), (350, 600), (800, 1000)]
    # the kernel's events: both of them, nested or not (50 + 20)
    assert trace.kernel_ns(HAND, "/device:TPU:0", "gram") == 70
    # the longest gap is 350..600, all inside bench.done_read; the gap
    # 800..1000 overlaps done_read by 120 and bucket_pick by 50
    assert trace.top_gaps(HAND, 2) == [["bench.done_read", 250e-9], ["bench.done_read", 200e-9]]
    # by self time: the nested call's 20 ns come off its parent's 50
    ops = dict(map(tuple, trace.top_ops(HAND)))
    assert ops["%while.4 reduce"] == pytest.approx(200e-9)
    assert ops["%custom-call.3 custom-call"] == pytest.approx(50e-9)
    assert "%fusion.9 fusion" not in ops


def test_the_traced_span_sets_the_window():
    rec = {"devices": HAND["devices"],
           "host": [("bench.traced", 200, 500)] + HAND["host"]}
    assert trace.window(rec) == (200, 700)
    assert trace.launches(rec) == 0 and trace.launches(HAND) == 1
    # busy inside 200..700: 200..220, 300..350, 600..700
    assert trace.busy_ns(rec, "/device:TPU:0") == 170
    # the traced span names no gap
    assert all(g[0] != "bench.traced" for g in trace.top_gaps(rec))


def _sweep_busy(events, lo, hi):
    """Busy time by a sweep over interval end points (independent of
    ``trace.merge``)."""
    pts = []
    for _, s, d in events:
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            pts += [(s, 1), (e, -1)]
    busy, depth, last = 0, 0, None
    for t, step in sorted(pts):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


@pytest.fixture(scope="module")
def recorded():
    if not FIXTURE.exists():
        pytest.skip("no recorded trace fixture")
    return json.loads(FIXTURE.read_text())


def test_recorded_trace_reduces_like_a_sweep(recorded):
    lo, hi = trace.window(recorded)
    assert recorded["host"] and recorded["devices"]
    for dev, evs in recorded["devices"].items():
        busy = trace.busy_ns(recorded, dev)
        assert busy == _sweep_busy(evs, lo, hi)
        idle = sum(g1 - g0 for g0, g1 in trace.gaps(recorded, dev))
        assert busy + idle == hi - lo
    assert recorded["expect"]["busy_ns"] == sum(
        trace.busy_ns(recorded, d) for d in recorded["devices"])


def test_recorded_trace_kernel_and_breakdown(recorded):
    dev = sorted(recorded["devices"])[0]
    k = trace.kernel_ns(recorded, dev, recorded["expect"]["kernel_match"])
    assert k == recorded["expect"]["kernel_ns"] and k > 0
    gaps = trace.top_gaps(recorded)
    assert len(gaps) <= 10 and all(g[0].startswith(("bench.", "host:none")) for g in gaps)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)


def _slice_record():
    """A slice around one chunk boundary: four ticks of 200 us before it
    (a 40 us front op, 10 us idle, the tick's 140 us loop, 10 us idle,
    the last tick's loop ending at 790 us), the boundary's 100 us gap,
    then the next chunk's first ops, whose loop ends after the slice."""
    loop, front = "%while.5 = (s32[8]) while(%t), body=%b", "%fusion.7 = f32[8] fusion(%p)"
    ops = []
    for j in range(4):
        t = 200_000 * j
        ops += [(front, t, 40_000), (loop, t + 50_000, 140_000)]
    ops += [("%fusion.2 = f32[8] fusion(%q)", 890_000, 10_000),
            ("%while.9 = (s32[8]) while(%u), body=%c", 900_000, 400_000)]
    host = [("bench.traced", 0, 1_000_000), ("bench.bucket_pick", 795_000, 5_000),
            ("bench.launch", 800_000, 40_000)]
    return {"devices": {"/device:TPU:0": ops}, "host": host}


def test_a_slice_gives_the_chunk_from_its_ticks():
    rec = _slice_record()
    got = trace.chunk_device(rec, "/device:TPU:0", 32)
    # ticks marked by the loop's starts 50, 250, 450, 650 us: 200 us apart,
    # 180 us busy each; the boundary gap runs 790..890 us
    assert got["busy_ns"] == 32 * 180_000
    assert got["period_ns"] == 32 * 200_000 + 100_000
    assert "%while.5 while" in got["how"]


def test_a_slice_with_an_uneven_marker_gives_nothing():
    """The third tick's loop starts 60 us late and ends on time: ticks
    then read 200, 260 and 140 us, 30 % off their mean."""
    rec = _slice_record()
    loop = rec["devices"]["/device:TPU:0"][1][0]
    rec["devices"]["/device:TPU:0"] = [
        (n, s + 60_000, d - 60_000) if (n == loop and s == 450_000) else (n, s, d)
        for n, s, d in rec["devices"]["/device:TPU:0"]]
    got = trace.chunk_device(rec, "/device:TPU:0", 32)
    assert "not evenly spaced" in got["why"]


def test_one_marker_before_the_boundary_gives_nothing():
    rec = _slice_record()
    rec["host"][0] = ("bench.traced", 600_000, 400_000)
    assert "bound no whole tick" in trace.chunk_device(rec, "/device:TPU:0", 32)["why"]


def test_a_long_gap_before_the_boundary_is_neither_the_boundary_nor_a_tick(monkeypatch):
    """The profiler stalls the device for 80 us in the second tick (on
    this record's scale, where a tick's idle is 10 us and the boundary lies
    within 50 us of the launch): that tick is left out, and the boundary
    is still the gap at the launch."""
    monkeypatch.setattr(trace, "TICK_GAP_NS", 50_000)
    monkeypatch.setattr(trace, "LAUNCH_NS", 50_000)
    rec = _slice_record()
    rec["devices"]["/device:TPU:0"] = [
        (n, s + (80_000 if 240_000 <= s < 800_000 else 0), d)
        for n, s, d in rec["devices"]["/device:TPU:0"]]
    got = trace.chunk_device(rec, "/device:TPU:0", 32)
    # ticks marked from 330 and 530 us: two whole ticks after the stall;
    # the boundary's gap is now 870..890 us
    assert got["busy_ns"] == 32 * 180_000
    assert got["period_ns"] == 32 * 200_000 + 20_000
    assert got["how"].startswith("2 ticks")
