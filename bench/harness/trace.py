"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
compact record: per device, the operations that ran on it (name, start,
duration, in nanoseconds on the profiler's one clock), and the harness's
own host spans (``bench.bucket_pick``, ``bench.launch``,
``bench.done_read``).  Everything else here works on that record, so the
tests can hand it a small recorded one.

* ``window``: the harness's ``bench.traced`` span (see
  ``system.Tracer``), or from the first host span to the end of the last.
* ``busy``: the union of a device's operation intervals inside the
  window (nested or overlapping operations count once).
* ``gaps``: the complement of ``busy`` inside the window; the longest
  are named by the host span that overlaps them most ("host:none" where
  none does).
* ``kernel_ns``: the summed durations of a kernel's operations.
* ``chunk_device``: one chunk's device busy time and its period on the
  device's clock, from whole traced chunks or from a slice around one
  chunk boundary.
"""
from __future__ import annotations

import re
from pathlib import Path

HOST_SPANS = ("bench.traced", "bench.bucket_pick", "bench.launch", "bench.done_read")
OPS_LINE = "XLA Ops"
DEVICE = re.compile(r"^/device:TPU:\d+$")


def load(trace_dir: Path) -> dict:
    """The compact record of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [(e.name, int(e.start_ns), int(e.duration_ns))
                                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append((e.name, int(e.start_ns), int(e.duration_ns)))
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


def window(rec: dict) -> tuple[int, int] | None:
    """The traced stretch: the ``bench.traced`` span, or where there is
    none, from the first host span's start to the last one's end."""
    for name, s, d in rec["host"]:
        if name == "bench.traced":
            return (s, s + d)
    if not rec["host"]:
        return None
    return (rec["host"][0][1], max(s + d for _, s, d in rec["host"]))


def launches(rec: dict) -> int:
    """Chunk launches inside the window: the boundaries it holds."""
    lo, hi = window(rec)
    return sum(1 for n, s, d in rec["host"] if n == "bench.launch" and lo <= s < hi)


def merge(intervals, lo: int, hi: int) -> list:
    """Union of (start, end) intervals clipped to [lo, hi], sorted."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(rec: dict, device: str) -> int:
    return busy_between(rec, device, *window(rec))


def gaps(rec: dict, device: str) -> list:
    """(start, end) of every idle interval of a device inside the window."""
    lo, hi = window(rec)
    busy = merge(((s, s + d) for _, s, d in rec["devices"][device]), lo, hi)
    out, at = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    return out


def busy_between(rec: dict, device: str, lo: int, hi: int) -> int:
    """A device's busy time inside [lo, hi]."""
    return sum(e - s for s, e in merge(((s, s + d) for _, s, d in rec["devices"][device]),
                                       lo, hi))


# a tick's period may differ from the mean of those traced by this much
# before its marker is taken to run other than once a tick
TICK_SPREAD = 0.25
# the device idles between ticks for microseconds; a longer gap is not
# a tick's (the profiler starting on the device), and the ticks around it
# are left out
TICK_GAP_NS = 1_000_000
# the boundary's gap lies within this of the next chunk's launch on the
# host (the host and device clocks agree to well under it)
LAUNCH_NS = 20_000_000


def chunk_device(rec: dict, device: str, ticks: int) -> dict:
    """One chunk's device busy time and device period, in ns, with how
    they were read (``how``), or ``{"why": ...}`` where the trace cannot
    give them.

    Where the window holds several chunk launches, it holds whole chunks:
    busy time and window over the launches.  Where it holds one, it is a
    slice around that chunk boundary (``system.Tracer``), most of it
    before the boundary.  The boundary is the longest idle gap near the
    launch.  The tick marker is the longest operation that starts and
    ends in the window before the boundary: a loop the scan body runs
    once a tick (the outer scan's own loop started before the slice, and
    the next chunk's ends after it).  Consecutive starts of the marker
    bound whole ticks: their mean spacing is the tick's period and the
    busy time between them its busy time.  The chunk then takes ``ticks``
    of those and the boundary's gap; its prologue and epilogue are
    counted in neither (under a millisecond against seconds)."""
    lo, hi = window(rec)
    n = launches(rec)
    if n > 1:
        return {"busy_ns": busy_ns(rec, device) / n, "period_ns": (hi - lo) / n,
                "how": f"{n} whole chunks"}
    if n < 1:
        return {"why": "no chunk launch in the traced window"}
    _, l0, ld = next(e for e in rec["host"] if e[0] == "bench.launch" and lo <= e[1] < hi)
    gs = gaps(rec, device)
    near = [g for g in gs if g[0] > lo and g[1] < hi
            and g[1] > l0 - LAUNCH_NS and g[0] < l0 + ld + LAUNCH_NS]
    if not near:
        return {"why": "no idle gap near the launch in the traced slice"}
    b0, b1 = max(near, key=lambda g: g[1] - g[0])
    evs = [(name, s, d) for name, s, d in rec["devices"][device]
           if s >= lo and s + d <= b0]
    if not evs:
        return {"why": "no whole operation before the boundary"}
    mark = max(evs, key=lambda e: e[2])[0]
    starts = sorted(s for name, s, _ in evs if name == mark)
    long_gaps = [g for g in gs if g[1] - g[0] > TICK_GAP_NS and g[1] <= b0]
    pairs = [(a, b) for a, b in zip(starts, starts[1:])
             if not any(g[0] < b and g[1] > a for g in long_gaps)]
    if not pairs:
        return {"why": f"{len(starts)} tick markers ({op_name(mark)}) bound no whole tick "
                       "before the boundary"}
    spans = [b - a for a, b in pairs]
    period = sum(spans) / len(spans)
    if max(abs(x - period) for x in spans) > TICK_SPREAD * period:
        return {"why": f"tick marker {op_name(mark)} is not evenly spaced: {spans}"}
    busy = sum(busy_between(rec, device, a, b) for a, b in pairs) / len(pairs)
    return {"busy_ns": ticks * busy, "period_ns": ticks * period + (b1 - b0),
            "how": f"{len(pairs)} ticks marked by {op_name(mark)}, "
                   f"{period / 1e6:.4f} ms a tick, boundary gap {(b1 - b0) / 1e6:.4f} ms"}


def host_span_of(rec: dict, g0: int, g1: int) -> str:
    """The host span that overlaps [g0, g1] most ("host:none" if none)."""
    best, name = 0, "host:none"
    for n, s, d in rec["host"]:
        if n == "bench.traced":
            continue
        ov = min(g1, s + d) - max(g0, s)
        if ov > best:
            best, name = ov, n
    return name


def kernel_events(rec: dict, device: str, match: str) -> list:
    """(name, ns) of a kernel's operations inside the window."""
    lo, hi = window(rec)
    return [(n, d) for n, s, d in rec["devices"][device] if match in n and s < hi and s + d > lo]


def kernel_ns(rec: dict, device: str, match: str) -> int:
    return sum(d for _, d in kernel_events(rec, device, match))


HLO = re.compile(r"^(%?[\w.\-]+) = .*? ([a-z][\w\-]*)\(")


def op_name(event_name: str) -> str:
    """``%fusion.12 fusion`` for an event named by its HLO instruction
    text ("%fusion.12 = f32[..] fusion(...), kind=..."); other names as
    they are."""
    m = HLO.match(event_name)
    return f"{m.group(1)} {m.group(2)}" if m else event_name[:120]


def self_times(events) -> list:
    """(name, self ns) of each event: its duration less that of the events
    nested in it (a loop's body runs inside the loop's own event)."""
    out, stack = [], []          # stack of [end, index]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(d, stack[-1][0] - s)
        out.append([name, d])
        stack.append([s + d, len(out) - 1])
    return out


def top_ops(rec: dict, n: int = 10) -> list:
    """[name, seconds] of the operations that took most self time, all
    devices, named by instruction and kind."""
    lo, hi = window(rec)
    tot: dict = {}
    for evs in rec["devices"].values():
        inside = [e for e in evs if e[1] < hi and e[1] + e[2] > lo]
        for name, t in self_times(inside):
            key = op_name(name)
            tot[key] = tot.get(key, 0) + t
    return [[k, v / 1e9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(rec: dict, n: int = 10) -> list:
    """[host span, seconds] of the longest idle gaps, all devices."""
    allg = sorted((g for dev in rec["devices"] for g in gaps(rec, dev)),
                  key=lambda g: g[0] - g[1])[:n]
    return [[host_span_of(rec, g0, g1), (g1 - g0) / 1e9] for g0, g1 in allg]
