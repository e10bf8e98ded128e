"""What the program marks in a profiler trace: its own spans and the phase
of the tick each device operation belongs to.

``harness.trace.load`` keeps the harness's host spans and each device's
operations; this module reads from the same ``.xplane.pb`` what the
program adds, into a record of two keys (and ``ops``, each device's
operation count and first start, to match the two records):

* ``program``: the program's host spans (``sim.pick_bucket``,
  ``sim.chunk_launch``, ``sim.chunk_wait``, ``jit_compile``; see
  ``repro.obs.trace.span``), as (name, start, duration, args) on the
  profiler's clock, sorted by start;
* ``phase``: per device, the tick phase of each operation, aligned with
  ``trace.load``'s ``devices[device]``: the innermost ``<phase>`` of a
  ``tick/<phase>[/<phase>]`` in the operation's op_name
  (``repro.sim.step.fused_tick``'s named scopes; ``forecast`` nests in
  ``shape``), None where there is none.

Some operations have no phase of their own: the chunk's scan loop and
its output writes lie outside the tick, and the chip's compiler leaves
some loops, and some operations it builds, without the scope of the code
they run (a ``cumsum`` lowers to a ``reduce_window_sum`` whose op_name
has no scope).  ``nested`` gives such an operation the phase of the
operation it runs inside, or else the one phase that every phased
operation nested in it shares; what is left is the unscoped share.

A program from before the scopes and spans gives an empty ``program``
and only None phases; the readers then read nothing.

The record needs the XSpace protobuf module that TensorFlow installs
(``tsl/profiler/protobuf/xplane_pb2.py``; the ``bench`` extra of
``pyproject.toml``): a TPU keeps each operation's op_name in its event
metadata's stats, which ``jax.profiler.ProfileData`` does not expose.
A traced run without the module fails rather than drop the metrics.

The readers (``bench/metrics/``) reach the record through ``of(ctx)``,
which finds the trace the run just wrote (the newest ``.xplane.pb``
under ``<checkout>/.bench_out``: the context does not carry the run's
trace directory), checks that its device operations are those of
``ctx.rec``, and keeps the record on the context.  The functions below
it work on the records alone, so the tests can hand them small ones.
"""
from __future__ import annotations

import importlib.util
import re
import sys
import time
from pathlib import Path

from harness import trace

PHASES = ("arrivals", "completions", "monitor", "forecast", "shape", "oom",
          "tenancy", "admit", "elastic", "metrics", "leap")
_ONE = "(?:" + "|".join(PHASES) + ")(?:/|$)"
PHASE = re.compile(r"(?:^|/)tick/((?:" + _ONE + ")+)")
PLACEMENT = ("oom", "admit", "elastic")
PROGRAM_SPANS = ("sim.pick_bucket", "sim.chunk_launch", "sim.chunk_wait", "jit_compile")
OUT = Path(__file__).resolve().parents[2] / ".bench_out"


def phase_of(values) -> str | None:
    """The innermost tick phase named by any of an operation's string
    stat values (``tick/shape/forecast/...`` is ``forecast``)."""
    for v in values:
        if isinstance(v, str):
            m = PHASE.search(v)
            if m:
                return m.group(1).rstrip("/").rsplit("/", 1)[-1]
    return None


def _xplane_pb2():
    """The ``XSpace`` protobuf module (TensorFlow's generated
    ``tsl/profiler/protobuf/xplane_pb2.py``), loaded from its file without
    importing TensorFlow; None where it is not installed."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        return None
    path = (Path(spec.submodule_search_locations[0]) / "tsl" / "profiler" / "protobuf"
            / "xplane_pb2.py")
    if not path.exists():
        return None
    mod = sys.modules.get("tsl_xplane_pb2")
    if mod is None:
        sub = importlib.util.spec_from_file_location("tsl_xplane_pb2", path)
        mod = importlib.util.module_from_spec(sub)
        sub.loader.exec_module(mod)
        sys.modules["tsl_xplane_pb2"] = mod
    return mod


def _value(st, names: dict):
    """An XStat's value: a string, a number, or the name a ``ref_value``
    points to."""
    kind = st.WhichOneof("value")
    if kind == "ref_value":
        return names.get(st.ref_value)
    return getattr(st, kind) if kind else None


def _start_ns(line, e) -> int:
    """An event's start on the profiler's clock, as ``ProfileData`` gives
    it to ``trace.load``."""
    return int(line.timestamp_ns + e.offset_ps / 1000)


def read_space(space) -> dict:
    """The program's host spans, and per device the tick phase of each
    operation of its ``XLA Ops`` line, in the line's order (from its event
    metadata's stats: a TPU puts the op_name there, as ``tf_op``), with
    the operation count and first start (ns)."""
    phase, ops, program = {}, {}, []
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        if trace.DEVICE.match(plane.name):
            by_meta = {k: phase_of(_value(st, names) for st in m.stats)
                       for k, m in plane.event_metadata.items()}
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ph = [by_meta.get(e.metadata_id) for e in line.events]
                    first = _start_ns(line, line.events[0]) if ph else None
                    phase[plane.name], ops[plane.name] = ph, (len(ph), first)
        elif plane.name.startswith("/host:"):
            spans = {k: m.name for k, m in plane.event_metadata.items()
                     if m.name in PROGRAM_SPANS}
            for line in plane.lines:
                for e in line.events:
                    if e.metadata_id in spans:
                        args = {names.get(st.metadata_id): _value(st, names) for st in e.stats}
                        program.append((spans[e.metadata_id], _start_ns(line, e),
                                        int(e.duration_ps / 1000), args))
    program.sort(key=lambda e: e[1])
    return {"program": program, "phase": phase, "ops": ops}


def load(path: Path) -> dict:
    """``read_space`` of one trace file."""
    pb2 = _xplane_pb2()
    if pb2 is None:
        raise RuntimeError("no XSpace protobuf module: install the bench extra "
                           "(TensorFlow) to read the tick phases of a trace")
    space = pb2.XSpace()
    space.ParseFromString(Path(path).read_bytes())
    return read_space(space)


def of(ctx) -> dict | None:
    """The program's part of the run's trace; None where the run traced
    no device.  Raises where the newest trace file is not the one
    ``ctx.rec`` read, or the protobuf module is missing."""
    if not ctx.devices:
        return None
    if not hasattr(ctx, "program_trace"):
        files = sorted(OUT.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {OUT}")
        t0 = time.perf_counter()
        got = load(files[-1])
        want = {d: (len(evs), evs[0][1] if evs else None)
                for d, evs in ctx.rec["devices"].items()}
        if got["ops"] != want:
            raise RuntimeError(f"{files[-1]} is not the trace this run read")
        ctx.program_trace = got
        ctx.note(f"program trace: {len(got['program'])} program spans, "
                 f"read in {time.perf_counter() - t0:.3f} s")
    return ctx.program_trace


def nested(events, phases) -> list:
    """Each operation's phase, where one with none takes the phase of the
    operation it runs inside, or else the one phase that every phased
    operation nested in it shares (None where they differ).  ``events``
    are (name, start, duration), nested as ``trace.self_times`` nests
    them; ``phases`` are aligned with them."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    parent, stack = [None] * len(events), []
    for i in order:
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= events[i][1]:
            stack.pop()
        parent[i] = stack[-1] if stack else None
        stack.append(i)
    inner = [set() for _ in events]     # the phases of the phased operations within
    for i in reversed(order):
        p = parent[i]
        if p is not None:
            inner[p] |= inner[i]
            if phases[i] is not None:
                inner[p].add(phases[i])
    out = list(phases)
    for i in order:
        if out[i] is None:
            p = parent[i]
            if p is not None and out[p] is not None:
                out[i] = out[p]
            elif len(inner[i]) == 1:
                (out[i],) = inner[i]
    return out


def _self_times(rec: dict, prog: dict, device: str) -> list:
    """[(phase, event name, whether the phase came from nesting), self ns]
    of a device's operations inside the window, each clipped to it, so
    that a loop and its body count once and the self times add to the
    busy time."""
    lo, hi = trace.window(rec)
    inside = [(name, max(s, lo), min(s + d, hi) - max(s, lo), ph)
              for (name, s, d), ph in zip(rec["devices"][device], prog["phase"][device])
              if s < hi and s + d > lo]
    got = nested([e[:3] for e in inside], [e[3] for e in inside])
    return trace.self_times([((ph, name, ph is not None and own is None), s, d)
                             for (name, s, d, own), ph in zip(inside, got)])


def phase_ns(rec: dict, prog: dict, device: str) -> dict:
    """Self time (ns) of a device's operations inside the window, per
    phase (None: no phase, after ``nested``)."""
    out: dict = {}
    for (ph, _, _), t in _self_times(rec, prog, device):
        out[ph] = out.get(ph, 0) + t
    return out


def phase_shares(ctx) -> dict | None:
    """Per phase (None: no phase), its self time over the device's busy
    time, in %, over the chips; None where no operation has a phase.
    Notes every share, the share whose phase came from nesting, and the
    operations left with no phase that took most self time."""
    if hasattr(ctx, "phase_shares"):
        return ctx.phase_shares
    ctx.phase_shares = None
    prog = of(ctx)
    if prog is None:
        return None
    tot, unscoped, from_nesting = {}, {}, 0
    for dev in ctx.devices:
        for (ph, name, inherited), t in _self_times(ctx.rec, prog, dev):
            tot[ph] = tot.get(ph, 0) + t
            from_nesting += t if inherited else 0
            if ph is None:
                key = trace.op_name(name)
                unscoped[key] = unscoped.get(key, 0) + t
    busy = ctx.busy_s * 1e9 * len(ctx.devices)      # the context's, summed over the chips
    if not busy or not any(ph is not None and t for ph, t in tot.items()):
        return None
    shares = {ph: 100.0 * t / busy for ph, t in tot.items()}
    ctx.phase_shares = shares
    named = ", ".join(f"{ph} {shares[ph]:.4f}" for ph in PHASES if ph in shares)
    ctx.note(f"tick phases, % of busy time by self time: {named}; "
             f"unscoped {shares.get(None, 0.0):.4f}; sum {sum(shares.values()):.4f}; "
             f"phase from nesting {100.0 * from_nesting / busy:.4f}")
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:3]
    ctx.note(f"top unscoped operations, seconds: {[[k, v / 1e9] for k, v in top]}")
    return shares
