"""Host-side span tracing: Chrome trace-event / Perfetto JSON, and the
``jax.profiler`` trace.

A :class:`Tracer` collects "X" (complete) events — name, category,
start timestamp, duration — from :func:`span` context managers placed
around sweep-driver phases (trace build, chunk-driver bucket pick,
launch and wait, jit compile, ring drain, per-combo cohorts).
:func:`tracing` installs a global tracer for a ``with`` region and
writes the JSON on exit.

Whenever a ``jax.profiler`` session is active, every ``span`` also
enters a ``jax.profiler.TraceAnnotation`` of the same name, its
``args`` becoming the event's stats: the span then lands on the
profiler's host plane, on the same clock as the device's operations.
With neither a tracer nor a profiler session, a span is a shared no-op
that costs a global read and the profiler's own active check; ``args``
are encoded only for a session that is on.

Load the Chrome JSON in ``chrome://tracing`` or https://ui.perfetto.dev.
"""
from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time

__all__ = ["Tracer", "span", "tracing", "current_tracer",
           "validate_trace"]

_NULL = contextlib.nullcontext()
_lock = threading.Lock()
_tracer: Tracer | None = None
_annotation = None      # jax.profiler.TraceAnnotation, once jax is imported


class Tracer:
    """Accumulates Chrome trace events (``ts``/``dur`` in microseconds
    relative to the tracer's epoch, per the trace-event spec)."""

    def __init__(self):
        self._epoch = time.perf_counter_ns()
        self._lock = threading.Lock()
        self.events: list[dict] = []

    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self._epoch) / 1e3

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "sweep", args: dict | None = None):
        t0 = self._now_us()
        error: str | None = None
        try:
            yield
        except BaseException as e:
            # close the span with an error tag and re-raise: the phase
            # still shows up in the waterfall (flagged), and the tracer
            # state stays consistent for whatever spans come after
            error = type(e).__name__
            raise
        finally:
            ev = {"name": name, "cat": cat, "ph": "X", "ts": t0,
                  "dur": self._now_us() - t0, "pid": os.getpid(),
                  "tid": threading.get_ident()}
            if args or error:
                ev["args"] = dict(args or {})
                if error:
                    ev["args"]["error"] = error
            with self._lock:
                self.events.append(ev)

    def instant(self, name: str, cat: str = "sweep",
                args: dict | None = None) -> None:
        ev = {"name": name, "cat": cat, "ph": "i", "ts": self._now_us(),
              "s": "p", "pid": os.getpid(), "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        with self._lock:
            self.events.append(ev)

    def to_json(self) -> dict:
        with self._lock:
            evs = sorted(self.events, key=lambda e: e["ts"])
        return {"traceEvents": evs, "displayTimeUnit": "ms"}

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f)


def current_tracer() -> Tracer | None:
    return _tracer


def _profiling():
    """The profiler's annotation type while a ``jax.profiler`` session is
    active, else None.  Without jax imported no session can be active,
    so a process that never imports jax never imports it here."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation if _annotation.is_enabled() else None


def span(name: str, cat: str = "sweep", args: dict | None = None):
    """Span against the installed tracer and, while a ``jax.profiler``
    session is active, the profiler's trace; a shared no-op context
    when neither is on.  ``args`` is read when the span closes, so the
    body may fill in what it learns (``_pick_bucket`` does)."""
    t = _tracer
    ann = _profiling()
    if ann is None:
        return t.span(name, cat, args) if t is not None else _NULL
    return _annotated(t, ann, name, cat, args)


@contextlib.contextmanager
def _annotated(t, ann, name, cat, args):
    a = ann(name)
    with a, (t.span(name, cat, args) if t is not None else _NULL):
        try:
            yield
        finally:
            if args:
                a.set_metadata(**args)


@contextlib.contextmanager
def tracing(path: str | None = None):
    """Install a global :class:`Tracer` for the ``with`` body; write the
    trace JSON to ``path`` on exit (even on error).  Yields the tracer.
    Nested ``tracing`` regions are refused — spans are process-global."""
    global _tracer
    t = Tracer()
    with _lock:
        if _tracer is not None:
            raise RuntimeError("a tracer is already installed")
        _tracer = t
    try:
        yield t
    finally:
        with _lock:
            _tracer = None
        if path is not None:
            t.save(path)


def validate_trace(doc) -> list[str]:
    """Schema check for a loaded (or stringified) trace document.

    Returns a list of problems (empty == valid):
      * top level is an object bearing a ``traceEvents`` list,
      * every event has name/ph/ts/pid/tid; ``X`` events have numeric
        ``dur >= 0``,
      * ``B``/``E`` events are properly nested per (pid, tid),
      * event ``ts`` are monotone non-decreasing in file order.
    """
    problems: list[str] = []
    if isinstance(doc, (str, bytes)):
        try:
            doc = json.loads(doc)
        except json.JSONDecodeError as e:
            return [f"not valid JSON: {e}"]
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["top level must be an object with a traceEvents list"]
    evs = doc["traceEvents"]
    if not isinstance(evs, list):
        return ["traceEvents must be a list"]
    stacks: dict[tuple, list[str]] = {}
    last_ts = None
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not an object")
            continue
        missing = [k for k in ("name", "ph", "ts", "pid", "tid")
                   if k not in ev]
        if missing:
            problems.append(f"event {i}: missing {missing}")
            continue
        ts = ev["ts"]
        if not isinstance(ts, (int, float)):
            problems.append(f"event {i}: non-numeric ts")
            continue
        if last_ts is not None and ts < last_ts:
            problems.append(f"event {i}: ts not monotone "
                            f"({ts} < {last_ts})")
        last_ts = ts
        ph = ev["ph"]
        key = (ev["pid"], ev["tid"])
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                problems.append(f"event {i}: X event needs dur >= 0")
        elif ph == "B":
            stacks.setdefault(key, []).append(ev["name"])
        elif ph == "E":
            stack = stacks.setdefault(key, [])
            if not stack:
                problems.append(f"event {i}: E without matching B")
            else:
                stack.pop()
    for key, stack in stacks.items():
        if stack:
            problems.append(f"unclosed B events on {key}: {stack}")
    return problems
