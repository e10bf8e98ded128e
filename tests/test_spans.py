"""The program's spans on the profiler's clock and the tick's named
phase scopes.

``repro.obs.span`` reaches a ``jax.profiler`` trace whenever a session is
active, and an installed obs ``Tracer`` besides; with neither it is the
shared no-op.  The chunk driver marks ``sim.pick_bucket``,
``sim.chunk_launch`` (every call of the chunk function) and
``sim.chunk_wait`` (the read of ``done``).  ``fused_tick`` names each
phase with a ``jax.named_scope``, which reaches the compiled program's
op_name metadata as ``tick/<phase>`` (the forecast's model call as
``tick/shape/forecast``).
"""
import dataclasses
import re
import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import REGISTRY, Tracer, span, tracing
from repro.obs import trace as obs_trace
from repro.sim import ClusterConfig, SimConfig, WorkloadConfig, generate
from repro.sim import step
from repro.sim.state import init_state

WL = WorkloadConfig(n_apps=24, max_components=4, max_runtime=900.0,
                    mean_burst_gap=4.0, mean_long_gap=60.0, seed=5)
BASE = SimConfig(cluster=ClusterConfig(n_hosts=2, max_running_apps=8),
                 workload=WL, max_ticks=64, policy="baseline",
                 forecaster="persist")
GP = dataclasses.replace(BASE, policy="pessimistic", forecaster="gp")
PHASES = ("arrivals", "completions", "monitor", "forecast", "shape", "oom",
          "tenancy", "admit", "elastic", "metrics", "leap")
SCOPE = re.compile(r'op_name="[^"]*?(?:^|/)tick/([^"]*)"')


def _phases(hlo_text: str) -> set:
    """The innermost ``tick/<phase>[/<phase>]`` of each op_name: the
    forecast runs under ``tick/shape/forecast``."""
    out = set()
    for path in SCOPE.findall(hlo_text):
        inner = None
        for part in path.split("/"):
            if part not in PHASES:
                break
            inner = part
        out.add(inner)
    return out - {None}


def _host_events(trace_dir) -> list:
    """(name, start_ns, duration_ns, stats) of every host event of the
    newest profiler trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    path = max(Path(trace_dir).rglob("*.xplane.pb"),
               key=lambda p: p.stat().st_mtime)
    data = ProfileData.from_file(str(path))
    return [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def _cohort(cfg, seeds=(1, 2)):
    wls = [generate(dataclasses.replace(cfg.workload, seed=s))
           for s in seeds]
    tr = step._device_trace(wls, batched=True)
    st = init_state(cfg, wls[0].n_apps, wls[0].max_components,
                    batch=len(seeds))
    return wls, tr, st


# ----------------------------------------------------------------------
# span: profiler, tracer, neither
# ----------------------------------------------------------------------

def test_driver_spans_reach_the_profiler_and_the_tracer(tmp_path):
    """Under a CPU profiler trace with an obs Tracer installed: the
    bucket pick, every chunk launch (with its args), every wait for
    ``done``, and the first call's compile land on the profiler's host
    plane and in the tracer's events."""
    # a cluster no other test builds, so the chunk program compiles here
    cfg = dataclasses.replace(
        BASE, cluster=ClusterConfig(n_hosts=3, max_running_apps=7))
    _, _, st_gp = _cohort(dataclasses.replace(
        GP, cluster=ClusterConfig(n_hosts=3, max_running_apps=7)))
    with tracing() as tracer:
        jax.profiler.start_trace(str(tmp_path))
        try:
            step._pick_bucket(GP, st_gp)
            res = step.run_cohort_scan(cfg, [1, 2], chunk=16)
        finally:
            jax.profiler.stop_trace()
    assert all(r.summary() for r in res)
    host = _host_events(tmp_path)
    names = [e[0] for e in host]
    for name in ("sim.pick_bucket", "sim.chunk_launch", "sim.chunk_wait",
                 "jit_compile"):
        assert name in names, name
    launches = [e for e in host if e[0] == "sim.chunk_launch"]
    waits = [e for e in host if e[0] == "sim.chunk_wait"]
    assert len(launches) == len(waits) >= 2
    assert launches[0][3] == {"bucket": "None", "ticks": 16, "members": 2,
                              "n": 1}
    assert [e[3]["n"] for e in launches] == [e[3]["n"] for e in waits]
    for launch, wait in zip(launches, waits):
        assert wait[1] >= launch[1] + launch[2]     # the wait follows its launch
    pick = next(e for e in host if e[0] == "sim.pick_bucket")
    assert pick[3] == {"bucket": 8, "ready": 0, "members": 2}
    # the obs tracer saw the same spans, with the same args
    evs = {e["name"]: e for e in tracer.events}
    for name in ("sim.pick_bucket", "sim.chunk_launch", "sim.chunk_wait",
                 "jit_compile"):
        assert name in evs, name
    assert evs["sim.pick_bucket"]["args"] == {"bucket": 8, "ready": 0,
                                              "members": 2}
    assert sum(e["name"] == "sim.chunk_launch"
               for e in tracer.events) == len(launches)


def test_span_with_neither_tracer_nor_profiler_records_nothing():
    assert obs_trace.current_tracer() is None
    assert obs_trace._profiling() is None
    args = {"k": 1}
    cm = span("idle", cat="test", args=args)
    assert cm is obs_trace._NULL              # the shared no-op
    with cm:
        pass
    assert args == {"k": 1}
    t = Tracer()                              # not installed: untouched
    with span("idle"):
        pass
    assert t.events == []


def test_span_args_are_read_when_it_closes(tmp_path):
    """A body may fill in its args; both the tracer and the profiler see
    what it filled in."""
    with tracing() as tracer:
        jax.profiler.start_trace(str(tmp_path))
        try:
            args = {}
            with span("late", cat="test", args=args):
                args["bucket"] = 16
        finally:
            jax.profiler.stop_trace()
    assert [e["args"] for e in tracer.events if e["name"] == "late"] == [
        {"bucket": 16}]
    assert [e[3] for e in _host_events(tmp_path) if e[0] == "late"] == [
        {"bucket": 16}]


def test_pick_bucket_leaves_no_registry_series():
    _, _, st = _cohort(GP)
    before = set(REGISTRY.snapshot())
    assert step._pick_bucket(GP, st) == 8
    assert not {k for k in set(REGISTRY.snapshot()) - before
                if k.startswith("forecast.")}


# ----------------------------------------------------------------------
# the chunk driver's wait
# ----------------------------------------------------------------------

class _SlowDone:
    """A ``done`` column whose copy to the host takes 30 ms."""

    def __init__(self):
        self.read_at = None

    def __array__(self, dtype=None, copy=None):
        time.sleep(0.03)
        self.read_at = time.perf_counter_ns()
        return np.ones((2,), bool)


def test_chunk_wait_closes_after_done_is_on_the_host():
    done = _SlowDone()

    def fn(tr, st):
        fn.launches += 1
        return types.SimpleNamespace(done=done, obs=None), None
    fn.launches = 0

    st0 = types.SimpleNamespace(done=None, obs=None)
    with tracing() as tracer:
        step._drive_chunks(BASE, 32, lambda size, bucket: fn, None, st0)
    (wait,) = [e for e in tracer.events if e["name"] == "sim.chunk_wait"]
    assert wait["args"] == {"n": 1}
    end_ns = tracer._epoch + (wait["ts"] + wait["dur"]) * 1e3
    assert wait["dur"] >= 30_000                   # us: it held the read
    assert end_ns >= done.read_at - 1_000          # and closed after it


# ----------------------------------------------------------------------
# the tick's named phases in the compiled program
# ----------------------------------------------------------------------

@pytest.mark.parametrize("variant,phases", [
    ("baseline", {"arrivals", "completions", "monitor", "oom", "admit",
                  "elastic", "metrics"}),
    ("gp-bucketed", {"arrivals", "completions", "monitor", "forecast",
                     "shape", "oom", "admit", "elastic", "metrics"}),
    ("baseline-leap", {"arrivals", "completions", "monitor", "oom",
                       "admit", "elastic", "metrics", "leap"}),
])
def test_compiled_chunk_program_names_each_phase(variant, phases):
    cfg = {"baseline": BASE, "gp-bucketed": GP,
           "baseline-leap": dataclasses.replace(BASE, leap=True)}[variant]
    wls, tr, st = _cohort(cfg)
    bucket = 8 if variant == "gp-bucketed" else None
    fn = step._chunk_fn(cfg, 2, step._shapes_key(wls[0], cfg), True, bucket)
    args = (tr, st) + ((jnp.full((2,), 64, jnp.int32),) if cfg.leap else ())
    text = fn.__wrapped__.lower(*args).compile().as_text()
    assert text.startswith("HloModule jit_tick_chunk")
    assert _phases(text) == phases
