"""The system under test, as the benchmark drives it.

This is the only module that imports the program.  It binds to the scan
engine's chunk step and chunk driver, which are private today:

* ``repro.sim.step._chunk_fn(cfg, 32, shapes, cohort=True, bucket)``, the
  compiled step of a seed cohort (one chip), and
  ``repro.sim.step._shard_chunk_fn(cfg, 32, shapes, mesh, bucket)``, the
  same step over a fleet laid across a device mesh (four chips);
* ``repro.sim.step._pick_bucket``, the forecast bucket the chunk driver
  ``_drive_chunks`` picks at every chunk boundary;
* ``repro.sim.state.init_state`` and ``DeviceTrace``, the state and the
  trace columns the step reads.

The loop below is ``_drive_chunks``'s own (pick the bucket, launch the
chunk, read ``done``), run until a wall-clock budget is spent instead of
until every application is done.  The step donates its state, so a state
that is launched is never read again.
"""
from __future__ import annotations

import concurrent.futures as cf
import contextlib
import dataclasses
import time

import jax
import numpy as np

CHUNK = 32          # ticks per device call: run_grid's default


def sim_config(spec: dict):
    """The program's ``SimConfig`` for a configuration file's ``sim``."""
    from repro.core.forecast.gp import GPConfig
    from repro.core.shaper.safeguard import SafeguardConfig
    from repro.sim import SimConfig
    from repro.sim.cluster import ClusterConfig

    s = spec["sim"]
    cfg = SimConfig(policy=s["policy"], forecaster=s["forecaster"],
                    cluster=ClusterConfig(**s["cluster"]),
                    window=s["window"], grace=s["grace"], horizon=s["horizon"],
                    gp=GPConfig(**s["gp"]),
                    safeguard=SafeguardConfig(**s["safeguard"]),
                    work_lost_on_kill=s["work_lost_on_kill"], leap=s["leap"],
                    forecast_bucket=s["forecast_bucket"])
    for key in ("calibration", "control", "obs"):
        if bool(s[key]) != bool(getattr(cfg, key).enabled):
            raise ValueError(f"{spec['name']}: {key} must be {s[key]}")
    return cfg


def buckets(cfg, slots: int) -> list:
    """Every forecast bucket the chunk driver can pick for this table and
    that can be built.  The full-table program (``None``, picked where more
    than half the table is ready) is left out of a forecasting cohort: its
    forecast sits under a ``lax.cond`` that the cohort's ``vmap`` batches,
    and the tiled forecast's ``custom_vmap`` rule then fails to trace
    (``AssertionError`` in ``jax._src.custom_batching``).  A window that
    picks it stops there."""
    from repro.sim import step as S
    if not S._bucketed(cfg):
        return [None]
    out, b = [], S._BUCKET_MIN
    while b < slots:
        out.append(b)
        b *= 2
    return out


@dataclasses.dataclass
class Sim:
    cfg: object
    tr: object            # DeviceTrace, placed
    st: object            # SimState, placed
    fns: dict             # bucket -> compiled chunk step
    bucketing: bool

    def pick(self):
        from repro.sim import step as S
        return S._pick_bucket(self.cfg, self.st) if self.bucketing else None


class Compiles:
    """Counts the compile requests JAX makes (a program built, from the
    persistent cache or by the compiler)."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.n += 1


def _placement(devices: list):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

    from repro.sim import step as S
    if len(devices) == 1:
        return None, SingleDeviceSharding(devices[0])
    mesh = Mesh(np.array(devices), (S.FLEET_AXIS,))
    return mesh, NamedSharding(mesh, PartitionSpec(S.FLEET_AXIS))


def upload(cfg, traces: list, devices: list):
    """The cohort's stacked trace and its fresh state, on ``devices``."""
    from repro.sim.state import DeviceTrace, init_state

    mesh, sharding = _placement(devices)
    if len(traces) % len(devices):
        raise ValueError(f"{len(traces)} members do not divide over "
                         f"{len(devices)} devices")
    col = lambda f, dt: np.stack([np.asarray(f(w), dt) for w in traces])  # noqa: E731
    host = DeviceTrace(
        submit=col(lambda w: w.submit, np.float32),
        runtime=col(lambda w: w.runtime, np.float32),
        cpu_req=col(lambda w: w.cpu_req, np.float32),
        mem_req=col(lambda w: w.mem_req, np.float32),
        is_core=col(lambda w: w.is_core, bool),
        is_jumpy=col(lambda w: w.is_jumpy, bool),
        levels=col(lambda w: w.levels, np.float32),
        exists=col(lambda w: w.cpu_req > 0, bool),
        tenant=col(lambda w: w.tenant, np.int32),
        gid=col(lambda w: np.arange(len(w.submit)), np.int32))
    tr = jax.device_put(host, sharding)
    n, c = traces[0].n_apps, traces[0].max_components
    st = jax.jit(lambda: init_state(cfg, n, c, batch=len(traces)),
                 out_shardings=sharding)()
    return mesh, tr, st


def build(cfg, traces: list, devices: list) -> Sim:
    from repro.sim import step as S

    mesh, tr, st = upload(cfg, traces, devices)
    shapes = S._shapes_key(traces[0], cfg)
    slots = cfg.cluster.max_running_apps * traces[0].max_components
    if mesh is None:
        fns = {b: S._chunk_fn(cfg, CHUNK, shapes, True, b) for b in buckets(cfg, slots)}
    else:
        fns = {b: S._shard_chunk_fn(cfg, CHUNK, shapes, mesh, b)
               for b in buckets(cfg, slots)}
    return Sim(cfg, tr, st, fns, S._bucketed(cfg))


def compile_all(sim: Sim) -> float:
    """Build every chunk program at once (a cold compile of a GP program
    takes tens of seconds; warm, each is a cache load).  Returns seconds."""
    t0 = time.perf_counter()

    def one(fn):
        fn.__wrapped__.lower(sim.tr, sim.st).compile()

    with cf.ThreadPoolExecutor(len(sim.fns)) as ex:
        for f in [ex.submit(one, fn) for fn in sim.fns.values()]:
            f.result()
    return time.perf_counter() - t0


def advance(sim: Sim, ticks: int) -> float:
    """Drive the cohort ``ticks`` ticks from where it stands (warm-up)."""
    t0 = time.perf_counter()
    for _ in range(-(-ticks // CHUNK)):
        sim.st, _ = sim.fns[sim.pick()](sim.tr, sim.st)
        np.asarray(sim.st.done)
    return time.perf_counter() - t0


@dataclasses.dataclass
class Window:
    seconds: float = 0.0           # window start to the last chunk's ready point,
                                   # less the check's snapshots (``paused``)
    chunks: list = dataclasses.field(default_factory=list)   # per chunk, below
    metrics: list = dataclasses.field(default_factory=list)  # TickMetrics per chunk
    snaps: dict = dataclasses.field(default_factory=dict)    # chunk index -> host state
    compiles: int = 0
    done_before: int = 0           # applications done when the window opened
    paused: float = 0.0            # seconds the check's snapshots took inside it


def host_state(st) -> dict:
    """A state on the host, as a dict of NumPy arrays."""
    st = jax.device_get(st)
    return {f.name: getattr(st, f.name) for f in dataclasses.fields(st)
            if getattr(st, f.name) is not None}


class Tracer:
    """Profiles a stretch of the window, once a third of it and at least
    ``FIRST`` chunks have run without the profiler.  Where a chunk is
    short, about ``seconds`` of whole chunks.  Where it is long, a slice of
    ``seconds`` around one chunk boundary, ``LEAD`` of it before the
    boundary, timed by earlier chunks of the same bucket: the profiler's
    buffer holds about 1.2 s of a forecasting chunk's operations (some 6
    million), and writing them out takes about 30 s a million; the
    device can idle only at a boundary, and the ticks before it show the
    tick's period (``trace.chunk_device``).  A ``bench.traced`` span marks what was
    traced; the harness's host actions are marked too."""

    FIRST = 3
    LEAD = 0.9

    def __init__(self, trace_dir, seconds: float):
        self.dir, self.seconds = trace_dir, seconds
        self.whole = None            # decided from a clean chunk period
        self.n = 0
        self.on = self.done = False
        self._span = None

    def plan(self, period_s: float) -> None:
        self.whole = 2 * period_s <= self.seconds
        self.n = max(1, int(round(self.seconds / period_s))) if self.whole else 1

    def start(self) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # the harness's own spans suffice
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench.traced")
        self._span.__enter__()
        self.on = True

    def stop(self) -> None:
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on, self.done = False, True


def window(sim: Sim, seconds: float, snap_at=(), compiles: Compiles | None = None,
           tracer: Tracer | None = None) -> Window:
    """Run whole chunks until ``seconds`` have passed; the last one is
    waited for.  Before chunk k (for k in ``snap_at``) and after the last,
    the state is copied to the host for the check.  The copies inside the
    window run while the device waits between chunks, and the window's
    clock stops for them: every time below is on that clock.  ``tracer``
    profiles a stretch."""
    w = Window()
    n0 = compiles.n if compiles else 0
    ann = (jax.profiler.TraceAnnotation if tracer else
           (lambda name: contextlib.nullcontext()))
    w.done_before = int(np.asarray(sim.st.done).sum())
    t0 = time.perf_counter()

    def now() -> float:
        return time.perf_counter() - t0 - w.paused

    k = planned = 0
    while True:
        if (tracer and tracer.whole is None and k >= Tracer.FIRST
                and now() >= seconds / 3):
            tracer.plan(float(np.median(np.diff([c["ready"] for c in w.chunks]))))
            planned = k
            if tracer.whole:
                tracer.start()
        if k in snap_at:
            p = time.perf_counter()
            w.snaps[k] = host_state(sim.st)
            w.paused += time.perf_counter() - p
        a = now()
        with ann("bench.bucket_pick"):
            b = sim.pick()
        if b not in sim.fns:
            raise RuntimeError(f"the chunk driver picked bucket {b}, whose program "
                               "cannot be built (see buckets())")
        with ann("bench.launch"):
            sim.st, ms = sim.fns[b](sim.tr, sim.st)
        launched = now()
        traced = bool(tracer and tracer.on)
        if tracer and tracer.whole is False and not tracer.done:
            if not tracer.on:
                # start LEAD of a slice before this chunk ends, as timed by
                # earlier chunks of its bucket (their work is the same);
                # a chunk of a bucket not seen yet is left to run
                same = [c["ready"] - p["ready"] for p, c in zip(w.chunks, w.chunks[1:])
                        if c["bucket"] == b]
                if same or k - planned >= Tracer.FIRST:
                    period = float(np.median(same or np.diff([c["ready"] for c in w.chunks])))
                    end = w.chunks[-1]["ready"] + period
                    time.sleep(max(0.0, end - Tracer.LEAD * tracer.seconds - now()))
                    tracer.start()
                    traced = True
            else:                        # stop the rest of it after the next launch
                time.sleep((1 - Tracer.LEAD) * tracer.seconds)
                tracer.stop()
        with ann("bench.done_read"):
            np.asarray(sim.st.done)
        ready = now()
        w.metrics.append(ms)
        w.chunks.append({"bucket": b, "start": a, "launched": launched,
                         "ready": ready, "traced": traced})
        k += 1
        if tracer and tracer.whole and tracer.on and sum(c["traced"] for c in w.chunks) >= tracer.n:
            tracer.stop()
        if ready >= seconds and not (tracer and not tracer.done):
            break
    w.seconds = ready
    w.compiles = (compiles.n - n0) if compiles else 0
    w.snaps[k] = host_state(sim.st)
    return w


def tick_rows(w: Window) -> dict:
    """The window's per-tick totals on the host, (members, ticks) each."""
    host = [jax.device_get(m) for m in w.metrics]
    return {f.name: np.concatenate([getattr(m, f.name) for m in host], axis=1)
            for f in dataclasses.fields(host[0])}
