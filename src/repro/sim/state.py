"""Device-resident simulation state (the scan engine's slot table).

The host-loop engines (``repro.sim.engine`` / ``engine_ref``) keep
cluster state in NumPy and pay a device round-trip per tick — every
``ShapeProblem`` field is a fresh ``device_put``, every forecast window
a host->device copy.  This module holds the SAME padded slot table as a
pytree of ``jnp`` arrays so the fused per-tick step (``repro.sim.step``)
can run whole tick *chunks* on device with host sync only at chunk
boundaries:

  * :class:`DeviceTrace` — the immutable workload columns (arrival
    times, reservations, utilization profiles), uploaded once per run;
  * :class:`SimState`    — everything that evolves per tick: the cluster
    slot table, monitor rings, FIFO-queue membership, per-app telemetry
    and (optionally) the conformal-calibration rings
    (:class:`~repro.core.uncertainty.online.CalibState`);
  * :class:`TickMetrics` — the per-tick scan outputs (``lax.scan`` ys)
    drained to the host at chunk boundaries;
  * :func:`drain_results` — folds final state + stacked metrics back
    into the engines' :class:`~repro.sim.metrics.SimResults`.

Both dataclasses are registered pytrees, so a whole seed cohort is just
``vmap`` over a stacked state (every array gains a leading seed axis and
one batched device program executes the cohort).  The sharded fleet
executor (``repro.sim.step.run_fleet_shard`` / ``repro.sim.shard``)
reuses the same stacked layout: the leading cohort axis becomes the
``shard_map`` mesh axis, padded up to a multiple of the mesh size
(:func:`round_up` / ``from_traces(..., pad_to=...)``) so every device
holds an equal slice.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.control import TenantState, control_init, tenancy_summary
from repro.core.uncertainty.online import (CalibState, calib_group_report,
                                           calib_init, calib_report)
from repro.obs.rings import ObsState, obs_init
from repro.sim.metrics import SimResults

Array = jax.Array

CPU, MEM = 0, 1


def round_up(n: int, multiple: int) -> int:
    """Smallest multiple of ``multiple`` that is >= ``n`` (mesh padding:
    a sharded fleet axis must divide evenly across the mesh devices)."""
    return -(-n // multiple) * multiple


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DeviceTrace:
    """Immutable workload columns on device (one upload per run).

    Mirrors :class:`~repro.sim.scenarios.schema.Trace`; ``exists`` is
    precomputed (``cpu_req > 0``) because every tick needs it.
    """

    submit: Array     # (N,) f32 nondecreasing arrival times
    runtime: Array    # (N,) f32 base runtime
    cpu_req: Array    # (N, C) f32 per-component reservation
    mem_req: Array    # (N, C) f32
    is_core: Array    # (N, C) bool
    is_jumpy: Array   # (N,) bool — step-change (unlearnable) profiles
    levels: Array     # (N, C, SEGMENTS, 2) f32 utilization knots
    exists: Array     # (N, C) bool == cpu_req > 0
    tenant: Array     # (N,) i32 owning tenant (all zero when untagged)
    gid: Array        # (N,) i32 global app id — row index for a fully
    #                   materialized trace; the streamed engine re-keys
    #                   window rows so gid keeps the submission-order
    #                   identity a row had in the full trace

    @classmethod
    def from_trace(cls, wl) -> "DeviceTrace":
        n = len(np.asarray(wl.submit))
        return cls(
            submit=jnp.asarray(wl.submit, jnp.float32),
            runtime=jnp.asarray(wl.runtime, jnp.float32),
            cpu_req=jnp.asarray(wl.cpu_req, jnp.float32),
            mem_req=jnp.asarray(wl.mem_req, jnp.float32),
            is_core=jnp.asarray(wl.is_core, bool),
            is_jumpy=jnp.asarray(wl.is_jumpy, bool),
            levels=jnp.asarray(wl.levels, jnp.float32),
            exists=jnp.asarray(wl.cpu_req > 0, bool),
            tenant=jnp.asarray(wl.tenant, jnp.int32),
            gid=jnp.arange(n, dtype=jnp.int32))

    @classmethod
    def from_traces(cls, wls, pad_to: int | None = None) -> "DeviceTrace":
        """Stacked cohort trace, (S, ...) per field — stacked on the
        host in one pass (one upload per field, not one per seed).

        ``pad_to`` rounds the cohort axis up by repeating the LAST trace
        (sharded fleets need the axis divisible by the mesh size; the
        padding rows simulate a real workload whose results the driver
        simply discards, so no phase needs a validity mask)."""
        wls = list(wls)
        if pad_to is not None:
            if pad_to < len(wls):
                raise ValueError(f"pad_to={pad_to} < cohort size {len(wls)}")
            wls = wls + [wls[-1]] * (pad_to - len(wls))
        col = lambda f, dt: jnp.asarray(  # noqa: E731
            np.stack([np.asarray(f(w), dt) for w in wls]))
        return cls(
            submit=col(lambda w: w.submit, np.float32),
            runtime=col(lambda w: w.runtime, np.float32),
            cpu_req=col(lambda w: w.cpu_req, np.float32),
            mem_req=col(lambda w: w.mem_req, np.float32),
            is_core=col(lambda w: w.is_core, bool),
            is_jumpy=col(lambda w: w.is_jumpy, bool),
            levels=col(lambda w: w.levels, np.float32),
            exists=col(lambda w: w.cpu_req > 0, bool),
            tenant=col(lambda w: w.tenant, np.int32),
            gid=col(lambda w: np.arange(len(np.asarray(w.submit))),
                    np.int32))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SimState:
    """Everything that evolves per tick, as one pytree of device arrays.

    A = slot-table apps, C = components, N = trace apps, W = monitor
    window.  Monitor rows are flat ``slot * C + comp`` exactly like the
    host :class:`~repro.core.monitor.Monitor`, so forecast-batch row ids
    (CPU rows then MEM rows) are identical across engines.
    """

    # cluster slot table
    slot_gid: Array       # (A,) i32, -1 = empty
    work_done: Array      # (A,) f32
    comp_running: Array   # (A, C) bool
    comp_host: Array      # (A, C) i32
    alloc: Array          # (A, C, 2) f32
    alive_since: Array    # (A, C) f32
    # monitor rings
    mon_buf: Array        # (A*C, W, 2) f32, oldest first
    mon_count: Array      # (A*C,) i32 samples seen per row
    # application lifecycle (FIFO queue is the `queued` mask: order is
    # derived, (submit0, gid) ascending — exactly bisect.insort's key)
    arrived: Array        # (N,) bool
    queued: Array         # (N,) bool
    done: Array           # (N,) bool
    failed: Array         # (N,) bool — ever OOM/conflict-failed
    finish_t: Array       # (N,) f32 completion time (0 until done)
    saved_work: Array     # (N,) f32 checkpointed progress
    has_saved: Array      # (N,) bool
    # counters / clock
    t: Array              # () f32 sim time (exact multiple of tick)
    failure_events: Array      # () i32
    oom_kills: Array           # () i32
    full_preemptions: Array    # () i32
    partial_preemptions: Array # () i32
    # conformal calibration rings (None when calibration is off — the
    # step function is specialized per config, so presence is static)
    calib: CalibState | None
    # tenant accounting (None when the control plane is off — same
    # static-presence convention, so tenancy-off programs are
    # structurally identical to pre-control-plane ones)
    tenancy: TenantState | None
    # per-tick telemetry rings (None when observability is off — same
    # static-presence convention again: obs-off programs are
    # bit-identical to pre-observability engines)
    obs: ObsState | None


def init_state(cfg, n_apps: int, max_components: int,
               batch: int | None = None) -> SimState:
    """Fresh device state for one simulation of ``cfg``.

    ``batch`` prepends a seed-cohort axis to every field (a fresh state
    is identical across seeds, so the stacked cohort state is built
    directly — no per-seed init + stack round trips)."""
    A = cfg.cluster.max_running_apps
    C = max_components
    N = n_apps
    W = cfg.window
    B = () if batch is None else (batch,)
    zi = lambda *s: jnp.zeros(B + s, jnp.int32)    # noqa: E731
    zf = lambda *s: jnp.zeros(B + s, jnp.float32)  # noqa: E731
    zb = lambda *s: jnp.zeros(B + s, bool)         # noqa: E731
    tenancy = None
    if cfg.control.enabled:
        tenancy = control_init(cfg.control, batch=batch)
    calib = None
    if cfg.calibration.enabled and cfg.forecaster != "oracle":
        calib = calib_init(2 * A * C, cfg.calibration, batch=batch,
                           n_groups=(cfg.control.max_tenants
                                     if cfg.control.enabled else 0))
    obs = (obs_init(cfg.obs, batch=batch, leap=cfg.leap)
           if cfg.obs.enabled else None)
    return SimState(
        slot_gid=jnp.full(B + (A,), -1, jnp.int32),
        work_done=zf(A), comp_running=zb(A, C), comp_host=zi(A, C),
        alloc=zf(A, C, 2), alive_since=zf(A, C),
        mon_buf=zf(A * C, W, 2), mon_count=zi(A * C),
        arrived=zb(N), queued=zb(N), done=zb(N), failed=zb(N),
        finish_t=zf(N), saved_work=zf(N), has_saved=zb(N),
        t=zf(),
        failure_events=zi(), oom_kills=zi(), full_preemptions=zi(),
        partial_preemptions=zi(), calib=calib, tenancy=tenancy, obs=obs)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TickMetrics:
    """Per-tick scan outputs; ``valid`` masks post-completion padding
    ticks (the step body is a no-op once every app is done, so chunk
    size cannot change results — only when telemetry is drained).

    Raw usage/allocation SUMS, not ratios: utilization and slack divide
    on the host at drain time.  XLA is free to rewrite a division by a
    loop-invariant constant (e.g. into a reciprocal multiply) depending
    on how the scan unrolls, which would make the last ulp of a ratio
    depend on the chunk size — the sums themselves are chunk-stable."""

    valid: Array       # () bool — this tick actually executed
    n_running: Array   # () i32
    used_cpu: Array    # () f32 cluster-total instantaneous usage
    used_mem: Array    # () f32
    alloc_cpu: Array   # () f32 cluster-total committed allocation
    alloc_mem: Array   # () f32
    # forecast-load telemetry: rows past the grace period this tick (the
    # rows a compacting forecaster would NEED; the full-batch scan path
    # computes the whole padded batch, so ready/batch is the masked-rows
    # overhead the bucketed path exists to close)
    forecast_rows: Array  # () i32
    # rows OFFERED to the forecast model this tick: the full padded
    # batch when it ran un-bucketed, this member's passes x bucket batch
    # under ragged bucketing, 0 for persist/oracle (no model call)
    forecast_rows_done: Array  # () i32
    # rows of the TILES the model's tile loop ran (forecast.base.TILE_ROWS
    # each): only tiles that hold ready rows run, packed across the whole
    # fold, so under a cohort vmap the member with the most passes holds
    # the cohort's total.  Under the fleet's shard_map each device folds
    # only its own members: there the count is that device's total, and
    # the fleet's is the sum of the devices' maxima.  0 for
    # persist/oracle/baseline
    forecast_tile_rows: Array  # () i32
    # event-leap telemetry: provably-idle ticks the leap engine skipped
    # immediately BEFORE this step's tick (always 0 under the uniform
    # engine).  drain_results re-expands each step into `lead` all-zero
    # ticks followed by the executed tick, so leap histories are
    # bit-identical to uniform ones.
    lead: Array        # () i32


def drain_results(cfg, wl, state: SimState, metrics: TickMetrics,
                  obs: dict | None = None) -> SimResults:
    """Fold final device state + stacked per-tick metrics (leading axis
    = ticks, already concatenated across chunks) into ``SimResults``.

    ``obs`` is one member's drained ring history (``field -> (T,)``)
    from :class:`repro.obs.rings.RingDrain` — attached verbatim to
    ``SimResults.obs`` (and, like ``forecast_rows``, excluded from
    ``summary()`` so telemetry can never perturb equivalence checks)."""
    res = SimResults(n_apps=int(wl.n_apps))
    valid = np.asarray(metrics.valid)
    # Re-expand leap steps into per-tick histories: each step stands for
    # `lead` skipped idle ticks (all-zero telemetry by the leap guard —
    # empty cluster, empty queue, quiescent calibration) followed by one
    # executed tick when `valid`.  Under the uniform engine lead == 0
    # everywhere and this reduces to plain valid-masking, so the two
    # modes produce bit-identical results.
    lead = np.asarray(metrics.lead, np.int64)
    reps = lead + valid.astype(np.int64)
    pos = np.cumsum(reps) - 1
    T = int(reps.sum())

    def expand(x):
        x = np.asarray(x)
        out = np.zeros(T, x.dtype)
        out[pos[valid]] = x[valid]
        return out

    res.n_running = [int(v) for v in expand(metrics.n_running)]
    H = cfg.cluster.n_hosts
    cap_cpu = np.float32(H) * np.float32(cfg.cluster.host_cpu)
    cap_mem = np.float32(H) * np.float32(cfg.cluster.host_mem)
    used_c = expand(metrics.used_cpu)
    used_m = expand(metrics.used_mem)
    alloc_c = expand(metrics.alloc_cpu)
    alloc_m = expand(metrics.alloc_mem)
    res.util_cpu = list(used_c / cap_cpu)
    res.util_mem = list(used_m / cap_mem)
    res.slack_cpu = [float((a - u) / a) if a > 0 else 0.0
                     for a, u in zip(alloc_c, used_c)]
    res.slack_mem = [float((a - u) / a) if a > 0 else 0.0
                     for a, u in zip(alloc_m, used_m)]

    done = np.asarray(state.done)
    # float32 subtraction: the host engines compute `t - submit` in
    # float32 (NEP 50 scalar promotion), and turnaround should not
    # depend on which engine produced it
    finish = np.asarray(state.finish_t, np.float32)
    submit0 = np.asarray(wl.submit, np.float32)
    for gid in np.nonzero(done)[0]:
        res.turnaround[int(gid)] = float(finish[gid] - submit0[gid])
    res.failed_apps = {int(g) for g in np.nonzero(np.asarray(state.failed))[0]}
    # forecast-load telemetry (scan-engine only; see TickMetrics): how
    # many rows were ready vs the full padded batch the program computes
    if cfg.policy != "baseline" and cfg.forecaster != "oracle":
        rows = expand(metrics.forecast_rows)
        AC = state.mon_count.shape[-1]
        res.forecast_rows = {
            "rows_ready": int(rows.sum()),
            "rows_batch": 2 * AC,
            "rows_bucketed": int(expand(metrics.forecast_rows_done).sum()),
            "ticks_forecasting": int((rows > 0).sum()),
            "ticks": T,
        }
    if obs is not None:
        res.obs = obs
    res.failure_events = int(state.failure_events)
    res.oom_kills = int(state.oom_kills)
    res.full_preemptions = int(state.full_preemptions)
    res.partial_preemptions = int(state.partial_preemptions)
    if state.calib is not None:
        res.calibration = calib_report(state.calib, cfg.calibration)
        gb = calib_group_report(state.calib, cfg.calibration)
        if gb is not None:
            res.calibration["groups"] = gb
    if state.tenancy is not None:
        ten = state.tenancy
        res.tenancy = tenancy_summary(
            cfg.control, wl, res.turnaround, res.failed_apps,
            dict(credit=np.asarray(ten.credit),
                 admitted=np.asarray(ten.admitted),
                 throttled=np.asarray(ten.throttled),
                 completed=np.asarray(ten.completed),
                 failed=np.asarray(ten.failed),
                 share_sum=np.asarray(ten.share_sum),
                 active_ticks=np.asarray(ten.active_ticks)))
    res.finalize(float(state.t))
    return res
