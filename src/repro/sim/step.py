"""Fused device-resident tick: the scan engine (paper §4 at fleet scale).

One tick of the simulation — progress -> monitor sample -> forecast ->
safeguard / conformal scale -> shaping policy (Algorithm 1) -> OS OOM ->
FIFO admission — as ONE traced function over the device state pytree
(:mod:`repro.sim.state`), driven by ``lax.scan`` over tick *chunks*.
The host-loop engines pay per tick: ~10 jitted dispatches, a dozen
``device_put`` s for the ``ShapeProblem``, and NumPy re-marshalling of
the slot table.  Here a whole chunk of ticks is one XLA call; the host
syncs only at chunk boundaries (metrics drain + termination check).

Semantics follow ``repro.sim.engine`` phase for phase.  Two deliberate
deviations mean the scan engine is not bit-identical to the host
engines: floating-point *accumulation order* (NumPy pairwise /
sequential sums vs XLA reductions), and the Algorithm-1 FIFO order on
EXACTLY tied submit times (the host engines' ``np.argsort`` is
unstable; here ``jnp.argsort`` is stable, breaking ties by slot index
— relevant only to replay traces with identical timestamps, since
generated arrival processes are tie-free).  The correctness anchors
are instead:

  * CHUNK INVARIANCE — results are independent of ``chunk`` by
    construction: everything that affects dynamics lives inside the
    step; ticks after global completion are no-ops (``active`` gating),
    so chunk=1 and chunk=32 are bit-identical;
  * COHORT EQUIVALENCE — a ``vmap`` over the seed axis executes a whole
    seed cohort as one batched program, bit-identical per seed to its
    solo run (XLA CPU reductions are batch-invariant; enforced by
    ``tests/test_scan_engine.py``);
  * the host ``engine`` <-> frozen ``engine_ref`` bit-equivalence
    remains separately enforced (``tests/test_sweep.py``).

Event-driven inner loops (admission, elastic re-placement, OOM victim
selection) are ``lax.while_loop`` s whose trip counts equal the number
of actual events — not O(slots x components) per tick.

Fleet scale: :func:`run_fleet_shard` lays the stacked cohort axis
across a JAX device mesh with ``shard_map`` (one SPMD program, no
collectives — sims are independent), adding a third anchor on top of
the two above: ``shard(mesh=1)`` is bit-identical to the cohort scan,
and any larger mesh is bit-identical per member to ``mesh=1``.  The
sweep-level executor that groups grid cells into fleets lives in
:mod:`repro.sim.shard`.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.control.credit import credit_quantile, credit_step
from repro.control.device import credit_mean, device_weights
from repro.control.fairness import dominant_shares, gate_mask
from repro.core.forecast.base import peak_over_horizon, persistence_peak
from repro.core.shaper import RAW_POLICIES, ShapeProblem
from repro.core.shaper.safeguard import (shaped_demand_raw,
                                         shaped_demand_scaled_raw)
from repro.core.uncertainty.online import (calib_begin, calib_observe,
                                           calib_scales)
from repro.kernels.fixed_order import onehot_sum, tree_sum
from repro.obs import REGISTRY, span
from repro.obs.rings import RING_FIELDS, RingDrain, obs_record
from repro.sim.metrics import SimResults
from repro.sim.state import (CPU, MEM, DeviceTrace, SimState, TickMetrics,
                             drain_results, init_state, round_up)

Array = jax.Array

__all__ = ["fused_tick", "fused_leap", "run_sim_scan", "run_cohort_scan",
           "run_fleet_shard", "FLEET_AXIS"]

# mesh axis name for sharded fleets (repro.sim.shard lays grid cells x
# seed cohorts along this axis)
FLEET_AXIS = "fleet"

SEGMENTS_AXIS = 2  # levels layout (N, C, SEGMENTS, 2)


# ----------------------------------------------------------------------
# small pure helpers over the slot table
# ----------------------------------------------------------------------

def _progress_rate(tr: DeviceTrace, st: SimState) -> Array:
    """(A,) work/second: (1 + running elastic) / (1 + n_elastic) when the
    full core set runs, 0 otherwise (mirrors ``Cluster.progress_rate``)."""
    run = st.slot_gid >= 0
    gid = jnp.maximum(st.slot_gid, 0)
    is_core = tr.is_core[gid]
    exists = tr.exists[gid]
    core_ok = ((is_core & st.comp_running).sum(1) == is_core.sum(1))
    n_el = (exists & ~is_core).sum(1)
    n_run_el = (st.comp_running & ~is_core).sum(1)
    rate = core_ok * (1.0 + n_run_el) / (1.0 + n_el)
    return jnp.where(run, rate, 0.0).astype(jnp.float32)


def _usage_at(tr: DeviceTrace, st: SimState, prog: Array) -> Array:
    """(A, C, 2) usage of running components at per-slot progress
    ``prog`` (mirrors ``Trace.usage`` + ``Cluster.usage_now``)."""
    S = tr.levels.shape[SEGMENTS_AXIS]
    C = tr.levels.shape[1]
    gid = jnp.maximum(st.slot_gid, 0)
    x = jnp.clip(prog, 0.0, 1.0) * (S - 1)
    s0 = jnp.minimum(x.astype(jnp.int32), S - 2)
    frac = (x - s0).astype(jnp.float32)
    # single fused gather of the two knots actually needed — NOT
    # levels[gid] (which would materialize the full (A, C, S, 2) table
    # every tick, ~10x the bytes of the result)
    comps = jnp.arange(C)[None, :]
    lv0 = tr.levels[gid[:, None], comps, s0[:, None]]      # (A, C, 2)
    lv1 = tr.levels[gid[:, None], comps, s0[:, None] + 1]
    out = lv0 + (lv1 - lv0) * frac[:, None, None]
    out = jnp.where(tr.is_jumpy[gid][:, None, None], lv0, out)
    req = jnp.stack([tr.cpu_req[gid], tr.mem_req[gid]], axis=-1)
    run = (st.slot_gid >= 0)[:, None] & st.comp_running
    return out * req * run[:, :, None]


def _free_resources(st: SimState, host_cap: Array) -> Array:
    """(H, 2) capacity minus committed allocations.

    Broadcast masked sum, not a scatter-add — this runs inside the
    admission while_loop and XLA CPU scatters stay serial under vmap."""
    H = host_cap.shape[0]
    live = st.comp_running.reshape(-1)
    host = st.comp_host.reshape(-1)
    mask = live[:, None] & (host[:, None] == jnp.arange(H)[None, :])
    used = tree_sum(jnp.where(mask[:, :, None],
                              st.alloc.reshape(-1, 2)[:, None, :], 0.0), 0)
    return host_cap - used


def _mon_reset(st: SimState, rows_mask: Array) -> SimState:
    """Zero monitor rings for flat rows where ``rows_mask``.

    Called ONCE per tick with the union of every phase's resets
    (completion, preemption, OOM, admission): within a tick the rings
    are only read in the shaping phase, and every resetting event makes
    the affected rows non-running there — so deferring the writes to the
    end of the tick is observation-equivalent and saves three full
    ring-buffer passes per tick."""
    buf = jnp.where(rows_mask[:, None, None], 0.0, st.mon_buf)
    cnt = jnp.where(rows_mask, 0, st.mon_count)
    return dataclasses.replace(st, mon_buf=buf, mon_count=cnt)


def _evict_slots(st: SimState, slots_mask: Array) -> SimState:
    """Batched ``Cluster.evict_apps`` over a boolean slot mask."""
    m = slots_mask
    return dataclasses.replace(
        st,
        slot_gid=jnp.where(m, -1, st.slot_gid),
        comp_running=st.comp_running & ~m[:, None],
        alloc=jnp.where(m[:, None, None], 0.0, st.alloc),
        work_done=jnp.where(m, 0.0, st.work_done))


def _tenant_counts(tenant: Array, mask: Array, T: int) -> Array:
    """(T,) i32 count of masked apps per tenant (one-hot reduction —
    the control plane's scatter-free ``np.add.at``)."""
    return ((tenant[:, None] == jnp.arange(T)[None, :])
            & mask[:, None]).sum(0).astype(jnp.int32)


def _worst_fit(free: Array, cpu: Array, mem: Array) -> tuple[Array, Array]:
    """Most-free-memory host among those fitting (cpu, mem); returns
    (host, fits) — host is garbage when nothing fits."""
    ok = (free[:, CPU] >= cpu) & (free[:, MEM] >= mem)
    h = jnp.argmax(jnp.where(ok, free[:, MEM], -jnp.inf))
    return h, ok.any()


# ----------------------------------------------------------------------
# tick phases
# ----------------------------------------------------------------------

def _completions(tr: DeviceTrace, st: SimState, t: Array,
                 tick: float) -> tuple[SimState, Array]:
    """Progress all slots one tick; evict finished apps.  Returns the
    monitor rows to reset (applied once at end of tick)."""
    C = st.comp_running.shape[1]
    N = tr.submit.shape[0]
    rate = _progress_rate(tr, st)
    work = st.work_done + rate * tick
    st = dataclasses.replace(st, work_done=work)
    run = st.slot_gid >= 0
    gid = jnp.maximum(st.slot_gid, 0)
    fin = run & (work >= tr.runtime[gid])
    # slot -> app scatter as a one-hot mask (vmap-friendly; each app
    # occupies at most one slot, so the reduction has one nonzero)
    fin_app = ((jnp.arange(N)[None, :] == gid[:, None])
               & fin[:, None]).any(0)
    done = st.done | fin_app
    finish_t = jnp.where(fin_app, jnp.maximum(st.finish_t, t), st.finish_t)
    st = _evict_slots(st, fin)
    return (dataclasses.replace(st, done=done, finish_t=finish_t),
            jnp.repeat(fin, C))


def _record_monitor(st: SimState, usage: Array) -> SimState:
    """Append one sample per running component (flat-row ring update)."""
    AC = st.mon_buf.shape[0]
    run = (st.slot_gid >= 0)[:, None] & st.comp_running
    m = run.reshape(AC)
    new = usage.reshape(AC, 2)
    shifted = jnp.concatenate([st.mon_buf[:, 1:], new[:, None, :]], axis=1)
    buf = jnp.where(m[:, None, None], shifted, st.mon_buf)
    cnt = st.mon_count + m
    return dataclasses.replace(st, mon_buf=buf, mon_count=cnt)


def _cluster_total(x: Array) -> Array:
    """Sum an (A, C, ...) table over slots and components in one fixed
    order of f32 adds (``kernels.fixed_order``): a solo program and a
    vmapped cohort or fleet one must read the same bits."""
    return tree_sum(tree_sum(x, 1), 0)


def _oracle_peaks(tr: DeviceTrace, st: SimState, horizon: int,
                  tick: float) -> Array:
    """(A, C, 2) true future peak usage over the horizon (variance 0)."""
    rate = _progress_rate(tr, st)
    gid = jnp.maximum(st.slot_gid, 0)
    peaks = jnp.zeros_like(st.alloc)
    for k in range(1, horizon + 1):
        prog = jnp.clip((st.work_done + rate * tick * k) / tr.runtime[gid],
                        0.0, 1.0)
        peaks = jnp.maximum(peaks, _usage_at(tr, st, prog))
    return peaks


def _bucketed_forecast(cfg, model, wins: Array, valid: Array,
                       ready: Array, bucket: int):
    """gp/arima forecast over the READY monitor rows only, in
    power-of-two buckets of ``bucket`` rows per resource (the model
    batch is ``2 * bucket``: CPU rows stacked over MEM rows, exactly
    like the full-batch path).

    Static shapes under jit forbid true compaction, so the ready rows
    are compacted by a stable argsort and consumed in
    ``ceil(n_ready / bucket)`` gather -> model -> scatter-back passes of
    one ``lax.while_loop`` — zero passes on idle ticks, and within-chunk
    ready growth past the driver's chunk-boundary bucket choice is
    absorbed by extra passes, never wrong results.  Per-row model
    independence (the property ``engine.forecast_peaks`` documents as
    "bit-identical across bucket sizes") makes every ready row's
    (mean, var) bit-identical to the full-batch path; non-ready rows
    come back 0, which downstream masking (``ready2`` in
    ``_shaped_demands``, ``deploy`` in ``calib_begin``) never reads.
    The scatter-back is a one-hot masked sum, not ``.at[].set`` — XLA
    CPU scatters serialize under the cohort vmap.  The pass loop runs
    under the tick's ``forecast`` scope.

    Each pass OFFERS the model ``2 * bucket`` rows and marks the ready
    ones ``live``; the model's tile loop RUNS only the tiles that hold
    live rows, packed across the whole fold (``forecast.base.packed``):
    under the cohort ``vmap``, a member past its last pass and the
    padding of every pass add no tiles.

    Returns (mean, var, n_pass, tile_rows): mean and var over the full
    ``(2 * AC,)`` row space; ``n_pass * 2 * bucket`` is the rows the
    member's passes offered (the ``rows_bucketed`` telemetry),
    ``tile_rows`` the rows of the tiles its passes ran for the fold.
    """
    AC = ready.shape[0]
    B = bucket
    # ready rows first (stable argsort), padded up to a multiple of B
    # with out-of-range sentinels so dynamic_slice never clamps a pass
    # start back over rows an earlier pass already wrote
    order = jnp.argsort(~ready).astype(jnp.int32)
    pad = round_up(AC, B) - AC
    if pad:
        order = jnp.concatenate([order, jnp.zeros((pad,), jnp.int32)])
    n_ready = ready.sum().astype(jnp.int32)
    cols = jnp.arange(AC)

    def cond(carry):
        return carry[0] * B < n_ready

    def body(carry):
        p, mean, var, tiles = carry
        idx = jax.lax.dynamic_slice(order, (p * B,), (B,))
        in_pass = (jnp.arange(B) + p * B) < n_ready
        w2 = jnp.concatenate([wins[idx], wins[idx + AC]])
        v2 = jnp.concatenate([valid[idx], valid[idx + AC]])
        fc, rows = model.forecast_packed(
            w2, cfg.horizon, valid=v2,
            live=jnp.concatenate([in_pass, in_pass]))
        peak, pvar = peak_over_horizon(fc)
        peak = peak.astype(jnp.float32)
        pvar = pvar.astype(jnp.float32)
        # one-hot scatter-back: each ready row appears in exactly one
        # pass (argsort is a permutation; the sentinel tail is masked),
        # so each output element is one value plus exact zeros.  A
        # masked select-and-sum, not a one-hot matmul: a TPU's default
        # f32 matmul would round the values to bf16
        oh = (idx[:, None] == cols[None, :]) & in_pass[:, None]  # (B, AC)

        def back(x):
            return onehot_sum(oh, x[:, None], 0)

        mean = mean + jnp.concatenate([back(peak[:B]), back(peak[B:])])
        var = var + jnp.concatenate([back(pvar[:B]), back(pvar[B:])])
        return p + 1, mean, var, tiles + rows

    z = jnp.zeros((2 * AC,), jnp.float32)
    with jax.named_scope("forecast"):
        n_pass, mean, var, tiles = jax.lax.while_loop(
            cond, body, (jnp.int32(0), z, z, jnp.int32(0)))
    return mean, var, n_pass, tiles


def _shaped_demands(cfg, model, tr: DeviceTrace, st: SimState,
                    tick: float, bucket: int | None = None
                    ) -> tuple[Array, SimState, Array, Array, Array]:
    """(A, C, 2) shaped demand table, updated calib state, the number of
    forecast rows actually past the grace period this tick, the rows
    offered to the forecast model for them, and the rows of the tiles
    its tile loop ran (both 0 for persist/oracle).

    Mirrors ``engine._shape_decisions``'s demand construction: running
    components default to their reservation; components past the grace
    period get ``clip(peak + beta, 0, request)`` — with the conformal
    per-series scale replacing K2 when calibration is on.  The model
    call runs under a ``forecast`` scope, nested in the tick's
    ``shape``."""
    A, C = st.comp_running.shape
    AC = A * C
    gid = jnp.maximum(st.slot_gid, 0)
    run = (st.slot_gid >= 0)[:, None] & st.comp_running       # (A, C)
    req = jnp.stack([tr.cpu_req[gid], tr.mem_req[gid]], axis=-1)
    demand = jnp.where(run[:, :, None], req, 0.0)

    if cfg.forecaster == "oracle":
        with jax.named_scope("forecast"):
            peaks = _oracle_peaks(tr, st, cfg.horizon, tick)
        shaped = shaped_demand_raw(peaks, req, jnp.zeros_like(peaks),
                                   cfg.safeguard)
        return (jnp.where(run[:, :, None], shaped, demand), st,
                jnp.int32(0), jnp.int32(0), jnp.int32(0))

    # forecast over the monitor rows (CPU rows then MEM rows); rows not
    # past the grace period are masked out of the demand afterwards.
    # Shapes are static under jit, so per-row compaction needs the
    # bucketed path: with ``bucket`` set (the driver's per-chunk choice)
    # the gp/arima model runs only over the ready rows, in
    # ceil(ready / bucket) passes of a fixed-shape batch.  Un-bucketed,
    # the model is offered the whole table with the ready rows ``live``:
    # its tile loop runs only the tiles that hold them (none during
    # warm-up/grace ticks and after global completion, solo or under a
    # cohort vmap).  The ready/offered gap either way is what the
    # ``forecast_rows`` telemetry measures (the gp block of
    # BENCH_engine.json).
    W = st.mon_buf.shape[1]
    ready = run.reshape(AC) & (st.mon_count >= cfg.grace)
    wins = jnp.concatenate([st.mon_buf[:, :, CPU], st.mon_buf[:, :, MEM]])
    age = jnp.arange(W)[None, :]
    vrow = age >= (W - jnp.minimum(st.mon_count, W))[:, None]
    valid = jnp.concatenate([vrow, vrow])
    fc_done = fc_tiles = jnp.int32(0)
    if cfg.forecaster == "persist":
        with jax.named_scope("forecast"):
            mean, var = persistence_peak(wins, valid)
    elif bucket is not None:
        mean, var, n_pass, fc_tiles = _bucketed_forecast(
            cfg, model, wins, valid, ready, bucket)
        fc_done = n_pass * jnp.int32(2 * bucket)
    else:
        with jax.named_scope("forecast"):
            fc, fc_tiles = model.forecast_packed(
                wins, cfg.horizon, valid=valid,
                live=jnp.concatenate([ready, ready]))
            mean, var = peak_over_horizon(fc)
            mean, var = mean.astype(jnp.float32), var.astype(jnp.float32)
        fc_done = jnp.where(ready.any(), jnp.int32(2 * AC), jnp.int32(0))

    req_rows = jnp.concatenate([req[:, :, CPU].reshape(AC),
                                req[:, :, MEM].reshape(AC)])
    if st.calib is None:
        shaped = shaped_demand_raw(mean, req_rows, var, cfg.safeguard)
        calib = st.calib
    else:
        # per-tenant tier (control plane on): rows map to the tenant
        # owning the slot (-1 for empty slots); with credit enabled the
        # target quantile is the tenant's credit-modulated level —
        # computed from the CURRENT (previous tick's) credit, exactly
        # like the host engine reads q_groups before its gate update
        groups = q_rows = q_groups = None
        if st.tenancy is not None and st.calib.group is not None:
            tslot = jnp.where(st.slot_gid >= 0, tr.tenant[gid], -1)
            g1 = jnp.repeat(tslot, C).astype(jnp.int32)
            groups = jnp.concatenate([g1, g1])
            if cfg.control.credit:
                qt = credit_quantile(st.tenancy.credit, st.calib.q,
                                     cfg.control.q_spread,
                                     cfg.calibration.q_min,
                                     cfg.calibration.q_max)
                q_rows = jnp.where(groups >= 0,
                                   qt[jnp.maximum(groups, 0)], st.calib.q)
                q_groups = qt
        scale = calib_scales(st.calib, cfg.calibration, cfg.safeguard.k2,
                             groups=groups, q_rows=q_rows,
                             q_groups=q_groups)
        shaped = shaped_demand_scaled_raw(
            mean, req_rows, var, jnp.float32(cfg.safeguard.k1), scale)
        sigma = jnp.sqrt(jnp.maximum(var, 0.0)).astype(jnp.float32)
        ready2 = jnp.concatenate([ready, ready])
        calib = calib_begin(st.calib, ready2, mean.astype(jnp.float32),
                            sigma, scale.astype(jnp.float32),
                            jnp.tile(st.mon_count, 2), cfg.horizon,
                            groups=groups)
    st = dataclasses.replace(st, calib=calib)

    ready2 = jnp.concatenate([ready, ready])
    rows = jnp.where(ready2, shaped, 0.0)
    shaped_tbl = jnp.stack([rows[:AC].reshape(A, C),
                            rows[AC:].reshape(A, C)], axis=-1)
    ready_tbl = ready.reshape(A, C)
    fc_rows = 2 * ready.sum().astype(jnp.int32)
    return (jnp.where(ready_tbl[:, :, None], shaped_tbl, demand), st,
            fc_rows, fc_done, fc_tiles)


def _shape_problem(cfg, tr: DeviceTrace, st: SimState, demand: Array,
                   t: Array, host_cap: Array) -> ShapeProblem:
    A = st.slot_gid.shape[0]
    gid = jnp.maximum(st.slot_gid, 0)
    app_exists = st.slot_gid >= 0
    n_run = app_exists.sum()
    key = tr.submit[gid] + jnp.where(app_exists, 0.0, 1e18)
    fifo = jnp.argsort(key)
    order = jnp.where(jnp.arange(A) < n_run, fifo, -1)
    return ShapeProblem(
        host_cpu=host_cap[:, CPU], host_mem=host_cap[:, MEM],
        app_exists=app_exists, app_order=order,
        comp_exists=st.comp_running,
        comp_core=tr.is_core[gid] & app_exists[:, None],
        comp_host=st.comp_host,
        comp_cpu=demand[:, :, CPU], comp_mem=demand[:, :, MEM],
        comp_alive=t - st.alive_since)


def _apply_decision(cfg, tr: DeviceTrace, st: SimState, dec,
                    usage: Array) -> tuple[SimState, Array, Array, Array]:
    """Kills + resizes from a ShapeDecision.  Returns (state, usage,
    conflict_failed, monitor_resets) — ``conflict_failed`` the
    optimistic policy's uncontrolled failures (per-app gid mask)."""
    A, C = st.comp_running.shape
    exists = st.slot_gid >= 0
    gid = jnp.maximum(st.slot_gid, 0)

    N = tr.submit.shape[0]
    kills = dec.kill_app & exists                              # (A,)
    n_kills = kills.sum()
    slot_of = (jnp.arange(N)[None, :] == gid[:, None]) & kills[:, None]
    kgids_mask = slot_of.any(0)                                # (N,)
    if not cfg.work_lost_on_kill:
        saved = jnp.where(
            kgids_mask,
            onehot_sum(slot_of, st.work_done[:, None], 0),
            st.saved_work)
        has = st.has_saved | kgids_mask
        st = dataclasses.replace(st, saved_work=saved, has_saved=has)
    usage = jnp.where(kills[:, None, None], 0.0, usage)
    if cfg.policy == "optimistic":
        # optimistic-concurrency conflict: an UNCONTROLLED failure
        conflict = kgids_mask
        st = dataclasses.replace(
            st, failure_events=st.failure_events + n_kills.astype(jnp.int32))
    else:
        conflict = jnp.zeros_like(kgids_mask)
        st = dataclasses.replace(
            st, queued=st.queued | kgids_mask,
            full_preemptions=st.full_preemptions + n_kills.astype(jnp.int32))
    st = _evict_slots(st, kills)

    kc = dec.kill_comp & exists[:, None] & st.comp_running     # (A, C)
    usage = jnp.where(kc[:, :, None], 0.0, usage)
    st = dataclasses.replace(
        st,
        comp_running=st.comp_running & ~kc,
        partial_preemptions=(st.partial_preemptions
                             + kc.sum().astype(jnp.int32)))

    live = st.comp_running
    alloc = jnp.stack([jnp.where(live, dec.alloc_cpu, 0.0),
                       jnp.where(live, dec.alloc_mem, 0.0)], axis=-1)
    st = dataclasses.replace(st, alloc=alloc)
    resets = jnp.repeat(kills, C) | kc.reshape(-1)
    return st, usage, conflict, resets


def _resolve_oom(tr: DeviceTrace, st: SimState, usage: Array,
                 host_cap: Array):
    """OS OOM handler (mirrors ``Cluster.resolve_oom``): for every host
    over memory capacity at entry, kill components by descending
    (usage - allocation) overage until the host fits.  One
    ``lax.while_loop`` whose trip count is H + number of kills."""
    A, C = st.comp_running.shape
    H = host_cap.shape[0]
    N = tr.submit.shape[0]
    on_host = (st.comp_running.reshape(-1)[:, None]
               & (st.comp_host.reshape(-1)[:, None]
                  == jnp.arange(H)[None, :]))             # (A*C, H)
    over0 = (tree_sum(jnp.where(on_host,
                                usage[:, :, MEM].reshape(-1)[:, None], 0.0), 0)
             > host_cap[:, MEM] + 1e-6)
    # victims are running at selection time, so their gid (and coreness)
    # cannot have changed since loop entry — gather the tables once
    gid0 = jnp.maximum(st.slot_gid, 0)
    core_tbl = tr.is_core[gid0].reshape(-1)                 # (A*C,)
    gid_tbl = gid0.repeat(C)                                # (A*C,)
    cap_mem = host_cap[:, MEM]

    def cond(carry):
        return carry[0] < H

    def body(carry):
        (h, usage, slot_gid, comp_running, alloc, work_done,
         failed, queued, monreset, oom_kills, fevents, partials) = carry
        on_h = comp_running & (st.comp_host == h)
        mem = usage[:, :, MEM]
        tot = _cluster_total(jnp.where(on_h, mem, 0.0))
        oh = jnp.arange(H) == h
        need = (jnp.where(oh, over0, False).any() & on_h.any()
                & (tot > onehot_sum(oh, cap_mem) + 1e-6))

        over = jnp.where(on_h, mem - alloc[:, :, MEM], -jnp.inf)
        flat = over.reshape(-1)
        # seed tie-break: largest overage, then largest (slot, comp)
        vic = (A * C - 1) - jnp.argmax(flat[::-1] == flat.max())
        ovic = jnp.arange(A * C) == vic                     # one-hot
        core = (ovic & core_tbl).any()
        vgid_oh = ((jnp.arange(N)[None, :] == gid_tbl[:, None])
                   & ovic[:, None]).any(0)                  # (N,) one-hot
        full = need & core
        part = need & ~core

        rowm = full & (ovic.reshape(A, C).any(1))           # (A,)
        killm = rowm[:, None] | (part & ovic.reshape(A, C))
        usage = jnp.where(killm[:, :, None], 0.0, usage)
        comp_running = comp_running & ~killm
        alloc = jnp.where(killm[:, :, None], 0.0, alloc)
        slot_gid = jnp.where(rowm, -1, slot_gid)
        work_done = jnp.where(rowm, 0.0, work_done)
        failed = failed | (full & vgid_oh)
        queued = queued | (full & vgid_oh)
        monreset = monreset | (part & ovic)
        oom_kills = oom_kills + full
        fevents = fevents + full
        partials = partials + part
        h = h + jnp.where(need, 0, 1)
        return (h, usage, slot_gid, comp_running, alloc, work_done,
                failed, queued, monreset, oom_kills, fevents, partials)

    # start past the last host when none is over capacity: the common
    # (healthy) tick pays only the over0 reduction, not H loop bodies
    h0 = jnp.where(over0.any(), jnp.int32(0), jnp.int32(H))
    init = (h0, usage, st.slot_gid, st.comp_running, st.alloc,
            st.work_done, st.failed, st.queued,
            jnp.zeros((A * C,), bool), jnp.int32(0), jnp.int32(0),
            jnp.int32(0))
    (_, usage, slot_gid, comp_running, alloc, work_done, failed, queued,
     monreset, oom_kills, fevents, partials) = jax.lax.while_loop(
        cond, body, init)
    st = dataclasses.replace(
        st, slot_gid=slot_gid, comp_running=comp_running, alloc=alloc,
        work_done=work_done, failed=failed, queued=queued,
        oom_kills=st.oom_kills + oom_kills,
        failure_events=st.failure_events + fevents,
        partial_preemptions=st.partial_preemptions + partials)
    return st, usage, monreset


def _admit_queued(cfg, tr: DeviceTrace, st: SimState, t: Array,
                  host_cap: Array,
                  elig_app: Array | None = None) -> tuple[SimState, Array]:
    """FIFO admission: pop (submit0, gid)-ascending heads while they
    admit (all core components must fit, worst-fit placement) — the
    engine's scheduler loop as an event-bounded ``while_loop``.
    ``elig_app`` (control plane, (N,) bool) restricts head selection to
    apps of gate-eligible tenants; ineligible entries stay queued.
    Returns (state, monitor rows to reset)."""
    A, C = st.comp_running.shape
    N = tr.submit.shape[0]

    H = host_cap.shape[0]

    def try_place(cur, gid):
        """Sequential worst-fit of app ``gid``'s components (core pass
        then elastic pass, mirroring ``Cluster.admit``).  Scans run over
        the component COLUMNS (no per-step gathers) and free updates are
        one-hot masked (no scatters) — both vmap cleanly."""
        cpu, mem = tr.cpu_req[gid], tr.mem_req[gid]      # (C,)
        needed = tr.exists[gid]
        core = needed & tr.is_core[gid]
        free0 = _free_resources(cur, host_cap)

        def core_step(carry, x):
            free, ok = carry
            cpu_c, mem_c, core_c = x
            h, fits = _worst_fit(free, cpu_c, mem_c)
            commit = core_c & fits & ok
            ok = ok & (~core_c | fits)
            oh = (jnp.arange(H) == h) & commit
            free = free - jnp.where(oh[:, None],
                                    jnp.stack([cpu_c, mem_c]), 0.0)
            return (free, ok), (h, commit)

        (free, ok), (h_core, c_core) = jax.lax.scan(
            core_step, (free0, jnp.bool_(True)), (cpu, mem, core),
            unroll=True)

        def el_step(carry, x):
            free = carry
            cpu_c, mem_c, el_c = x
            h, fits = _worst_fit(free, cpu_c, mem_c)
            commit = el_c & fits & ok
            oh = (jnp.arange(H) == h) & commit
            free = free - jnp.where(oh[:, None],
                                    jnp.stack([cpu_c, mem_c]), 0.0)
            return free, (h, commit)

        free, (h_el, c_el) = jax.lax.scan(
            el_step, free, (cpu, mem, needed & ~core), unroll=True)
        placement = jnp.where(
            c_core, h_core,
            jnp.where(c_el, h_el, -1)).astype(jnp.int32)
        return ok, placement

    def cond(carry):
        return carry[2]

    def _q(queued):
        return queued if elig_app is None else queued & elig_app

    def body(carry):
        cur, resets, _ = carry
        qm = _q(cur.queued)
        has_q = qm.any()
        # FIFO head: earliest submit, ties broken by global app id so
        # admission order is independent of a row's position in the
        # table (materialized traces have gid == row index, so this is
        # bit-identical to the plain argmin; the streamed engine re-keys
        # window rows and relies on the gid tie-break)
        smin = jnp.min(jnp.where(qm, tr.submit, jnp.inf))
        tied = qm & (tr.submit == smin)
        head = jnp.argmin(jnp.where(tied, tr.gid, jnp.iinfo(jnp.int32).max))
        empty = cur.slot_gid < 0
        slot = jnp.argmax(empty)
        fits, placement = try_place(cur, head)
        ok = has_q & empty.any() & fits

        placed = placement >= 0
        ogid = (jnp.arange(N) == head) & ok          # one-hot app
        if cfg.work_lost_on_kill:
            resume = jnp.float32(0.0)
        else:   # preempt-to-checkpoint: resume from the saved progress
            resume = jnp.where((ogid & cur.has_saved).any(),
                               onehot_sum(ogid, cur.saved_work),
                               0.0)
        osl = (jnp.arange(A) == slot) & ok           # one-hot slot
        row = lambda x, new: jnp.where(  # noqa: E731
            osl.reshape((A,) + (1,) * (x.ndim - 1)), new, x)
        nxt = dataclasses.replace(
            cur,
            slot_gid=row(cur.slot_gid, head.astype(jnp.int32)),
            work_done=row(cur.work_done, resume),
            comp_running=row(cur.comp_running, placed[None, :]),
            comp_host=row(cur.comp_host, jnp.maximum(placement, 0)[None, :]),
            alloc=row(cur.alloc,
                      jnp.where(placed[:, None],
                                jnp.stack([tr.cpu_req[head],
                                           tr.mem_req[head]], -1),
                                0.0)[None]),
            alive_since=row(cur.alive_since, t),
            queued=cur.queued & ~ogid,
            has_saved=cur.has_saved & ~ogid)
        resets = resets | jnp.repeat(osl, C)
        cont = ok & _q(nxt.queued).any() & (nxt.slot_gid < 0).any()
        return nxt, resets, cont

    # no empty slot (saturated cluster) => the head cannot admit: skip
    # the whole loop instead of paying one doomed placement attempt
    cont0 = _q(st.queued).any() & (st.slot_gid < 0).any()
    st, resets, _ = jax.lax.while_loop(
        cond, body, (st, jnp.zeros((A * C,), bool), cont0))
    return st, resets


def _place_missing_elastic(tr: DeviceTrace, st: SimState, t: Array,
                           host_cap: Array) -> SimState:
    """Best-effort re-placement of missing elastic components, walked in
    row-major (slot, component) order over the entry snapshot — an
    event-bounded ``while_loop`` over the actually-missing set."""
    A, C = st.comp_running.shape
    gid = jnp.maximum(st.slot_gid, 0)
    missing = ((st.slot_gid >= 0)[:, None] & tr.exists[gid]
               & ~tr.is_core[gid] & ~st.comp_running).reshape(-1)
    n_miss = missing.sum()

    H = host_cap.shape[0]
    req_cpu, req_mem = tr.cpu_req[gid], tr.mem_req[gid]    # (A, C)

    def place(st):
        # ascending flat indices, missing entries first (stable argsort)
        order = jnp.argsort(~missing)
        free0 = _free_resources(st, host_cap)

        def cond(carry):
            return carry[0] < n_miss

        def body(carry):
            i, free, comp_running, comp_host, alloc, alive = carry
            oe = jnp.arange(A * C) == order[i]
            m2 = oe.reshape(A, C)                          # one-hot (A, C)
            cpu = onehot_sum(m2, req_cpu)
            mem = onehot_sum(m2, req_mem)
            h, fits = _worst_fit(free, cpu, mem)
            oh = (jnp.arange(H) == h) & fits
            free = free - jnp.where(oh[:, None],
                                    jnp.stack([cpu, mem]), 0.0)
            m2f = m2 & fits
            comp_running = comp_running | m2f
            comp_host = jnp.where(m2f, h.astype(jnp.int32), comp_host)
            alloc = jnp.where(m2f[:, :, None],
                              jnp.stack([cpu, mem]), alloc)
            alive = jnp.where(m2f, t, alive)
            return i + 1, free, comp_running, comp_host, alloc, alive

        (_, _, comp_running, comp_host, alloc, alive) = jax.lax.while_loop(
            cond, body, (jnp.int32(0), free0, st.comp_running,
                         st.comp_host, st.alloc, st.alive_since))
        return dataclasses.replace(st, comp_running=comp_running,
                                   comp_host=comp_host, alloc=alloc,
                                   alive_since=alive)

    # most ticks have nothing missing: skip the sort + free computation
    return jax.lax.cond(n_miss > 0, place, lambda s: s, st)


# ----------------------------------------------------------------------
# the fused tick
# ----------------------------------------------------------------------

def fused_tick(cfg, model, tr: DeviceTrace, st: SimState,
               bucket: int | None = None,
               lead: Array | None = None) -> tuple[SimState, TickMetrics]:
    """One simulation tick as a pure function (cfg and model static).

    Phase order is exactly ``engine.run_sim``'s loop body; the whole
    body is gated on ``active`` (some app unfinished AND the tick budget
    not exhausted) so post-completion scan padding is a no-op.

    ``bucket`` (static, from the driver's per-chunk choice) routes the
    gp/arima forecast through :func:`_bucketed_forecast`; ``lead`` is
    the leap engine's skipped-idle-tick count for this step, threaded
    into the tick's telemetry (metrics + obs ring) so histories can be
    re-expanded on the host.
    """
    A, C = st.comp_running.shape
    H = cfg.cluster.n_hosts
    tick = cfg.cluster.tick
    host_cap = jnp.stack(
        [jnp.full((H,), cfg.cluster.host_cpu, jnp.float32),
         jnp.full((H,), cfg.cluster.host_mem, jnp.float32)], axis=-1)

    # Post-completion scan padding is a NATURAL no-op: with every app
    # done there are no running slots, no queue, no arrivals and no
    # outstanding calibration predictions, so every phase below mutates
    # nothing — only the clock needs explicit gating.  (The max_ticks
    # budget is enforced by the driver slicing the last chunk exactly,
    # so a truncated sim never executes ticks past its budget either.)
    #
    # Each phase runs under a ``jax.named_scope`` (``tick/<phase>`` in
    # the compiled program's op_name metadata, which a profiler trace
    # of the device attributes its operations by; the innermost phase
    # counts, as `forecast` nests in `shape`); scopes change only
    # metadata, never the program's results.
    rec = st.obs is not None
    ctl = st.tenancy is not None          # static pytree-structure branch
    with jax.named_scope("tick"):
        # telemetry rings (repro.obs): static pytree-structure branch
        # like `ctl`.  Entry-of-tick counter snapshots turn the
        # cumulative counters into per-tick DELTAS — raw sums only, never
        # ratios, so the rings stay chunk-invariant (see ObsState).
        if rec:
            with jax.named_scope("metrics"):
                oom0, fail0 = st.oom_kills, st.failure_events
                pre0 = st.full_preemptions + st.partial_preemptions
                cres0 = st.calib.resolved if st.calib is not None else None
                cerr0 = st.calib.errors if st.calib is not None else None
                obs_dem = None                   # shaped-demand sums, (2,)
                obs_throttled = jnp.int32(0)
                obs_credit = jnp.float32(0.0)

        # 1. arrivals, with the tick's clock and its `active` gate
        with jax.named_scope("arrivals"):
            active = ~st.done.all()
            t_prev = st.t
            t = st.t + jnp.float32(tick)
            new = ~st.arrived & (tr.submit <= t)
            st = dataclasses.replace(st, arrived=st.arrived | new,
                                     queued=st.queued | new)

        # 2. progress + completions (monitor resets accumulate across
        # phases and apply once at end of tick — see _mon_reset)
        with jax.named_scope("completions"):
            done_before = st.done
            st, resets = _completions(tr, st, t, tick)

            # control-plane event accounting (mirrors HostControl.note_*):
            # good events are completions + covered conformal
            # resolutions, bad events are failures + miscoverage;
            # `fail_t` tracks failures alone for the per-tenant failed
            # counter.
            if ctl:
                Tn = cfg.control.max_tenants
                comp_t = _tenant_counts(tr.tenant, st.done & ~done_before, Tn)
                good_t = comp_t
                bad_t = jnp.zeros((Tn,), jnp.int32)
                fail_t = jnp.zeros((Tn,), jnp.int32)

        # 3. monitor sampling
        with jax.named_scope("monitor"):
            gid = jnp.maximum(st.slot_gid, 0)
            prog = jnp.clip(st.work_done / tr.runtime[gid], 0.0, 1.0)
            usage = _usage_at(tr, st, prog)
            st = _record_monitor(st, usage)
            if st.calib is not None:
                rows = jnp.concatenate([usage[:, :, CPU].reshape(-1),
                                        usage[:, :, MEM].reshape(-1)])
                grp = ctl and st.calib.group_resolved is not None
                if grp:
                    gr0, ge0 = st.calib.group_resolved, st.calib.group_errors
                st = dataclasses.replace(
                    st, calib=calib_observe(st.calib, rows,
                                            jnp.tile(st.mon_count, 2),
                                            cfg.calibration, active=active))
                if grp:
                    derr = st.calib.group_errors - ge0
                    good_t = good_t + (st.calib.group_resolved - gr0) - derr
                    bad_t = bad_t + derr

        # 4. shaping (static branch: the baseline policy never shapes).
        # The engine skips this phase when no slot is occupied; here an
        # empty slot table makes every sub-step a no-op (empty kill
        # masks, all-zero allocations over an all-zero table), so no
        # gate is needed.  The model call inside `_shaped_demands` runs
        # under a nested `forecast` scope (`tick/shape/forecast`).
        fc_rows = fc_done = fc_tiles = jnp.int32(0)
        if cfg.policy != "baseline":
            with jax.named_scope("shape"):
                demand, st, fc_rows, fc_done, fc_tiles = _shaped_demands(
                    cfg, model, tr, st, tick, bucket)
                if rec:
                    obs_dem = demand.sum((0, 1))  # (2,) shaped-demand totals
                prob = _shape_problem(cfg, tr, st, demand, t, host_cap)
                dec = RAW_POLICIES[cfg.policy](prob)
                st, usage, conflict, resets4 = _apply_decision(
                    cfg, tr, st, dec, usage)
                st = dataclasses.replace(
                    st, failed=st.failed | conflict,
                    queued=st.queued | conflict)
                resets = resets | resets4
                if ctl:
                    c4 = _tenant_counts(tr.tenant, conflict, Tn)
                    fail_t = fail_t + c4
                    bad_t = bad_t + c4

        # 5. OS OOM (uncontrolled failures) — fails recorded + requeued
        with jax.named_scope("oom"):
            q5 = st.queued
            st, usage, resets5 = _resolve_oom(tr, st, usage, host_cap)
            if ctl:
                oomed = _tenant_counts(tr.tenant, st.queued & ~q5, Tn)
                fail_t = fail_t + oomed
                bad_t = bad_t + oomed

        # 6. scheduler: FIFO admission + elastic re-placement.  With the
        # control plane on, a wDRF gate runs first: per-tenant dominant
        # shares from the live allocation table decide which tenants may
        # admit this tick (HostControl.gate, vectorized).
        elig_app = None
        if ctl:
            with jax.named_scope("tenancy"):
                ten = st.tenancy
                credit = (credit_step(ten.credit, good_t, bad_t,
                                      cfg.control.credit_gamma,
                                      cfg.control.credit_floor)
                          if cfg.control.credit else ten.credit)
                occ = st.slot_gid >= 0
                tslot = jnp.where(
                    occ, tr.tenant[jnp.maximum(st.slot_gid, 0)], -1)
                oh_slot = tslot[:, None] == jnp.arange(Tn)[None, :]  # (A, T)
                alloc_t = jnp.where(oh_slot[:, :, None],
                                    st.alloc.sum(1)[:, None, :], 0.0).sum(0)
                share = dominant_shares(alloc_t, host_cap.sum(0),
                                        device_weights(cfg.control))
                queued_t = _tenant_counts(tr.tenant, st.queued, Tn)
                active_t = (share > 0) | (queued_t > 0)
                if cfg.control.gate:
                    slack = (jnp.float32(cfg.control.slack) * credit
                             if cfg.control.credit
                             else jnp.float32(cfg.control.slack))
                    elig_t = gate_mask(share, active_t, slack)
                else:
                    elig_t = jnp.ones((Tn,), bool)
                st = dataclasses.replace(st, tenancy=dataclasses.replace(
                    ten, credit=credit,
                    throttled=ten.throttled + jnp.where(elig_t, 0, queued_t),
                    completed=ten.completed + comp_t,
                    failed=ten.failed + fail_t,
                    share_sum=(ten.share_sum
                               + (share * active_t).astype(jnp.float32)),
                    active_ticks=(ten.active_ticks
                                  + active_t.astype(jnp.int32))))
                elig_app = elig_t[jnp.clip(tr.tenant, 0, Tn - 1)]
                q6 = st.queued
                if rec:
                    obs_throttled = jnp.where(elig_t, 0, queued_t).sum()
                    obs_credit = credit_mean(credit, active_t)
        with jax.named_scope("admit"):
            q_admit = st.queued
            st, resets6 = _admit_queued(cfg, tr, st, t, host_cap, elig_app)
            if ctl:
                st = dataclasses.replace(st, tenancy=dataclasses.replace(
                    st.tenancy, admitted=st.tenancy.admitted
                    + _tenant_counts(tr.tenant, q6 & ~st.queued, Tn)))
        with jax.named_scope("elastic"):
            st = _place_missing_elastic(tr, st, t, host_cap)

        # 7. metrics (raw sums; the ratios divide on the host at drain)
        with jax.named_scope("metrics"):
            st = _mon_reset(st, resets | resets5 | resets6)
            used = _cluster_total(usage)
            alloc = _cluster_total(jnp.where(st.comp_running[:, :, None],
                                             st.alloc, 0.0))
            metrics = TickMetrics(
                valid=active,
                n_running=(st.slot_gid >= 0).sum().astype(jnp.int32),
                used_cpu=used[CPU], used_mem=used[MEM],
                alloc_cpu=alloc[CPU], alloc_mem=alloc[MEM],
                forecast_rows=fc_rows, forecast_rows_done=fc_done,
                forecast_tile_rows=fc_tiles,
                lead=jnp.int32(0) if lead is None else lead)

            if rec:
                zero = jnp.int32(0)
                st = dataclasses.replace(st, obs=obs_record(st.obs, active, {
                    "used_cpu": used[CPU], "used_mem": used[MEM],
                    "queue": st.queued.sum().astype(jnp.int32),
                    "gap_cpu": (obs_dem[CPU] - used[CPU]
                                if obs_dem is not None else jnp.float32(0.0)),
                    "gap_mem": (obs_dem[MEM] - used[MEM]
                                if obs_dem is not None else jnp.float32(0.0)),
                    "oom": st.oom_kills - oom0,
                    "fail": st.failure_events - fail0,
                    "preempt": (st.full_preemptions + st.partial_preemptions
                                - pre0),
                    "admitted": (q_admit & ~st.queued).sum().astype(jnp.int32),
                    "throttled": obs_throttled,
                    "credit": obs_credit,
                    "cov_resolved": (st.calib.resolved - cres0
                                     if cres0 is not None else zero),
                    "cov_errors": (st.calib.errors - cerr0
                                   if cerr0 is not None else zero),
                }, lead=lead))

            st = dataclasses.replace(st, t=jnp.where(active, t, t_prev))
    return st, metrics


def fused_leap(cfg, model, tr: DeviceTrace, st: SimState, left: Array,
               bucket: int | None = None
               ) -> tuple[SimState, Array, TickMetrics]:
    """One EVENT-DRIVEN leap step: skip a run of provably-idle ticks,
    then execute one real :func:`fused_tick`.

    A tick is provably idle — every phase of the uniform step a no-op —
    when the cluster is empty, the FIFO queue is empty, calibration has
    no pending predictions (``CalibState.left`` ages per executed tick,
    so outstanding scores must run, not leap) and the next arrival is
    still in the future.  Tenancy needs no guard: with zero events
    ``credit_step`` is an identity and every counter increments by zero.
    The skip itself is a scalar ``while_loop`` that replays the uniform
    engine's EXACT ``t + tick`` float32 accumulation (~3 scalar ops per
    skipped tick instead of a full fused tick), so arrival tick indices
    — and therefore all downstream results — are bit-identical for any
    tick value.  Under a cohort vmap each member skips its own idle
    spans: a chunk costs ~max(per-member non-idle ticks) steps.

    ``left`` is the member's remaining tick budget (the driver seeds it
    with ``max_ticks``); it caps the skip and gates the executed tick,
    replacing the uniform driver's last-chunk slicing.  Budget-truncated
    idle tails still record their skipped ticks (metrics ``lead`` /
    a zero obs column) so truncated histories match uniform ones.

    Returns (state, left', metrics): ``left' = left - lead - executed``.
    The skip and the budget gating run under the ``tick/leap`` scope,
    the executed tick under its own phases.
    """
    tick_f = jnp.float32(cfg.cluster.tick)
    leap = lambda: jax.named_scope("tick/leap")  # noqa: E731
    with leap():
        active = ~st.done.all() & (left > 0)
        idle = active & (st.slot_gid < 0).all() & ~st.queued.any()
        if st.calib is not None:
            idle = idle & (st.calib.left == 0).all()
        next_sub = jnp.min(jnp.where(st.arrived, jnp.inf, tr.submit))

        def wcond(carry):
            t_c, n = carry
            return idle & (n < left) & (next_sub > t_c + tick_f)

        def wbody(carry):
            t_c, n = carry
            return t_c + tick_f, n + 1

        t2, lead = jax.lax.while_loop(wcond, wbody, (st.t, jnp.int32(0)))
        st = dataclasses.replace(st, t=t2)
        run = active & (left - lead > 0)
    # the tick always executes (a vmapped cond would lower to a select
    # and run both branches anyway) and is discarded when the budget
    # ran out; `run` implies the tick's own `active` gate
    st2, m = fused_tick(cfg, model, tr, st, bucket=bucket, lead=lead)
    with leap():
        st = jax.tree.map(lambda a, b: jnp.where(run, a, b), st2, st)
        m = dataclasses.replace(m, valid=m.valid & run, lead=lead)
        if st.obs is not None:
            # budget exhausted mid-skip: the skipped ticks still happened
            # — record them as one zero column standing for `lead` idle
            # ticks
            tail = ~run & (lead > 0)
            st = dataclasses.replace(st, obs=obs_record(
                st.obs, tail, {name: 0 for name, _ in RING_FIELDS},
                lead=lead - 1))
        return st, left - lead - run.astype(jnp.int32), m


# ----------------------------------------------------------------------
# chunked scan drivers
# ----------------------------------------------------------------------

def _make_model(cfg):
    from repro.sim.engine import _make_model as mk
    return mk(cfg)


def _cfg_key(cfg):
    """Hashable compile key: everything the traced program depends on
    (NOT the workload config — shapes are keyed separately, so sweep
    cells across scenarios share compilations)."""
    return (cfg.cluster, cfg.policy, cfg.forecaster, cfg.safeguard,
            cfg.calibration, cfg.control, cfg.obs, cfg.window, cfg.grace,
            cfg.horizon, cfg.gp, cfg.arima, cfg.work_lost_on_kill,
            cfg.leap, cfg.forecast_bucket)


_CHUNK_CACHE: dict = {}

# device-trace upload cache: workload configs are frozen (hashable)
# dataclasses and the engines never mutate a Trace, so repeated runs of
# the same cell (e.g. benchmark reps, sweep baselines) reuse the upload.
# Bounded LRU — a long-lived process sweeping many scenarios must not
# pin every uploaded trace in device memory forever.
_TRACE_CACHE: "dict" = {}
_TRACE_CACHE_MAX = 16


def _device_trace(wls, batched: bool, *, pad_to: int | None = None,
                  place=None, place_key=None) -> DeviceTrace:
    build = (
        (lambda ws: DeviceTrace.from_traces(ws, pad_to=pad_to)) if batched
        else lambda ws: DeviceTrace.from_trace(ws[0]))
    if place is not None:
        inner = build
        build = lambda ws: place(inner(ws))  # noqa: E731
    cfgs = tuple(getattr(w, "cfg", None) for w in wls)
    if any(c is None for c in cfgs):
        return build(wls)
    # the key carries the layout too: a batched single-seed cohort has a
    # leading seed axis that a solo upload of the same config lacks, and
    # a sharded fleet (place_key = mesh devices) a different placement
    key = (batched, pad_to, place_key, cfgs)
    tr = _TRACE_CACHE.pop(key, None)
    if tr is None:
        tr = build(wls)
        while len(_TRACE_CACHE) >= _TRACE_CACHE_MAX:
            _TRACE_CACHE.pop(next(iter(_TRACE_CACHE)))
    _TRACE_CACHE[key] = tr          # (re)insert as most recently used
    return tr


def _chunk_launcher(fn, metric: str, bucket: int | None, ticks: int):
    """Wrap a fresh jitted chunk fn.  Every call is a ``sim.chunk_launch``
    span, the enqueue (args ``bucket``, ``ticks``, ``members`` and ``n``,
    this program's launch count, which the driver's ``sim.chunk_wait``
    of the same chunk repeats).  The FIRST call — which traces and
    compiles synchronously before dispatching — is also measured: the
    wall feeds a ``repro.obs`` histogram (manifests snapshot it) and a
    ``jit_compile`` span.  ``wrapped.__wrapped__`` is the jitted fn,
    ``wrapped.launches`` the calls so far."""

    @functools.wraps(fn)
    def wrapped(tr, st, *rest):
        wrapped.launches += 1
        n = wrapped.launches
        args = {"bucket": bucket, "ticks": ticks,
                "members": st.t.shape[0] if st.t.ndim else 1, "n": n}
        with span("sim.chunk_launch", cat="execute", args=args):
            if n > 1:
                return fn(tr, st, *rest)
            with span("jit_compile", cat="compile",
                      args={"metric": metric}):
                t0 = time.perf_counter()
                out = fn(tr, st, *rest)
            REGISTRY.histogram(metric).observe(time.perf_counter() - t0)
            return out

    wrapped.launches = 0
    return wrapped


def _chunk_body(cfg, model, chunk: int, bucket):
    """The (un-vmapped) chunk step body: a ``lax.scan`` over
    :func:`fused_tick` (uniform) or :func:`fused_leap` (the tick budget
    then rides in the carry).

    The function's name, ``tick_chunk``, names the jitted program
    (``jit_tick_chunk``), and with it the key of JAX's persistent
    compilation cache, which ignores op_name metadata: a program cached
    before the tick's phases had named scopes cannot stand in for one
    that has them."""
    if cfg.leap:
        def tick_chunk(tr, st, left):
            def body(carry, _):
                s, l, m = fused_leap(cfg, model, tr, *carry, bucket=bucket)
                return (s, l), m
            (st, left), ms = jax.lax.scan(body, (st, left), None,
                                          length=chunk)
            return st, left, ms
        return tick_chunk, (1, 2)

    def tick_chunk(tr, st):
        def body(s, _):
            return fused_tick(cfg, model, tr, s, bucket=bucket)
        return jax.lax.scan(body, st, None, length=chunk)
    return tick_chunk, (1,)


# distinct (cfg-key, bucket) jit-cache entries created by the bucketed
# forecast path — surfaced as a registry gauge so bucket proliferation
# (compile cost) is observable in manifests and the engine bench
_BUCKET_JIT_KEYS: set = set()


def _note_bucket_entry(key) -> None:
    _BUCKET_JIT_KEYS.add(key)
    REGISTRY.gauge("scan.bucket_cache_entries").set(len(_BUCKET_JIT_KEYS))


def _chunk_fn(cfg, chunk: int, shapes, cohort: bool,
              bucket: int | None = None):
    key = (_cfg_key(cfg), chunk, shapes, cohort, bucket)
    fn = _CHUNK_CACHE.get(key)
    if fn is None:
        model = _make_model(cfg)
        run_chunk, donate = _chunk_body(cfg, model, chunk, bucket)
        if cohort:
            run_chunk = jax.vmap(run_chunk)
        fn = _CHUNK_CACHE[key] = _chunk_launcher(
            jax.jit(run_chunk, donate_argnums=donate), "scan.compile_s",
            bucket, chunk)
        if bucket is not None:
            _note_bucket_entry(key)
    return fn


def _shapes_key(wl, cfg):
    return (int(wl.n_apps), int(wl.max_components),
            cfg.cluster.max_running_apps, cfg.window)


def _concat_metrics(parts: list, axis: int = 0) -> TickMetrics:
    """Per-chunk device outputs concatenated along the tick axis (which
    is axis 1 for cohort runs: vmap puts the seed axis first)."""
    host = [jax.device_get(p) for p in parts]
    return jax.tree.map(lambda *xs: np.concatenate(xs, axis=axis), *host)


# smallest forecast bucket, in monitor rows per resource (the model
# batch is 2x this: CPU + MEM rows): small enough that mostly-idle
# tables pay little, large enough to bound the distinct compilations
# per config at log2(AC / _BUCKET_MIN)
_BUCKET_MIN = 8


def _bucketed(cfg) -> bool:
    """Does this config route forecasts through the bucketed path?"""
    return (cfg.forecast_bucket and cfg.policy != "baseline"
            and cfg.forecaster in ("gp", "arima"))


def _pick_bucket(cfg, st) -> int | None:
    """Per-chunk bucket choice: the smallest power-of-two (>= the floor)
    covering the CURRENT max ready-row count across members, read on the
    host at the chunk boundary (where the driver syncs anyway).  Ready
    growth within the chunk is absorbed by extra ``_bucketed_forecast``
    passes, not a bigger bucket, so the choice affects performance only
    — never results.  ``None`` (the full-batch path) when the bucket
    would cover the whole table anyway.

    A ``sim.pick_bucket`` span covers the host work (the copies of the
    state's slot and monitor columns); its args are the choice
    (``bucket``, rows per resource), ``ready`` (the largest member's
    ready-row count n) and ``members``."""
    args = {}
    with span("sim.pick_bucket", cat="execute", args=args):
        mc = np.asarray(st.mon_count)
        AC = mc.shape[-1]
        run = ((np.asarray(st.slot_gid) >= 0)[..., None]
               & np.asarray(st.comp_running)).reshape(mc.shape)
        n = int((run & (mc >= cfg.grace)).sum(-1).max())
        b = _BUCKET_MIN
        while b < n:
            b *= 2
        b = None if b >= AC else b
        args.update(bucket=b, ready=n,
                    members=mc.shape[0] if mc.ndim > 1 else 1)
    return b


def _ring_drain(cfg, chunk: int, st):
    if st.obs is None:
        return None
    if chunk > cfg.obs.ring:
        raise ValueError(
            f"chunk={chunk} exceeds the telemetry ring capacity "
            f"{cfg.obs.ring}: rings are drained once per chunk, so "
            "undrained ticks would be overwritten (raise "
            "SimConfig.obs.ring or shrink the chunk)")
    return RingDrain()


def _drive_chunks(cfg, chunk: int, fn_for_size, tr, st):
    """Run chunks until every sim is done or the tick budget is spent.

    ``fn_for_size(size, bucket)`` returns the compiled chunk step (the
    scan and shard engines differ only in this factory); ``bucket`` is
    re-chosen at every chunk boundary from the live ready-row count.
    The budget is enforced by slicing the LAST chunk to exactly the
    remaining ticks (one extra compile at most): the step itself gates
    only on completion, so a truncated sim must never execute a tick
    past ``max_ticks``.

    When telemetry rings are present the host drains them at every
    chunk boundary (returned ``RingDrain``; ``None`` when obs is off),
    which is why ring capacity must cover a whole chunk.

    Spans (``repro.obs.span``): ``sim.pick_bucket``, then the chunk
    function's ``sim.chunk_launch`` (the enqueue), then
    ``sim.chunk_wait`` around the read of ``done``, which returns once
    the chunk has run: how long the host waited for it.
    """
    drain = _ring_drain(cfg, chunk, st)
    bucketing = _bucketed(cfg)
    parts = []
    remaining = cfg.max_ticks
    while remaining > 0:
        size = min(chunk, remaining)
        fn = fn_for_size(size, _pick_bucket(cfg, st) if bucketing else None)
        st, ms = fn(tr, st)                # the sim.chunk_launch span
        # np.asarray, not st.done.all(): the fleet state is sharded
        # across devices and the host-side gather is the cheap form
        with span("sim.chunk_wait", cat="execute", args={"n": fn.launches}):
            done = bool(np.asarray(st.done).all())
        parts.append(ms)
        remaining -= size
        if drain is not None:
            with span("ring_drain", cat="drain"):
                drain.drain(st.obs)
        if done:
            break
    return st, parts, drain


def _drive_chunks_leap(cfg, chunk: int, fn_for_size, tr, st):
    """Leap-mode chunk driver.  A leap step consumes a VARIABLE number
    of ticks, so the host cannot enforce ``max_ticks`` by slicing the
    last chunk; instead the per-member budget rides in the scan carry
    (seeded here, decremented by skipped + executed ticks inside
    :func:`fused_leap`) and every chunk runs the full ``chunk`` steps —
    one compiled size, no last-chunk recompile.  Termination is per
    member: done, or budget spent."""
    drain = _ring_drain(cfg, chunk, st)
    bucketing = _bucketed(cfg)
    left = jnp.full(st.t.shape, cfg.max_ticks, jnp.int32)
    parts = []
    while True:
        fn = fn_for_size(chunk, _pick_bucket(cfg, st) if bucketing else None)
        st, left, ms = fn(tr, st, left)    # the sim.chunk_launch span
        with span("sim.chunk_wait", cat="execute", args={"n": fn.launches}):
            done = np.asarray(st.done)
        parts.append(ms)
        if drain is not None:
            with span("ring_drain", cat="drain"):
                drain.drain(st.obs)
        if bool(np.all(done.all(axis=-1) | (np.asarray(left) <= 0))):
            break
    return st, parts, drain


def run_sim_scan(cfg, wl=None, *, chunk: int = 32) -> SimResults:
    """Run one simulation on the device-resident scan engine.

    Semantically equivalent to ``engine.run_sim`` (same phase order,
    same event rules) but executes ``chunk`` ticks per XLA call with no
    host round-trips in between.  Results are independent of ``chunk``
    (bit-identical; see module docstring for the correctness anchors).
    """
    from repro.sim.scenarios.registry import build_trace
    from repro.sim.scenarios.stream import StreamConfig, run_sim_stream
    if isinstance(cfg.workload, StreamConfig):
        # streamed ingestion: bounded device window, rows re-keyed at
        # chunk boundaries (bit-identical to the materialized run)
        return run_sim_stream(cfg, wl, chunk=chunk)
    wl = wl if wl is not None else build_trace(cfg.workload)
    tr = _device_trace([wl], batched=False)
    st = init_state(cfg, wl.n_apps, wl.max_components)
    shapes = _shapes_key(wl, cfg)
    driver = _drive_chunks_leap if cfg.leap else _drive_chunks
    st, parts, drain = driver(
        cfg, chunk,
        lambda size, bucket: _chunk_fn(cfg, size, shapes, False, bucket),
        tr, st)
    return drain_results(
        cfg, wl, st, _concat_metrics(parts),
        obs=drain.history(0) if drain is not None else None)


def run_cohort_scan(cfg, seeds, *, chunk: int = 32,
                    wls=None) -> list[SimResults]:
    """Run a whole seed cohort as ONE batched device program.

    The per-seed states (and traces) are stacked and the chunk step is
    ``vmap`` ped over the seed axis: a sweep cell's cohort costs one
    compilation and one program launch per chunk instead of
    ``len(seeds)`` interleaved host loops.  Each seed's results are
    bit-identical to its ``run_sim_scan`` solo run.
    """
    from repro.sim.scenarios.registry import build_trace
    from repro.sim.scenarios.stream import StreamConfig
    seeds = list(seeds)
    if not seeds:
        return []
    cfgs = [dataclasses.replace(
        cfg, workload=dataclasses.replace(cfg.workload, seed=int(s)))
        for s in seeds]
    if wls is None:
        wls = [build_trace(c.workload) for c in cfgs]
    if isinstance(cfg.workload, StreamConfig):
        # streamed members keep their own windows (the vmapped path
        # assumes one static trace layout per member) — solo streamed
        # runs per seed, each still bit-identical to its scan run
        return [run_sim_scan(c, w, chunk=chunk)
                for c, w in zip(cfgs, wls)]
    if len(seeds) == 1:
        # a cohort of one is just a solo run (and must not go through
        # the vmapped path, whose trace/state layouts carry a seed axis)
        return [run_sim_scan(cfgs[0], wls[0], chunk=chunk)]
    shapes = {(int(w.n_apps), int(w.max_components)) for w in wls}
    if len(shapes) != 1:
        raise ValueError(f"cohort traces disagree on shape: {shapes}")
    tr = _device_trace(wls, batched=True)
    st = init_state(cfg, wls[0].n_apps, wls[0].max_components,
                    batch=len(seeds))
    shapes = _shapes_key(wls[0], cfg)
    driver = _drive_chunks_leap if cfg.leap else _drive_chunks
    st, parts, drain = driver(
        cfg, chunk,
        lambda size, bucket: _chunk_fn(cfg, size, shapes, True, bucket),
        tr, st)
    metrics = _concat_metrics(parts, axis=1)   # leaves: (S, ticks_total)
    if drain is not None:
        # the rings are already drained; slicing them per member would
        # dispatch eager device ops for data drain_results never reads
        st = dataclasses.replace(st, obs=None)
    out = []
    for i, (c, w) in enumerate(zip(cfgs, wls)):
        # lazy device slices: drain_results touches only the telemetry
        # fields, so the big buffers (monitor rings, score rings) are
        # never copied back to the host
        st_i = jax.tree.map(lambda x, i=i: x[i], st)
        ms_i = jax.tree.map(lambda x, i=i: x[i], metrics)
        out.append(drain_results(
            c, w, st_i, ms_i,
            obs=drain.history(i) if drain is not None else None))
    return out


# ----------------------------------------------------------------------
# sharded fleet driver (shard_map over a device mesh)
# ----------------------------------------------------------------------

def _resolve_mesh(mesh):
    """Normalize ``mesh`` (None = all local devices, int = first N
    devices, or a ready-made ``Mesh``) to a 1-D fleet mesh."""
    from jax.sharding import Mesh
    if isinstance(mesh, Mesh):
        return mesh
    devs = jax.devices()
    n = len(devs) if mesh is None else int(mesh)
    if not 1 <= n <= len(devs):
        raise ValueError(f"mesh={mesh!r}: need 1..{len(devs)} devices "
                         f"({len(devs)} visible; set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N for "
                         "forced host devices on CPU)")
    return Mesh(np.array(devs[:n]), (FLEET_AXIS,))


def _shard_chunk_fn(cfg, chunk: int, shapes, mesh,
                    bucket: int | None = None):
    """Compiled chunk step for a sharded fleet: the SAME vmapped chunk
    body as the cohort path, laid across the mesh with ``shard_map`` —
    each device advances its slice of the fleet independently (no
    collectives: sims never communicate), so one SPMD program executes
    the whole fleet with host sync only at chunk boundaries."""
    from jax.sharding import PartitionSpec as P
    key = (_cfg_key(cfg), chunk, shapes, "shard", bucket,
           tuple(d.id for d in mesh.devices.flat))
    fn = _CHUNK_CACHE.get(key)
    if fn is None:
        model = _make_model(cfg)
        run_chunk, donate = _chunk_body(cfg, model, chunk, bucket)
        spec = P(FLEET_AXIS)
        n_args = 1 + len(donate)        # (tr, st[, left])
        sharded = jax.shard_map(jax.vmap(run_chunk), mesh=mesh,
                                in_specs=(spec,) * n_args,
                                out_specs=(spec,) * n_args,
                                check_vma=False)
        fn = _CHUNK_CACHE[key] = _chunk_launcher(
            jax.jit(sharded, donate_argnums=donate), "shard.compile_s",
            bucket, chunk)
        if bucket is not None:
            _note_bucket_entry(key)
    return fn


def run_fleet_shard(cfg, seeds=None, *, chunk: int = 32, wls=None,
                    cfgs=None, mesh=None) -> list[SimResults]:
    """Run a fleet of sims as ONE SPMD program across a device mesh.

    The fleet axis is ``run_cohort_scan``'s stacked cohort axis, padded
    up to a multiple of the mesh size and laid across the devices with
    ``shard_map``: each device ``vmap``s its slice of the fleet through
    the fused tick chunks, and the host syncs only at chunk boundaries
    (metrics drain + global termination check).  Members may differ in
    their WORKLOAD only (seed or scenario — both are trace data, not
    compiled structure); every other config knob is static in the traced
    program, which is exactly what ``repro.sim.shard`` groups sweep
    cells by.

    Fleet members are specified either as ``seeds`` (expanded against
    ``cfg`` exactly like ``run_cohort_scan``) or as explicit ``cfgs``
    (fully-resolved configs agreeing with ``cfg`` on everything but
    ``workload``).  ``mesh`` is ``None`` (all visible devices), a device
    count, or a ready-made 1-D ``Mesh`` over the ``"fleet"`` axis.

    Correctness anchors (``tests/test_shard.py``): ``mesh=1`` is
    bit-identical to ``run_cohort_scan``, and any larger mesh is
    bit-identical per member to ``mesh=1`` (forecasts run in fixed-size
    tiles, ``forecast.base.batched``, and the rest of a tick is per
    member, so re-slicing the fleet axis cannot change a member's
    numerics).  Padding members are real sims whose results
    are simply never drained.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sim.scenarios.registry import build_trace
    if cfgs is None:
        if seeds is None:
            raise ValueError("pass seeds or cfgs")
        cfgs = [dataclasses.replace(
            cfg, workload=dataclasses.replace(cfg.workload, seed=int(s)))
            for s in seeds]
    cfgs = list(cfgs)
    if not cfgs:
        return []
    for i, c in enumerate(cfgs):
        if dataclasses.replace(c, workload=cfg.workload) != cfg:
            raise ValueError(
                f"fleet member {i} differs from the base config beyond "
                "its workload (policy/forecaster/safeguard/... are "
                "static in the SPMD program)")
    if wls is None:
        wls = [build_trace(c.workload) for c in cfgs]
    from repro.sim.scenarios.stream import StreamConfig
    if any(isinstance(c.workload, StreamConfig) for c in cfgs):
        # streamed members re-key their device windows at chunk
        # boundaries, which the static SPMD fleet layout cannot express
        # — fall back to solo streamed runs per member (bit-identical
        # to what the fleet would produce)
        return [run_sim_scan(c, w, chunk=chunk)
                for c, w in zip(cfgs, wls)]
    shapes = {(int(w.n_apps), int(w.max_components)) for w in wls}
    if len(shapes) != 1:
        raise ValueError(f"fleet traces disagree on shape: {shapes}")

    B = len(cfgs)
    mesh = _resolve_mesh(mesh)
    padded = round_up(B, int(mesh.devices.size))
    sharding = NamedSharding(mesh, P(FLEET_AXIS))
    tr = _device_trace(wls, batched=True, pad_to=padded,
                       place=lambda t: jax.device_put(t, sharding),
                       place_key=tuple(d.id for d in mesh.devices.flat))
    n_apps, max_comp = wls[0].n_apps, wls[0].max_components
    # jit the fresh state straight into the sharded layout: a fresh
    # state is all zeros, so materializing it on the default device and
    # re-placing it would pay ~25 eager dispatches + transfers per run
    init_key = ("fleet_init", _cfg_key(cfg), n_apps, max_comp, padded,
                tuple(d.id for d in mesh.devices.flat))
    init_fn = _CHUNK_CACHE.get(init_key)
    if init_fn is None:
        init_fn = _CHUNK_CACHE[init_key] = jax.jit(
            lambda: init_state(cfg, n_apps, max_comp, batch=padded),
            out_shardings=sharding)
    st = init_fn()
    shapes_k = _shapes_key(wls[0], cfg)
    driver = _drive_chunks_leap if cfg.leap else _drive_chunks
    st, parts, drain = driver(
        cfg, chunk,
        lambda size, bucket: _shard_chunk_fn(cfg, size, shapes_k, mesh,
                                             bucket),
        tr, st)
    metrics = _concat_metrics(parts, axis=1)   # leaves: (padded, ticks)
    # ONE bulk device->host gather, then cheap NumPy slices per member:
    # slicing the sharded axis on device would pay a cross-device
    # gather per field per member
    st = jax.device_get(st)
    out = []
    for i, (c, w) in enumerate(zip(cfgs, wls)):
        st_i = jax.tree.map(lambda x, i=i: x[i], st)
        ms_i = jax.tree.map(lambda x, i=i: x[i], metrics)
        # padding members past the real fleet are never drained here
        out.append(drain_results(
            c, w, st_i, ms_i,
            obs=drain.history(i) if drain is not None else None))
    return out
