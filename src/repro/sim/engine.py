"""Simulation engine (paper §4): FIFO scheduler + monitor + forecast +
resource shaper, advanced in 60 s monitoring ticks.

Per tick:
  1. arrivals enter the FIFO queue (priority = ORIGINAL submit time, so a
     resubmitted-after-failure app re-enters "commensurate to its original
     priority" — paper §3.2);
  2. running apps progress (elastic rate model), completions recorded;
  3. the monitor samples per-component CPU/memory usage;
  4. past the grace period, the forecaster predicts each component's
     future utilization (mean + variance), the safeguard buffer (Eq. 9)
     turns it into a shaped demand, and the shaping policy (baseline /
     optimistic / pessimistic Algorithm 1) computes allocations +
     preemptions, which are applied through the preemption primitives.
     With ``SimConfig.calibration`` enabled, Eq. 9's dynamic term uses
     an online split-conformal quantile instead of the fixed K2
     sigma-multiplier (``repro.core.uncertainty``): realized peaks are
     scored against deployed bounds each tick and the calibrated scale
     tracks the target coverage.  Disabled (the default), the path is
     bit-identical to ``engine_ref``;
  5. the OS OOM handler fires for any host whose true usage exceeds
     capacity (the uncontrolled-failure channel);
  6. the scheduler admits queued apps into freed capacity and re-places
     missing elastic components.

Forecast + shaping run as jitted, vmapped JAX on fixed-size padded
batches — identical code paths to the live framework's shaper service.

This is the VECTORIZED engine: every per-tick scan (completion detection,
kill/evict application, monitor resets, OOM candidate selection) is a
NumPy array op over the padded slot table instead of a Python loop over
slots, so one tick costs O(array-op).  ``repro.sim.engine_ref`` keeps the
original loop-based implementation as a golden reference; the two are
bit-identical on any workload (``tests/test_sweep.py``).

The jitted forecast path is cached at module level keyed by
(model, horizon, batch bucket, window width), so every sim in a process —
in particular every cell of a ``repro.sim.sweep`` grid — shares one
compilation per shape instead of recompiling per ``run_sim`` call.
``run_sim(..., forecast_fn=...)`` lets the sweep driver swap in a
cross-sim batching client that stacks windows from all concurrently
running sims into one padded batch (row-deterministic, hence still
bit-identical to a solo run).
"""
from __future__ import annotations

import bisect
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.forecast import (ARIMAConfig, ARIMAForecaster, GPConfig,
                                 GPForecaster)
from repro.core.forecast.base import peak_over_horizon
from repro.core.monitor import Monitor
from repro.core.shaper import (POLICIES, SafeguardConfig, ShapeProblem,
                               shaped_demand, shaped_demand_scaled)
from repro.core.uncertainty import (CalibrationConfig, OnlineCalibrator,
                                    bucket_pow2, sigma_from_var_np)
from repro.control import HostControl, TenancyConfig, tenancy_summary
from repro.obs import ObsConfig
from repro.sim.cluster import CPU, MEM, Cluster, ClusterConfig
from repro.sim.metrics import SimResults
from repro.sim.scenarios.registry import build_trace
from repro.sim.workload import Workload, WorkloadConfig


@dataclasses.dataclass(frozen=True)
class SimConfig:
    cluster: ClusterConfig = ClusterConfig()
    workload: WorkloadConfig = WorkloadConfig()
    policy: str = "pessimistic"          # baseline | optimistic | pessimistic
    forecaster: str = "gp"               # oracle | gp | arima | persist
    safeguard: SafeguardConfig = SafeguardConfig()
    # conformal calibration of the safeguard's dynamic term (disabled by
    # default — the legacy K2-sigma path stays bit-identical to
    # engine_ref; see repro.core.uncertainty)
    calibration: CalibrationConfig = CalibrationConfig()
    # multi-tenant control plane: admission gate (wDRF), credit-aware
    # shaping, per-tenant conformal pools (disabled by default — the
    # tenancy-off path is bit-identical to the pre-control-plane
    # engines; see repro.control)
    control: TenancyConfig = TenancyConfig()
    # device-side telemetry rings (disabled by default — SimState.obs is
    # then structurally absent and obs-off programs are bit-identical to
    # pre-observability engines; scan/shard only, the host engines
    # ignore it like forecast_rows; see repro.obs)
    obs: ObsConfig = ObsConfig()
    window: int = 24                     # monitor window (ticks)
    grace: int = 10                      # grace period (paper §5: 10 min)
    horizon: int = 3                     # forecast look-ahead (ticks)
    gp: GPConfig = GPConfig(history=10, max_patterns=10, opt_steps=10)
    arima: ARIMAConfig = ARIMAConfig()
    max_ticks: int = 100_000
    work_lost_on_kill: bool = True       # kill primitive loses all work
    # event-driven leap ticks (scan/shard engines only; the host engines
    # ignore it): each scan step first skips a run of provably-idle
    # ticks — empty cluster, empty queue, quiescent calibration — with a
    # cheap clock loop that replays the uniform engine's exact f32 time
    # accumulation, then executes one real tick.  Bit-identical to
    # leap=False (uniform stays the reference; tests/test_scan_engine.py
    # enforces the equivalence across all scenario families).
    leap: bool = False
    # ragged bucketed forecast batching (scan/shard engines, gp/arima):
    # compact forecast-ready monitor rows and run the model over
    # power-of-two buckets sized per chunk instead of the full padded
    # batch (the measured ~6.7x masked-row overhead).  One jit cache
    # entry per bucket, mirroring forecast_peaks' host-side padding;
    # per-row model independence makes it bit-identical, so it defaults
    # on.
    forecast_bucket: bool = True


# power-of-two padding for every jitted batch path (the shared
# convention lives in repro.core.uncertainty.scoring; engine_ref keeps
# its own frozen copy by design)
_bucket = bucket_pow2


def _make_model(cfg: SimConfig):
    if cfg.forecaster == "gp":
        return GPForecaster(cfg.gp)
    if cfg.forecaster == "arima":
        return ARIMAForecaster(cfg.arima)
    if cfg.forecaster in ("persist", "oracle"):
        return None
    raise ValueError(f"unknown forecaster {cfg.forecaster!r} "
                     "(expected oracle | gp | arima | persist)")


# process-wide jit cache: (model, horizon, bucket, window-width) -> fn.
# Models are frozen dataclasses, so two sweep cells with the same
# forecaster config hash to the same compiled function.
_JIT_CACHE: dict = {}
_JIT_LOCK = threading.Lock()


def _jitted_peak_forecast(model, horizon: int, b: int, width: int):
    key = (model, horizon, b, width)
    with _JIT_LOCK:
        fn = _JIT_CACHE.get(key)
        if fn is None:

            @jax.jit
            def fn(w, v):
                fc = model.forecast_batch(w, horizon, valid=v)
                # future PEAK utilization (paper §4.2: predictor outputs a
                # future peak) — shared reduction with the scan engine
                return peak_over_horizon(fc)

            _JIT_CACHE[key] = fn
    return fn


def forecast_peaks(model, horizon: int, windows: np.ndarray,
                   valid: np.ndarray):
    """Pad (n, W) windows to a power-of-two bucket and run the shared
    jitted peak forecast.  Row i's result depends only on row i (every
    batch runs in the same fixed-size tiles, ``forecast.base.batched``;
    verified bit-identical across bucket sizes), so callers may freely stack
    windows from many sims into one call."""
    n, width = windows.shape
    b = _bucket(n)
    fn = _jitted_peak_forecast(model, horizon, b, width)
    wpad = np.zeros((b, width), np.float32)
    vpad = np.zeros((b, width), bool)
    wpad[:n], vpad[:n] = windows, valid
    peak, pvar = fn(jnp.asarray(wpad), jnp.asarray(vpad))
    return np.asarray(peak)[:n], np.asarray(pvar)[:n]


class _BatchedForecaster:
    """Per-sim forecast client over the process-wide jit cache."""

    def __init__(self, cfg: SimConfig):
        self.cfg = cfg
        self._model = _make_model(cfg)

    def __call__(self, windows: np.ndarray, valid: np.ndarray):
        """windows: (n, W) -> (peak_mean, peak_var) each (n,)."""
        if self.cfg.forecaster == "persist":
            mean = windows[:, -1]
            var = windows.var(axis=1, where=valid) + 1e-6
            return mean, var
        return forecast_peaks(self._model, self.cfg.horizon, windows, valid)


def _oracle_peaks(cluster: Cluster, wl: Workload, horizon: int,
                  tick: float) -> np.ndarray:
    """(A, C, 2) true future peak usage over the horizon (variance 0)."""
    A, C = cluster.A, cluster.C
    out = np.zeros((A, C, 2), np.float32)
    run = cluster.running_slots()
    if run.size == 0:
        return out
    gids = cluster.slot_gid[run]
    rate = cluster.progress_rate(wl)[run]
    peaks = np.zeros((run.size, C, 2), np.float32)
    for k in range(1, horizon + 1):
        prog = np.clip((cluster.work_done[run] + rate * tick * k)
                       / wl.runtime[gids], 0.0, 1.0)
        u = wl.usage(gids, prog) * cluster.comp_running[run][:, :, None]
        peaks = np.maximum(peaks, u)
    out[run] = peaks
    return out


def _shaped_demand_padded(peak: np.ndarray, req: np.ndarray,
                          var: np.ndarray, sg: SafeguardConfig) -> np.ndarray:
    """``shaped_demand`` over a leading axis padded to a power-of-two
    bucket, so the jitted elementwise kernel compiles O(log n) times per
    safeguard config instead of once per distinct tick batch size.  The
    op is row-independent, so padding cannot change the real rows."""
    n = peak.shape[0]
    b = _bucket(n)
    if b == n:
        return np.asarray(shaped_demand(peak, req, var, sg))

    def pad(a):
        z = np.zeros((b,) + a.shape[1:], a.dtype)
        z[:n] = a
        return z

    return np.asarray(shaped_demand(pad(peak), pad(req), pad(var), sg))[:n]


def _shaped_demand_scaled_padded(peak: np.ndarray, req: np.ndarray,
                                 var: np.ndarray, k1: float,
                                 scale: np.ndarray) -> np.ndarray:
    """Bucket-padded ``shaped_demand_scaled`` (conformal safeguard)."""
    n = peak.shape[0]
    b = _bucket(n)

    def pad(a):
        if b == n:
            return a
        z = np.zeros((b,) + a.shape[1:], a.dtype)
        z[:n] = a
        return z

    out = shaped_demand_scaled(pad(peak), pad(req), pad(var),
                               np.float32(k1), pad(scale.astype(np.float32)))
    return np.asarray(out)[:n]


def _shape_decisions(cfg: SimConfig, cl: Cluster, wl: Workload, mon: Monitor,
                     fc, policy_fn, submit0: np.ndarray, run: np.ndarray,
                     t: float, tick: float, calib=None, ctl=None):
    """Forecast -> safeguard -> Algorithm 1 for one tick (shared by the
    vectorized and reference engines).  Returns numpy
    (kill_app, kill_comp, alloc_cpu, alloc_mem)."""
    A, C = cl.A, cl.C
    gids = cl.slot_gid[run]
    req = np.stack([wl.cpu_req[gids], wl.mem_req[gids]], -1)  # (n,C,2)
    running = cl.comp_running[run]
    demand = np.where(running[:, :, None], req, 0.0).astype(np.float32)

    if cfg.forecaster == "oracle":
        # perfect information needs no training history: the grace
        # period (paper §5) exists only for statistical models
        peaks = _oracle_peaks(cl, wl, cfg.horizon, tick)[run]
        var = np.zeros_like(peaks)
        ready = running
        shaped = _shaped_demand_padded(peaks, req, var, cfg.safeguard)
        demand = np.where(ready[:, :, None], shaped, demand)
    else:
        rc = np.nonzero(running)
        mslots = run[rc[0]] * C + rc[1]
        ready = mon.ready(mslots, cfg.grace)
        if ready.any():
            sel = np.nonzero(ready)[0]
            wins, vmask = mon.windows(mslots[sel])
            n = sel.size
            wflat = np.concatenate([wins[:, :, CPU], wins[:, :, MEM]])
            vflat = np.concatenate([vmask, vmask])
            mean, var = fc(wflat, vflat)
            reqs = req[rc[0][sel], rc[1][sel]]     # (n, 2)
            if calib is None:
                for r, off in ((CPU, 0), (MEM, n)):
                    sh = _shaped_demand_padded(
                        mean[off:off + n], reqs[:, r], var[off:off + n],
                        cfg.safeguard)
                    demand[rc[0][sel], rc[1][sel], r] = sh
            else:
                # conformal safeguard: per-series calibrated quantile
                # replaces K2 (rows follow the batch layout: CPU then MEM)
                M = mon.count.shape[0]
                rows = np.concatenate([mslots[sel], M + mslots[sel]])
                groups, q_rows = None, None
                if ctl is not None:
                    # per-tenant pools + credit-modulated target level:
                    # rows map to the tenant owning the slot; q_groups
                    # reads the PREVIOUS tick's credit (the control
                    # update runs later, at admission time)
                    tg = wl.tenant[cl.slot_gid[run[rc[0][sel]]]]
                    groups = np.concatenate([tg, tg])
                    qg = ctl.q_groups(calib.q, cfg.calibration.q_min,
                                      cfg.calibration.q_max)
                    q_rows = qg[groups]
                scale = calib.scales(rows, groups=groups, q=q_rows)
                for r, off in ((CPU, 0), (MEM, n)):
                    sh = _shaped_demand_scaled_padded(
                        mean[off:off + n], reqs[:, r], var[off:off + n],
                        cfg.safeguard.k1, scale[off:off + n])
                    demand[rc[0][sel], rc[1][sel], r] = sh
                sigma = sigma_from_var_np(var).astype(np.float32)
                counts = np.concatenate([mon.count[mslots[sel]]] * 2)
                calib.begin(rows, mean.astype(np.float32), sigma,
                            scale.astype(np.float32), counts,
                            groups=groups)

    # build the fixed-size ShapeProblem over ALL slots
    dem_full = np.zeros((A, C, 2), np.float32)
    dem_full[run] = demand
    app_exists = cl.slot_gid >= 0
    order = np.full((A,), -1, np.int64)
    fifo = np.argsort(submit0[np.maximum(cl.slot_gid, 0)]
                      + np.where(app_exists, 0, 1e18))
    order[:run.size] = fifo[:run.size]
    prob = ShapeProblem(
        host_cpu=jnp.asarray(cl.host_cap[:, CPU]),
        host_mem=jnp.asarray(cl.host_cap[:, MEM]),
        app_exists=jnp.asarray(app_exists),
        app_order=jnp.asarray(order),
        comp_exists=jnp.asarray(cl.comp_running),
        comp_core=jnp.asarray(
            wl.is_core[np.maximum(cl.slot_gid, 0)]
            & app_exists[:, None]),
        comp_host=jnp.asarray(cl.comp_host),
        comp_cpu=jnp.asarray(dem_full[:, :, CPU]),
        comp_mem=jnp.asarray(dem_full[:, :, MEM]),
        comp_alive=jnp.asarray(t - cl.alive_since),
    )
    dec = policy_fn(prob)
    return (np.asarray(dec.kill_app), np.asarray(dec.kill_comp),
            np.asarray(dec.alloc_cpu), np.asarray(dec.alloc_mem))


def run_sim(cfg: SimConfig, wl: Workload | None = None, *,
            forecast_fn=None) -> SimResults:
    """Run one simulation to completion (vectorized engine).

    ``forecast_fn(windows, valid) -> (mean, var)`` overrides the default
    per-process forecast client — the sweep driver passes a cross-sim
    batching client here.

    ``cfg.workload`` may be ANY registered scenario config (google,
    diurnal, flashcrowd, heavytail, colocated, replay, ...): the default
    workload is built through the scenario registry, and the engine
    consumes the canonical ``Trace`` unchanged.
    """
    wl = wl if wl is not None else build_trace(cfg.workload)
    N, C = wl.n_apps, wl.max_components
    cl = Cluster(cfg.cluster, C)
    A = cl.A
    mon = Monitor(slots=A * C, window=cfg.window)
    fc = forecast_fn if forecast_fn is not None else _BatchedForecaster(cfg)
    # per-tick "no request" signal for the sweep's barrier batch mode:
    # a registered sim that ticks without forecasting (grace period,
    # empty cluster, baseline policy) tells the batcher so full-cohort
    # detection is exact and idle ticks stop paying the leader timeout
    idle_fn = getattr(fc, "idle", None)
    fc_calls = [0]
    if idle_fn is not None:
        inner_fc = fc

        def fc(windows, valid, _inner=inner_fc):
            fc_calls[0] += 1
            return _inner(windows, valid)
    policy_fn = POLICIES[cfg.policy]
    res = SimResults(n_apps=N)
    tick = cfg.cluster.tick
    all_comps = np.arange(C)[None, :]     # broadcast helper for mon resets
    # multi-tenant control plane (admission gate + credit accounting)
    hc = None
    if cfg.control.enabled:
        if wl.n_tenants > cfg.control.max_tenants:
            raise ValueError(
                f"trace has {wl.n_tenants} tenants > control.max_tenants="
                f"{cfg.control.max_tenants}")
        hc = HostControl(cfg.control)
    # online conformal calibration (oracle forecasts are exact — there
    # is no residual distribution to calibrate); with the control plane
    # on, scores additionally pool per tenant (the series -> group ->
    # fleet tier)
    calib = None
    if cfg.calibration.enabled and cfg.forecaster != "oracle":
        calib = OnlineCalibrator(n_series=2 * A * C, horizon=cfg.horizon,
                                 fallback=cfg.safeguard.k2,
                                 cfg=cfg.calibration,
                                 n_groups=(cfg.control.max_tenants
                                           if hc is not None else 0))

    queue: list[tuple[float, int]] = []   # (original submit, gid) sorted
    arrived = 0
    done = np.zeros((N,), bool)
    submit0 = wl.submit.copy()            # original submit (priority key)
    # preempt-to-checkpoint mode (work_lost_on_kill=False): a preempted
    # app resumes from its last "checkpoint" (saved progress) instead of
    # restarting — the TPU adaptation's beyond-paper ablation
    saved_work: dict[int, float] = {}

    def requeue(gid: int):
        bisect.insort(queue, (float(submit0[gid]), gid))

    t = 0.0
    for step in range(cfg.max_ticks):
        if done.all():
            break
        t += tick

        # 1. arrivals ---------------------------------------------------
        while arrived < N and wl.submit[arrived] <= t:
            requeue(arrived)
            arrived += 1

        # 2. progress + completions (array scan over the slot table) ------
        rate = cl.progress_rate(wl)
        cl.work_done += rate * tick
        run = cl.running_slots()
        fin = run[cl.work_done[run] >= wl.runtime[cl.slot_gid[run]]]
        if fin.size:
            mon.reset_slot((fin[:, None] * C + all_comps).ravel())
            fin_gids = cl.evict_apps(fin)
            done[fin_gids] = True
            for gid in fin_gids:
                res.record_completion(int(gid), submit0[gid], t)
            if hc is not None:
                hc.note_completed(wl.tenant[fin_gids])

        # 3. monitor sampling --------------------------------------------
        usage = cl.usage_now(wl)
        run = cl.running_slots()
        if run.size:
            rc = np.nonzero(cl.comp_running[run])  # (slot_i, c)
            mslots = run[rc[0]] * C + rc[1]
            mon.record(mslots, usage[run][rc][:, CPU], usage[run][rc][:, MEM])
        if calib is not None:
            if hc is not None:
                gr0 = calib.group_resolved.copy()
                ge0 = calib.group_errors.copy()
            calib.observe(np.concatenate([usage[:, :, CPU].ravel(),
                                          usage[:, :, MEM].ravel()]),
                          mon.count)
            if hc is not None:
                # covered / miscovered conformal resolutions feed the
                # tenant credit score alongside completions / failures
                derr = calib.group_errors - ge0
                hc.note_calib(calib.group_resolved - gr0 - derr, derr)

        # 4. shaping ------------------------------------------------------
        # two distinct kill channels (paper §4.2): controlled preemptions
        # (Algorithm 1, work lost but clean) vs uncontrolled OS OOM kills
        # (the "application failures" metric of Figs. 3-4)
        preempted_this_tick: list[int] = []
        oom_failed_this_tick: list[int] = []
        calls_before = fc_calls[0]
        if cfg.policy != "baseline" and run.size:
            kill_app, kill_comp, alloc_cpu, alloc_mem = _shape_decisions(
                cfg, cl, wl, mon, fc, policy_fn, submit0, run, t, tick,
                calib=calib, ctl=hc)

            kills = np.nonzero(kill_app & (cl.slot_gid >= 0))[0]
            if kills.size:
                if not cfg.work_lost_on_kill:
                    for gid0, wd in zip(cl.slot_gid[kills],
                                        cl.work_done[kills]):
                        saved_work[int(gid0)] = float(wd)
                kgids = cl.evict_apps(kills)
                usage[kills] = 0.0
                mon.reset_slot((kills[:, None] * C + all_comps).ravel())
                if cfg.policy == "optimistic":
                    # optimistic-concurrency conflict: an UNCONTROLLED
                    # failure (paper: "the system will let one of the
                    # two fail")
                    oom_failed_this_tick.extend(int(g) for g in kgids)
                else:
                    preempted_this_tick.extend(int(g) for g in kgids)
                    res.full_preemptions += kills.size
            ks, kc = np.nonzero(kill_comp & (cl.slot_gid >= 0)[:, None]
                                & cl.comp_running)
            if ks.size:
                cl.kill_components(ks, kc)
                usage[ks, kc] = 0.0
                mon.reset_slot(ks * C + kc)
                res.partial_preemptions += ks.size
            live = cl.comp_running
            cl.alloc[:, :, CPU] = np.where(live, alloc_cpu, 0.0)
            cl.alloc[:, :, MEM] = np.where(live, alloc_mem, 0.0)
        if idle_fn is not None and fc_calls[0] == calls_before:
            idle_fn()

        # 5. OOM (uncontrolled failures) -----------------------------------
        oom_gids, oom_partial = cl.resolve_oom(wl, usage)
        for gid in oom_gids:
            oom_failed_this_tick.append(gid)
            res.oom_kills += 1
        res.partial_preemptions += len(oom_partial)
        if oom_partial:
            parr = np.asarray(oom_partial, np.int64)
            mon.reset_slot(parr[:, 0] * C + parr[:, 1])

        for gid in oom_failed_this_tick:
            res.record_failure(gid)
        if hc is not None and oom_failed_this_tick:
            hc.note_failed(wl.tenant[np.asarray(oom_failed_this_tick)])
        for gid in oom_failed_this_tick + preempted_this_tick:
            requeue(gid)

        # 6. scheduler: FIFO admission + elastic re-placement --------------
        # with the control plane on, the tick's events first fold into
        # the tenant credit, then the wDRF gate decides which tenants
        # may admit this tick (ineligible tenants' apps stay queued)
        elig = None
        if hc is not None:
            T = cfg.control.max_tenants
            alloc_t = np.zeros((T, 2), np.float32)
            run6 = cl.running_slots()
            if run6.size:
                np.add.at(alloc_t, wl.tenant[cl.slot_gid[run6]],
                          cl.alloc[run6].sum(1))
            queued_t = np.bincount(wl.tenant[[g for _, g in queue]],
                                   minlength=T)
            elig = hc.gate(alloc_t, cl.host_cap.sum(0), queued_t)
        while queue:
            if elig is None:
                i0 = 0
            else:
                # FIFO head among ELIGIBLE tenants (queue is sorted by
                # (submit0, gid), so the first eligible entry is the
                # same head the fused tick's masked argmin selects)
                i0 = next((i for i, (_, g) in enumerate(queue)
                           if elig[wl.tenant[g]]), -1)
                if i0 < 0:
                    break
            _, gid = queue[i0]
            slot = cl.admit(gid, wl, t)
            if slot < 0:
                break
            queue.pop(i0)
            if hc is not None:
                hc.note_admitted(int(wl.tenant[gid]))
            if not cfg.work_lost_on_kill and gid in saved_work:
                cl.work_done[slot] = saved_work.pop(gid)  # resume from ckpt
            mon.reset_slot(slot * C + np.arange(C))
        cl.place_missing_elastic(wl, t)

        # 7. metrics -------------------------------------------------------
        res.record_tick(t, cl, usage)

    if calib is not None:
        res.calibration = calib.report()
        gb = calib.group_report()
        if gb is not None:
            res.calibration["groups"] = gb
    if hc is not None:
        res.tenancy = tenancy_summary(cfg.control, wl, res.turnaround,
                                      res.failed_apps, hc.arrays())
    res.finalize(t)
    return res
