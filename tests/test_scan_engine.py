"""Scan-engine correctness anchors (repro.sim.state / repro.sim.step).

The device-resident engine's contracts, in order of strength:

  * CHUNK INVARIANCE — results are bit-identical for any tick chunking
    (everything that affects dynamics lives inside the fused step);
  * COHORT EQUIVALENCE — a vmapped seed cohort reproduces each seed's
    solo run bit for bit;
  * HOST AGREEMENT — on the quick-grid configs the scan engine's
    turnaround table and headline counters equal the host engine's
    (the engines share every decision rule; only float accumulation
    order and the FIFO tie-break on exactly equal submit times differ,
    neither of which these workloads excite);
  * the frozen ``engine_ref`` anchor for the HOST engine lives in
    ``tests/test_sweep.py`` and is unaffected by any of this.
"""
import dataclasses
import math
from pathlib import Path

import numpy as np
import pytest

from repro.control import TenancyConfig
from repro.core.uncertainty import CalibrationConfig
from repro.obs import ObsConfig
from repro.sim import (ClusterConfig, SimConfig, WorkloadConfig, generate,
                       run_sim)
from repro.sim.scenarios import make_config
from repro.sim.step import run_cohort_scan, run_sim_scan

WL = WorkloadConfig(n_apps=24, max_components=6, max_runtime=1200.0,
                    mean_burst_gap=4.0, mean_long_gap=60.0, seed=7)
CL = ClusterConfig(n_hosts=3, max_running_apps=16)
BASE = SimConfig(cluster=CL, workload=WL, max_ticks=3000)


def _results_equal(a, b) -> bool:
    return a.first_difference(b) is None


# ----------------------------------------------------------------------
# chunk invariance: chunk=1 == chunk=32, bit for bit
# ----------------------------------------------------------------------

@pytest.mark.parametrize("policy,forecaster", [
    ("baseline", "persist"),
    ("pessimistic", "persist"),
    ("pessimistic", "oracle"),
    ("optimistic", "oracle"),
])
def test_chunk_invariance(policy, forecaster):
    cfg = dataclasses.replace(BASE, policy=policy, forecaster=forecaster)
    wl = generate(cfg.workload)
    r1 = run_sim_scan(cfg, wl, chunk=1)
    r32 = run_sim_scan(cfg, wl, chunk=32)
    assert _results_equal(r1, r32)


def test_chunk_invariance_with_calibration():
    cfg = dataclasses.replace(
        BASE, policy="pessimistic", forecaster="persist",
        calibration=CalibrationConfig(enabled=True, adaptive=True))
    wl = generate(cfg.workload)
    r1 = run_sim_scan(cfg, wl, chunk=1)
    r32 = run_sim_scan(cfg, wl, chunk=32)
    assert _results_equal(r1, r32)
    assert r1.calibration == r32.calibration


def test_chunk_invariance_checkpoint_mode():
    cfg = dataclasses.replace(BASE, policy="pessimistic",
                              forecaster="oracle", work_lost_on_kill=False)
    wl = generate(cfg.workload)
    assert _results_equal(run_sim_scan(cfg, wl, chunk=1),
                          run_sim_scan(cfg, wl, chunk=32))


# ----------------------------------------------------------------------
# vmapped cohort == solo runs, bit for bit, per seed
# ----------------------------------------------------------------------

def test_cohort_matches_solo_runs():
    cfg = dataclasses.replace(BASE, policy="pessimistic",
                              forecaster="persist")
    seeds = [0, 1, 2, 3]
    cohort = run_cohort_scan(cfg, seeds, chunk=16)
    assert len(cohort) == len(seeds)
    for seed, res in zip(seeds, cohort):
        solo_cfg = dataclasses.replace(
            cfg, workload=dataclasses.replace(cfg.workload, seed=seed))
        solo = run_sim_scan(solo_cfg, chunk=16)
        assert _results_equal(solo, res), f"seed {seed} diverged"


def test_cohort_rejects_mismatched_shapes():
    cfg = dataclasses.replace(BASE, policy="baseline", forecaster="persist")
    wls = [generate(dataclasses.replace(cfg.workload, seed=0)),
           generate(dataclasses.replace(cfg.workload, seed=1,
                                        n_apps=WL.n_apps + 1))]
    with pytest.raises(ValueError, match="shape"):
        run_cohort_scan(cfg, [0, 1], wls=wls)


# Float sums the compiler may order: a one-hot sum (exact in any order)
# and the pattern-set cotangents of the Pallas Gram's VJP, which the GP,
# differentiating only its hyper-parameters, never reads.
_COMPILER_ORDERED_OK = {("fixed_order.py", "onehot_sum"), ("ops.py", "gram")}
_SUMMING = {"reduce_sum", "reduce_prod", "dot_general", "cumsum", "cumprod",
            "cumlogsumexp", "cholesky", "triangular_solve"}


@pytest.mark.parametrize("variant", ["bucketed", "unbucketed", "leap"])
def test_gp_tick_sums_in_a_fixed_order(monkeypatch, variant):
    """The paper's GP tick, with the Pallas Gram selected as on a TPU,
    holds no float sum whose order is left to the compiler outside
    ``_COMPILER_ORDERED_OK``.  A TPU compiler orders a ``reduce`` (and a
    library Cholesky, solve or matvec) by layout, which differs between
    a solo program and a vmapped cohort or sharded fleet, so the members
    would part from their solo runs in the last bit there; the CPU runs
    of this file cannot see that."""
    import jax
    import jax.numpy as jnp
    from jax._src import source_info_util

    from repro.kernels import ops
    from repro.sim import step
    from repro.sim.state import init_state
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(SimConfig(), leap=variant == "leap")
    wl = generate(dataclasses.replace(cfg.workload, n_apps=8))
    tr = step._device_trace([wl], batched=False)
    st = init_state(cfg, wl.n_apps, wl.max_components)
    bucket = 8 if variant == "bucketed" else None
    run_chunk, _ = step._chunk_body(cfg, step._make_model(cfg), 1, bucket)
    args = (tr, st, jnp.int32(1)) if cfg.leap else (tr, st)
    bad = set()

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":   # the kernel's own order
                continue
            x = e.invars[0].aval if e.invars else None
            if (e.primitive.name in _SUMMING
                    and jnp.issubdtype(x.dtype, jnp.floating)
                    and not (e.primitive.name == "reduce_sum" and math.prod(
                        x.shape[a] for a in e.params["axes"]) <= 2)):
                f = source_info_util.user_frame(e.source_info.traceback)
                where = (f.file_name.rsplit("/", 1)[-1], f.function_name)
                if where not in _COMPILER_ORDERED_OK:
                    bad.add((e.primitive.name, x.shape, *where, f.start_line))
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub)

    walk(jax.make_jaxpr(run_chunk)(*args).jaxpr)
    assert not bad, sorted(bad)


# ----------------------------------------------------------------------
# scan engine vs host engine
# ----------------------------------------------------------------------

@pytest.mark.parametrize("policy,forecaster", [
    ("baseline", "persist"),
    ("pessimistic", "persist"),
    ("pessimistic", "oracle"),
    ("optimistic", "oracle"),
])
def test_scan_agrees_with_host_engine(policy, forecaster):
    cfg = dataclasses.replace(BASE, policy=policy, forecaster=forecaster)
    wl = generate(cfg.workload)
    scan = run_sim_scan(cfg, wl, chunk=32)
    host = run_sim(cfg, wl)
    assert scan.turnaround == host.turnaround
    s, h = scan.summary(), host.summary()
    for k in ("completed", "failed_frac", "failure_events", "oom_kills",
              "full_preemptions", "partial_preemptions", "sim_hours"):
        assert s[k] == h[k], (k, s[k], h[k])
    # telemetry ratios differ only in reduction order
    np.testing.assert_allclose(scan.util_mem, host.util_mem, rtol=1e-5)
    np.testing.assert_allclose(scan.slack_mem, host.slack_mem, rtol=1e-5)


def test_scan_agrees_with_host_engine_gp_gated():
    """The gp/arima model call is gated on any(ready) inside the fused
    tick (PR-5 forecast gating): the gate must not change results vs
    the host engine, and the forecast_rows telemetry must report the
    masked-batch load without leaking into summary()."""
    cfg = dataclasses.replace(
        BASE, policy="pessimistic", forecaster="gp",
        workload=dataclasses.replace(WL, n_apps=12))
    wl = generate(cfg.workload)
    scan = run_sim_scan(cfg, wl, chunk=16)
    host = run_sim(cfg, wl)
    assert scan.turnaround == host.turnaround
    s, h = scan.summary(), host.summary()
    for k in ("completed", "failed_frac", "failure_events", "oom_kills",
              "full_preemptions", "partial_preemptions", "sim_hours"):
        assert s[k] == h[k], (k, s[k], h[k])
    # telemetry ratios differ only in reduction order (module doc)
    np.testing.assert_allclose(scan.util_mem, host.util_mem, rtol=1e-5)
    fr = scan.forecast_rows
    assert fr is not None and host.forecast_rows is None
    assert fr["rows_batch"] == 2 * CL.max_running_apps * WL.max_components
    assert 0 < fr["ticks_forecasting"] <= fr["ticks"]
    assert "forecast_rows" not in scan.summary()


def test_scan_agrees_with_host_engine_calibrated():
    cfg = dataclasses.replace(
        BASE, policy="pessimistic", forecaster="persist",
        calibration=CalibrationConfig(enabled=True))
    wl = generate(cfg.workload)
    scan = run_sim_scan(cfg, wl, chunk=8)
    host = run_sim(cfg, wl)
    assert scan.turnaround == host.turnaround
    for k in ("resolved", "miscovered", "dropped", "coverage",
              "scores_recorded"):
        assert scan.calibration[k] == host.calibration[k], k


def test_scan_max_ticks_truncation_matches_host():
    """The tick budget must cut the scan at EXACTLY max_ticks even when
    the chunk size does not divide it."""
    cfg = dataclasses.replace(BASE, policy="pessimistic",
                              forecaster="persist", max_ticks=10)
    wl = generate(cfg.workload)
    scan = run_sim_scan(cfg, wl, chunk=32)
    host = run_sim(cfg, wl)
    assert scan.sim_time == host.sim_time
    assert len(scan.util_cpu) == len(host.util_cpu) == 10
    assert scan.turnaround == host.turnaround


# ----------------------------------------------------------------------
# leap engine: event-driven ticks == uniform ticks, bit for bit
# ----------------------------------------------------------------------

@pytest.mark.parametrize(
    "family", ["google", "diurnal", "flashcrowd", "heavytail", "colocated"])
def test_leap_matches_uniform_every_family(family):
    """``SimConfig.leap=True`` skips provably-idle tick runs with a
    scalar while_loop that accumulates time EXACTLY like the uniform
    engine (``t + float32(tick)`` per skipped tick) — so summaries,
    turnaround tables, per-tick telemetry, tenancy counters AND the
    drained obs ring histories must all be bit-identical, on every
    scenario family, with the control plane and rings both live."""
    cfg = dataclasses.replace(
        BASE, policy="pessimistic", forecaster="persist",
        workload=make_config(family, base=WL, n_apps=16, n_tenants=3),
        control=TenancyConfig(enabled=True),
        obs=ObsConfig(enabled=True))
    uni = run_sim_scan(cfg, chunk=16)
    leap = run_sim_scan(dataclasses.replace(cfg, leap=True), chunk=16)
    assert _results_equal(uni, leap)
    assert uni.tenancy == leap.tenancy
    assert uni.obs is not None and uni.obs.keys() == leap.obs.keys()
    for name in uni.obs:
        assert np.array_equal(uni.obs[name], leap.obs[name]), name


def test_leap_chunk_invariance_with_calibration():
    """Leap budgets ride in the scan carry (``left``), not in last-chunk
    slicing — chunking must still not matter, including for the
    conformal calibration counters."""
    cfg = dataclasses.replace(
        BASE, policy="pessimistic", forecaster="persist", leap=True,
        calibration=CalibrationConfig(enabled=True, adaptive=True))
    wl = generate(cfg.workload)
    r1 = run_sim_scan(cfg, wl, chunk=1)
    r32 = run_sim_scan(cfg, wl, chunk=32)
    assert _results_equal(r1, r32)
    assert r1.calibration == r32.calibration


def test_leap_cohort_matches_solo_runs():
    cfg = dataclasses.replace(BASE, policy="pessimistic",
                              forecaster="persist", leap=True)
    seeds = [0, 1]
    cohort = run_cohort_scan(cfg, seeds, chunk=16)
    for seed, res in zip(seeds, cohort):
        solo_cfg = dataclasses.replace(
            cfg, workload=dataclasses.replace(cfg.workload, seed=seed))
        assert _results_equal(run_sim_scan(solo_cfg, chunk=16), res), seed


def test_leap_max_ticks_truncation_matches_uniform():
    """A budget that runs out mid-idle-gap must still yield EXACTLY
    max_ticks of history (the truncated tail of a leap is re-expanded
    into zero ticks, same as the uniform engine's idle ticks)."""
    cfg = dataclasses.replace(BASE, policy="pessimistic",
                              forecaster="persist", max_ticks=10)
    wl = generate(cfg.workload)
    uni = run_sim_scan(cfg, wl, chunk=32)
    leap = run_sim_scan(dataclasses.replace(cfg, leap=True), wl, chunk=32)
    assert uni.sim_time == leap.sim_time
    assert len(leap.util_cpu) == 10
    assert _results_equal(uni, leap)


# ----------------------------------------------------------------------
# ragged bucketed forecast batching (gp / arima)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("forecaster", ["gp", "arima"])
def test_bucketed_forecast_agrees_with_host_engine(forecaster):
    """The bucketed path compacts forecast-ready monitor rows into
    power-of-2 passes; per-row model independence (the documented
    ``forecast_peaks`` contract) makes it bit-identical to the full
    padded batch — and hence to the host engine."""
    cfg = dataclasses.replace(
        BASE, policy="pessimistic", forecaster=forecaster,
        workload=dataclasses.replace(WL, n_apps=12))
    wl = generate(cfg.workload)
    scan = run_sim_scan(cfg, wl, chunk=16)
    host = run_sim(cfg, wl)
    assert scan.turnaround == host.turnaround
    s, h = scan.summary(), host.summary()
    for k in ("completed", "failed_frac", "failure_events", "oom_kills",
              "full_preemptions", "partial_preemptions", "sim_hours"):
        assert s[k] == h[k], (k, s[k], h[k])
    # the telemetry proves the bucket engaged: the model computed fewer
    # rows than ticks_forecasting * the full padded batch
    fr = scan.forecast_rows
    assert fr["rows_bucketed"] > 0
    assert fr["rows_bucketed"] < fr["ticks_forecasting"] * fr["rows_batch"]


def test_bucketed_forecast_off_is_bit_identical():
    cfg = dataclasses.replace(
        BASE, policy="pessimistic", forecaster="gp",
        workload=dataclasses.replace(WL, n_apps=12))
    wl = generate(cfg.workload)
    on = run_sim_scan(cfg, wl, chunk=16)
    off = run_sim_scan(dataclasses.replace(cfg, forecast_bucket=False),
                       wl, chunk=16)
    assert _results_equal(on, off)
    # off-path telemetry reports the full padded batch per stride
    assert off.forecast_rows["rows_bucketed"] == (
        off.forecast_rows["ticks_forecasting"]
        * off.forecast_rows["rows_batch"])


def test_bucketed_forecast_chunk_invariance():
    """The bucket is re-chosen per chunk from the host snapshot — an odd
    chunk size exercises different bucket sequences, yet results must
    stay bit-identical."""
    cfg = dataclasses.replace(
        BASE, policy="pessimistic", forecaster="gp",
        workload=dataclasses.replace(WL, n_apps=12))
    wl = generate(cfg.workload)
    assert _results_equal(run_sim_scan(cfg, wl, chunk=7),
                          run_sim_scan(cfg, wl, chunk=32))


def test_leap_with_bucketed_gp_matches_uniform_unbucketed():
    """Both tentpole paths composed vs neither: still bit-identical."""
    cfg = dataclasses.replace(
        BASE, policy="pessimistic", forecaster="gp",
        workload=dataclasses.replace(WL, n_apps=12),
        calibration=CalibrationConfig(enabled=True))
    wl = generate(cfg.workload)
    plain = run_sim_scan(
        dataclasses.replace(cfg, forecast_bucket=False), wl, chunk=16)
    fast = run_sim_scan(
        dataclasses.replace(cfg, leap=True), wl, chunk=16)
    assert _results_equal(plain, fast)
    assert plain.calibration == fast.calibration


def _every_tile(model):
    """chip_smoke.py's ``EveryTile``: the model with its packed batches
    run as plain ones, every tile run."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    return smoke.EveryTile(model)


def test_packed_tiles_match_every_tile_in_a_cohort_chunk():
    """A GP cohort chunk of the bucketed path, whose members need
    different numbers of passes, gives the same state and per-tick
    totals, bit for bit, with the tile loop packing the cohort's ready
    rows as with every tile run; the rows offered
    (``forecast_rows_done``) do not move, and the tiles run hold every
    ready row and no more than the members' rows offered."""
    import jax

    from repro.sim import step
    from repro.sim.state import init_state
    cfg = dataclasses.replace(
        BASE, policy="pessimistic", forecaster="gp",
        workload=dataclasses.replace(WL, n_apps=12))
    wls = [generate(dataclasses.replace(cfg.workload, seed=s))
           for s in (0, 1, 2)]
    tr = step._device_trace(wls, batched=True)
    st = init_state(cfg, wls[0].n_apps, wls[0].max_components, batch=3)
    model = step._make_model(cfg)

    def program(m):
        return jax.jit(jax.vmap(step._chunk_body(cfg, m, 16, 8)[0]))

    packed, every = program(model), program(_every_tile(model))
    st, _ = packed(tr, st)              # past the grace period
    st_p, ms_p = packed(tr, st)
    st_e, ms_e = every(tr, st)
    for a, b in zip(jax.tree.leaves(st_p), jax.tree.leaves(st_e)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for f in dataclasses.fields(ms_p):
        if f.name != "forecast_tile_rows":
            np.testing.assert_array_equal(getattr(ms_p, f.name),
                                          getattr(ms_e, f.name), f.name)
    ready = np.asarray(ms_p.forecast_rows)          # (members, ticks)
    done = np.asarray(ms_p.forecast_rows_done)
    tiles = np.asarray(ms_p.forecast_tile_rows).max(axis=0)
    assert (done.min(0) < done.max(0)).any()        # members part ways
    assert (tiles % 16 == 0).all() and (tiles >= ready.sum(0)).all()
    assert (tiles <= 3 * done.max(0)).all()
    assert tiles.sum() < 3 * done.max(0).sum()


def test_unbucketed_cohort_chunk_matches_solo_chunks():
    """The un-bucketed GP chunk program (``bucket=None``: the whole
    monitor table offered, its ready rows live) builds and runs under
    the cohort vmap, and each member's state and per-tick totals equal
    its solo chunks bit for bit; a member is offered all ``2 * AC`` rows
    on a tick with a ready row and none on other ticks, and the tiles run
    hold the cohort's ready rows, rounded up to whole tiles."""
    import jax

    from repro.sim import step
    from repro.sim.state import init_state
    cfg = dataclasses.replace(
        BASE, policy="pessimistic", forecaster="gp",
        workload=dataclasses.replace(WL, n_apps=12))
    wls = [generate(dataclasses.replace(cfg.workload, seed=s))
           for s in (0, 1, 2)]
    shapes = step._shapes_key(wls[0], cfg)
    N, C = wls[0].n_apps, wls[0].max_components
    AC = cfg.cluster.max_running_apps * C

    def chunks(cohort, tr, st):
        fn = step._chunk_fn(cfg, 16, shapes, cohort, None)
        parts = []
        for _ in range(2):                  # the second past the grace
            st, ms = fn(tr, st)
            parts.append(ms)
        return jax.device_get(st), step._concat_metrics(parts, int(cohort))

    st_c, ms_c = chunks(True, step._device_trace(wls, batched=True),
                        init_state(cfg, N, C, batch=len(wls)))
    for m, wl in enumerate(wls):
        st_s, ms_s = chunks(False, step._device_trace([wl], batched=False),
                            init_state(cfg, N, C))
        for a, b in zip(jax.tree.leaves(st_c), jax.tree.leaves(st_s)):
            np.testing.assert_array_equal(np.asarray(a)[m], np.asarray(b))
        for f in dataclasses.fields(ms_c):
            if f.name != "forecast_tile_rows":
                np.testing.assert_array_equal(
                    getattr(ms_c, f.name)[m], getattr(ms_s, f.name), f.name)
    ready = np.asarray(ms_c.forecast_rows)          # (members, ticks)
    done = np.asarray(ms_c.forecast_rows_done)
    tiles = np.asarray(ms_c.forecast_tile_rows)
    assert ready.any()
    np.testing.assert_array_equal(done, np.where(ready > 0, 2 * AC, 0))
    np.testing.assert_array_equal(tiles.min(0), tiles.max(0))
    np.testing.assert_array_equal(tiles[0], -(-ready.sum(0) // 16) * 16)


# ----------------------------------------------------------------------
# sweep integration: engine="scan" cohort fast path
# ----------------------------------------------------------------------

def test_sweep_scan_engine_matches_solo_scan_runs():
    from repro.sim.sweep import quick_base_config, run_grid
    base = quick_base_config(n_apps=24, n_hosts=3, seed=0)
    res = run_grid(base, axes={"policy": ["baseline", "pessimistic"],
                               "forecaster": ["persist"]},
                   seeds=[0, 1], engine="scan")
    assert len(res.cells) == 4
    assert res.forecast_batches == 0        # batcher retired
    for cell in res.cells:
        cfg = base
        for k, v in cell["overrides"].items():
            cfg = dataclasses.replace(cfg, **{k: v})
        cfg = dataclasses.replace(
            cfg, workload=dataclasses.replace(cfg.workload,
                                              seed=cell["seed"]))
        assert run_sim_scan(cfg).summary() == cell["summary"]


def test_sweep_scan_engine_heterogeneous_cells():
    """Cells that share a combo name but are not seed-homogeneous fall
    back to solo scan runs (still correct, just unbatched)."""
    from repro.sim.sweep import quick_base_config, run_grid
    base = quick_base_config(n_apps=16, n_hosts=2, seed=0)
    res = run_grid(base, axes={"policy": ["pessimistic"],
                               "forecaster": ["persist"]},
                   seeds=[3], engine="scan")
    assert len(res.cells) == 1
    cfg = dataclasses.replace(
        base, policy="pessimistic", forecaster="persist",
        workload=dataclasses.replace(base.workload, seed=3))
    assert run_sim_scan(cfg).summary() == res.cells[0]["summary"]


# ----------------------------------------------------------------------
# barrier batch mode: idle ticks no longer pay the leader timeout
# ----------------------------------------------------------------------

def test_barrier_idle_signal_completes_cohort(monkeypatch):
    """A leader whose cohort peers tick WITHOUT requesting must return
    as soon as their idle signals arrive — not after the barrier
    timeout."""
    import threading
    import time

    from repro.sim import sweep as SW

    # stub the forecast: this test times the BARRIER, not the model
    monkeypatch.setattr(
        SW, "forecast_peaks",
        lambda model, horizon, w, v: (w[:, -1], w.var(axis=1) + 1e-6))
    batcher = SW.ForecastBatcher(mode="barrier", barrier_timeout_s=30.0)
    cfg = dataclasses.replace(SW.quick_base_config(), forecaster="gp")
    requester = batcher.client(cfg)
    idler = batcher.client(cfg)
    wins = np.zeros((2, cfg.window), np.float32)
    val = np.ones((2, cfg.window), bool)
    out = {}

    def lead():
        out["result"] = requester(wins, val)

    t = threading.Thread(target=lead)
    t0 = time.monotonic()
    t.start()
    time.sleep(0.05)
    idler.idle()                      # the second sim's tick needs nothing
    t.join(timeout=10.0)
    elapsed = time.monotonic() - t0
    assert not t.is_alive(), "leader never returned"
    assert elapsed < 5.0, f"leader waited the barrier timeout ({elapsed})"
    assert out["result"][0].shape == (2,)
    requester.close()
    idler.close()