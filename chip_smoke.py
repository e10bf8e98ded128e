#!/usr/bin/env python3
"""On-chip smoke test: the paper's simulation on a TPU, end to end.

Runs the paper's own configuration, ``SimConfig()`` unchanged: 50 hosts
of 32 CPU / 128 GB, a 128-slot table, 12 components per app, a 24-tick
monitor window, the GP forecaster (h=10, N=10, 10 Adam steps) and the
pessimistic policy over the 500-app ``google`` workload.  Everything
goes through the normal entry points (``run_grid``, ``run_sim_scan``,
``run_cohort_scan``, ``run_sim``, ``run_fleet_shard``) in one process,
and every phase checks what comes out:

  * full run: ``run_grid(engine="scan")`` on a cohort of 4 seeds, up to
    ``FULL_TICKS`` ticks (it stops earlier once every app completed);
    every member must complete apps with finite turnaround and
    utilization, and the first GP chunk program it compiled must hold
    the Pallas Gram kernel (``tpu_custom_call``);
  * on a ``SLICE_TICKS`` slice of seed 0: bucketed == unbucketed,
    leap == uniform and cohort member == solo run, bit for bit, and
    scan == host engine on the GP agreement contract of
    ``tests/test_scan_engine.py`` (equal turnaround and headline
    counters);
  * the Pallas Gram against ``ref.gram`` on the chip, and the GP and
    ARIMA forecasts on the chip against the same call on the host CPU,
    within the tolerances stated below;
  * a 4-seed cohort chunk of the bucketed GP path after
    ``PACK_AFTER`` chunks: the tile loop packing the cohort's ready rows into
    the fewest tiles == the same chunk with every tile run
    (``EveryTile``), state and per-tick totals bit for bit.

``--chips 4`` runs only the sharded fleet: ``run_fleet_shard`` over 8
seeds on a 4-device mesh against ``mesh=1``, member by member, and
checks that the arrays the mesh=4 run placed span the 4 devices.

Time, compile seconds and ticks/s lines are informational.  The script
exits non-zero, printing no result line, when JAX finds no TPU, when
the repository's ``src`` is not next to it, or when any check fails or
any phase raises.  Otherwise the last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Usage::

    python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

COHORT_SEEDS = (0, 1, 2, 3)
FLEET_SEEDS = tuple(range(8))
FULL_TICKS = 3000       # the full run's budget; run_grid's default chunk
CHUNK = 32
# comparisons run on a slice of seed 0 as ONE chunk: the bucketed path
# then keeps its first bucket and absorbs ready-row growth in extra
# passes, so each variant compiles one program
SLICE_TICKS = 300
# chunks run before the packing check's chunk: most slots are then past
# the grace period, and members need different numbers of passes
PACK_AFTER = 4

GRAM_BATCH = 256        # GP rows per Gram check (a forecast bucket)
# Pallas Gram vs ref.gram, both on the chip.  Off the diagonal the two
# differ by rounding only.  At zero distance the |a|^2 + |b|^2 - 2 a.b
# identity cancels: d2 is f32 rounding, ~1e-6 |a|^2, and r = sqrt(d2)
# turns it into ~1e-3 |a|; with |a|^2 ~ 11 (D = h + 1) and ell = 0.7 the
# exp kernel's diagonal moves by up to ~1e-2 relative (4e-3 seen between
# the two on a CPU).  A one-pass bf16 product would move it by ~0.3.
GRAM_RTOL, GRAM_ATOL = 1e-4, 1e-6
GRAM_DIAG_RTOL = 2e-2
# GP / ARIMA forecast_batch on the chip vs the host CPU, every (row,
# horizon step) of the mean and of the variance:
# rel = |chip - cpu| / (|cpu| + FC_FLOOR), in the series' own units.
# The median tests precision, the max is a sanity bound.  Read on a CPU
# for the GP at FC_ROWS = 1024 rows x 3 steps (PERF.md): a 1-ulp input
# change moves the median by 1.1e-7 (mean) / 5.0e-6 (var) and the worst
# element by 2.5e-2 / 8.5e-2 (10 Adam steps amplify it); bf16-rounded
# inputs, the size of a one-pass bf16 matmul's error, move the median
# by 7.1e-4 / 1.0e-2.
FC_FLOOR = 1e-3
FC_MEDIAN_REL = {"mean": 1e-4, "var": 1e-3}
FC_MAX_REL = {"mean": 0.25, "var": 1.0}
FC_ROWS = 1024
# the op a Mosaic (Pallas TPU) kernel lowers to in a compiled program
KERNEL_OP = "tpu_custom_call"

# the scan vs host contract's headline counters
HOST_KEYS = ("completed", "failed_frac", "failure_events", "oom_kills",
             "full_preemptions", "partial_preemptions", "sim_hours")


def base_config():
    """The paper's configuration, unchanged."""
    from repro.sim import SimConfig
    return SimConfig()


class Smoke:
    """Runs phases, prints one PASS/FAIL line per check, remembers
    every failure (a raising phase is a failure too)."""

    def __init__(self):
        self.failed: list[str] = []

    def check(self, name: str, ok: bool, detail: str = "") -> None:
        print(f"{'PASS' if ok else 'FAIL'} {name}"
              + (f" -- {detail}" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)

    def phase(self, name: str, fn, *args):
        t0 = time.perf_counter()
        try:
            return fn(self, *args)
        except Exception:
            traceback.print_exc()
            sys.stderr.flush()
            self.check(f"phase {name} ran", False, "raised (traceback above)")
        finally:
            print(f"# phase {name}: {time.perf_counter() - t0:.1f} s",
                  flush=True)


def _compile_s() -> float:
    """First-call seconds of every scan/shard chunk program so far."""
    from repro.obs import REGISTRY
    return sum(REGISTRY.histogram(m).snapshot()["sum"]
               for m in ("scan.compile_s", "shard.compile_s"))


def _same(sm: Smoke, name: str, a, b) -> None:
    d = a.first_difference(b)
    sm.check(name, d is None, d or "bit-identical")


def _rel(chip, cpu):
    import numpy as np
    return np.abs(chip - cpu) / (np.abs(cpu) + FC_FLOOR)


# ----------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------

def gram_on_chip(sm: Smoke) -> None:
    """Pallas Gram == ref.gram, both on the chip, at the GP's shapes."""
    import jax
    import numpy as np

    from repro.kernels import ops
    rng = np.random.default_rng(0)
    X = rng.standard_normal((GRAM_BATCH, 10, 11)).astype(np.float32)
    xs = rng.standard_normal((GRAM_BATCH, 1, 11)).astype(np.float32)
    ell, sf = 0.7, 1.3
    for kind in ("exp", "rbf"):
        def grams(impl):
            f = jax.jit(jax.vmap(lambda a, b: ops.gram(
                a, b, ell, sf, kind=kind, impl=impl)))
            return np.asarray(f(X, X)), np.asarray(f(xs, X))
        (kp, cp), (kr, cr) = grams("pallas"), grams("jnp")
        diag = np.broadcast_to(np.eye(10, dtype=bool), kp.shape)
        off = np.abs(kp - kr)[~diag]
        tol_off = (GRAM_ATOL + GRAM_RTOL * np.abs(kr))[~diag]
        cross_ok = np.all(np.abs(cp - cr) <= GRAM_ATOL + GRAM_RTOL * np.abs(cr))
        d_rel = np.abs(kp - kr)[diag] / np.abs(kr[diag])
        sm.check(f"pallas gram ({kind}) == ref.gram on chip",
                 bool(np.all(off <= tol_off) and cross_ok
                      and np.all(d_rel <= GRAM_DIAG_RTOL)),
                 f"off-diag max |d| {off.max():.3e}, cross max |d| "
                 f"{np.abs(cp - cr).max():.3e} (rtol {GRAM_RTOL}, atol "
                 f"{GRAM_ATOL}); diag max rel {d_rel.max():.3e} "
                 f"(rtol {GRAM_DIAG_RTOL})")


def _windows(n: int, width: int):
    """(n, width) monitor-like windows: piecewise-linear utilization
    profiles times a request, with partly filled (young) series."""
    import numpy as np
    rng = np.random.default_rng(1)
    req = rng.uniform(0.5, 32.0, n)
    knots = np.clip(np.cumsum(rng.normal(0.0, 0.15, (n, 8)), 1)
                    + rng.uniform(0.3, 0.9, (n, 1)), 0.1, 1.0)
    x = np.linspace(0.0, 1.0, width)
    start = rng.uniform(0.0, 0.6, (n, 1))
    prog = np.clip(start + rng.uniform(0.1, 0.4, (n, 1)) * x, 0.0, 1.0) * 7
    s0 = np.minimum(prog.astype(int), 6)
    fr = prog - s0
    lv = (np.take_along_axis(knots, s0, 1) * (1 - fr)
          + np.take_along_axis(knots, s0 + 1, 1) * fr)
    count = rng.integers(10, width + 1, n)
    valid = np.arange(width)[None, :] >= (width - count)[:, None]
    return np.where(valid, lv * req[:, None], 0.0).astype(np.float32), valid


def forecasts_vs_cpu(sm: Smoke) -> None:
    """GP and ARIMA ``forecast_batch`` on the chip vs the host CPU."""
    import jax
    import numpy as np

    from repro.core.forecast import ARIMAForecaster, GPForecaster
    cfg = base_config()
    w, v = _windows(FC_ROWS, cfg.window)
    cpu = jax.devices("cpu")[0]
    # on a CPU, impl="auto" is the jnp reference: that is "the same call"
    pairs = {
        "gp": (GPForecaster(cfg.gp),
               GPForecaster(dataclasses.replace(cfg.gp, impl="jnp"))),
        "arima": (ARIMAForecaster(cfg.arima), ARIMAForecaster(cfg.arima)),
    }
    for name, (m_chip, m_cpu) in pairs.items():
        def run(model, dev, rows=FC_ROWS):
            f = jax.jit(lambda w, v: model.forecast_batch(
                w, cfg.horizon, valid=v))
            fc = f(jax.device_put(w[:rows], dev),
                   jax.device_put(v[:rows], dev))
            return {"mean": np.asarray(fc.mean), "var": np.asarray(fc.var)}
        chip = run(m_chip, jax.devices()[0])
        host = run(m_cpu, cpu)
        # informational: the same call with every f32 matmul at full
        # precision, to tell a matmul-precision deviation from the rest
        with jax.default_matmul_precision("highest"):
            chip_hi = run(m_chip, jax.devices()[0])
        # informational: the engines' bit-identity contracts (bucketed,
        # cohort, mesh) need a row's forecast not to depend on its batch
        few = run(m_chip, jax.devices()[0], rows=8)
        d = max(float(np.abs(few[k] - chip[k][:8]).max()) for k in few)
        print(f"# {name} on chip: 8 rows alone vs in the {FC_ROWS}-row "
              f"batch: {'bit-identical' if d == 0 else f'max |d| {d:.3e}'}",
              flush=True)
        for what in ("mean", "var"):
            c, h = chip[what], host[what]
            r, r_hi = _rel(c, h), _rel(chip_hi[what], h)
            sm.check(f"{name} forecast {what}: chip == cpu",
                     bool(np.all(np.isfinite(c))
                          and np.median(r) <= FC_MEDIAN_REL[what]
                          and r.max() <= FC_MAX_REL[what]),
                     f"{r.size} values {c.shape}, median rel "
                     f"{np.median(r):.3e} (<= {FC_MEDIAN_REL[what]}), p99 "
                     f"{np.quantile(r, 0.99):.3e}, max {r.max():.3e} "
                     f"(<= {FC_MAX_REL[what]}); at matmul precision "
                     f"highest: median {np.median(r_hi):.3e}, max "
                     f"{r_hi.max():.3e}")


def full_run(sm: Smoke) -> list:
    """The paper's configuration on a 4-seed scan cohort via run_grid.
    Returns the keys of the chunk programs the run compiled."""
    from repro.sim import step
    from repro.sim.sweep import run_grid
    cfg = dataclasses.replace(base_config(), max_ticks=FULL_TICKS)
    print(f"# full run: SimConfig() {cfg.forecaster}/{cfg.policy}, "
          f"{cfg.workload.n_apps} apps, {cfg.cluster.n_hosts} hosts, "
          f"A={cfg.cluster.max_running_apps} C={cfg.workload.max_components}"
          f" W={cfg.window}, seeds {list(COHORT_SEEDS)}, budget "
          f"{FULL_TICKS} ticks, chunk {CHUNK}", flush=True)
    c0, before = _compile_s(), set(step._CHUNK_CACHE)
    t0 = time.perf_counter()
    res = run_grid(cfg, seeds=COHORT_SEEDS, engine="scan", chunk=CHUNK,
                   forecast_diag=False)
    wall = time.perf_counter() - t0
    comp = _compile_s() - c0
    sm.check("full run used the scan engine", res.engine == "scan",
             res.engine)
    ticks = 0
    for cell in res.cells:
        s = cell["summary"]
        n = round(s["sim_hours"] * 3600.0 / cfg.cluster.tick)
        ticks += n
        finite = all(math.isfinite(s[k]) for k in
                     ("turnaround_mean", "util_cpu_mean", "util_mem_mean"))
        sm.check(f"full run seed {cell['seed']}: completed > 0, finite "
                 "turnaround and utilization",
                 s["completed"] > 0 and finite,
                 f"{n} ticks, completed {s['completed']}/{s['n_apps']}, "
                 f"turnaround_mean {s['turnaround_mean']!r} s, util_cpu "
                 f"{s['util_cpu_mean']!r}, util_mem {s['util_mem_mean']!r},"
                 f" failed_frac {s['failed_frac']!r}")
    print(f"# full run: compile {comp:.1f} s, wall {wall:.1f} s, {ticks} "
          f"member-ticks, {ticks / max(wall - comp, 1e-9):.1f} member-"
          "ticks/s excluding compile", flush=True)
    # the chunk programs this run compiled, in the order it met them
    return [k for k in step._CHUNK_CACHE if k not in before]


def kernel_in_chunk_program(sm: Smoke, keys) -> None:
    """The first chunk program the full run compiled holds the Pallas
    kernel: that cached program, lowered again at the run's shapes."""
    import jax

    from repro.sim import step
    from repro.sim.scenarios.registry import build_trace
    from repro.sim.state import init_state
    cfg = dataclasses.replace(base_config(), max_ticks=FULL_TICKS)
    wls = [build_trace(dataclasses.replace(cfg.workload, seed=s))
           for s in COHORT_SEEDS]
    fn = step._CHUNK_CACHE[keys[0]].__wrapped__     # the jitted program
    tr = step._device_trace(wls, batched=True)
    st = jax.eval_shape(lambda: init_state(
        cfg, wls[0].n_apps, wls[0].max_components, batch=len(wls)))
    text = fn.lower(tr, st).compile().as_text()
    n = text.count(KERNEL_OP)
    sm.check("compiled GP chunk program holds the Pallas kernel", n > 0,
             f"{n} {KERNEL_OP} ops; program (chunk, shapes, cohort, "
             f"bucket) = {keys[0][1:]}")


def slice_comparisons(sm: Smoke) -> None:
    """Bit-identity contracts and scan vs host on a slice of seed 0."""
    import numpy as np

    from repro.sim import run_sim
    from repro.sim.scenarios.registry import build_trace
    from repro.sim.step import run_cohort_scan, run_sim_scan
    base = base_config()
    cfg = dataclasses.replace(
        base, max_ticks=SLICE_TICKS,
        workload=dataclasses.replace(base.workload, seed=COHORT_SEEDS[0]))
    wl = build_trace(cfg.workload)
    print(f"# slice: seed {cfg.workload.seed}, {SLICE_TICKS} ticks, one "
          "chunk", flush=True)
    t0 = time.perf_counter()
    solo = run_sim_scan(cfg, wl, chunk=SLICE_TICKS)
    print(f"# slice solo scan: {time.perf_counter() - t0:.1f} s, "
          f"{solo.summary()['completed']} completed", flush=True)
    unb = run_sim_scan(dataclasses.replace(cfg, forecast_bucket=False),
                       wl, chunk=SLICE_TICKS)
    _same(sm, "bucketed == unbucketed", solo, unb)
    leap = run_sim_scan(dataclasses.replace(cfg, forecast_bucket=False,
                                            leap=True),
                        wl, chunk=SLICE_TICKS)
    _same(sm, "leap == uniform (unbucketed)", leap, unb)
    cohort = run_cohort_scan(cfg, COHORT_SEEDS, chunk=SLICE_TICKS)
    _same(sm, f"cohort member (seed {COHORT_SEEDS[0]} of "
              f"{len(COHORT_SEEDS)}) == solo", cohort[0], solo)
    t0 = time.perf_counter()
    host = run_sim(cfg, wl)
    print(f"# slice host engine: {time.perf_counter() - t0:.1f} s",
          flush=True)
    s, h = solo.summary(), host.summary()
    bad_ta = sorted(g for g in set(solo.turnaround) | set(host.turnaround)
                    if solo.turnaround.get(g) != host.turnaround.get(g))
    bad_keys = [k for k in HOST_KEYS if s[k] != h[k]]
    util_ok = (len(solo.util_mem) == len(host.util_mem)
               and np.allclose(solo.util_mem, host.util_mem, rtol=1e-5,
                               atol=0.0))
    # where the two runs part: the first tick whose running-app count
    # or memory utilization differs (the phase to look at next)
    n = min(len(solo.util_mem), len(host.util_mem))
    split = next((i for i in range(n)
                  if solo.n_running[i] != host.n_running[i]
                  or not np.isclose(solo.util_mem[i], host.util_mem[i],
                                    rtol=1e-5, atol=0.0)), None)
    sm.check("scan == host engine (turnaround, headline counters, "
             "util_mem rtol 1e-5)",
             not bad_ta and not bad_keys and util_ok,
             f"{len(solo.turnaround)} turnarounds, {len(bad_ta)} differ "
             f"{[(g, solo.turnaround.get(g), host.turnaround.get(g)) for g in bad_ta[:3]]}; "
             f"counters differ {[(k, s[k], h[k]) for k in bad_keys]}; "
             f"util_mem ok {util_ok}; first differing tick {split}")


class EveryTile:
    """The model with its packed batches run as plain ones: the tile loop
    runs every tile (``forecast.base.batched``) and counts none."""

    def __init__(self, model):
        self.model = model

    def forecast_packed(self, windows, horizon, *, valid=None, live):
        import jax.numpy as jnp
        return (self.model.forecast_batch(windows, horizon, valid=valid),
                jnp.int32(0))


def packed_tiles(sm: Smoke) -> None:
    """A 4-seed GP cohort chunk: packed tiles == every tile, bit for bit."""
    import jax
    import numpy as np

    from repro.sim import step
    from repro.sim.scenarios.registry import build_trace
    from repro.sim.state import init_state
    cfg = dataclasses.replace(base_config(), max_ticks=FULL_TICKS)
    wls = [build_trace(dataclasses.replace(cfg.workload, seed=s))
           for s in COHORT_SEEDS]
    tr = step._device_trace(wls, batched=True)
    st = init_state(cfg, wls[0].n_apps, wls[0].max_components,
                    batch=len(wls))
    shapes = step._shapes_key(wls[0], cfg)
    for _ in range(PACK_AFTER):
        st, _ = step._chunk_fn(cfg, CHUNK, shapes, True,
                               step._pick_bucket(cfg, st))(tr, st)
    bucket = step._pick_bucket(cfg, st)
    every = jax.jit(jax.vmap(step._chunk_body(
        cfg, EveryTile(step._make_model(cfg)), CHUNK, bucket)[0]))
    st_e, ms_e = jax.device_get(every(tr, st))
    # the engine's own program (it donates the state, so it runs last)
    st_p, ms_p = jax.device_get(
        step._chunk_fn(cfg, CHUNK, shapes, True, bucket)(tr, st))
    bad = [k for k, (a, b) in enumerate(zip(jax.tree.leaves(st_p),
                                            jax.tree.leaves(st_e)))
           if not np.array_equal(a, b, equal_nan=True)]
    bad += [f.name for f in dataclasses.fields(ms_p)
            if f.name != "forecast_tile_rows"
            and not np.array_equal(getattr(ms_p, f.name),
                                   getattr(ms_e, f.name))]
    tiles = int(np.asarray(ms_p.forecast_tile_rows).max(axis=0).sum())
    offered = int((len(wls) * np.asarray(ms_p.forecast_rows_done)
                   .max(axis=0)).sum())
    sm.check(f"cohort chunk from tick {PACK_AFTER * CHUNK}, bucket {bucket}: "
             "packed tiles == every tile", not bad,
             f"differing state leaves / tick totals {bad[:8]}; "
             f"{int(np.asarray(ms_p.forecast_rows).sum())} ready rows in "
             f"{tiles} tile rows run, {offered} rows offered")


def fleet_on_mesh(sm: Smoke) -> None:
    """Sharded fleet: mesh=4 == mesh=1 per member, and what the mesh=4
    run placed spans 4 devices."""
    import jax

    from repro.sim import step
    from repro.sim.scenarios.registry import build_trace
    cfg = dataclasses.replace(base_config(), max_ticks=SLICE_TICKS)
    wls = [build_trace(dataclasses.replace(cfg.workload, seed=s))
           for s in FLEET_SEEDS]
    print(f"# fleet: {len(FLEET_SEEDS)} seeds, {SLICE_TICKS} ticks, one "
          "chunk, mesh 4 vs mesh 1", flush=True)
    c0, t0 = _compile_s(), time.perf_counter()
    wide = step.run_fleet_shard(cfg, FLEET_SEEDS, chunk=SLICE_TICKS,
                                wls=wls, mesh=4)
    print(f"# fleet mesh=4: wall {time.perf_counter() - t0:.1f} s, compile "
          f"{_compile_s() - c0:.1f} s", flush=True)
    # the mesh=4 run's uploaded fleet trace stays cached on its devices:
    # read its layout before the mesh=1 run adds arrays of its own
    placed = [a for a in jax.live_arrays()
              if step.FLEET_AXIS in getattr(
                  getattr(a.sharding, "mesh", None), "axis_names", ())]
    devs = sorted({d.id for a in placed for d in a.sharding.device_set})
    rows = sorted({a.sharding.shard_shape(a.shape)[0] for a in placed})
    want = len(FLEET_SEEDS) // 4
    sm.check("the mesh=4 run's fleet arrays span 4 devices",
             bool(placed) and len(devs) == 4 and rows == [want],
             f"{len(placed)} arrays on devices {devs}, {rows} fleet rows "
             f"per device (want {want})")
    c0, t0 = _compile_s(), time.perf_counter()
    narrow = step.run_fleet_shard(cfg, FLEET_SEEDS, chunk=SLICE_TICKS,
                                  wls=wls, mesh=1)
    print(f"# fleet mesh=1: wall {time.perf_counter() - t0:.1f} s, compile "
          f"{_compile_s() - c0:.1f} s", flush=True)
    for s, a, b in zip(FLEET_SEEDS, wide, narrow):
        _same(sm, f"fleet member seed {s}: mesh=4 == mesh=1", a, b)


def run_phases(sm: Smoke, chips: int) -> None:
    if chips == 4:
        sm.phase("fleet on a 4-chip mesh", fleet_on_mesh)
        return
    sm.phase("gram on chip", gram_on_chip)
    sm.phase("forecasts vs cpu", forecasts_vs_cpu)
    keys = sm.phase("full run", full_run)
    sm.phase("kernel in chunk program", kernel_in_chunk_program, keys)
    sm.phase("slice comparisons", slice_comparisons)
    sm.phase("packed tiles", packed_tiles)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python chip_smoke.py",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded fleet on a 4-chip mesh")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU visible (JAX sees {devs[0].platform}); "
              "this check runs only on the chip", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} TPU "
              "device(s) visible", file=sys.stderr)
        return 1

    from repro.sim.compile_cache import enable_compile_cache
    print(f"# device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}"
          f", jax {jax.__version__}", flush=True)
    print(f"# compile cache: {enable_compile_cache()}", flush=True)

    sm = Smoke()
    t0 = time.perf_counter()
    run_phases(sm, args.chips)
    print(f"# total: {time.perf_counter() - t0:.1f} s, scan/shard compile "
          f"{_compile_s():.1f} s", flush=True)
    if sm.failed:
        print(f"chip_smoke: {len(sm.failed)} check(s) failed: {sm.failed}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
