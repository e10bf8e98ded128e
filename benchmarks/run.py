"""Benchmark harness entry point — one section per paper table/figure.

  fig2   forecast-error distributions (ARIMA vs GP-Exp vs GP-RBF)
  fig3   oracle-based policy comparison (baseline/optimistic/pessimistic)
         — a thin repro.sim.sweep grid; writes BENCH_fig3.json
  fig4   K1 x K2 safeguard heat maps (ARIMA + GP)
         — a thin repro.sim.sweep grid; writes BENCH_fig4.json
  fig5   prototype: baseline vs dynamic on live training jobs
  scenarios  cross-scenario robustness grid (every workload family x
         policy); writes BENCH_scenarios.json
  calibration  Gaussian-vs-conformal safeguard study (coverage /
         turnaround / failure trade-offs); writes BENCH_calibration.json
  engine  host-loop vs device-resident scan engine vs vmapped seed
         cohort throughput (+ GP forecast-row overhead); writes
         BENCH_engine.json
  shard  scan cohort vs shard_map device-mesh fleets; writes
         BENCH_shard.json (on CPU, set XLA_FLAGS=--xla_force_host_
         platform_device_count=8 for a mesh)
  kernels  Pallas kernel microbenches
  obs    observability plane: telemetry-ring overhead + identity, trace
         and manifest validity; writes BENCH_obs.json (+ .trace.json /
         .manifest.json artifacts)
  roofline dry-run-derived roofline table (if dryrun_results.json exists)

``python -m benchmarks.run [--only SECTION] [--full] [--compare DIR]``

Every section writes at most one ``BENCH_<name>.json`` artifact (all
gitignored; CI uploads them).  Arbitrary ad-hoc grids — any policy x
forecaster x safeguard x scenario x seed cross product — run through
``python -m repro.sim.sweep`` directly.

``--compare DIR`` diffs the artifacts in the cwd against the committed
baselines in DIR (``benchmarks/baselines`` in CI) and exits nonzero on
regression — see ``benchmarks.compare`` for the tolerance policy.
Without ``--only``, ``--compare`` runs the diff alone (compare-only
mode: CI produces artifacts via the per-section smokes first).
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

from repro.sim.compile_cache import enable_compile_cache

SECTIONS = ("fig2", "fig3", "fig4", "fig5", "scenarios", "calibration",
            "engine", "shard", "replay", "kernels", "obs", "roofline")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=SECTIONS)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale runs (hours); default is CI scale")
    ap.add_argument("--compare", default=None, metavar="DIR",
                    help="diff cwd BENCH_*.json against the baselines "
                         "in DIR; nonzero exit on regression.  Without "
                         "--only, runs the diff alone")
    args = ap.parse_args()
    if args.compare is not None and args.only is None:
        from benchmarks import compare
        sys.exit(compare.main([args.compare]))
    enable_compile_cache()
    quick = not args.full
    sections = [args.only] if args.only else list(SECTIONS)
    failures = 0

    for sec in sections:
        print(f"\n===== {sec} " + "=" * (60 - len(sec)), flush=True)
        t0 = time.time()
        try:
            if sec == "fig2":
                from benchmarks import forecast_error
                forecast_error.main(quick)
            elif sec == "fig3":
                from benchmarks import oracle_policies
                oracle_policies.main(quick)
            elif sec == "fig4":
                from benchmarks import beta_heatmap
                beta_heatmap.main(quick)
            elif sec == "fig5":
                from benchmarks import prototype
                prototype.main(quick)
            elif sec == "scenarios":
                from benchmarks import scenario_sweep
                scenario_sweep.main(quick)
            elif sec == "calibration":
                from benchmarks import calibration
                calibration.main(quick)
            elif sec == "engine":
                from benchmarks import engine
                engine.run(quick)
            elif sec == "shard":
                # on CPU the mesh needs forced host devices in the
                # environment; with one device the bench skips the
                # throughput criterion
                from benchmarks import shard
                shard.run()
            elif sec == "replay":
                from benchmarks import replay
                replay.run(quick)
            elif sec == "kernels":
                from benchmarks import kernels
                kernels.main(quick)
            elif sec == "obs":
                from benchmarks import obs
                obs.run()
            elif sec == "roofline":
                if os.path.exists("dryrun_results.json"):
                    from benchmarks import roofline
                    rows = roofline.load("dryrun_results.json", "single")
                    roofline.print_table(rows)
                    for why, r in roofline.pick_hillclimb(rows).items():
                        print(f"# hillclimb[{why}]: {r['arch']} x "
                              f"{r['shape']} bound={r['bound']}")
                else:
                    print("dryrun_results.json not found — run "
                          "`python -m repro.launch.dryrun` first")
        except Exception:
            failures += 1
            traceback.print_exc()
        print(f"----- {sec} done in {time.time() - t0:.0f}s", flush=True)

    if args.compare is not None:
        from benchmarks import compare
        failures += compare.main([args.compare])
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
