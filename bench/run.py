#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json`` on the chips of this host.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It generates the cell's traffic from ``--seed``, uploads it, builds every
chunk program the cell can pick (from JAX's persistent compilation cache
in ``<checkout>/.jax_cache`` after a cell's first run, or where
``JAX_COMPILATION_CACHE_DIR`` points), warms the simulation up to the
traffic's warm tick, and then drives chunks of the cohort for
``--seconds``.  With ``--trace 0`` it reports the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics from a profiler trace of
a few chunks.  Either way it then checks what the window produced against
the plain reference (``harness/check.py``).  Lines on standard error say
what happened; the last line of standard output is the result object.

It exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when anything fails.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    from harness.cell import load_cell, log, run
    cell = load_cell(args.workload, ROOT)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"no chips for this cell: JAX finds {len(devices)} "
            f"{devices[0].platform} device(s), the cell needs {cell.chips} TPU chip(s)")
        return 2
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import repro.sim.step  # noqa: F401  (the system under test; import is set-up)

    out = ROOT / ".bench_out" / args.workload
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    result = run(cell, args.seed, args.seconds, bool(args.trace),
                 devices[:cell.chips], T_START, out)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:
        traceback.print_exc()
        sys.exit(1)
