#!/usr/bin/env python3
"""Readings that the correctness limits are set from: the program's
compared numbers over many seeds, and the control's, in one process on
the chips of this host (the programs are built once).

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

For each seed it makes a whole benchmark run of the cell and then puts
the control (the plain reference one precision down, ``reference.CONTROL``)
in the program's place over the same chunk and windows.  One JSON line
per seed: the program's numbers and limits (``checks``), the control's
numbers (``control``).  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    from harness.cell import load_cell, log, run
    cell = load_cell(args.workload, ROOT)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"no chips for this cell: {len(devices)} {devices[0].platform} device(s)")
        return 2
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = ROOT / ".bench_out" / f"control-{args.workload}"
    out.mkdir(parents=True, exist_ok=True)
    for seed in args.seeds:
        t0 = time.perf_counter()
        res = run(cell, seed, args.seconds, False, devices[:cell.chips], t0, out,
                  with_control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"], "checks": res["checks"],
                          "control": res["control"], "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
