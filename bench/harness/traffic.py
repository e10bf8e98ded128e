"""The benchmark's own traffic generator: the paper's Google-shaped batch
workload (arXiv:1807.00368 §4.1 and §5.1), drawn from the same
distributions as the program's ``google`` family, vectorised with
NumPy's ``Generator`` so a paper-sized trace takes seconds.

A traffic mix is a JSON file under ``bench/traffic/`` that names the
family, the cohort size and the distribution parameters; this module is
the one generator that reads them.  It emits the program's canonical
``Trace`` (the schema is the program's input format, so it is the one
thing taken from it here), one per cohort member.

Each member draws from two streams.  Its arrivals, structure, demands and
runtimes come from the traffic file's fixed ``structure_seed`` (member
``i`` from ``structure_seed * members + i``), so every run of a cell
gets the same set of applications at the same times and the same work on
the chip.  Its utilization profiles come from the run's ``--seed`` (member
``i`` from ``seed * members + i``), so every seed feeds the forecasts,
the safeguard and the policy other data.

The distributions, per application:

* arrivals: bimodal gaps, exponential with mean ``mean_burst_gap`` with
  probability ``burst_prob``, else with mean ``mean_long_gap`` (seconds);
* structure: elastic with probability ``elastic_frac`` (3 core
  components plus 2.. elastic ones), else rigid (1 or 2 core
  components), at most ``max_components`` in all;
* demands: CPU log-uniform in [min_cpu, max_cpu] rounded to quarter
  cores, memory log-uniform in [min_mem, max_mem] GB, the two
  coordinator cores of an elastic application capped at 0.5 CPU / 2 GB;
* runtime: log-uniform in [min_runtime, max_runtime] seconds;
* utilization: per component and resource a random walk over
  ``SEGMENTS`` knots (steps N(0, 0.18), start U(min_level, 0.7), clipped
  to [min_level, 1]), spikes to U(0.9, 1) with probability
  ``spike_prob`` per knot, and one knot per series raised to at least
  U(0.92, 1), so every profile touches its reservation; a share
  ``jumpy_frac`` of applications step between knots instead of ramping.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

SEGMENTS = 32

# the keys a google traffic file gives, besides its family and cohort
PARAMS = ("elastic_frac", "max_components", "min_runtime", "max_runtime",
          "mean_burst_gap", "mean_long_gap", "burst_prob", "min_cpu",
          "max_cpu", "min_mem", "max_mem", "min_level", "spike_prob",
          "jumpy_frac")


def load(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("family") != "google":
        raise ValueError(f"{path}: unknown traffic family {mix.get('family')!r}")
    missing = [k for k in PARAMS if k not in mix["params"]]
    if "structure_seed" not in mix:
        missing.append("structure_seed")
    if missing:
        raise ValueError(f"{path}: missing parameters {missing}")
    return mix


def _log_uniform(rng, lo: float, hi: float, size) -> np.ndarray:
    return np.exp(rng.uniform(np.log(lo), np.log(hi), size))


def google(p: dict, n_apps: int, seed: int, profile_seed: int | None = None):
    """One member's trace: ``n_apps`` applications drawn from ``seed``, their
    utilization profiles from ``profile_seed`` (``seed`` where not given)."""
    from repro.sim.scenarios.schema import Trace

    rng = np.random.default_rng(seed)
    N, C = n_apps, int(p["max_components"])

    burst = rng.random(N) < p["burst_prob"]
    gaps = rng.standard_exponential(N) * np.where(
        burst, p["mean_burst_gap"], p["mean_long_gap"])
    submit = np.cumsum(gaps)

    is_elastic = rng.random(N) < p["elastic_frac"]
    n_core = np.where(is_elastic, 3, rng.integers(1, 3, N))
    room = C - n_core
    n_elastic = np.where(is_elastic,
                         rng.integers(2, np.maximum(room + 1, 3)), 0)
    n_elastic = np.minimum(n_elastic, room)
    idx = np.arange(C)[None, :]
    exists = idx < (n_core + n_elastic)[:, None]
    is_core = idx < n_core[:, None]

    cpu = np.round(_log_uniform(rng, p["min_cpu"], p["max_cpu"], (N, C)) * 4) / 4
    mem = _log_uniform(rng, p["min_mem"], p["max_mem"], (N, C))
    light = is_elastic[:, None] & (idx < 2)
    cpu = np.where(light, np.minimum(cpu, 0.5), cpu)
    mem = np.where(light, np.minimum(mem, 2.0), mem)
    cpu_req = np.where(exists, np.maximum(cpu, p["min_cpu"]), 0.0).astype(np.float32)
    mem_req = np.where(exists, np.maximum(mem, p["min_mem"]), 0.0).astype(np.float32)

    runtime = _log_uniform(rng, p["min_runtime"], p["max_runtime"], N).astype(np.float32)

    # utilization walks, in float32 (the width of the stored profile)
    if profile_seed is not None:
        rng = np.random.default_rng(profile_seed)
    shape = (N, C, SEGMENTS, 2)
    lo = np.float32(p["min_level"])
    steps = rng.standard_normal(shape, dtype=np.float32) * np.float32(0.18)
    start = lo + (np.float32(0.7) - lo) * rng.random((N, C, 1, 2), dtype=np.float32)
    walk = np.clip(start + np.cumsum(steps, axis=2), lo, np.float32(1.0))
    spikes = rng.random(shape, dtype=np.float32) < p["spike_prob"]
    walk[spikes] = 0.9 + 0.1 * rng.random(int(spikes.sum()), dtype=np.float32)
    peak = rng.integers(0, SEGMENTS, (N, C, 1, 2))
    top = np.take_along_axis(walk, peak, axis=2)
    floor = 0.92 + 0.08 * rng.random(top.shape, dtype=np.float32)
    np.put_along_axis(walk, peak, np.maximum(top, floor), axis=2)
    levels = walk * exists[:, :, None, None]

    is_jumpy = rng.random(N) < p["jumpy_frac"]
    return Trace(submit=submit.astype(np.float32), is_elastic=is_elastic,
                 is_jumpy=is_jumpy, n_core=n_core.astype(np.int64),
                 n_elastic=n_elastic.astype(np.int64), runtime=runtime,
                 cpu_req=cpu_req, mem_req=mem_req, is_core=is_core & exists,
                 levels=levels.astype(np.float32)).validate()


def cohort(mix: dict, n_apps: int, seed: int, workers: int = 8) -> list:
    """The traces of every member of a run with ``--seed seed``, drawn on
    a few threads (NumPy's generators and array passes release the GIL)."""
    import concurrent.futures as cf

    S = int(mix["members"])
    base = int(mix["structure_seed"]) * S

    def one(i):
        return google(mix["params"], n_apps, base + i, seed * S + i)

    with cf.ThreadPoolExecutor(min(workers, S)) as ex:
        return list(ex.map(one, range(S)))
