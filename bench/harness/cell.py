"""One run of one cell: set-up, the measured window, the check, the
result.  ``run.py`` wraps it with the command line and the look for a
chip; the tests call it on the CPU with a cell of their own.

Everything about a cell is found by name: ``BENCHMARK.json`` names the
cell's configuration (its file is under ``bench/configs/``), its traffic
mix (``bench/traffic/<traffic>.json``) and its per-layer metrics (each a
reader in ``bench/metrics/<name>.py``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
# the stretch the traced run records (``system.Tracer``): whole chunks
# where they are short, else a slice around one chunk boundary
TRACE_SECONDS = 0.35


def log(*parts) -> None:
    print("#", *parts, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    spec: dict          # the configuration file
    mix: dict           # the traffic file
    end_to_end: list    # metric entries of BENCHMARK.json
    per_layer: list


def load_cell(name: str, root: Path = ROOT) -> Cell:
    from harness import traffic
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    spec = json.loads((root / conf["file"]).read_text())
    mix = traffic.load(root / "bench" / "traffic" / f"{w['traffic']}.json")

    def mine(m):
        return "workloads" not in m or name in m["workloads"]
    return Cell(name, int(w["chips"]), spec, mix,
                [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)])


def reader(metric: str, bench: Path = BENCH):
    path = bench / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Context:
    """What a per-layer metric's reader reads: the trace record, the
    window's chunks and per-tick counts, the configuration, the peaks.

    ``chunk_busy_s`` and ``chunk_period_s`` are one chunk's device busy
    time and its period on the device's clock, averaged over the chips
    (``trace.chunk_device``); None where the trace cannot give them."""

    def __init__(self, rec, window, ticks, spec, peak, chunk):
        from harness import trace
        self.rec, self.window, self.ticks, self.spec, self.peak = rec, window, ticks, spec, peak
        self.chunk = chunk
        self.notes = []
        self.devices = sorted(rec["devices"]) if rec and trace.window(rec) else []
        self.traced = [i for i, c in enumerate(window.chunks) if c["traced"]]
        self.window_s = self.busy_s = 0.0
        self.chunk_busy_s = self.chunk_period_s = None
        if self.devices:
            lo, hi = trace.window(rec)
            self.window_s = (hi - lo) / 1e9
            self.busy_s = sum(trace.busy_ns(rec, d) for d in self.devices) / 1e9 / len(self.devices)
            per = [trace.chunk_device(rec, d, chunk) for d in self.devices]
            for d, c in zip(self.devices, per):
                self.note(f"chunk on {d}: {c.get('how') or c['why']}")
            if all("busy_ns" in c for c in per):
                self.chunk_busy_s = sum(c["busy_ns"] for c in per) / 1e9 / len(per)
                self.chunk_period_s = sum(c["period_ns"] for c in per) / 1e9 / len(per)

    def kernel_events(self, match: str) -> list:
        """(name, seconds) of a kernel's operations in the traced stretch,
        all chips."""
        from harness import trace
        return [(n, d / 1e9) for dev in self.devices for n, d in trace.kernel_events(self.rec, dev, match)]

    def kernel_s(self, match: str) -> float:
        """A kernel's device time in the traced stretch, per chip."""
        return sum(d for _, d in self.kernel_events(match)) / len(self.devices)

    def note(self, text: str) -> None:
        self.notes.append(text)


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices: list,
        t_start: float, out_dir: Path, with_control: bool = False) -> dict:
    """Set up, measure, check; returns the result object.  With
    ``with_control`` it also reads the control's numbers (``control.py``;
    the benchmark's own runs do not)."""
    import jax

    from harness import check, peaks, system, traffic
    from harness import trace as tr

    setup = {"import": time.perf_counter() - t_start}
    t = time.perf_counter()
    traces = traffic.cohort(cell.mix, int(cell.spec["n_apps"]), seed)
    setup["traces"] = time.perf_counter() - t

    t = time.perf_counter()
    cfg = system.sim_config(cell.spec)
    sim = system.build(cfg, traces, devices)
    jax.block_until_ready((sim.tr, sim.st))
    setup["upload"] = time.perf_counter() - t
    compiles = system.Compiles()
    setup["programs"] = system.compile_all(sim)
    warm = int(cell.mix["warm_ticks"])
    t = time.perf_counter()
    system.advance(sim, warm)
    setup["warm_up"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    for k, v in setup.items():
        log(f"set-up {k}: {v:.3f} s")
    log(f"set-up: {setup_s:.3f} s, {compiles.n} programs built, warm tick {warm}")

    # the chunk the check replays: drawn from the seed among the first
    # chunks the window surely holds (at half the warm-up's pace)
    per_chunk = setup["warm_up"] / max(1, -(-warm // system.CHUNK))
    reach = max(1, int(0.5 * seconds / max(per_chunk, 1e-9)))
    k = int(np.random.default_rng(seed).integers(0, min(reach, 8)))
    trace_dir = out_dir / "trace" if trace else None
    # each chip's operations fill the profiler's buffers: four chips, a
    # quarter of the stretch each
    tracer = system.Tracer(trace_dir, TRACE_SECONDS / len(devices)) if trace else None
    w = system.window(sim, seconds, snap_at=(k, k + 1), compiles=compiles, tracer=tracer)
    mem = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(m.get("peak_bytes_in_use", 0) for m in mem)
    ticks = system.tick_rows(w)
    cohort_ticks = len(w.chunks) * system.CHUNK
    sim_ticks = int(ticks["valid"].sum())
    last = w.snaps[max(w.snaps)]
    completed = int(last["done"].sum()) - w.done_before
    log(f"window: {w.seconds:.4f} s, {len(w.chunks)} chunks, {cohort_ticks} cohort ticks, "
        f"{sim_ticks} member ticks, {w.compiles} compiles inside, "
        f"{w.paused:.4f} s of snapshots off its clock")
    buckets = {}
    for c in w.chunks:
        buckets[str(c["bucket"])] = buckets.get(str(c["bucket"]), 0) + 1
    log(f"buckets picked: {buckets}")
    log(f"apps completed: {completed} ({completed / w.seconds:.4f}/s)")
    short = int((last["arrived"].all(axis=-1)).sum())
    if short:
        log(f"WARNING: {short} members ran out of arrivals before the window ended")
    del sim

    result = {"correct": None, "attempted": sim_ticks, "failed": 0}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak_bytes)}
    metrics = {}
    breakdown = None
    if trace:
        rec = tr.load(trace_dir) if any(trace_dir.rglob("*.xplane.pb")) else None
        pk = peaks.peaks(dev.device_kind) if dev.platform == "tpu" else None
        ctx = Context(rec, w, ticks, cell.spec, pk, system.CHUNK)
        for m in cell.per_layer:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        for n in ctx.notes:
            log(n)
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.window_s
        if ctx.devices:
            breakdown = {"device_ops": tr.top_ops(rec), "idle_gaps": tr.top_gaps(rec)}
        clean = w.chunks[:ctx.traced[0]] if ctx.traced else w.chunks
        host = np.median(np.diff([c["ready"] for c in clean])) if len(clean) > 1 else None
        log(f"traced: {len(ctx.traced)} chunks, busy {ctx.busy_s:.6f} s of {ctx.window_s:.6f} s; "
            f"a chunk on the device: busy {ctx.chunk_busy_s} s of {ctx.chunk_period_s} s "
            f"(untraced, on the host's clock: {host} s)")
    else:
        values = {"sim_ticks_per_s": sim_ticks / w.seconds, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}

    t = time.perf_counter()
    verdict = check.judge(cell.spec, traces, w, k)
    log(f"check: {time.perf_counter() - t:.3f} s, chunk {k}, {json.dumps(verdict['info'])}")
    result["correct"] = bool(verdict["correct"])
    result["failed"] = 0 if verdict["correct"] else sim_ticks
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    if with_control:
        result["control"] = check.control(cell.spec, traces, w, k)
        log(f"control: {json.dumps(result['control'])}")
    result["checks"] = verdict["checks"]
    for name, c in verdict["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result
