"""What decides ``correct``: the timed path's own output against the plain
reference (``reference.py``), once the window has closed.

The window copies the cohort's state to the host before one chunk drawn
from the seed (k) and after it (k + 1), and keeps the state after its last
chunk.  Each number below is compared against a limit of the
configuration's file (``limits``):

* ``state_mismatch``: the reference steps every member from the state
  before chunk k through the chunk's 32 ticks.  Counted are the entries
  where the program's state after the chunk, or its per-tick totals,
  differ: exactly for what is discrete (slots, placements, queue, arrival,
  completion and failure flags, sample counts, counters, running apps,
  forecast rows ready and computed, the forecast bucket), beyond a relative
  ``REL_TOL`` for float32 values that a few operations give (progress,
  clock, finish and start times, unshaped allocations).  Where the
  program's float32 forecasts tip a worst-fit near-tie that its state
  after the chunk cannot show (the application left within the chunk),
  a member that disagrees is replayed with up to ``MAX_FLIPS`` such
  blind ties taken the other way, and counts as the replay that agrees.
* ``usage_gap_max``: from the same replay, the largest gap in what the
  monitor sampled: per monitor row, |program - reference| over the row's
  largest sample; per tick, the cluster's usage totals over its
  allocation total.  A sample is a profile read at the application's
  progress, so a rounding of the progress moves it by the profile's
  slope, which the row's largest sample bounds.
* ``alloc_gap_med`` and ``alloc_gap_p99`` (forecasting configurations):
  in the state after chunk k and after the last chunk, every running
  component with ``grace`` samples holds the allocation its last tick
  shaped from the monitor window it still holds.  The reference forecasts
  each such window and applies the safeguard; the gap is
  |program - reference| / reservation, per component and resource, over
  the rows where the reference's allocation lies below the reservation
  (the clip hides the forecast elsewhere): its median and its 99th
  percentile.  The largest gap is printed, not compared: a few
  ill-conditioned evidence fits per run turn float32 rounding into gaps
  of 1e-2, as large as the control's.
"""
from __future__ import annotations

import numpy as np

from harness import reference as R

REL_TOL = 1e-5      # float32 values from a few operations: 2^-17 ~ 8 ulp
ABS_TOL = 1e-6
MAX_FLIPS = 2       # blind ties one member's replay may take the other way
MAX_TRIES = 48      # member replays one check may spend searching them

EXACT_STATE = ("slot_gid", "comp_running", "arrived", "queued", "done",
               "failed", "mon_count", "failure_events", "oom_kills",
               "full_preemptions", "partial_preemptions")
CLOSE_STATE = ("work_done", "finish_t", "t")
EXACT_TICK = ("valid", "n_running", "forecast_rows", "forecast_rows_done")
USAGE_TICK = (("used_cpu", "alloc_cpu"), ("used_mem", "alloc_mem"))


def member(state: dict, i: int) -> dict:
    return {k: np.array(v[i], copy=True) for k, v in state.items()}


def _far(a, b) -> np.ndarray:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.abs(a - b) > ABS_TOL + REL_TOL * np.abs(b)


def _usage_gap(prog: dict, ref: dict) -> float:
    """Largest monitor-sample gap over its row's largest sample."""
    scale = np.abs(ref["mon_buf"]).max(axis=1, keepdims=True) + ABS_TOL
    return float((np.abs(prog["mon_buf"].astype(np.float64) - ref["mon_buf"]) / scale).max())


def _count_state(prog: dict, ref: dict, ready: np.ndarray, where: list) -> dict:
    """Entries of one member's state where program and reference differ;
    the first few of each kind are appended to ``where``."""
    run = (ref["slot_gid"] >= 0)[:, None] & ref["comp_running"]
    plain = run & ~ready.reshape(run.shape)
    bad = {}
    for k in EXACT_STATE:
        bad[k] = np.asarray(prog[k]) != np.asarray(ref[k])
    for k in CLOSE_STATE:
        bad[k] = _far(prog[k], ref[k])
    bad["comp_host"] = (prog["comp_host"] != ref["comp_host"]) & run
    bad["alive_since"] = _far(prog["alive_since"], ref["alive_since"]) & run
    bad["alloc"] = _far(prog["alloc"], ref["alloc"]) & plain[:, :, None]
    out = {}
    for k, m in bad.items():
        out[k] = int(np.count_nonzero(m))
        for idx in list(zip(*np.nonzero(m)))[:3] if np.ndim(m) else ([()] if m else []):
            where.append([k, [int(i) for i in idx], float(np.asarray(prog[k])[idx]),
                          float(np.asarray(ref[k])[idx])])
    return out


def _compare(cl, prog: dict, end: dict, rows: list, ticks: dict):
    """Where one member's program state ``prog`` and per-tick totals
    ``ticks`` (per name, one row of ticks) differ from the reference's
    ``end`` and ``rows``: (counts by kind, the first few entries, the
    usage gap)."""
    ready = cl._ready(prog) if cl.gp is not None else np.zeros(prog["mon_count"].shape, bool)
    where: list = []
    counts = _count_state(prog, end, ready, where)
    for k in EXACT_TICK:
        ref = np.array([r[k] for r in rows])
        counts["tick." + k] = int(np.count_nonzero(np.asarray(ticks[k]) != ref))
    usage_gap = _usage_gap(prog, end)
    for k, scale in USAGE_TICK:
        ref = np.array([r[k] for r in rows])
        den = np.maximum(np.abs(np.asarray(ticks[scale], np.float64)), ABS_TOL)
        gap = np.abs(np.asarray(ticks[k], np.float64) - ref) / den
        usage_gap = max(usage_gap, float(gap.max()))
    return counts, where, usage_gap


def _retrace(spec: dict, arrays: dict, start: dict, prog: dict, ticks: dict, budget: list):
    """Blind worst-fit ties (``reference.Cluster.flips``), taken the other
    way, that make one member's replay agree with the program's output:
    up to ``MAX_FLIPS`` of them, greedily, the narrowest first.  A blind
    tie is the program's float32 rounding against the reference's (an
    application that leaves within the chunk shows no host) and leaves
    the cluster in another state from there on.  Returns (flips, the
    reference's state, what ``_compare`` finds), or None where nothing
    within ``budget`` (a one-item list: member replays left) agrees."""
    n = len(ticks["valid"])

    def attempt(flips):
        if budget[0] <= 0:
            return None
        budget[0] -= 1
        cl = R.Cluster(spec)
        cl.flips = frozenset(flips)
        ends, rows = cl.chunk([arrays], [start], n, hints=[prog])
        found = _compare(cl, prog, ends[0], rows[0], ticks)
        return sum(found[0].values()), cl.blind, ends[0], found

    flips, got = (), attempt(())
    while got is not None and got[0] and len(flips) < MAX_FLIPS:
        best = None
        for _, j in sorted((m, j) for j, m in enumerate(got[1]) if j > max(flips, default=-1)):
            tried = attempt(flips + (j,))
            if tried is None:
                break
            if best is None or tried[0] < best[1][0]:
                best = (flips + (j,), tried)
            if not tried[0]:
                break
        if best is None or best[1][0] >= got[0]:
            return None
        flips, got = best
    if got is None or got[0]:
        return None
    return flips, got[2], got[3]


def replay(spec: dict, traces: list, before: dict, after: dict, ticks: dict, bucket) -> dict:
    """Step the reference through one chunk from ``before``; count where
    ``after`` and the chunk's per-tick totals ``ticks`` (member, tick)
    disagree.  ``bucket`` is the one the program picked for the chunk.
    A member that disagrees is replayed again with blind ties taken the
    other way (``_retrace``); where that agrees, it is counted so."""
    cl = R.Cluster(spec)
    arrays = [R.trace_arrays(t) for t in traces]
    n = len(traces)
    starts = [member(before, i) for i in range(n)]
    progs = [member(after, i) for i in range(n)]
    ends, rows = cl.chunk(arrays, starts, len(ticks["valid"][0]), hints=progs)
    counts: dict = {}
    where: list = []
    usage_gap = 0.0
    flipped = {}
    budget = [MAX_TRIES]
    for i in range(n):
        mine = {k: v[i] for k, v in ticks.items()}
        found = _compare(cl, progs[i], ends[i], rows[i], mine)
        if sum(found[0].values()) and cl.tie_mem > 0:
            again = _retrace(spec, arrays[i], starts[i], progs[i], mine, budget)
            if again is not None:
                flipped[i], ends[i], found = again
        for k, v in found[0].items():
            counts[k] = counts.get(k, 0) + v
        where += [[i] + entry for entry in found[1]]
        usage_gap = max(usage_gap, found[2])
    ref_bucket = cl.bucket(starts) if cl.bucketed else None
    counts["bucket"] = int(ref_bucket != bucket)
    free_gap = max(float(np.abs(cl._free(progs[i]).astype(np.float64) - cl._free(ends[i]))[:, R.MEM].max())
                   for i in range(n))
    return {"state_mismatch": sum(counts.values()), "usage_gap_max": usage_gap,
            "detail": {k: v for k, v in counts.items() if v}, "where": where[:12],
            "free_mem_gap_max": free_gap, "ties": len(cl.ties),
            "tie_margin_max": max(cl.ties, default=0.0), "blind_ties": len(cl.blind),
            "flips": {str(i): list(f) for i, f in flipped.items()},
            "retrace_replays": MAX_TRIES - budget[0]}


def alloc_gaps(spec: dict, traces: list, states: list) -> dict:
    """Shaped allocations held in ``states`` against the reference's
    safeguarded forecast of the windows they were shaped from."""
    sim = spec["sim"]
    gp = R.GP(sim["gp"], sim["horizon"])
    k1, k2, grace = sim["safeguard"]["k1"], sim["safeguard"]["k2"], sim["grace"]
    wins, valids, reqs, progs = [], [], [], []
    for state in states:
        for i in range(len(traces)):
            st = member(state, i)
            tr = traces[i]
            A, C = st["comp_running"].shape
            run = ((st["slot_gid"] >= 0)[:, None] & st["comp_running"]).reshape(-1)
            rows = np.nonzero(run & (st["mon_count"] >= grace))[0]
            if not len(rows):
                continue
            w, v = R.windows_of(st["mon_buf"], st["mon_count"], rows)
            gid = np.maximum(st["slot_gid"], 0)[rows // C]
            comp = rows % C
            req = np.concatenate([tr.cpu_req[gid, comp], tr.mem_req[gid, comp]])
            alloc = st["alloc"].reshape(A * C, 2)[rows]
            wins.append(w)
            valids.append(v)
            reqs.append(req)
            progs.append(np.concatenate([alloc[:, R.CPU], alloc[:, R.MEM]]))
    if not wins:
        return {"rows": 0}
    req = np.concatenate(reqs).astype(np.float64)
    peak, var = gp.forecast(np.concatenate(wins), np.concatenate(valids))
    ref = R.shaped(peak, req, var, k1, k2)
    gap = np.abs(np.concatenate(progs) - ref) / req
    inner = ref < req * (1 - 1e-6)
    return gap_stats(gap, inner)


def gap_stats(gap: np.ndarray, inner: np.ndarray) -> dict:
    out = {"rows": int(len(gap)), "rows_unclipped": int(inner.sum()),
           "alloc_gap_max": float(gap.max()) if len(gap) else 0.0}
    if inner.any():
        out["alloc_gap_med"] = float(np.median(gap[inner]))
        out["alloc_gap_p99"] = float(np.quantile(gap[inner], 0.99))
    return out


def judge(spec: dict, traces: list, w, k: int) -> dict:
    """Every compared number of a run, with its limit, and ``correct``."""
    from harness.system import tick_rows
    ticks = tick_rows(w)
    sl = slice(k * len(w.metrics[0].valid[0]), (k + 1) * len(w.metrics[0].valid[0]))
    chunk_ticks = {name: v[:, sl] for name, v in ticks.items()}
    rep = replay(spec, traces, w.snaps[k], w.snaps[k + 1], chunk_ticks, w.chunks[k]["bucket"])
    numbers = {"state_mismatch": rep["state_mismatch"], "usage_gap_max": rep["usage_gap_max"]}
    info = {"replay_chunk": k, "replay_detail": rep["detail"], "replay_where": rep["where"],
            "free_mem_gap_max": rep["free_mem_gap_max"], "ties": rep["ties"],
            "tie_margin_max": rep["tie_margin_max"], "blind_ties": rep["blind_ties"],
            "flips": rep["flips"], "retrace_replays": rep["retrace_replays"]}
    if spec["sim"]["policy"] != "baseline":
        last = max(w.snaps)
        gaps = alloc_gaps(spec, traces, [w.snaps[k + 1], w.snaps[last]])
        info.update(alloc_rows=gaps.get("rows", 0), alloc_rows_unclipped=gaps.get("rows_unclipped", 0),
                    alloc_gap_max=gaps.get("alloc_gap_max", 0.0))
        # where nothing was shaped there is nothing to compare: the gaps
        # then read a whole reservation, and fail
        numbers["alloc_gap_med"] = gaps.get("alloc_gap_med", 1.0)
        numbers["alloc_gap_p99"] = gaps.get("alloc_gap_p99", 1.0)
    limits = spec["limits"]
    checks = {}
    correct = True
    for name, value in numbers.items():
        checks[name] = {"value": value, "limit": limits[name]}
        correct &= bool(value <= limits[name])
    return {"correct": correct, "checks": checks, "info": info}


def control(spec: dict, traces: list, w, k: int) -> dict:
    """The control's numbers: the reference one precision down
    (``reference.CONTROL``) put in the program's place.  It steps the same
    chunk from the same state and shapes the same windows; the reference
    then judges its output as it judges the program's."""
    n = len(w.metrics[0].valid[0])
    start = w.snaps[k]
    members = [member(start, i) for i in range(len(traces))]
    cl = R.Cluster(spec, R.CONTROL)
    ends, rows = cl.chunk([R.trace_arrays(t) for t in traces], members, n)
    after = {key: np.stack([e[key] for e in ends]) for key in ends[0]}
    ticks = {key: np.array([[r[key] for r in m] for m in rows]) for key in rows[0][0]}
    rep = replay(spec, traces, start, after, ticks, w.chunks[k]["bucket"])
    out = {"state_mismatch": rep["state_mismatch"], "usage_gap_max": rep["usage_gap_max"]}
    if spec["sim"]["policy"] != "baseline":
        sim = spec["sim"]
        fine = R.GP(sim["gp"], sim["horizon"], R.FINE)
        low = R.GP(sim["gp"], sim["horizon"], R.CONTROL)
        k1, k2, grace = sim["safeguard"]["k1"], sim["safeguard"]["k2"], sim["grace"]
        gaps, inner = [], []
        for state in (w.snaps[k + 1], w.snaps[max(w.snaps)]):
            for i, tr in enumerate(traces):
                st = member(state, i)
                C = st["comp_running"].shape[1]
                run = ((st["slot_gid"] >= 0)[:, None] & st["comp_running"]).reshape(-1)
                rows_i = np.nonzero(run & (st["mon_count"] >= grace))[0]
                if not len(rows_i):
                    continue
                win, val = R.windows_of(st["mon_buf"], st["mon_count"], rows_i)
                gid = np.maximum(st["slot_gid"], 0)[rows_i // C]
                req = np.concatenate([tr.cpu_req[gid, rows_i % C],
                                      tr.mem_req[gid, rows_i % C]]).astype(np.float64)
                p, v = fine.forecast(win, val)
                ref = R.shaped(p, req, v, k1, k2)
                p, v = low.forecast(win, val)
                ctl = np.asarray(R.shaped(p, req, v, k1, k2), np.float32)
                gaps.append(np.abs(ctl - ref) / req)
                inner.append(ref < req * (1 - 1e-6))
        st = gap_stats(np.concatenate(gaps), np.concatenate(inner))
        out.update({k: st[k] for k in ("alloc_gap_med", "alloc_gap_p99", "alloc_gap_max")})
    return out
