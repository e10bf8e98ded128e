"""Worst-fit ties in the reference's placement.  Where allocations are
shaped, the reference counts a host whose free memory lies within
``tie_mem`` of the most free as tied with it and takes the host that the
program's state shows; a placement off by more than that is the
reference's own worst fit, so the program's differs and is counted."""
import numpy as np
import pytest
from conftest import tiny

from harness import reference as R


@pytest.fixture
def cluster():
    return R.Cluster(tiny("paper-gp.google-c8").spec)


def _free(tie, off):
    free = np.array([[32.0, 100.0], [32.0, 100.0 - off * tie], [32.0, 60.0]], np.float32)
    return free


@pytest.mark.parametrize("off,placed", [(0.9, 1), (1.1, 0), (3.0, 0)])
def test_a_placement_within_the_tie_is_followed_and_beyond_it_is_not(cluster, off, placed):
    tie = float(cluster.tie_mem)
    assert tie > 0
    assert cluster._worst_fit(_free(tie, off), 1.0, 1.0, want=1) == placed


def test_unshaped_allocations_have_no_tie():
    cl = R.Cluster(tiny("paper-baseline.google-c8").spec)
    assert float(cl.tie_mem) == 0.0
    free = np.array([[32.0, 100.0], [32.0, 99.99]], np.float32)
    assert cl._worst_fit(free, 1.0, 1.0, want=1) == 0


@pytest.mark.parametrize("off,noted", [(0.9, True), (1.1, False)])
def test_a_blind_tie_is_noted_and_flipped_only_within_the_band(cluster, off, noted):
    tie = float(cluster.tie_mem)
    free = _free(tie, off)
    assert cluster._worst_fit(free, 1.0, 1.0) == 0
    assert len(cluster.blind) == int(noted)
    cluster.blind, cluster.flips = [], frozenset({0})
    assert cluster._worst_fit(free, 1.0, 1.0) == (1 if noted else 0)



def _empty(spec, traces):
    """Every member's state before its first tick."""
    A = spec["sim"]["cluster"]["max_running_apps"]
    W = spec["sim"]["window"]
    n, C = traces[0].cpu_req.shape
    return [{"t": np.float32(0), "slot_gid": np.full(A, -1, np.int32),
             "comp_running": np.zeros((A, C), bool), "comp_host": np.zeros((A, C), np.int32),
             "alloc": np.zeros((A, C, 2), np.float32), "alive_since": np.zeros((A, C), np.float32),
             "work_done": np.zeros(A, np.float32), "arrived": np.zeros(n, bool),
             "queued": np.zeros(n, bool), "done": np.zeros(n, bool), "failed": np.zeros(n, bool),
             "finish_t": np.zeros(n, np.float32), "mon_buf": np.zeros((A * C, W, 2), np.float32),
             "mon_count": np.zeros(A * C, np.int32), "failure_events": np.int32(0),
             "oom_kills": np.int32(0), "full_preemptions": np.int32(0),
             "partial_preemptions": np.int32(0)} for _ in traces]


def _stack(states):
    return {k: np.stack([s[k] for s in states]) for k in states[0]}


def test_a_chunk_that_took_blind_ties_the_other_way_is_replayed_so():
    """The program's output stands in as the reference's own chunk with
    blind ties taken the other way; the replay reads it as agreeing, and
    counts a placement moved away from any tie."""
    from harness import check, traffic
    c = tiny("paper-gp.google-c8", n_apps=200)
    traces = traffic.cohort(c.mix, int(c.spec["n_apps"]), 5)
    arrays = [R.trace_arrays(t) for t in traces]
    warm, _ = R.Cluster(c.spec).chunk(arrays, _empty(c.spec, traces), 48)
    before = _stack(warm)
    blind = R.Cluster(c.spec)
    blind.chunk(arrays, [check.member(before, i) for i in range(len(traces))], 32)
    assert blind.blind
    flipped = 0
    for j in np.argsort(blind.blind, kind="stable")[:4]:
        cl = R.Cluster(c.spec)
        cl.flips = frozenset({int(j)})
        ends, rows = cl.chunk(arrays, [check.member(before, i) for i in range(len(traces))], 32)
        after = _stack(ends)
        ticks = {k: np.array([[r[k] for r in m] for m in rows]) for k in rows[0][0]}
        rep = check.replay(c.spec, traces, before, after, ticks, R.Cluster(c.spec).bucket(
            [check.member(before, i) for i in range(len(traces))]))
        assert rep["state_mismatch"] == 0, rep
        flipped += bool(rep["flips"])
        run = np.argwhere((after["slot_gid"] >= 0)[:, :, None] & after["comp_running"])
        i, a, k = run[0]
        after["comp_host"][i, a, k] = (after["comp_host"][i, a, k] + 1) % c.spec["sim"]["cluster"]["n_hosts"]
        assert check.replay(c.spec, traces, before, after, ticks, None)["state_mismatch"] > 0
    assert flipped
