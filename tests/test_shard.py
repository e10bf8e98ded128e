"""Sharded fleet correctness anchors (repro.sim.step.run_fleet_shard /
repro.sim.shard).

Contracts, in order of strength:

  * MESH-1 IDENTITY — ``run_fleet_shard(mesh=1)`` is bit-identical per
    member to ``run_cohort_scan`` (the shard engine is the cohort scan
    laid across a mesh; a 1-wide mesh must be a no-op);
  * MESH INVARIANCE — any wider mesh is bit-identical per member to
    ``mesh=1`` (re-slicing the fleet axis cannot change a member's
    numerics; XLA CPU reductions are batch-size invariant).  Wide
    meshes need forced host devices, so those tests skip on a single
    device and run in CI under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; one
    subprocess test keeps the multi-device path exercised in every
    tier-1 run;
  * the sweep's ``engine="shard"`` groups cells into fleets (cells x
    seeds, across scenarios); with no mesh on one device it runs
    ``scan``, and a mesh wider than the visible devices is an error.
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import pytest

from repro.sim import ClusterConfig, SimConfig, WorkloadConfig, generate
from repro.sim.step import run_cohort_scan, run_fleet_shard, run_sim_scan

WL = WorkloadConfig(n_apps=20, max_components=5, max_runtime=1200.0,
                    mean_burst_gap=4.0, mean_long_gap=60.0, seed=3)
CL = ClusterConfig(n_hosts=3, max_running_apps=12)
BASE = SimConfig(cluster=CL, workload=WL, max_ticks=2500,
                 policy="pessimistic", forecaster="persist")

multi_device = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >=4 devices (XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


def _results_equal(a, b) -> bool:
    return a.first_difference(b) is None


# ----------------------------------------------------------------------
# mesh=1 identity: shard is the cohort scan laid across a 1-wide mesh
# ----------------------------------------------------------------------

def test_mesh1_matches_cohort_scan():
    seeds = [0, 1, 2]
    cohort = run_cohort_scan(BASE, seeds, chunk=16)
    fleet = run_fleet_shard(BASE, seeds, chunk=16, mesh=1)
    for s, a, b in zip(seeds, cohort, fleet):
        assert _results_equal(a, b), f"seed {s} diverged"


def test_explicit_cfgs_cross_scenario_fleet():
    """A fleet may mix WORKLOADS (scenario families), not just seeds."""
    from repro.sim.scenarios import make_config
    other = dataclasses.replace(
        BASE, workload=make_config("flashcrowd", base=BASE.workload))
    fleet = run_fleet_shard(BASE, cfgs=[BASE, other], chunk=16, mesh=1)
    assert _results_equal(fleet[0], run_sim_scan(BASE, chunk=16))
    assert _results_equal(fleet[1], run_sim_scan(other, chunk=16))


def test_fleet_rejects_non_workload_heterogeneity():
    other = dataclasses.replace(BASE, policy="baseline")
    with pytest.raises(ValueError, match="beyond its workload"):
        run_fleet_shard(BASE, cfgs=[BASE, other])


def test_fleet_rejects_mismatched_shapes():
    other = dataclasses.replace(
        BASE, workload=dataclasses.replace(WL, seed=1,
                                           n_apps=WL.n_apps + 1))
    with pytest.raises(ValueError, match="shape"):
        run_fleet_shard(BASE, cfgs=[BASE, other])


def test_forecast_rows_telemetry():
    """The scan/shard engines report the masked-forecast load the
    ROADMAP asks to measure (rows past grace vs the full padded batch)."""
    res = run_fleet_shard(BASE, [0, 1], chunk=16, mesh=1)[0]
    fr = res.forecast_rows
    assert fr is not None
    A, C = CL.max_running_apps, WL.max_components
    assert fr["rows_batch"] == 2 * A * C
    assert 0 < fr["rows_ready"] <= fr["rows_batch"] * fr["ticks"]
    assert 0 < fr["ticks_forecasting"] <= fr["ticks"]
    # telemetry must not leak into the engine-agreement summary
    assert "forecast_rows" not in res.summary()


# ----------------------------------------------------------------------
# wide meshes (forced host devices)
# ----------------------------------------------------------------------

@multi_device
def test_wide_mesh_matches_mesh1():
    seeds = list(range(6))
    narrow = run_fleet_shard(BASE, seeds, chunk=16, mesh=1)
    wide = run_fleet_shard(BASE, seeds, chunk=16, mesh=4)
    for s, a, b in zip(seeds, narrow, wide):
        assert _results_equal(a, b), f"seed {s} diverged"


@multi_device
def test_padding_roundup_discarded():
    """A fleet that does not divide the mesh gets padded with repeats
    of the last member; padding must never leak into results."""
    seeds = [0, 1, 2, 3, 4]                  # 5 members, mesh 2 -> pad 6
    fleet = run_fleet_shard(BASE, seeds, chunk=16, mesh=2)
    assert len(fleet) == len(seeds)
    for s, res in zip(seeds, fleet):
        solo_cfg = dataclasses.replace(
            BASE, workload=dataclasses.replace(BASE.workload, seed=s))
        assert _results_equal(res, run_sim_scan(solo_cfg, chunk=16)), s


@multi_device
def test_sweep_shard_engine_matches_solo_scans():
    from repro.sim.sweep import (_apply_overrides, _set_path,
                                 quick_base_config, run_grid)
    base = quick_base_config(n_apps=20, n_hosts=3, seed=0)
    res = run_grid(base, axes={"scenario": ["google", "flashcrowd"],
                               "policy": ["baseline", "pessimistic"],
                               "forecaster": ["persist"]},
                   seeds=[0, 1], engine="shard", mesh=4)
    assert res.engine == "shard"
    assert res.mesh_devices == 4
    assert res.forecast_batches == 0          # batcher retired
    assert len(res.cells) == 8
    for cell in res.cells:
        cfg = _apply_overrides(base, cell["overrides"])
        cfg = _set_path(cfg, "workload.seed", cell["seed"])
        assert run_sim_scan(cfg).summary() == cell["summary"], cell["name"]


@multi_device
def test_group_fleets_cells_by_static_config():
    from repro.sim.scenarios import build_trace
    from repro.sim.shard import group_fleets
    from repro.sim.sweep import expand_grid, quick_base_config
    base = quick_base_config(n_apps=20, n_hosts=3, seed=0)
    grid = expand_grid(base,
                       axes={"scenario": ["google", "flashcrowd"],
                             "policy": ["baseline", "pessimistic"]},
                       seeds=[0, 1])
    workloads = {c.cfg.workload: build_trace(c.cfg.workload) for c in grid}
    fleets = group_fleets(grid, workloads)
    # scenario x seed fold into ONE fleet per static config (= policy)
    assert sorted(len(f) for f in fleets) == [4, 4]
    for fleet in fleets:
        assert len({c.cfg.policy for c in fleet}) == 1


# ----------------------------------------------------------------------
# single-device behaviour
# ----------------------------------------------------------------------

def test_sweep_shard_falls_back_to_scan_on_one_device(capsys):
    from repro.sim.sweep import quick_base_config, run_grid
    if jax.device_count() > 1:
        pytest.skip("fallback only triggers on a single device")
    base = quick_base_config(n_apps=20, n_hosts=3, seed=0)
    axes = {"policy": ["pessimistic"], "forecaster": ["persist"]}
    # mesh=4 over-asks the single visible device: an error, never a
    # silent one-device run
    with pytest.raises(ValueError, match="mesh=4"):
        run_grid(base, axes=axes, seeds=[0, 1], engine="shard", mesh=4)
    # mesh=None on one device runs the scan engine and says so
    res = run_grid(base, axes=axes, seeds=[0, 1], engine="shard")
    assert res.engine == "scan"
    assert res.mesh_devices == 0
    assert "running engine=scan" in capsys.readouterr().out


# ----------------------------------------------------------------------
# forced-host-device subprocess: the multi-device path stays exercised
# even when the parent run has a single device
# ----------------------------------------------------------------------

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import dataclasses, json
from repro.sim import ClusterConfig, SimConfig, WorkloadConfig
from repro.sim.step import run_fleet_shard

WL = WorkloadConfig(n_apps=12, max_components=4, max_runtime=900.0,
                    mean_burst_gap=4.0, mean_long_gap=60.0, seed=3)
cfg = SimConfig(cluster=ClusterConfig(n_hosts=2, max_running_apps=8),
                workload=WL, max_ticks=1500,
                policy="pessimistic", forecaster="persist")
fleet = run_fleet_shard(cfg, [0, 1, 2, 3], chunk=16, mesh=4)
print(json.dumps([{"turnaround": r.turnaround, "summary": r.summary()}
                  for r in fleet]))
"""


def test_wide_mesh_bit_identity_subprocess():
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        filter(None, [os.path.join(os.path.dirname(__file__), "..", "src"),
                      os.environ.get("PYTHONPATH", "")])))
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    child = json.loads(out.stdout.splitlines()[-1])
    assert len(child) == 4

    WLc = dataclasses.replace(WL, n_apps=12, max_components=4,
                              max_runtime=900.0)
    cfg = dataclasses.replace(
        BASE, cluster=ClusterConfig(n_hosts=2, max_running_apps=8),
        workload=WLc, max_ticks=1500)
    for seed, got in zip([0, 1, 2, 3], child):
        solo_cfg = dataclasses.replace(
            cfg, workload=dataclasses.replace(cfg.workload, seed=seed))
        want = run_sim_scan(solo_cfg, chunk=16)
        # JSON round-trip stringifies dict keys — normalize ours the
        # same way before comparing
        assert got["turnaround"] == json.loads(
            json.dumps(want.turnaround)), f"seed {seed}"
        assert got["summary"] == json.loads(
            json.dumps(want.summary())), f"seed {seed}"


_CHILD_ONE_PER_DEVICE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
import jax
from repro.sim import ClusterConfig, SimConfig, WorkloadConfig
from repro.sim.step import FLEET_AXIS, run_fleet_shard

WL = WorkloadConfig(n_apps=12, max_components=4, max_runtime=900.0,
                    mean_burst_gap=4.0, mean_long_gap=60.0, seed=3)
cfg = SimConfig(cluster=ClusterConfig(n_hosts=2, max_running_apps=8),
                workload=WL, max_ticks=1500,
                policy="pessimistic", forecaster="persist")
seeds = [0, 1, 2, 3]
wide = run_fleet_shard(cfg, seeds, chunk=16, mesh=4)
# the mesh=4 fleet's uploaded trace stays cached on its devices: read
# its layout before the mesh=1 upload joins it
rows = sorted({(len(a.sharding.device_set), a.sharding.shard_shape(a.shape)[0])
               for a in jax.live_arrays()
               if getattr(a.sharding, "mesh", None) is not None
               and FLEET_AXIS in a.sharding.mesh.axis_names})
narrow = run_fleet_shard(cfg, seeds, chunk=16, mesh=1)
print(json.dumps({"rows": rows, "same": [
    repr((a.summary(), sorted(a.turnaround.items()), a.util_cpu, a.util_mem,
          a.slack_cpu, a.slack_mem, a.n_running))
    == repr((b.summary(), sorted(b.turnaround.items()), b.util_cpu,
             b.util_mem, b.slack_cpu, b.slack_mem, b.n_running))
    for a, b in zip(wide, narrow)]}))
"""


def test_fleet_one_member_per_device_subprocess():
    """A fleet of 4 on mesh=4 puts ONE member on each device (no cap on
    the mesh width) and stays bit-identical per member to mesh=1."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        filter(None, [os.path.join(os.path.dirname(__file__), "..", "src"),
                      os.environ.get("PYTHONPATH", "")])))
    out = subprocess.run([sys.executable, "-c", _CHILD_ONE_PER_DEVICE],
                         env=env, capture_output=True, text=True,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    child = json.loads(out.stdout.splitlines()[-1])
    assert child["rows"] == [[4, 1]]          # 4 devices, 1 row each
    assert child["same"] == [True] * 4
