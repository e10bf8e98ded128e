"""CPU rehearsal of ``chip_smoke.py``: every phase of the on-chip smoke
runs here at a small size, so its own code (comparisons, first-diff
reports, registry reads, the fleet placement check) has run before it
meets the chip.

Only the sizes change: a small GP cluster and workload, fewer seeds,
rows and ticks.  ``jax.devices()[0]`` is the CPU here, so "chip vs CPU"
compares the CPU with itself, and the compiled chunk program holds no
Mosaic kernel: that one check must fail here, and nothing else.
"""
import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.sim import ClusterConfig, SimConfig, WorkloadConfig

ROOT = Path(__file__).resolve().parents[1]
KERNEL_CHECK = "compiled GP chunk program holds the Pallas kernel"

SMALL = SimConfig(
    cluster=ClusterConfig(n_hosts=3, max_running_apps=16),
    workload=WorkloadConfig(n_apps=12, max_components=6, max_runtime=1200.0,
                            mean_burst_gap=4.0, mean_long_gap=60.0),
    policy="pessimistic", forecaster="gp")


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def smoke(monkeypatch):
    cs = _load_smoke()
    for name, value in {"base_config": lambda: SMALL, "FULL_TICKS": 200,
                        "CHUNK": 16, "SLICE_TICKS": 48,
                        "COHORT_SEEDS": (0, 1), "GRAM_BATCH": 4,
                        "FC_ROWS": 32}.items():
        monkeypatch.setattr(cs, name, value)
    return cs


def test_smoke_phases_rehearse_on_cpu(smoke, capsys):
    """The one-chip phases run end to end; the only failing check is the
    Mosaic-kernel marker, which a CPU program cannot hold."""
    sm = smoke.Smoke()
    smoke.run_phases(sm, 1)
    out = capsys.readouterr().out
    assert sm.failed == [KERNEL_CHECK], out
    for line in ("PASS bucketed == unbucketed", "PASS leap == uniform",
                 "PASS cohort member (seed 0 of 2) == solo",
                 "PASS scan == host engine", "PASS gp forecast mean",
                 "PASS arima forecast var", "PASS pallas gram (rbf)",
                 "PASS full run seed 1", f"FAIL {KERNEL_CHECK} -- 0 ",
                 "PASS cohort chunk from tick 64, bucket ",
                 "# gp on chip: 8 rows alone vs in the 32-row batch: "
                 "bit-identical"):
        assert line in out, (line, out)


def test_smoke_reports_first_difference(smoke, capsys):
    """A broken contract names the field and index where it parts."""
    from repro.sim.step import run_sim_scan
    cfg = dataclasses.replace(SMALL, max_ticks=48)
    a = run_sim_scan(cfg, chunk=16)
    b = run_sim_scan(cfg, chunk=16)
    b.util_mem[5] += 1e-7
    sm = smoke.Smoke()
    smoke._same(sm, "a == a", a, a)
    smoke._same(sm, "a == b", a, b)
    out = capsys.readouterr().out
    assert sm.failed == ["a == b"]
    assert "PASS a == a -- bit-identical" in out
    # one float32 ulp in one tick: too small for the summary's mean
    assert "FAIL a == b -- util_mem[5] of " in out, out


_CHILD_FLEET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import importlib.util, json, sys
from repro.sim import ClusterConfig, SimConfig, WorkloadConfig
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
cs.base_config = lambda: SimConfig(
    cluster=ClusterConfig(n_hosts=2, max_running_apps=8),
    workload=WorkloadConfig(n_apps=12, max_components=4, max_runtime=900.0,
                            mean_burst_gap=4.0, mean_long_gap=60.0),
    policy="pessimistic", forecaster="gp")
cs.SLICE_TICKS = 48
sm = cs.Smoke()
cs.run_phases(sm, 4)
print(json.dumps(sm.failed))
"""


def test_smoke_fleet_phase_on_four_cpu_devices():
    """``--chips 4``'s phase on 4 forced CPU devices: the mesh=4 run's
    arrays span 4 devices with 2 of the 8 members each, and every
    member equals its mesh=1 run."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        filter(None, [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")])))
    out = subprocess.run(
        [sys.executable, "-c", _CHILD_FLEET, str(ROOT / "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.splitlines()[-1]) == [], out.stdout
    assert ("PASS the mesh=4 run's fleet arrays span 4 devices -- "
            in out.stdout), out.stdout
    assert "on devices [0, 1, 2, 3], [2] fleet rows per device" \
        in out.stdout, out.stdout
    for s in range(8):
        assert f"PASS fleet member seed {s}: mesh=4 == mesh=1" in out.stdout


def test_smoke_refuses_a_cpu_only_machine(tmp_path):
    """No TPU: exit non-zero with no result line; copied away from the
    repository: exit 2."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    here = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert here.returncode == 1 and "no TPU visible" in here.stderr
    assert '"ok"' not in here.stdout
    alone = tmp_path / "chip_smoke.py"
    alone.write_text((ROOT / "chip_smoke.py").read_text())
    away = subprocess.run([sys.executable, str(alone)], env=env,
                          capture_output=True, text=True, timeout=120)
    assert away.returncode == 2 and '"ok"' not in away.stdout
