"""The harness end to end without a chip: a tiny cohort of every
configuration, and a fleet over four CPU devices (the sharded chunk
step a four-chip cell would run), through the whole run to its result
object; and the command itself, which refuses a host without the chips a
cell asks for."""
import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest
from conftest import tiny

from harness import cell

ROOT = Path(cell.__file__).resolve().parents[2]


@pytest.mark.parametrize("name,chips,trace", [
    ("paper-baseline.google-c8", 1, False),
    ("paper-gp.google-c8", 1, True),
    ("paper-gp.google-c8", 4, False),
])
def test_a_run_reaches_its_result(tmp_path, name, chips, trace):
    c = tiny(name, members=2 * chips, chips=chips)
    res = cell.run(c, 2**33 + 5, 0.2, trace, jax.devices()[:chips], time.perf_counter(), tmp_path)
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    assert res["device"]["count"] == chips and res["device"]["platform"] == "cpu"
    assert res["attempted"] > 0 and res["failed"] == 0
    if trace:
        # no device trace on a CPU: the device readers find nothing (a
        # host-clock reader reads where the window has untraced chunks)
        assert set(res["metrics"]) <= {"boundary_host_ms"}
        assert res["device"]["busy_s"] == 0.0
    else:
        assert set(res["metrics"]) == {"sim_ticks_per_s", "setup_s"}
        assert res["metrics"]["sim_ticks_per_s"]["value"] > 0
    json.dumps(res)


def _command(cwd: Path):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper-gp.google-c8",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300)


def test_the_command_refuses_a_host_without_a_tpu():
    out = _command(ROOT)
    assert out.returncode != 0 and out.stdout.strip() == "", out.stdout
    assert "no chips for this cell" in out.stderr


def test_the_command_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _command(tmp_path)
    assert out.returncode != 0 and out.stdout.strip() == ""
