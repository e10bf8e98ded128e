"""The benchmark's own tests: on the CPU, with four host devices for the
fleet, and with the program's ``src`` and the harness importable.

    PYTHONPATH=src python -m pytest bench/tests -q
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
_BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_BENCH.parent / "src"), str(_BENCH)]


def tiny(name: str, *, members: int = 2, hosts: int = 8, slots: int = 16,
         n_apps: int = 300, warm: int = 64, structure_seed: int | None = None,
         chips: int | None = None):
    """A cell of ``BENCHMARK.json`` cut to a size the CPU runs in seconds:
    the same configuration, traffic and limits, fewer hosts, slots,
    applications and members; ``chips`` lays the cohort over that many
    devices as a fleet."""
    import copy

    from harness import cell
    c = cell.load_cell(name)
    c.spec = copy.deepcopy(c.spec)
    c.spec["sim"]["cluster"].update(n_hosts=hosts, max_running_apps=slots)
    c.spec["n_apps"] = n_apps
    c.mix = dict(c.mix, members=members, warm_ticks=warm)
    if structure_seed is not None:
        c.mix["structure_seed"] = structure_seed
    if chips is not None:
        c.chips = chips
    return c
