"""Sweep subsystem tests: grid expansion, vectorized-engine equivalence,
solo-vs-sweep bit-identity, artifact schema."""
import dataclasses
import itertools
import json

import numpy as np
import pytest

from repro.sim import (ClusterConfig, SimConfig, WorkloadConfig, generate,
                       run_sim, run_sim_reference)
from repro.sim.sweep import expand_grid, quick_base_config, run_grid

WL = WorkloadConfig(n_apps=40, max_components=8, max_runtime=1200.0,
                    mean_burst_gap=4.0, mean_long_gap=60.0, seed=7)
CL = ClusterConfig(n_hosts=4, max_running_apps=32)
BASE = SimConfig(cluster=CL, workload=WL, max_ticks=4000)


def _results_equal(a, b) -> bool:
    return a.first_difference(b) is None


# ----------------------------------------------------------------------
# grid expansion
# ----------------------------------------------------------------------

def test_grid_covers_cross_product_exactly_once():
    axes = {"policy": ["baseline", "pessimistic"],
            "forecaster": ["persist", "oracle"],
            "safeguard.k1": [0.0, 0.05, 0.25]}
    seeds = [0, 1]
    cells = expand_grid(BASE, axes, seeds)
    assert len(cells) == 2 * 2 * 3 * 2
    seen = {(c.cfg.policy, c.cfg.forecaster, c.cfg.safeguard.k1, c.seed)
            for c in cells}
    want = set(itertools.product(["baseline", "pessimistic"],
                                 ["persist", "oracle"],
                                 [0.0, 0.05, 0.25], seeds))
    assert seen == want                      # every combo exactly once


def test_grid_zipped_axis_and_explicit_cells():
    cells = expand_grid(
        BASE,
        axes={("policy", "forecaster"): [("baseline", "persist"),
                                         ("pessimistic", "oracle")]},
        seeds=[3],
        cells=[{"policy": "optimistic", "forecaster": "oracle"}])
    combos = [(c.cfg.policy, c.cfg.forecaster) for c in cells]
    assert combos == [("baseline", "persist"), ("pessimistic", "oracle"),
                      ("optimistic", "oracle")]
    assert all(c.cfg.workload.seed == 3 for c in cells)


def test_grid_base_seed_kept_when_seeds_none():
    cells = expand_grid(BASE, {"policy": ["baseline"]}, seeds=None)
    assert len(cells) == 1 and cells[0].cfg.workload.seed == WL.seed


def test_grid_nested_override_leaves_base_untouched():
    cells = expand_grid(BASE, {"safeguard.k2": [9.0]}, seeds=[0])
    assert cells[0].cfg.safeguard.k2 == 9.0
    assert BASE.safeguard.k2 != 9.0


# ----------------------------------------------------------------------
# vectorized engine == seed (reference) engine, bit for bit
# ----------------------------------------------------------------------

@pytest.mark.parametrize("policy,forecaster", [
    ("baseline", "persist"),
    ("pessimistic", "oracle"),
    ("optimistic", "oracle"),
    ("pessimistic", "persist"),     # exercises monitor windows + grace
])
def test_vectorized_engine_matches_reference(policy, forecaster):
    cfg = dataclasses.replace(BASE, policy=policy, forecaster=forecaster)
    wl = generate(cfg.workload)
    vec = run_sim(cfg, wl)
    ref = run_sim_reference(cfg, wl)
    s, r = vec.summary(), ref.summary()
    # the headline counters the paper plots ...
    for k in ("completed", "failed_frac", "failure_events", "oom_kills",
              "full_preemptions", "partial_preemptions"):
        assert s[k] == r[k], (k, s[k], r[k])
    # ... and in fact the entire result, bit for bit
    assert _results_equal(vec, ref)


def test_vectorized_engine_matches_reference_checkpoint_mode():
    cfg = dataclasses.replace(BASE, policy="pessimistic",
                              forecaster="oracle", work_lost_on_kill=False)
    wl = generate(cfg.workload)
    assert _results_equal(run_sim(cfg, wl), run_sim_reference(cfg, wl))


# ----------------------------------------------------------------------
# sweep driver
# ----------------------------------------------------------------------

def test_sweep_cell_bit_identical_to_solo_run():
    """Same seed => same SimResults whether a cell runs alone or inside a
    thread-pooled sweep with cross-sim forecast batching."""
    base = quick_base_config(n_apps=30, n_hosts=3, seed=0)
    res = run_grid(base,
                   axes={"policy": ["baseline", "pessimistic"],
                         "forecaster": ["persist", "gp"]},
                   seeds=[0, 1], workers=4)
    assert len(res.cells) == 8
    for overrides, seed in (({"policy": "pessimistic", "forecaster": "gp"}, 1),
                            ({"policy": "baseline", "forecaster": "persist"}, 0)):
        cell = next(c for c in res.cells
                    if c["overrides"] == overrides and c["seed"] == seed)
        cfg = base
        for k, v in overrides.items():
            cfg = dataclasses.replace(cfg, **{k: v})
        cfg = dataclasses.replace(
            cfg, workload=dataclasses.replace(cfg.workload, seed=seed))
        assert run_sim(cfg).summary() == cell["summary"]


def test_sweep_aggregates_and_artifact(tmp_path):
    out = tmp_path / "BENCH_sweep.json"
    base = quick_base_config(n_apps=24, n_hosts=3, seed=0)
    res = run_grid(base,
                   axes={"policy": ["baseline", "pessimistic"],
                         "forecaster": ["oracle"]},
                   seeds=[0, 1], out_path=str(out))
    data = json.loads(out.read_text())
    assert data["schema"] == 3
    assert "google" in data["scenarios"]        # per-scenario trace stats
    assert len(data["cells"]) == 4 and len(data["aggregates"]) == 2
    for c in data["cells"]:
        for key in ("turnaround_mean", "failed_frac", "util_mem_mean"):
            assert key in c["summary"]
    by_policy = {a["overrides"]["policy"]: a for a in data["aggregates"]}
    assert by_policy["baseline"]["turnaround_speedup"] == 1.0
    assert np.isfinite(by_policy["pessimistic"]["turnaround_speedup"])
    assert by_policy["pessimistic"]["n_seeds"] == 2
    # deterministic per seed: rerun reproduces the same summaries
    res2 = run_grid(base, axes={"policy": ["baseline", "pessimistic"],
                                "forecaster": ["oracle"]}, seeds=[0, 1])
    assert [c["summary"] for c in res2.cells] == \
        [c["summary"] for c in res.cells]


def test_batcher_propagates_leader_failure(monkeypatch):
    """A failing forecast must raise in EVERY participating sim instead of
    deadlocking followers on their never-set events."""
    import threading

    from repro.sim import sweep as SW

    monkeypatch.setattr(
        SW, "forecast_peaks",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")))
    batcher = SW.ForecastBatcher(wait_s=0.05)
    cfg = dataclasses.replace(quick_base_config(), forecaster="gp")
    clients = [batcher.client(cfg) for _ in range(2)]
    wins = np.zeros((2, cfg.window), np.float32)
    val = np.ones((2, cfg.window), bool)
    errs = []

    def call(c):
        try:
            c(wins, val)
        except RuntimeError as e:
            errs.append(str(e))

    threads = [threading.Thread(target=call, args=(c,)) for c in clients]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in threads), "batcher deadlocked"
    assert errs == ["boom", "boom"]


def test_sweep_reference_engine_option():
    base = quick_base_config(n_apps=16, n_hosts=2, seed=0)
    kw = dict(axes={"policy": ["pessimistic"], "forecaster": ["oracle"]},
              seeds=[0])
    vec = run_grid(base, **kw)
    ref = run_grid(base, engine="reference", **kw)
    assert vec.cells[0]["summary"] == ref.cells[0]["summary"]


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The entry points' persistent compile cache: the environment's
    directory when set, else one fixed, git-ignored path in the
    checkout (never a temporary or per-process name)."""
    import pathlib

    import jax

    from repro.sim import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = pathlib.Path(compile_cache.enable_compile_cache())
        assert path == compile_cache.CHECKOUT_CACHE
        assert jax.config.jax_compilation_cache_dir == str(path)
        root = pathlib.Path(__file__).resolve().parents[1]
        assert path.parent == root
        assert f"{path.name}/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_cold_compiles_bypass_the_cache(tmp_path):
    """A compile timed inside ``cold_compiles`` neither reads nor writes
    the persistent cache; outside it, the cache takes the entry."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from repro.sim.compile_cache import cold_compiles
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()
        with cold_compiles():
            jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(7)).block_until_ready()
        assert not any(tmp_path.iterdir())
        jax.jit(lambda x: jnp.cos(x) * 5.0)(jnp.ones(9)).block_until_ready()
        assert any(tmp_path.iterdir())
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
