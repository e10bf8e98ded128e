"""The benchmark's copy of the ``google`` generator: the canonical schema,
and the same distributions as the program's ``repro.sim.workload``.

Each marginal is compared by its mean over 20,000 applications from each
generator; the two may differ by at most five standard errors of that
difference (the generators draw from different random streams, so only
the distributions can agree)."""
import json
from pathlib import Path

import numpy as np
import pytest

from harness import traffic

MIX = json.loads((Path(traffic.__file__).parents[1] / "traffic" / "google-c8.json").read_text())
N = 20_000


@pytest.fixture(scope="module")
def pair():
    import dataclasses

    from repro.sim.workload import WorkloadConfig, generate
    cfg = dataclasses.replace(WorkloadConfig(), n_apps=N, seed=3,
                              **{k: MIX["params"][k] for k in traffic.PARAMS})
    return traffic.google(MIX["params"], N, 3), generate(cfg)


def _marginals(t):
    ex = t.cpu_req > 0
    lv = t.levels[ex]
    return {
        "gap_s": np.diff(t.submit.astype(np.float64)),
        "elastic": t.is_elastic.astype(float),
        "jumpy": t.is_jumpy.astype(float),
        "n_core": t.n_core.astype(float),
        "n_elastic": t.n_elastic.astype(float),
        "cpu_req": t.cpu_req[ex].astype(float),
        "log_mem_req": np.log(t.mem_req[ex]).astype(float),
        "log_runtime": np.log(t.runtime).astype(float),
        "level": lv.mean(axis=(1, 2)).astype(float),
        "level_max": lv.max(axis=1).mean(1).astype(float),
    }


def test_trace_is_canonical(pair):
    mine, _ = pair
    assert mine.validate() is mine
    assert mine.n_apps == N and mine.max_components == MIX["params"]["max_components"]
    assert mine.levels.dtype == np.float32 and mine.submit.dtype == np.float32


@pytest.mark.parametrize("name", ["gap_s", "elastic", "jumpy", "n_core", "n_elastic",
                                  "cpu_req", "log_mem_req", "log_runtime", "level",
                                  "level_max"])
def test_marginals_match_the_program_generator(pair, name):
    a, b = (_marginals(t)[name] for t in pair)
    se = np.sqrt(a.var() / len(a) + b.var() / len(b))
    assert abs(a.mean() - b.mean()) <= 5 * se + 1e-12, (a.mean(), b.mean(), se)


def test_members_draw_their_profiles_from_the_run_seed():
    mix = dict(MIX, members=3)
    one = traffic.cohort(mix, 50, 2**33 + 1)
    again = traffic.cohort(mix, 50, 2**33 + 1)
    other = traffic.cohort(mix, 50, 2**33 + 2)
    assert all(np.array_equal(x.levels, y.levels) for x, y in zip(one, again))
    assert not np.array_equal(one[0].submit, one[1].submit)
    # member i: applications from structure_seed * members + i, the same
    # for every run; profiles from seed * members + i
    for a, b in zip(one, other):
        for col in ("submit", "runtime", "cpu_req", "mem_req", "is_core", "n_elastic"):
            assert np.array_equal(getattr(a, col), getattr(b, col))
        assert not np.array_equal(a.levels, b.levels)
    want = traffic.google(MIX["params"], 50, MIX["structure_seed"] * 3 + 2, (2**33 + 1) * 3 + 2)
    assert np.array_equal(one[2].levels, want.levels) and np.array_equal(one[2].submit, want.submit)
