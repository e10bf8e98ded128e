"""The check fails a run whose timed path is broken underneath.

Each test drives a whole run of a tiny cell past the look for a chip,
with the chunk step (or the chunk driver's bucket pick) broken, and
sees ``correct`` come out false: a step that returns its state
unchanged; a step that leaves half the cohort's members unstepped; on
four devices, the bucket picked from the first device's members alone
(the exchange between chips left out); and an answer altered where it is
produced (one application's progress)."""
import time

import jax
import jax.numpy as jnp
import pytest
from conftest import tiny

from harness import cell, system

SEED = 2**32 + 17


def _run(monkeypatch, tmp_path, name, chips, wrap=None, pick=None, **size):
    real = system.build

    def broken(fn):
        step = wrap(fn)
        step.__wrapped__ = fn.__wrapped__     # the program that set-up builds
        return step

    def build(cfg, traces, devices):
        sim = real(cfg, traces, devices)
        if wrap is not None:
            sim.fns = {b: broken(fn) for b, fn in sim.fns.items()}
        if pick is not None:
            monkeypatch.setattr(type(sim), "pick", pick)
        return sim

    monkeypatch.setattr(system, "build", build)
    c = tiny(name, **{"members": 2 * chips, "chips": chips, **size})
    return cell.run(c, SEED, 0.2, False, jax.devices()[:chips], time.perf_counter(), tmp_path)


def _copy(st):
    return jax.tree.map(jnp.copy, st)


def unchanged(fn):
    def step(tr, st):
        keep = _copy(st)
        _, ms = fn(tr, st)
        return keep, ms
    return step


def half(fn):
    def step(tr, st):
        keep = _copy(st)
        new, ms = fn(tr, st)
        n = new.t.shape[0] // 2
        return jax.tree.map(lambda a, b: a.at[:n].set(b[:n]), new, keep), ms
    return step


def altered(field):
    """One answer altered where it is produced: the first running
    application of member 0 reports three quarters of its progress."""
    def wrap(fn):
        def step(tr, st):
            new, ms = fn(tr, st)
            slot = jnp.argmax(new.slot_gid[0] >= 0)
            x = getattr(new, field).at[0, slot].multiply(0.75)
            return new.__class__(**{**new.__dict__, field: x}), ms
        return step
    return wrap


CELLS = [("paper-baseline.google-c8", 1), ("paper-gp.google-c8", 1),
         ("paper-gp.google-c8", 4)]


@pytest.mark.parametrize("name,chips", CELLS)
@pytest.mark.parametrize("fault", [unchanged, half, altered("work_done")],
                         ids=["unchanged", "half", "answer"])
def test_a_broken_step_is_not_correct(monkeypatch, tmp_path, name, chips, fault):
    res = _run(monkeypatch, tmp_path, name, chips, wrap=fault)
    assert res["correct"] is False, res["checks"]


def test_a_bucket_picked_without_the_exchange_is_not_correct(monkeypatch, tmp_path):
    """A fleet's only exchange between chips is the host's gather of
    every member's state at a chunk boundary, from which the bucket is
    picked.  Left out, the bucket covers the first device's members only;
    that changes no state, only the rows the forecast computes, and shows
    where it picks another bucket: in this fleet (8 members, structure
    seed 3) at the window's first chunk, 16 rows where the fleet needs 128."""
    from repro.sim import step as S

    def first_device_only(self):
        one = jax.tree.map(lambda x: x[: x.shape[0] // 4] if x.ndim else x,
                           jax.device_get(self.st))
        return S._pick_bucket(self.cfg, one)

    res = _run(monkeypatch, tmp_path, "paper-gp.google-c8", 4, pick=first_device_only,
               members=8, structure_seed=3)
    assert res["correct"] is False, res["checks"]
