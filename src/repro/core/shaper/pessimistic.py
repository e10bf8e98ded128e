"""Pessimistic preemption policy — paper Algorithm 1, in JAX.

Greedy pass over running applications in scheduler-policy order:

  * an application's CORE components are fitted first, host by host; if
    any host would go negative the whole application is marked for FULL
    preemption (paper lines 11-21, 34-36);
  * surviving applications then fit their ELASTIC components one at a
    time, oldest-first (sorted by timeAlive, line 25) — a component that
    does not fit is PARTIALLY preempted on its own (lines 26-33, 37-38);
  * every surviving component is resized to its shaped demand
    (forecast peak + beta, lines 39-41).

Faithfulness notes: core checks use ``< 0`` and elastic checks ``<= 0``
exactly as in the listing; beta is already folded into the demands by
the caller (the listing subtracts ``futureX - beta`` — we precompute
``demand = clip(forecast + beta, 0, request)`` via safeguard.shaped_demand).

The whole policy is a ``lax.scan`` over the (padded, fixed-size) app
table with an inner scan over the component table, so one jitted call
shapes the entire cluster — this is what lets the live framework run the
policy every monitoring tick for thousands of nodes.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels.fixed_order import onehot_sum, tree_sum

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShapeProblem:
    """Fixed-size cluster state handed to a shaping policy.

    A = max apps, C = max components per app, H = hosts.
    Demands are the shaped targets (forecast + beta) per component.
    """

    host_cpu: Array          # (H,) capacity
    host_mem: Array          # (H,)
    app_exists: Array        # (A,) bool
    app_order: Array         # (A,) int — processing order (policy-sorted),
                             #   entries are app indices; padded with -1
    comp_exists: Array       # (A, C) bool
    comp_core: Array         # (A, C) bool
    comp_host: Array         # (A, C) int32 host index (0 if absent)
    comp_cpu: Array          # (A, C) shaped cpu demand
    comp_mem: Array          # (A, C) shaped mem demand
    comp_alive: Array        # (A, C) seconds alive (elastic sort key)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShapeDecision:
    kill_app: Array          # (A,) bool — full preemption
    kill_comp: Array         # (A, C) bool — partial (elastic) preemption
    alloc_cpu: Array         # (A, C) granted allocation (0 for killed)
    alloc_mem: Array         # (A, C)
    cpu_free: Array          # (H,) remaining after allocation
    mem_free: Array          # (H,)


def pessimistic_shape_raw(p: ShapeProblem) -> ShapeDecision:
    """Unjitted Algorithm 1 — inline this inside larger jitted programs
    (the fused scan engine traces it once per tick chunk instead of
    paying a separate dispatch per tick)."""
    A, C = p.comp_exists.shape
    H = p.host_cpu.shape[0]

    # elastic processing order per app: oldest (largest timeAlive) first,
    # so the newest components are the ones that hit exhausted capacity.
    alive_key = jnp.where(p.comp_exists & ~p.comp_core,
                          p.comp_alive, -jnp.inf)
    elastic_order = jnp.argsort(-alive_key, axis=1)          # (A, C)

    # Everything the sequential pass needs is pre-gathered OUTSIDE the
    # scan, batched over all apps: rows permuted into processing order,
    # per-app core demand aggregated per host, elastic demands permuted
    # into eviction order, and host one-hots materialized.  The scan
    # body is then pure masked arithmetic — no dynamic gathers or
    # scatters, which XLA CPU serializes (and which stay serial under
    # the scan engine's vmap over seed cohorts).
    a_all = jnp.maximum(p.app_order, 0)
    valid_all = (p.app_order >= 0) & p.app_exists[a_all]
    exists = p.comp_exists[a_all]                            # (A, C)
    is_core = p.comp_core[a_all]
    host = p.comp_host[a_all]
    # cpu/mem fused on a trailing resource lane: halves the op count of
    # the sequential passes (tiny-tensor op overhead dominates there)
    row_dem = jnp.stack([p.comp_cpu[a_all], p.comp_mem[a_all]], -1)
    core = exists & is_core
    host_oh = host[:, :, None] == jnp.arange(H)[None, None, :]  # (A, C, H)
    # a fixed-order sum (``kernels.fixed_order``): an app's core
    # components on one host add in the same order in every program
    core_dem_all = tree_sum(
        jnp.where((core[:, :, None] & host_oh)[..., None],
                  row_dem[:, :, None, :], 0.0), 1)           # (A, H, 2)
    order = elastic_order[a_all]                             # (A, C)
    ar = jnp.arange(A)[:, None]
    ord_dem = row_dem[ar, order]                             # (A, C, 2)
    ord_el = (exists & ~is_core)[ar, order]
    ord_oh = host_oh[ar, order]                              # (A, C, H)

    xs = (valid_all, core_dem_all, ord_dem, ord_el, ord_oh)

    def app_step(carry, x):
        free = carry                                         # (H, 2)
        valid, core_dem, o_dem, o_el, o_oh = x

        # ---- core components (lines 11-19): aggregate per-host demand ----
        trial = free - core_dem
        remove = valid & jnp.any(trial < 0.0)
        commit_core = valid & ~remove
        free = jnp.where(commit_core, trial, free)

        # ---- elastic components (lines 25-33): sequential oldest-first ----
        def comp_step(f, x2):
            dem, el_c, oh = x2                   # (2,), (), (H,)
            is_el = commit_core & el_c
            tcm = onehot_sum(oh[:, None], f, 0) - dem            # (2,)
            kill_c = is_el & jnp.any(tcm <= 0.0)
            commit = is_el & ~kill_c
            f = f - jnp.where((oh & commit)[:, None], dem, 0.0)
            return f, kill_c

        # fully unrolled: C is small and the body is a handful of scalar
        # ops — loop-carry overhead would dominate the work (the scan
        # engine runs this every tick inside a fused chunk)
        free, kill_pos = jax.lax.scan(
            comp_step, free, (o_dem, o_el, o_oh), unroll=True)

        return free, (remove, kill_pos)

    free0 = jnp.stack([p.host_cpu, p.host_mem], -1)
    free, (removes, kill_pos) = jax.lax.scan(
        app_step, free0, xs, unroll=8)
    cpu_free, mem_free = free[:, 0], free[:, 1]

    # scatter scan outputs back: kill positions -> component order, then
    # processing order -> app-index order
    kill_rows = jnp.zeros((A, C), bool).at[ar, order].set(kill_pos)
    kill_app = jnp.zeros((A,), bool).at[a_all].max(removes)
    kill_comp = jnp.zeros((A, C), bool).at[a_all].max(kill_rows)

    survive = (p.comp_exists & p.app_exists[:, None]
               & ~kill_app[:, None] & ~kill_comp)
    alloc_cpu = jnp.where(survive, p.comp_cpu, 0.0)
    alloc_mem = jnp.where(survive, p.comp_mem, 0.0)
    return ShapeDecision(kill_app=kill_app, kill_comp=kill_comp,
                         alloc_cpu=alloc_cpu, alloc_mem=alloc_mem,
                         cpu_free=cpu_free, mem_free=mem_free)


#: jitted entry point (one dispatch per call — the host-loop engines)
pessimistic_shape = jax.jit(pessimistic_shape_raw)
