"""Float sums whose order of adds is fixed by the program, not the compiler.

A ``reduce`` leaves the order of its adds to the compiler, and a TPU
compiler picks it by the operand's layout: a reduced dimension in the
vector lanes is summed by a rotate-and-add tree, one in the sublanes or
the major dimensions by a chain of vector adds.  Layout assignment sees
the whole program, so the same traced function can sum in one order in a
solo program and in another when it is vmapped over a seed cohort or a
sharded fleet, and the two results part in the last bit.  The engines
promise a cohort member, or a fleet member, the solo run's exact bits,
so every float sum on the simulator's device path is written here as
explicit slices and elementwise adds, which no layout reorders, or, where
it picks one value out of zeros, as a one-hot sum, exact in any order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def tree_sum(x: Array, axis: int = -1) -> Array:
    """Sum over ``axis`` as a pairwise tree: halves are added until one
    remains, an odd length first padded with an exact zero."""
    x = jnp.moveaxis(x, axis, 0)
    while x.shape[0] > 1:
        if x.shape[0] % 2:
            x = jnp.concatenate([x, jnp.zeros_like(x[:1])])
        half = x.shape[0] // 2
        x = x[:half] + x[half:]
    return x[0]


def onehot_sum(mask: Array, x: Array, axis=None) -> Array:
    """``where(mask, x, 0).sum(axis)`` where ``mask`` selects at most one
    entry along ``axis``: one value plus exact zeros, the same bits in
    any order of adds, so this one sum may be left to the compiler."""
    return jnp.where(mask, x, 0.0).sum(axis)
