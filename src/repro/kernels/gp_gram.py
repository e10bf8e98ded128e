"""Pallas TPU kernel: batched history-kernel Gram matrix (paper Eq. 6).

This is the arithmetic hot spot of fleet-scale GP forecasting: with B
component series, N patterns each of dimension D = h+1, every monitoring
tick rebuilds B Gram matrices (N x N) plus B cross-vectors — O(B N^2 D)
flops that are 100% MXU-friendly once phrased as a matmul via

    ||a - b||^2 = ||a||^2 + ||b||^2 - 2 a.b

The kernel fuses the distance computation with the kernel application
(exp / rbf) so the (M, N) distance intermediate never round-trips to HBM.

TPU adaptation notes (vs a CUDA pairwise-distance kernel):
  * tiles are MXU/VPU aligned — D is padded to a multiple of 128 (lane
    dim) by the wrapper in ops.py; M/N tiles are multiples of 8 (sublane);
  * the -2 a.b term is a (bm, D) x (D, bn) matmul hitting the MXU at
    HIGHEST precision with fp32 accumulation;
  * hyper-parameters (ell, sf) arrive as a small VMEM vector so the same
    compiled kernel serves every evidence-maximization step.

Zero-padding contract: padded D columns are zero in BOTH operands, so
they contribute nothing to any pairwise distance; padded M/N rows produce
garbage rows/cols that the wrapper slices off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

# fallback tile sizes — ops.py may override per shape
DEF_BM = 128
DEF_BN = 128


def _gram_kernel(xa_ref, xb_ref, params_ref, out_ref, *, kind: str):
    """One (bm, bn) tile of the Gram matrix. Full D is resident."""
    xa = xa_ref[...].astype(jnp.float32)           # (bm, D)
    xb = xb_ref[...].astype(jnp.float32)           # (bn, D)
    ell = params_ref[0, 0]
    sf = params_ref[0, 1]
    na = jnp.sum(xa * xa, axis=1, keepdims=True)    # (bm, 1)
    nb = jnp.sum(xb * xb, axis=1, keepdims=True).T  # (1, bn)
    # MXU matmul at full f32 precision: the distance identity cancels,
    # so bf16-rounded operands would corrupt d2
    ab = jax.lax.dot_general(
        xa, xb, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)         # (bm, bn)
    d2 = jnp.maximum(na + nb - 2.0 * ab, 0.0)
    if kind == "exp":
        r = jnp.sqrt(d2 + 1e-12)
        k = jnp.exp(-r / ell)
    else:  # rbf
        k = jnp.exp(-0.5 * d2 / (ell * ell))
    out_ref[...] = (sf * sf) * k


@functools.partial(jax.jit, static_argnames=("kind", "bm", "bn", "interpret"))
def gp_gram(xa: Array, xb: Array, params: Array, *, kind: str = "exp",
            bm: int = DEF_BM, bn: int = DEF_BN,
            interpret: bool = False) -> Array:
    """Gram matrix between padded pattern sets.

    xa: (M, D), xb: (N, D) with M % bm == 0, N % bn == 0, D % 128 == 0
    (the ops.py wrapper pads).  params: (1, 128) vector, [0,0]=ell,
    [0,1]=sigma_f.  Returns (M, N) float32.
    """
    M, D = xa.shape
    N, _ = xb.shape
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    grid = (M // bm, N // bn)
    return pl.pallas_call(
        functools.partial(_gram_kernel, kind=kind),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, D), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, D), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 128), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(xa, xb, params)
