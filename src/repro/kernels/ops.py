"""Public jit'd wrappers for the Pallas kernels, with shape padding and
backend dispatch.

``impl`` semantics (every op):
  * "auto"   — Pallas on TPU, pure-jnp reference elsewhere (CPU dry-run /
               tests compile the reference; TPU deployment gets the kernel);
  * "pallas" — force the kernel (interpret-mode off-TPU, used by tests);
  * "jnp"    — force the reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import gp_gram as _gg
from repro.kernels import ref
from repro.kernels.fixed_order import tree_sum

Array = jax.Array


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad_axis(x: Array, axis: int, to: int) -> Array:
    pad = to - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ----------------------------------------------------------------------
# gram — history-kernel Gram matrix
# ----------------------------------------------------------------------

def gram(xa: Array, xb: Array, lengthscale, sigma_f, *, kind: str = "exp",
         impl: str = "auto") -> Array:
    """Gram matrix k_h(xa, xb) (paper Eq. 6). xa: (M,D), xb: (N,D).

    Differentiable on every path: the Pallas forward carries a
    ``custom_vjp`` whose backward is the VJP of :func:`ref.gram`, so the
    GP evidence loss keeps the kernel in its forward pass under
    ``jax.grad`` (``pallas_call`` has no reverse-mode rule of its own).
    """
    if impl == "jnp" or (impl == "auto" and not _on_tpu()):
        return ref.gram(xa, xb, lengthscale, sigma_f, kind=kind)
    return _pallas_gram(xa, xb, jnp.asarray(lengthscale, jnp.float32),
                        jnp.asarray(sigma_f, jnp.float32), kind,
                        not _on_tpu())


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _pallas_gram(xa: Array, xb: Array, ell: Array, sf: Array, kind: str,
                 interpret: bool) -> Array:
    M, D = xa.shape
    N = xb.shape[0]
    # pick tiles: small problems use one tile, large problems 128x128
    bm = min(_round_up(M, 8), 128)
    bn = min(_round_up(N, 8), 128)
    Dp = _round_up(D, 128)
    Mp, Np = _round_up(M, bm), _round_up(N, bn)
    xa_p = _pad_axis(_pad_axis(xa.astype(jnp.float32), 1, Dp), 0, Mp)
    xb_p = _pad_axis(_pad_axis(xb.astype(jnp.float32), 1, Dp), 0, Np)
    params = jnp.zeros((1, 128), jnp.float32)
    params = params.at[0, 0].set(ell)
    params = params.at[0, 1].set(sf)
    out = _gg.gp_gram(xa_p, xb_p, params, kind=kind, bm=bm, bn=bn,
                      interpret=interpret)
    return out[:M, :N]


def _pallas_gram_fwd(xa, xb, ell, sf, kind, interpret):
    return _pallas_gram(xa, xb, ell, sf, kind, interpret), (xa, xb, ell, sf)


def _pallas_gram_bwd(kind, interpret, res, g):
    # the VJP of ref.gram with ell and sf spread over the (M, N) grid,
    # so each parameter's cotangent is one fixed-order sum of per-entry
    # terms (a broadcast's transpose would be a compiler-ordered reduce)
    xa, xb, ell, sf = res
    grid = (xa.shape[0], xb.shape[0])
    _, vjp = jax.vjp(lambda *a: ref.gram(*a, kind=kind), xa, xb,
                     jnp.full(grid, ell), jnp.full(grid, sf))
    g_xa, g_xb, g_ell, g_sf = vjp(g)
    return (g_xa, g_xb, tree_sum(tree_sum(g_ell, 1), 0),
            tree_sum(tree_sum(g_sf, 1), 0))


_pallas_gram.defvjp(_pallas_gram_fwd, _pallas_gram_bwd)


# ----------------------------------------------------------------------
# attention
# ----------------------------------------------------------------------

def attention(q: Array, k: Array, v: Array, *, causal: bool = True,
              sm_scale: float | None = None, impl: str = "auto",
              bq: int | None = None, bk: int | None = None) -> Array:
    """Multi-head (GQA) attention. q: (B,Hq,S,D), k/v: (B,Hkv,T,D).

    Queries are aligned to the END of the key sequence (decode semantics:
    q_offset = T - S), which also covers self-attention (T == S).
    """
    B, Hq, S, D = q.shape
    T = k.shape[2]
    if impl == "jnp" or (impl == "auto" and not _on_tpu()):
        return ref.attention(q, k, v, causal=causal, sm_scale=sm_scale)
    if S < 8:  # decode-style tiny q: blockwise machinery not worth it
        return ref.attention(q, k, v, causal=causal, sm_scale=sm_scale)
    bq = bq or min(_round_up(S, 8), _fa.DEF_BQ)
    bk = bk or min(_round_up(T, 128), _fa.DEF_BK)
    Sp, Tp, Dp = _round_up(S, bq), _round_up(T, bk), _round_up(D, 128)
    q_p = _pad_axis(_pad_axis(q, 3, Dp), 2, Sp)
    k_p = _pad_axis(_pad_axis(k, 3, Dp), 2, Tp)
    v_p = _pad_axis(_pad_axis(v, 3, Dp), 2, Tp)
    if Tp != T and not causal:
        # padded keys must not receive mass: bias via causal offset trick
        # doesn't apply; mask by writing NEG_INF into padded K is wrong for
        # exp kernel — instead fall back to reference for non-causal pads.
        return ref.attention(q, k, v, causal=causal, sm_scale=sm_scale)
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)   # scale by TRUE head dim, not padded
    out = _fa.flash_attention(
        q_p, k_p, v_p, causal=causal, sm_scale=sm_scale, bq=bq, bk=bk,
        q_offset=T - S, interpret=not _on_tpu())
    return out[:, :, :S, :D]
