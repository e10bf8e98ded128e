"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics contracts: each Pallas kernel's test sweeps shapes
and dtypes and asserts allclose against the function here.  They are also
the runtime fallback on non-TPU backends (the dry-run and the CPU test
environment compile these; the Pallas path is the TPU deployment path).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fixed_order import tree_sum

Array = jax.Array


# ----------------------------------------------------------------------
# gp_gram — history-kernel Gram matrix (paper Eq. 6)
# ----------------------------------------------------------------------

def sq_dists(xa: Array, xb: Array) -> Array:
    """Pairwise squared Euclidean distances, (M,D) x (N,D) -> (M,N)."""
    # every sum in f32 adds of a fixed order (no matmul: a TPU's default
    # f32 matmul is one bf16 pass, and the cancellation in this identity
    # would turn that into large distance errors for near-identical
    # patterns; and the GP's gradient needs the same bits in a solo and
    # a batched program, see ``fixed_order``)
    na = tree_sum(xa * xa, -1)
    nb = tree_sum(xb * xb, -1)
    ab = tree_sum(xa[:, None, :] * xb[None, :, :], -1)
    d2 = na[:, None] + nb[None, :] - 2.0 * ab
    return jnp.maximum(d2, 0.0)


def gram(xa: Array, xb: Array, lengthscale: Array, sigma_f: Array,
         kind: str = "exp") -> Array:
    """k_h(x, x') of Eq. (6): a stationary kernel on pattern vectors.

    kind="exp": sf^2 * exp(-r / ell)        (paper's choice — Fig. 2)
    kind="rbf": sf^2 * exp(-r^2 / (2 ell^2))
    """
    d2 = sq_dists(xa.astype(jnp.float32), xb.astype(jnp.float32))
    if kind == "exp":
        r = jnp.sqrt(d2 + 1e-12)
        k = jnp.exp(-r / lengthscale)
    elif kind == "rbf":
        k = jnp.exp(-0.5 * d2 / (lengthscale ** 2))
    else:
        raise ValueError(f"unknown kernel kind: {kind}")
    return (sigma_f ** 2) * k


# ----------------------------------------------------------------------
# flash_attention — causal/full multi-head attention with GQA
# ----------------------------------------------------------------------

def attention(q: Array, k: Array, v: Array, *, causal: bool = True,
              sm_scale: float | None = None) -> Array:
    """Reference attention.  q: (B,Hq,S,D), k/v: (B,Hkv,T,D) with
    Hq % Hkv == 0 (GQA).  Returns (B,Hq,S,D) in q.dtype."""
    B, Hq, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0
    group = Hq // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    qf = q.astype(jnp.float32)
    kf = jnp.repeat(k.astype(jnp.float32), group, axis=1)
    vf = jnp.repeat(v.astype(jnp.float32), group, axis=1)
    logits = jnp.einsum("bhsd,bhtd->bhst", qf, kf) * sm_scale
    if causal:
        # query i (global position T-S+i) attends keys 0..T-S+i
        qpos = jnp.arange(S)[:, None] + (T - S)
        kpos = jnp.arange(T)[None, :]
        logits = jnp.where(kpos <= qpos, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", w, vf)
    return out.astype(q.dtype)
