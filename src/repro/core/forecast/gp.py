"""GP regression with a history-dependent kernel (paper §3.1.2).

Time series are modeled as the state-space form of Eq. (4):

    y_t = f(y_{t-1}, ..., y_{t-h}) + eps_t

and f is learned by standard GP regression over *pattern* inputs (Eq. 5):

    x~_t = [t, y_{t-h}, ..., y_{t-1}]

so the kernel compares observation histories, not just time stamps
(Eq. 6).  Two stationary kernels are supported, matching the paper's
Fig. 2 comparison:

  * ``exp``  — exponential  k(r) = sf^2 * exp(-r / ell)      (paper's pick)
  * ``rbf``  — squared-exp  k(r) = sf^2 * exp(-r^2 / 2 ell^2)

The posterior mean/variance are the closed forms of Eqs. (7)-(8); hyper-
parameters (ell, sf, sn) are tuned by evidence maximization (a fixed
number of Adam steps on the log marginal likelihood — no cross
validation, per the paper's argument).  The dataset is windowed to the
latest N patterns to keep the O(N^3) solve tractable (paper end of
§3.1.2); N and h are static so everything jits and vmaps.

The Gram-matrix construction — the arithmetic hot spot when batching
over a fleet's worth of series — is delegated to ``repro.kernels.ops``
which dispatches to the Pallas TPU kernel (``kernels/gp_gram.py``) on
TPU and to the pure-jnp reference elsewhere.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.forecast.base import Forecast, batched, packed
from repro.kernels import ops as kops
from repro.kernels.fixed_order import tree_sum

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class GPConfig:
    history: int = 10          # h — pattern length (paper uses 10/20/40)
    max_patterns: int = 10     # N — latest patterns kept (paper: N = h)
    kernel: str = "exp"        # "exp" (paper's choice) or "rbf"
    opt_steps: int = 25        # evidence-maximization Adam steps
    opt_lr: float = 0.08
    jitter: float = 1e-5
    impl: str = "auto"         # gram impl: "auto" | "pallas" | "jnp"


def build_patterns(window: Array, h: int, n: int) -> tuple[Array, Array, Array]:
    """Build (X, y, row_valid) from the last ``n`` patterns of a window.

    X[i] = [t_i, y_{t_i-h}, ..., y_{t_i-1}],  y[i] = y_{t_i}   (Eq. 5)

    The time feature is normalized to [0, 1] over the window so that its
    scale is commensurate with standardized observations.
    """
    T = window.shape[0]
    n_avail = T - h
    assert n_avail >= 1, "window must be longer than history"
    n = min(n, n_avail)
    # pattern i predicts target index  T - n + i  (the n most recent)
    tgt = jnp.arange(T - n, T)
    t_feat = tgt.astype(jnp.float32) / jnp.float32(max(T - 1, 1))
    # history rows: indices tgt-h .. tgt-1
    offs = jnp.arange(-h, 0)
    hist = window[tgt[:, None] + offs[None, :]]          # (n, h)
    X = jnp.concatenate([t_feat[:, None], hist], axis=1)  # (n, h+1)
    y = window[tgt]
    valid = jnp.ones((n,), dtype=bool)
    return X, y, valid


def _standardize(y: Array, valid: Array) -> tuple[Array, Array, Array]:
    w = valid.astype(y.dtype)
    cnt = jnp.maximum(tree_sum(w), 1.0)
    mu = tree_sum(y * w) / cnt
    var = tree_sum((y - mu) ** 2 * w) / cnt
    sd = jnp.sqrt(jnp.maximum(var, 1e-10))
    return (y - mu) / sd, mu, sd


# The GP's linear algebra is N x N with N = max_patterns (10), and every
# sum in it is a fixed-order tree (``kernels.fixed_order``): a library
# Cholesky, triangular solve or matvec lets the compiler order its adds by
# layout, and a forecast row must read the same bits in a solo program as
# in a vmapped cohort or sharded fleet.

def _cholesky(a: Array) -> Array:
    """Lower Cholesky factor of the symmetric part of ``a``, one column
    at a time, each taken out of the trailing matrix by an outer
    product (so entry (i, j) is a_ij - l_i0 l_j0 - l_i1 l_j1 - ...)."""
    a = 0.5 * (a + a.T)
    n = a.shape[0]
    rows = jnp.arange(n)
    cols = []
    for j in range(n):
        d = jnp.sqrt(a[j, j])
        c = jnp.where(rows > j, a[:, j] / d, jnp.where(rows == j, d, 0.0))
        cols.append(c)
        a = a - c[:, None] * c[None, :]
    return jnp.stack(cols, axis=1)


def _solve_lower(L: Array, b: Array) -> Array:
    """Solve L z = b for z; ``b`` is (n,) or (n, m).  Each solved entry's
    column is subtracted from the rest (forward substitution)."""
    n = L.shape[0]
    z = []
    for k in range(n):
        z.append(b[k] / L[k, k])
        b = b - L[:, k].reshape((n,) + (1,) * (b.ndim - 1)) * z[k]
    return jnp.stack(z)


def _cho_solve(L: Array, b: Array) -> Array:
    """Solve (L L^T) x = b for x: forward, then back substitution."""
    n = L.shape[0]
    z = _solve_lower(L, b)
    x = [None] * n
    for k in reversed(range(n)):
        x[k] = z[k] / L[k, k]
        z = z - L[k, :].reshape((n,) + (1,) * (z.ndim - 1)) * x[k]
    return jnp.stack(x)


def _diag(a: Array) -> Array:
    """The diagonal as a gather (``jnp.diagonal`` is a masked reduce)."""
    i = jnp.arange(a.shape[0])
    return a[i, i]


@jax.custom_vjp
def _evidence(K: Array, s2: Array, y: Array, row_valid: Array) -> Array:
    """Negative log marginal likelihood of ``y`` under the Gram ``K``
    plus noise ``s2`` on the valid rows."""
    return _evidence_fwd(K, s2, y, row_valid)[0]


def _evidence_fwd(K, s2, y, row_valid):
    # invalid rows: decouple them with enormous noise so they carry no info
    noise = jnp.where(row_valid, s2, 1e6)
    L = _cholesky(K + jnp.diag(noise))
    alpha = _cho_solve(L, y)
    n_eff = row_valid.sum().astype(y.dtype)
    nll = (0.5 * tree_sum(y * alpha)
           + tree_sum(jnp.where(row_valid, jnp.log(_diag(L)), 0.0))
           + 0.5 * n_eff * jnp.log(2.0 * jnp.pi))
    return nll, (L, alpha, row_valid)


def _evidence_bwd(res, g):
    # d nll / dK = (K^-1 - alpha alpha^T) / 2 (Rasmussen & Williams
    # eq. 5.9), written out so the backward pass holds no reduce either
    L, alpha, row_valid = res
    kinv = _cho_solve(L, jnp.eye(L.shape[0], dtype=L.dtype))
    dK = 0.5 * g * (kinv - alpha[:, None] * alpha[None, :])
    ds2 = tree_sum(jnp.where(row_valid, _diag(dK), 0.0))
    return dK, ds2, g * alpha, None


_evidence.defvjp(_evidence_fwd, _evidence_bwd)


def _neg_log_marginal(log_params: Array, X: Array, y: Array,
                      row_valid: Array, kernel: str, jitter: float,
                      impl: str) -> Array:
    ell, sf, sn = jnp.exp(log_params)
    K = kops.gram(X, X, ell, sf, kind=kernel, impl=impl)
    return _evidence(K, sn ** 2 + jitter, y, row_valid)


def _optimize_evidence(X, y, row_valid, cfg: GPConfig) -> Array:
    """A fixed Adam loop on the log marginal likelihood (no line search —
    deterministic cost, which matters when vmapping over a fleet)."""
    loss = partial(_neg_log_marginal, X=X, y=y, row_valid=row_valid,
                   kernel=cfg.kernel, jitter=cfg.jitter, impl=cfg.impl)
    grad = jax.grad(loss)
    init = jnp.log(jnp.asarray([1.0, 1.0, 0.3], dtype=jnp.float32))
    b1, b2, eps = 0.9, 0.999, 1e-8

    def step(state, i):
        p, m, v = state
        g = grad(p)
        g = jnp.where(jnp.isfinite(g), g, 0.0)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mh = m / (1 - b1 ** (i + 1.0))
        vh = v / (1 - b2 ** (i + 1.0))
        p = p - cfg.opt_lr * mh / (jnp.sqrt(vh) + eps)
        p = jnp.clip(p, -6.0, 6.0)
        return (p, m, v), None

    (p, _, _), _ = jax.lax.scan(
        step, (init, jnp.zeros_like(init), jnp.zeros_like(init)),
        jnp.arange(cfg.opt_steps, dtype=jnp.float32))
    return p


@dataclasses.dataclass(frozen=True)
class GPForecaster:
    """History-kernel GP forecaster (paper's non-parametric model)."""

    cfg: GPConfig = GPConfig()

    def forecast(self, window: Array, horizon: int, *,
                 valid: Array | None = None) -> Forecast:
        cfg = self.cfg
        T = window.shape[0]
        h = cfg.history
        if valid is None:
            valid = jnp.ones((T,), dtype=bool)
        window = window.astype(jnp.float32)
        z, mu, sd = _standardize(window, valid)
        X, y, _ = build_patterns(z, h, cfg.max_patterns)
        n = X.shape[0]
        # a pattern row is valid iff its whole history + target are observed
        tgt = jnp.arange(T - n, T)
        offs = jnp.arange(-h, 1)  # history + target
        row_valid = jnp.all(valid[tgt[:, None] + offs[None, :]], axis=1)

        log_params = _optimize_evidence(X, y, row_valid, cfg)
        ell, sf, sn = jnp.exp(log_params)

        K = kops.gram(X, X, ell, sf, kind=cfg.kernel, impl=cfg.impl)
        noise = jnp.where(row_valid, sn ** 2 + cfg.jitter, 1e6)
        L = _cholesky(K + jnp.diag(noise))
        alpha = _cho_solve(L, y)

        # iterated k-step-ahead: feed the predictive mean back into the
        # history (standard for NARX-style GP forecasting); the predictive
        # variance at each step quantifies uncertainty (Eq. 8).
        hist = z[-h:]
        means, variances = [], []
        for k in range(horizon):
            t_next = (T + k) / max(T - 1, 1)
            xs = jnp.concatenate([jnp.asarray([t_next], jnp.float32), hist])[None, :]
            ks = kops.gram(xs, X, ell, sf, kind=cfg.kernel, impl=cfg.impl)[0]
            mean_k = tree_sum(ks * alpha)
            v = _solve_lower(L, ks)         # ks^T K^-1 ks = |L^-1 ks|^2
            var_k = sf ** 2 + sn ** 2 - tree_sum(v * v)
            var_k = jnp.maximum(var_k, 1e-9)
            means.append(mean_k)
            variances.append(var_k)
            hist = jnp.concatenate([hist[1:], mean_k[None]])

        mean = jnp.stack(means) * sd + mu
        var = jnp.stack(variances) * sd ** 2
        # degenerate window (fewer than h+1 valid points): fall back to
        # persistence with inflated variance rather than NaN.
        enough = valid.sum() >= (h + 1)
        last = window[-1]
        mean = jnp.where(enough, mean, last)
        var = jnp.where(enough, var, (0.5 * jnp.abs(last) + 1.0) ** 2)
        return Forecast(mean=mean, var=var)

    def forecast_batch(self, windows: Array, horizon: int, *,
                       valid: Array | None = None) -> Forecast:
        # shared vmap wrapper (repro.core.forecast.base.batched): per-row
        # independence is the contract the engines' bucketed/padded
        # batch paths rely on, so there is exactly one batching idiom
        return batched(self.forecast, windows, horizon, valid)

    def forecast_packed(self, windows: Array, horizon: int, *,
                        valid: Array | None = None, live: Array):
        # forecast_batch for the ``live`` rows alone, in the fewest tiles:
        # (Forecast, tile rows run) (repro.core.forecast.base.packed)
        return packed(self.forecast, windows, horizon, valid, live)
