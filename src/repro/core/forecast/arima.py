"""ARIMA(p,d,q) forecasting (paper §3.1.1) — pure JAX, batchable.

The paper uses auto-ARIMA (AIC order selection, |p| <= 3 in practice,
d = 1 "enough in most cases").  We reproduce that pipeline with fixed
shapes so it jits and vmaps over a fleet of series:

  1. difference the window d times (d in {0, 1});
  2. fit ARMA(p, q) by the Hannan-Rissanen two-stage method — a long
     AR(m) OLS fit supplies innovation estimates, then a second OLS
     regresses on p lags of the series and q lags of the innovations.
     Both stages are closed-form masked least squares (no iterative
     MLE), which is what makes a 24-candidate grid x fleet-size batch
     feasible every monitoring tick;
  3. AIC = n log(sigma^2) + 2 (p + q + 2) selects the order (the +2
     counts the intercept and the variance);
  4. k-step forecasts via the ARMA recursion with future innovations
     zeroed; the forecast VARIANCE comes from the psi-weight recursion
       psi_0 = 1,  psi_j = theta_j + sum_i phi_i psi_{j-i}
     integrated d times, Var[e(k)] = sigma^2 * sum_{j<k} psi_j^2
     (the paper's MSE identity for the unbiased forecast).

Note the paper's §3.1.1 caveat: these are *in-sample* innovation
variances — they ignore parameter uncertainty, and the resulting bands
are systematically narrow ("over-confidence").  This is the property
that makes ARIMA's K2 term ineffective in Fig. 4a, and we deliberately
do not correct it: it is the phenomenon under study.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.forecast.base import Forecast, batched, packed

Array = jax.Array

MAX_P = 3
MAX_Q = 2
LONG_AR = 6          # stage-1 long-AR order m
RIDGE = 1e-4
# a TPU's default f32 matmul is one bf16 pass: on the chip it moved the
# forecasts' median by 1.8e-4 relative and the worst row by 1.4e2 from
# the CPU's, against 6e-8 and 5e-3 at full precision
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class ARIMAConfig:
    max_p: int = MAX_P
    max_q: int = MAX_Q
    max_d: int = 1
    long_ar: int = LONG_AR


def _masked_lstsq(A: Array, z: Array, row_mask: Array, col_mask: Array) -> Array:
    """Ridge-regularized masked OLS.  Excluded columns get beta = 0."""
    W = row_mask.astype(jnp.float32)
    Aw = A * W[:, None] * col_mask[None, :]
    G = jnp.dot(Aw.T, Aw, precision=HIGHEST) + RIDGE * jnp.eye(A.shape[1], dtype=A.dtype)
    # pin excluded columns: identity row forces beta_j = 0
    G = jnp.where(col_mask[:, None] * col_mask[None, :] > 0, G,
                  jnp.eye(A.shape[1], dtype=A.dtype))
    b = jnp.dot(Aw.T, z * W, precision=HIGHEST)
    beta = jnp.linalg.solve(G, b)
    return beta * col_mask


def _lags(z: Array, k: int) -> Array:
    """(T, k) matrix whose column j is z lagged by j+1 (zeros pre-sample)."""
    T = z.shape[0]
    idx = jnp.arange(T)[:, None] - (jnp.arange(k)[None, :] + 1)
    ok = idx >= 0
    return jnp.where(ok, z[jnp.clip(idx, 0)], 0.0), ok


def _fit_arma(z: Array, zmask: Array, p_mask: Array, q_mask: Array,
              cfg: ARIMAConfig):
    """Hannan-Rissanen ARMA fit with static MAX_P/MAX_Q shapes.

    p_mask: (MAX_P,) 1/0 — which AR coefficients are active.
    q_mask: (MAX_Q,) — which MA coefficients are active.
    Returns (delta, phi, theta, sigma2, resid, n_eff)."""
    T = z.shape[0]
    m = cfg.long_ar
    # stage 1: long AR(m) for innovation estimates
    L1, ok1 = _lags(z, m)
    rows1 = zmask & jnp.all(ok1, axis=1)
    A1 = jnp.concatenate([jnp.ones((T, 1), z.dtype), L1], axis=1)
    beta1 = _masked_lstsq(A1, z, rows1, jnp.ones((m + 1,), z.dtype))
    e = jnp.where(rows1, z - jnp.dot(A1, beta1, precision=HIGHEST), 0.0)

    # stage 2: regress z_t on [1, z lags (P), e lags (Q)]
    Lz, okz = _lags(z, cfg.max_p)
    Le, oke = _lags(e, cfg.max_q)
    need = jnp.concatenate([
        jnp.ones((T, 1), bool),
        okz & (p_mask[None, :] > 0),
        oke & (q_mask[None, :] > 0)], axis=1)
    # rows valid where every *active* regressor is in-sample; also require
    # stage-1 residuals valid over the MA lags actually used
    e_rows = jnp.roll(rows1, 1)  # e_{t-1} needs row t-1 valid; approx for q>=1
    rows2 = zmask & jnp.all(need, axis=1) & jnp.where(q_mask.sum() > 0,
                                                      e_rows, True)
    A2 = jnp.concatenate([jnp.ones((T, 1), z.dtype), Lz, Le], axis=1)
    cmask = jnp.concatenate([jnp.ones((1,), z.dtype), p_mask, q_mask])
    beta2 = _masked_lstsq(A2, z, rows2, cmask)
    resid = jnp.where(rows2, z - jnp.dot(A2, beta2, precision=HIGHEST), 0.0)
    n_eff = jnp.maximum(rows2.sum(), 1).astype(z.dtype)
    sigma2 = jnp.maximum((resid ** 2).sum() / n_eff, 1e-10)
    delta = beta2[0]
    phi = beta2[1:1 + cfg.max_p]
    theta = beta2[1 + cfg.max_p:]
    return delta, phi, theta, sigma2, resid, n_eff


def _psi_weights(phi: Array, theta: Array, horizon: int, d: Array) -> Array:
    """psi_j for j in [0, horizon), integrated d times (d traced 0/1)."""
    P, Q = phi.shape[0], theta.shape[0]
    psi = jnp.zeros((horizon,), phi.dtype).at[0].set(1.0)

    def body(j, psi):
        th = jnp.where(j <= Q, theta[jnp.clip(j - 1, 0, Q - 1)], 0.0)
        idx = j - 1 - jnp.arange(P)
        prev = jnp.where(idx >= 0, psi[jnp.clip(idx, 0)], 0.0)
        val = th + jnp.sum(phi * prev)
        return psi.at[j].set(val)

    psi = jax.lax.fori_loop(1, horizon, body, psi)
    # d=1 integration: psi~_j = cumsum(psi)_j
    psi_int = jnp.cumsum(psi)
    return jnp.where(d > 0, psi_int, psi)


@dataclasses.dataclass(frozen=True)
class ARIMAForecaster:
    """Auto-ARIMA forecaster (paper's parametric model)."""

    cfg: ARIMAConfig = ARIMAConfig()

    def forecast(self, window: Array, horizon: int, *,
                 valid: Array | None = None) -> Forecast:
        cfg = self.cfg
        window = window.astype(jnp.float32)
        T = window.shape[0]
        if valid is None:
            valid = jnp.ones((T,), dtype=bool)
        # scale-normalize for conditioning
        w = valid.astype(jnp.float32)
        mu = (window * w).sum() / jnp.maximum(w.sum(), 1.0)
        sd = jnp.sqrt(jnp.maximum(
            ((window - mu) ** 2 * w).sum() / jnp.maximum(w.sum(), 1.0), 1e-8))
        y = (window - mu) / sd

        # candidate grid (static): (p, d, q)
        cands = [(p, d, q)
                 for d in range(cfg.max_d + 1)
                 for p in range(cfg.max_p + 1)
                 for q in range(cfg.max_q + 1)
                 if p + q > 0]

        def eval_cand(p, d, q):
            if d == 0:
                z, zm = y, valid
            else:
                z = jnp.diff(y, prepend=y[:1])
                zm = valid & jnp.roll(valid, 1)
                zm = zm.at[0].set(False)
            p_mask = (jnp.arange(cfg.max_p) < p).astype(jnp.float32)
            q_mask = (jnp.arange(cfg.max_q) < q).astype(jnp.float32)
            delta, phi, theta, sig2, resid, n = _fit_arma(
                z, zm, p_mask, q_mask, cfg)
            aic = n * jnp.log(sig2) + 2.0 * (p + q + 2)
            # k-step recursion on z, future innovations = 0
            zbuf = jnp.concatenate([z, jnp.zeros((horizon,), z.dtype)])
            ebuf = jnp.concatenate([resid, jnp.zeros((horizon,), z.dtype)])

            def step(carry, j):
                zb, eb = carry
                t = T + j
                zl = jax.lax.dynamic_slice(zb, (t - cfg.max_p,), (cfg.max_p,))[::-1]
                el = jax.lax.dynamic_slice(eb, (t - cfg.max_q,), (cfg.max_q,))[::-1]
                zt = delta + jnp.sum(phi * p_mask * zl) + jnp.sum(theta * q_mask * el)
                zb = jax.lax.dynamic_update_index_in_dim(zb, zt, t, 0)
                return (zb, eb), zt

            (_, _), zf = jax.lax.scan(step, (zbuf, ebuf), jnp.arange(horizon))
            if d == 0:
                mean = zf
            else:
                mean = y[-1] + jnp.cumsum(zf)
            psi = _psi_weights(phi * p_mask, theta * q_mask, horizon,
                               jnp.asarray(d))
            var = sig2 * jnp.cumsum(psi ** 2)
            return aic, mean, var

        aics, means, vars_ = [], [], []
        for (p, d, q) in cands:
            a, mn, vr = eval_cand(p, d, q)
            aics.append(a)
            means.append(mn)
            vars_.append(vr)
        aics = jnp.stack(aics)
        means = jnp.stack(means)
        vars_ = jnp.stack(vars_)
        aics = jnp.where(jnp.isfinite(aics), aics, jnp.inf)
        best = jnp.argmin(aics)
        mean = means[best] * sd + mu
        var = vars_[best] * sd ** 2

        enough = valid.sum() >= (cfg.long_ar + cfg.max_p + 2)
        last = window[-1]
        mean = jnp.where(enough, mean, last)
        var = jnp.where(enough, var, (0.5 * jnp.abs(last) + 1.0) ** 2)
        return Forecast(mean=mean, var=jnp.maximum(var, 1e-9))

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
