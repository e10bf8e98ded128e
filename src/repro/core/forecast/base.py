"""Forecaster protocol — the paper's §3.1 utilization-forecasting module.

Every forecaster consumes a fixed-length window of past observations of a
single resource time series (CPU or memory of one application component,
sampled once per monitoring tick) and produces a ``Forecast``: the k-step
ahead predictive mean together with a *variance* that quantifies the
uncertainty of the prediction.  The variance is what the resource shaper's
safe-guard buffer (Eq. 9) consumes — it is a first-class output, not a
diagnostic.

All forecasters are pure-JAX and batchable with ``vmap`` over thousands of
component series, which is how the fleet-scale deployment runs them.
"""
from __future__ import annotations

import dataclasses
from typing import Protocol

import jax
import jax.numpy as jnp

from repro.core.uncertainty.scoring import (gaussian_quantile_scale,
                                            sigma_from_var)

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Forecast:
    """k-step-ahead predictive distribution for one series.

    mean, var have shape ``(horizon,)``; ``var`` is the *predictive*
    variance (not a parameter confidence interval — see paper §3.1.1 for
    why the distinction matters).
    """

    mean: Array
    var: Array

    @property
    def sigma(self) -> Array:
        """Predictive standard deviation (shared clamp, see Eq. 9)."""
        return sigma_from_var(self.var)

    def quantile(self, q, *, scale: Array | None = None) -> Array:
        """Upper q-quantile of the predictive distribution.

        Default is the Gaussian form ``mean + z(q) * sigma`` (the
        paper's §3.1 distributional assumption, which Eq. 9's K2 bands
        instantiate).  Pass ``scale`` — e.g. a calibrated score
        quantile from :mod:`repro.core.uncertainty.conformal` — to get
        a *distribution-free* quantile ``mean + scale * sigma`` instead;
        ``q`` is then only the nominal level the scale was built for.
        """
        z = gaussian_quantile_scale(q) if scale is None else scale
        return self.mean + z * self.sigma

    def interval(self, q_lo, q_hi, *,
                 scale_lo: Array | None = None,
                 scale_hi: Array | None = None) -> tuple[Array, Array]:
        """(lower, upper) predictive interval at the given levels."""
        return (self.quantile(q_lo, scale=scale_lo),
                self.quantile(q_hi, scale=scale_hi))


class Forecaster(Protocol):
    """A forecaster maps an observation window to a Forecast.

    ``window`` is shape ``(T,)`` float32 — the most recent T observations,
    oldest first.  ``valid`` is an optional same-shape boolean mask for
    series younger than the window (the grace period of §5 means shapers
    only act once enough points exist, but forecasters must not NaN on
    short histories).
    """

    def forecast(self, window: Array, horizon: int, *,
                 valid: Array | None = None) -> Forecast:
        ...


# Rows per fixed-shape tile of a batched forecast.  A TPU compiler picks
# fusions, and with them rounding, by batch size: a row's forecast is
# bit-identical across batch sizes only when every row is computed in a
# program of one shape.  The engines' bucketed, cohort and sharded paths
# rely on that.  16 is the smallest bucketed pass (2 x 8 rows).
# ``batched`` runs every tile of its batch; ``packed`` runs only the
# ceil(live rows / TILE_ROWS) tiles that hold the rows it is told are
# live, each of them this same program.
TILE_ROWS = 16


def batched(forecast_fn, windows: Array, horizon: int,
            valid: Array | None = None) -> Forecast:
    """vmap a single-series forecast fn over (B, T) windows, as a loop
    over ``TILE_ROWS``-row tiles; an outer ``vmap`` (a seed cohort, a
    fleet's rows) folds its axis into the rows, so every row runs in
    the same tile program whatever the batch."""
    if valid is None:
        valid = jnp.ones(windows.shape, dtype=bool)

    def fn(w, v):
        return forecast_fn(w, horizon, valid=v)
    return _tiled(fn)(windows, valid)


def packed(forecast_fn, windows: Array, horizon: int, valid: Array | None,
           live: Array) -> tuple[Forecast, Array]:
    """``batched`` for a batch whose ``live`` ((B,) bool) rows alone are
    read: the live rows of the whole fold are packed into the fewest
    tiles and the other rows come back 0.  Returns ``(Forecast,
    tile_rows)``, ``tile_rows`` the rows of the tiles that ran (i32, the
    same for every member of the fold)."""
    valid = jnp.ones(windows.shape, dtype=bool) if valid is None else valid
    return _tiled(lambda w, v: forecast_fn(w, horizon, valid=v),
                  pack=True)(live, windows, valid)


def _tiled(row_fn, pack: bool = False):
    """``row_fn`` over the rows of its arguments, ``TILE_ROWS`` rows at a
    time.  With ``pack``, the first argument is the rows' ``live``
    mask: the live rows move to the front (a stable argsort), one
    ``while_loop`` runs the tiles that hold them, and each comes back to
    its own row by an integer gather; the tile program is the same."""
    def all_tiles(*xs):
        n = xs[0].shape[0]
        pad = -n % TILE_ROWS
        tiles = tuple(
            jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)).reshape(
                (-1, TILE_ROWS) + x.shape[1:]) for x in xs)
        out = jax.lax.map(lambda t: jax.vmap(row_fn)(*t), tiles)
        return jax.tree.map(lambda y: y.reshape((-1,) + y.shape[2:])[:n],
                            out)

    def live_tiles(live, *xs):
        n = live.shape[0]
        order = jnp.argsort(~live, stable=True)
        xs = [jnp.pad(x[order], [(0, -n % TILE_ROWS)] + [(0, 0)] * (x.ndim - 1))
              for x in xs]
        n_tiles = (live.sum(dtype=jnp.int32) + TILE_ROWS - 1) // TILE_ROWS
        shapes = jax.eval_shape(jax.vmap(row_fn),
                                *[x[:TILE_ROWS] for x in xs])
        buf = jax.tree.map(
            lambda s: jnp.zeros(xs[0].shape[:1] + s.shape[1:], s.dtype),
            shapes)

        def body(carry):
            i, buf = carry
            start = i * TILE_ROWS
            y = jax.vmap(row_fn)(*[jax.lax.dynamic_slice_in_dim(
                x, start, TILE_ROWS) for x in xs])
            return i + 1, jax.tree.map(
                lambda b, y: jax.lax.dynamic_update_slice_in_dim(
                    b, y, start, 0), buf, y)

        _, buf = jax.lax.while_loop(lambda c: c[0] < n_tiles, body,
                                    (jnp.int32(0), buf))
        # live row r sits at packed position (live rows before r)
        pos = jnp.cumsum(live, dtype=jnp.int32) - 1

        def back(y):
            keep = live.reshape((n,) + (1,) * (y.ndim - 1))
            return jnp.where(keep, y[jnp.maximum(pos, 0)], 0)
        return jax.tree.map(back, buf), n_tiles * TILE_ROWS

    run = jax.custom_batching.custom_vmap(live_tiles if pack else all_tiles)

    @run.def_vmap
    def _fold(axis_size, in_batched, *xs):
        xs = [x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
              for x, b in zip(xs, in_batched)]
        n = xs[0].shape[1]

        def unfold(out):
            out = jax.tree.map(
                lambda y: y.reshape((axis_size, n) + y.shape[1:]), out)
            return out, jax.tree.map(lambda _: True, out)
        out = run(*[x.reshape((axis_size * n,) + x.shape[2:]) for x in xs])
        if not pack:
            return unfold(out)
        fc, rows = out
        fc, fc_batched = unfold(fc)
        # the fold's tile rows: one count for every member
        return (fc, rows), (fc_batched, False)

    return run


def peak_over_horizon(fc: Forecast) -> tuple[Array, Array]:
    """(peak mean, its variance) from a batched ``(B, horizon)`` Forecast.

    The paper's predictor outputs a *future peak* utilization (§4.2): we
    take the max of the predictive path and carry that step's variance.
    Shared by the host engines' jitted peak path and the fused scan
    engine, so the two can never drift on this reduction.
    """
    k = jnp.argmax(fc.mean, axis=1)
    peak = jnp.take_along_axis(fc.mean, k[:, None], 1)[:, 0]
    pvar = jnp.take_along_axis(fc.var, k[:, None], 1)[:, 0]
    return peak, pvar


def persistence_peak(windows: Array, valid: Array) -> tuple[Array, Array]:
    """The ``persist`` forecaster's (mean, var) over ``(B, W)`` windows.

    Mean = last observation, var = masked window variance + 1e-6 —
    jnp mirror of the host engines' NumPy path (same masked-moment
    formula, so solo/batched/scan paths agree)."""
    w = valid.astype(windows.dtype)
    cnt = jnp.maximum(w.sum(axis=1), 1.0)
    mu = (windows * w).sum(axis=1) / cnt
    var = (((windows - mu[:, None]) ** 2) * w).sum(axis=1) / cnt
    return windows[:, -1], var + 1e-6
