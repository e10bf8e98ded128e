"""forecast_tile_fill_pct (layer: forecast): of the rows of the tiles the
forecast's tile loop ran on the chip over the window's ticks, the share
that was ready (past the grace period).  Per tick, the program counts
each member's ready rows (``TickMetrics.forecast_rows``) and the rows of
the tiles run (``forecast_tile_rows``); the tile loop packs the ready
rows of the whole cohort, so the member with the most passes holds the
cohort's count.  The share is the sum of ready rows over the sum of the
largest member's tile rows, over the window's ticks.  A program that
does not count tile rows reads nothing; like the forecast's other
counter, it is read in a run that traced the chip, and in no other."""
import numpy as np


def read(ctx):
    if not ctx.devices or "forecast_tile_rows" not in ctx.ticks:
        return None
    ready = np.asarray(ctx.ticks["forecast_rows"], np.int64)       # (members, ticks)
    tiles = int(np.asarray(ctx.ticks["forecast_tile_rows"], np.int64).max(axis=0).sum())
    if not tiles:
        return None
    ctx.note(f"forecast tiles: {int(ready.sum())} ready rows in {tiles} tile rows run")
    return 100.0 * int(ready.sum()) / tiles
