"""forecast_rows_useful_pct (layer: forecast): of the monitor rows the
forecast computed on the chip over the window's ticks, the share that
was ready (past the grace period).  Per tick, the program counts each
member's ready rows (``TickMetrics.forecast_rows``) and the rows its own
passes computed (``forecast_rows_done``); under the cohort's ``vmap``
the pass loop runs the largest member's passes for every member, so the
chip computed members x the largest ``forecast_rows_done``.  The share
is the sum of ready rows over the sum of that, over the window's ticks.
It is read beside the forecast's device metrics, in a run that traced
the chip, and in no other."""
import numpy as np


def read(ctx):
    if not ctx.devices:
        return None
    ready = np.asarray(ctx.ticks["forecast_rows"], np.int64)      # (members, ticks)
    done = np.asarray(ctx.ticks["forecast_rows_done"], np.int64)
    computed = int((done.shape[0] * done.max(axis=0)).sum())
    if not computed:
        return None
    ctx.note(f"forecast rows: {int(ready.sum())} ready of {computed} computed "
             f"({int(done.sum())} by the members' own passes)")
    return 100.0 * int(ready.sum()) / computed
