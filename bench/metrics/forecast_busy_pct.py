"""forecast_busy_pct (layer: forecast): the self time of the device
operations in the tick's ``forecast`` phase (the model call:
``_bucketed_forecast``'s pass loop, the un-bucketed model branch, or
``persistence_peak``) over the device's busy time in the traced
stretch, per chip.  Each operation's phase is the ``tick/<phase>`` named
scope in its op_name (``harness.program_trace``); every phase's share
and the share with no phase are noted."""
from harness import program_trace


def read(ctx):
    shares = program_trace.phase_shares(ctx)
    if shares is None:
        return None
    return shares.get("forecast", 0.0)
