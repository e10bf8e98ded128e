"""placement_busy_pct (layer: placement): the self time of the device
operations in the tick's placement phases, ``oom`` (``_resolve_oom``),
``admit`` (``_admit_queued``) and ``elastic``
(``_place_missing_elastic``), over the device's busy time in the traced
stretch, per chip (``harness.program_trace``)."""
from harness import program_trace


def read(ctx):
    shares = program_trace.phase_shares(ctx)
    if shares is None:
        return None
    return sum(shares.get(ph, 0.0) for ph in program_trace.PLACEMENT)
