"""tick_device_us (layer: fused tick): device busy time per cohort tick
(each chip steps its members' ticks together, so a cohort tick is one
tick of the chunk program), read from the device trace: one chunk's busy
time (``trace.chunk_device``: over whole traced chunks, or from the
ticks a slice holds) over the ticks of a chunk."""


def read(ctx):
    if ctx.chunk_busy_s is None:
        return None
    return 1e6 * ctx.chunk_busy_s / ctx.chunk
