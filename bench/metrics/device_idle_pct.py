"""device_idle_pct (layer: device): the share of a chunk's period on the
device's clock in which no operation runs on a chip, read from the device
trace (``trace.chunk_device``): 1 - busy / window over whole traced
chunks; where a chunk is too long to trace whole, from the ticks and the
chunk boundary that a slice around the boundary holds."""


def read(ctx):
    if ctx.chunk_busy_s is None or not ctx.chunk_period_s:
        return None
    return 100.0 * (1.0 - ctx.chunk_busy_s / ctx.chunk_period_s)
