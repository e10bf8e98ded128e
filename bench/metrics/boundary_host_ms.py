"""boundary_host_ms (layer: chunk driver): the mean host time from one
chunk's ready point to the next chunk's launch returning (bucket pick,
dispatch), on the harness's clock, over the chunks of the traced run
before the profiler started.  The window's clock stops while the check
copies a state to the host (``system.window``), so those copies are not
in it."""


def read(ctx):
    ch = ctx.window.chunks
    ch = ch[:ctx.traced[0]] if ctx.traced else ch
    gaps = [b["launched"] - a["ready"] for a, b in zip(ch, ch[1:])]
    if not gaps:
        return None
    return 1e3 * sum(gaps) / len(gaps)
