"""gram_roofline_pct (layer: Pallas Gram kernel): the least time the
chip needs for the Gram calls of the traced stretch, over the kernel's
device time there.  Each call's work comes from its own shapes: the
output ``f32[B, Mp, Np]`` is a batch of B Gram matrices between M and N
pattern vectors of width D, padded to Mp x Np; M, N and D are the GP's
(N patterns of width h + 1, against themselves or against one query,
``harness.kernel_cost.gp_forecast_grams``), and the work counted is that
of the unpadded shapes.  The least time is the larger of operations over
peak FLOP/s and bytes over peak HBM bandwidth (``harness.peaks``): the
bytes bound it."""
import re

from harness import kernel_cost

KERNEL = "gp_gram"
OUT = re.compile(r"= f32\[(\d+),(\d+),(\d+)\]")


def read(ctx):
    if not ctx.devices:
        return None
    events = ctx.kernel_events(KERNEL)
    if not events:
        return None
    gp = ctx.spec["sim"]["gp"]
    n, d = int(gp["max_patterns"]), int(gp["history"]) + 1
    ops = nbytes = 0
    for name, _ in events:
        m = OUT.search(name)
        if not m:
            return None
        b, mp = int(m.group(1)), int(m.group(2))
        o, by = kernel_cost.gram(n if mp >= n else 1, n, d)
        ops += b * o
        nbytes += b * by
    k = sum(t for _, t in events)
    least, bound = kernel_cost.least_seconds(ops, nbytes, ctx.peak)
    ctx.note(f"gram roofline: {len(events)} calls, {ops:.6g} ops, {nbytes:.6g} bytes, "
             f"{bound} bound, least {least:.6g} s, kernel {k:.6g} s")
    return 100.0 * least / k
