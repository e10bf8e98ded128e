"""gram_busy_pct (layer: Pallas Gram kernel): the Gram kernel's device
time over the device's busy time in the traced stretch, per chip."""


# the kernel's operations are named after its jitted wrapper, gp_gram
# (the trace shows "%jvp_jit_gp_gram__.8 = ... custom-call(...)")
KERNEL = "gp_gram"


def read(ctx):
    if not ctx.devices or not ctx.busy_s:
        return None
    k = ctx.kernel_s(KERNEL)
    return 100.0 * k / ctx.busy_s if k else None
