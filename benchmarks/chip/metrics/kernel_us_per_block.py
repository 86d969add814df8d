"""kernel_us_per_block: device time of the fused keystream kernel (the
Pallas kernel ``_keystream_kernel``, kernels/keystream), summed over the
traced window, per block served in it.

Matched on the device's ``XLA Ops`` line by PATTERN."""

PATTERN = r"keystream_kernel"


def kernel_s(trace):
    return sum(ln.select(PATTERN).total_s() for ln in trace.device_ops())


def read(ctx):
    if ctx.trace is None or not ctx.blocks_served():
        return None
    total = kernel_s(ctx.trace)
    if total <= 0:
        return None
    return total * 1e6 / ctx.blocks_served()
