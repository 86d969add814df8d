"""keystream_kernel_roofline: share of the HBM roofline the fused
keystream kernel reaches: (blocks served x bytes per block the algorithm
needs) / the chip's published HBM bandwidth / the kernel's device time.

The bytes per block are chipbench.roofline.bytes_per_block: round
constants, noise and matrix words in, l words out.  No compute bound is
taken: no int32 VPU peak of the chip is published.  The kernel's events
are matched on the device's ``XLA Ops`` line by PATTERN, as
kernel_us_per_block matches them."""

from chipbench.roofline import bytes_per_block

PATTERN = r"keystream_kernel"


def read(ctx):
    if ctx.trace is None or "hbm_bytes_per_s" not in ctx.peaks:
        return None
    t = sum(ln.select(PATTERN).total_s() for ln in ctx.trace.device_ops())
    if t <= 0 or not ctx.blocks_served():
        return None
    need = ctx.blocks_served() * bytes_per_block(ctx.cipher)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / t
