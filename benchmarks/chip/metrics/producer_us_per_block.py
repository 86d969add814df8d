"""producer_us_per_block: device time of the farm producer's program (the
jit of AesProducer.producer_fn's inner function ``producer``, core/
producer.py), summed over the traced window, per block served in it.

Matched on the device's ``XLA Modules`` line by PATTERN."""

PATTERN = r"^jit_producer\b"


def read(ctx):
    if ctx.trace is None or not ctx.blocks_served():
        return None
    total = sum(ln.select(PATTERN).total_s()
                for ln in ctx.trace.device_modules())
    if total <= 0:
        return None
    return total * 1e6 / ctx.blocks_served()
