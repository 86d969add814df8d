"""worker_host_us_per_block: the farm worker's host time per block
served: (union of ``plane.job`` spans - the union of the
``hhe.device_wait`` spans inside them) / blocks served in the window.
The scheduler's self time off the wait on the device."""

from chipbench import spans


def read(ctx):
    if ctx.trace is None or not ctx.blocks_served():
        return None
    w = spans.worker(ctx.trace)
    if w is None:
        return None
    return spans.length_s(w.host) * 1e6 / ctx.blocks_served()
