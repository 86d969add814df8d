"""device_idle_pct: 100 x (1 - the union of the device's op intervals /
the traced window), averaged over the chips used."""

from chipbench.trace import busy_s


def read(ctx):
    if ctx.trace is None or ctx.trace_window_s <= 0:
        return None
    ops = ctx.trace.device_ops()
    if not ops:
        return None
    busy = sum(busy_s(ln) for ln in ops) / len(ops)
    return 100.0 * (1.0 - busy / ctx.trace_window_s)
