"""idle_worker_free_pct: 100 x the device idle time (the gaps between
merged device ops) during which the farm worker runs no ``plane.job``
span / the traced window.  Device idle because nothing fed the worker:
the front end's share."""

from chipbench import spans


def read(ctx):
    if ctx.trace is None or ctx.trace_window_s <= 0:
        return None
    w = spans.worker(ctx.trace)
    if w is None:
        return None
    _, free = spans.idle_split(ctx.trace, w)
    return 100.0 * free / ctx.trace_window_s
