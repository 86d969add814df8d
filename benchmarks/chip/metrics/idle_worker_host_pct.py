"""idle_worker_host_pct: 100 x the device idle time (the gaps between
merged device ops) during which the farm worker is inside a
``plane.job`` span and not in ``hhe.device_wait`` / the traced window.
Device idle that the scheduler's own host steps cause; with
idle_worker_free_pct it sums to at most device_idle_pct."""

from chipbench import spans


def read(ctx):
    if ctx.trace is None or ctx.trace_window_s <= 0:
        return None
    w = spans.worker(ctx.trace)
    if w is None:
        return None
    in_host, _ = spans.idle_split(ctx.trace, w)
    return 100.0 * in_host / ctx.trace_window_s
