"""worker_busy_pct: 100 x the union of the serving plane's ``plane.job``
spans (every piece of work its one farm worker thread runs) / the traced
window.  How busy the worker is; chipbench.spans finds its thread."""

from chipbench import spans


def read(ctx):
    if ctx.trace is None or ctx.trace_window_s <= 0:
        return None
    w = spans.worker(ctx.trace)
    if w is None:
        return None
    return 100.0 * spans.length_s(w.jobs) / ctx.trace_window_s
