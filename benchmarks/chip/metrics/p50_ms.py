"""p50_ms: median latency, from each request's due time to its reply, of
every request due in the window; a request that failed or never came
counts as the whole wait the run allowed."""

from chipbench.traffic import nearest_rank


def read(ctx):
    return nearest_rank(ctx.latencies_ms(), 0.50)
