"""p95_ms: 95th percentile (nearest rank) of the latencies p50_ms reads."""

from chipbench.traffic import nearest_rank


def read(ctx):
    return nearest_rank(ctx.latencies_ms(), 0.95)
