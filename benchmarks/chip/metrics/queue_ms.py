"""queue_ms: median of the replies' latency_ms, the scheduler's own span
from admission to the materialized window holding the request's last
lane (serve/hhe_loop.py)."""

import statistics


def read(ctx):
    spans = [s.server_ms for s in ctx.sent if s.ok]
    return statistics.median(spans) if spans else None
