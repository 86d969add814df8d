"""frontend_ms: median over answered requests of the client's latency
from sending to the reply, less the reply's latency_ms: the frame codec,
the wait for the plane's one worker thread, _respond and the reply."""

import statistics


def read(ctx):
    rest = [(s.done - s.sent) * 1e3 - s.server_ms for s in ctx.sent
            if s.ok]
    return statistics.median(rest) if rest else None
