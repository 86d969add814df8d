"""Host cost of the serving path's program steps, per window: the same
full windows served through one ``HHEServer`` with no profiler running
and under ``jax.profiler`` tracing (the chip benchmark's options).

    PYTHONPATH=src python scripts/span_cost.py --windows 1000

Prints one JSON line: the wall time per window of each pass, in the
order run (off, on, off, on, off, on), and the difference of the
medians.  The server is rubato-128l with the serving CLI's defaults
(window 64, engine auto); requests are ``keystream`` ops of ten full
windows each.  Run it on the machine the figure is for: a CPU run times
the CPU backend.
"""

from __future__ import annotations

import argparse
import json
import statistics
import tempfile
import time

import jax

from repro.core.cipher import CipherBatch
from repro.serve.hhe_loop import HHERequest, HHEServer

CIPHER = "rubato-128l"
WINDOW = 64
CHUNK = 10          # full windows per request
REPS = 3


def serve(srv: HHEServer, session: int, windows: int) -> float:
    """Seconds per window to serve ``windows`` full windows."""
    requests = max(1, windows // CHUNK)
    t0 = time.perf_counter()
    for _ in range(requests):
        srv.submit(HHERequest(session_id=session, blocks=CHUNK * WINDOW))
        srv.pop_completed()
    srv.flush()
    return (time.perf_counter() - t0) / (requests * CHUNK)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--windows", type=int, default=1000)
    args = ap.parse_args(argv)

    from repro.serve.server import enable_compile_cache

    enable_compile_cache()
    srv = HHEServer(CipherBatch(CIPHER, seed=1), window=WINDOW)
    session = srv.open_session().index
    srv.warmup()
    serve(srv, session, CHUNK)                  # every shape once
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    off, on = [], []
    for _ in range(REPS):
        off.append(serve(srv, session, args.windows))
        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d, profiler_options=opts)
            try:
                on.append(serve(srv, session, args.windows))
            finally:
                jax.profiler.stop_trace()
    print(json.dumps({
        "device": jax.devices()[0].device_kind,
        "engine": srv.farm.engine.name, "cipher": CIPHER, "window": WINDOW,
        "windows": args.windows, "off_s_per_window": off,
        "on_s_per_window": on,
        "on_cost_us_per_window": (statistics.median(on)
                                  - statistics.median(off)) * 1e6,
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
