#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: the cell at several offered rates, in
one process, to find the highest rate the plane sustains.

    python benchmarks/chip/sweep.py --workload hera-128a.small-zipf \
        --rates 100,200,400 --seconds 10 --seed 5

A rate is sustained (``sustained``) where completions keep pace with
arrivals (at least 98% of the requests due in the window are answered
in it, and the last is answered within 250 ms of its close), the backlog
does not grow (the median latency of the window's last quarter is at
most 1.5 times its first quarter's, plus 5 ms), and no request fails or
is refused as saturated.  One JSON line per rate, in the order given;
the sweep stops after two rates in a row that are not sustained.  The
cell's rate is set once, by hand, to 4/5 of the highest sustained rate;
the benchmark's own runs never search for one.
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import statistics
import sys

from run import HERE, ROOT, configure_jax, pin_environment


def summarize(result: dict, ctx) -> dict:
    sent = ctx.sent
    n = len(sent)
    lat = ctx.latencies_ms()
    quarter = max(1, n // 4)
    answered_in = sum(1 for s in sent if s.ok and s.done <= ctx.t_end)
    last = max((s.done for s in sent if s.ok), default=ctx.t_end)
    return {
        "sent": n,
        "answered_in_window_share": answered_in / n if n else 0.0,
        "saturated": sum(1 for s in sent if s.reply is not None
                         and s.reply.get("error") == "saturated"),
        "failed": result["failed"],
        "p50_ms": statistics.median(lat),
        "p95_ms": sorted(lat)[max(0, -(-95 * n // 100) - 1)],
        "p50_first_quarter_ms": statistics.median(lat[:quarter]),
        "p50_last_quarter_ms": statistics.median(lat[-quarter:]),
        "drain_ms": max(0.0, (last - ctx.t_end) * 1e3),
        "correct": result["correct"],
    }


def sustained(line: dict) -> bool:
    return (line["answered_in_window_share"] >= 0.98
            and line["drain_ms"] <= 250.0
            and line["p50_last_quarter_ms"]
            <= 1.5 * line["p50_first_quarter_ms"] + 5.0
            and line["saturated"] == 0 and line["failed"] == 0)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax

    from chipbench import harness
    from chipbench.cells import load_cell

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    configure_jax()
    base = load_cell(args.workload, pathlib.Path(ROOT) / "BENCHMARK.json")
    misses = 0
    for rate in (float(r) for r in args.rates.split(",")):
        cell = dataclasses.replace(base, params=dict(base.params,
                                                     rate_per_s=rate))
        result, ctx = harness.run_cell(cell, args.seed, args.seconds,
                                       with_context=True)
        line = {"rate_per_s": rate, **summarize(result, ctx)}
        line["sustained"] = sustained(line)
        print(json.dumps(line), flush=True)
        misses = 0 if line["sustained"] else misses + 1
        if misses == 2:
            break
    return 0


if __name__ == "__main__":
    pin_environment()
    sys.exit(main())
