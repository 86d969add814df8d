#!/usr/bin/env python3
"""Chip benchmark of the served keystream path: one run of one cell.

    python benchmarks/chip/run.py --workload rubato-128l.bulk-tensors \
        --seed 7 --seconds 20 --trace 0

Run from the root of a checkout on a machine with a TPU.  The run sets up
the serving plane of the cell's configuration, warms every shape the
cell's traffic uses, drives the traffic for ``--seconds``, checks the
served bytes against a NumPy reference, and prints one JSON object as the
last line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics read from a device trace of the window), ``device``
and, last, ``checks``: each number compared with its limit.  It exits
non-zero and prints no result where JAX's first device is not a TPU,
where the machine has fewer chips than the cell asks for, and where the
device kind is not in ``peaks.json``.
"""

from __future__ import annotations

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def pin_environment() -> None:
    """Fix what the run depends on before Python hashes a string or JAX
    starts: string hashing (the program seeds each tenant's nonces from
    ``hash(tenant_id)``), a tuner cache that never exists (so "auto"
    picks the engine from the device alone), the checkout's compile
    cache, and TPU logs off (they would go to a fixed path)."""
    os.environ.setdefault("CHIPBENCH_T0", repr(time.time()))
    if os.environ.get("PYTHONHASHSEED") != "0":
        os.environ["PYTHONHASHSEED"] = "0"
        os.execv(sys.executable, [sys.executable] + sys.argv)
    os.environ["REPRO_TUNER_CACHE"] = os.path.join(
        HERE, "no-tuner-cache", "never.json")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def configure_jax() -> None:
    """The compile cache in the checkout, every program written to it."""
    import jax

    from repro.serve.server import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def parse(argv):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw .xplane.pb of a --trace 1 run here")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0:
        print("run: --seed must be a whole number >= 0", file=sys.stderr)
        return 2
    t_start = float(os.environ["CHIPBENCH_T0"])
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import json
    import pathlib

    from chipbench.cells import load_cell, load_peaks

    cell = load_cell(args.workload, pathlib.Path(ROOT) / "BENCHMARK.json")
    if os.path.exists(os.environ["REPRO_TUNER_CACHE"]):
        print(f"run: {os.environ['REPRO_TUNER_CACHE']} must not exist",
              file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run: needs a TPU, but JAX's first device is on platform "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"run: the cell asks for {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 3
    load_peaks(devices[0].device_kind)           # unknown kind: KeyError
    configure_jax()

    from chipbench.harness import run_cell

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=t_start, keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    pin_environment()
    sys.exit(main())
