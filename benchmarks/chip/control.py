#!/usr/bin/env python3
"""Readings that set a cell's limits: the program on many seeds, and the
control on a few, each at the cell's own size and load, in one process.

    python benchmarks/chip/control.py --workload hera-128a.small-zipf \
        --seeds 11,12,13 --seconds 5 --mode control

``--mode program`` runs the cell as the benchmark does.  ``--mode
control`` first switches each tenant onto the program's own threefry
producer, the "TPU-native fast" XOF that a later PR could be tempted to
serve in place of AES-CTR: the bytes change, the shapes and the work do
not.  The comparison has to fail it.  One JSON line per seed: the
numbers compared, ``correct`` and the wall time.  The benchmark's own
runs never run this.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys
import time

from run import HERE, ROOT, configure_jax, pin_environment


def threefry_everywhere(registry) -> None:
    """Serve every tenant's stream from the threefry producer."""
    from repro.core.producer import make_producer

    for tid in registry.tenant_ids():
        batch = registry.peek(tid).batch
        batch.producer = make_producer("threefry", batch.params)
        batch._mat_host = [batch.producer.session_material(s.nonce)
                           for s in batch.sessions]
        batch._tables = None


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--mode", choices=("program", "control"),
                    required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax

    from chipbench.cells import load_cell
    from chipbench.harness import run_cell

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    configure_jax()
    cell = load_cell(args.workload, pathlib.Path(ROOT) / "BENCHMARK.json")
    hook = threefry_everywhere if args.mode == "control" else None
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.time()
        try:
            r = run_cell(cell, seed, args.seconds, after_setup=hook)
            line = {"seed": seed, "mode": args.mode,
                    "correct": r["correct"], "attempted": r["attempted"],
                    "failed": r["failed"], "checks": r["checks"],
                    "metrics": r["metrics"]}
        except Exception as e:          # a control that crashes has failed
            line = {"seed": seed, "mode": args.mode, "correct": False,
                    "crashed": f"{type(e).__name__}: {e}"}
        line["wall_s"] = time.time() - t
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    pin_environment()
    sys.exit(main())
