"""The command refuses a host without a TPU: it exits non-zero and
prints no result."""

import json
import os
import pathlib
import subprocess
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]


def run(*args, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONHASHSEED", None)
    return subprocess.run(
        [sys.executable, str(BENCH / "run.py"), *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=timeout)


def no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            pass
    return True


def test_exits_non_zero_without_a_tpu():
    p = run("--workload", "rubato-128l.bulk-tensors", "--seed",
            str(2**31 + 3), "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr and "'cpu'" in p.stderr
    assert no_result(p.stdout)


def test_unknown_cell_and_negative_seed_exit_non_zero():
    p = run("--workload", "nope.none", "--seed", "1", "--seconds", "1")
    assert p.returncode != 0 and no_result(p.stdout)
    p = run("--workload", "rubato-128l.bulk-tensors", "--seed", "-4",
            "--seconds", "1")
    assert p.returncode != 0 and no_result(p.stdout)
