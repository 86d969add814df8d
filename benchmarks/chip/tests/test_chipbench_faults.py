"""The comparison that decides `correct`, driven through a whole run on
the CPU (the harness's look for a chip skipped, the program's "auto"
engine): sound runs pass, and each fault the cells can have, planted in
the program underneath, fails them, as does the control, the program's
own threefry producer served in place of AES-CTR."""

import pathlib
import sys

import jax.numpy as jnp
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
EXTRA = BENCH / "tests" / "fixtures" / "extra"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from chipbench.cells import load_cell  # noqa: E402
from chipbench.harness import run_cell  # noqa: E402
from repro.core.farm import KeystreamFarm  # noqa: E402

SECONDS = 1.0


def cell(name):
    return load_cell(name, EXTRA / "BENCHMARK.json", dirs=(EXTRA, BENCH))


def stale(consume):
    """A step that returns its state unchanged: every window after the
    first serves the first window's keystream again."""
    def patched(self, constants):
        if not hasattr(self, "_first"):
            self._first = consume(self, constants)
        return self._first
    return patched


def half(consume):
    """Half of the batch left out: every other lane comes back 0 (a
    part-full window's padding is its second half, so the odd lanes)."""
    def patched(self, constants):
        z = consume(self, constants)
        return z.at[1::2].set(0)
    return patched


def altered(consume):
    """One answer altered where it is produced: one word of each window
    moves by one."""
    def patched(self, constants):
        z = consume(self, constants)
        q = jnp.uint32(self.batch.params.mod.q)
        return z.at[0, 0].set((z[0, 0] + 1) % q)
    return patched


@pytest.mark.parametrize("name", ["hera-tiny.tiny-open",
                                  "hera-tiny.tiny-bulk"])
def test_sound_run_is_correct(name):
    r = run_cell(cell(name), 2**31 + 11, SECONDS)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["checks"]["checked_words"]["value"] > 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {m["name"] for m in
                                 cell(name).end_to_end}


@pytest.mark.parametrize("fault", [stale, half, altered])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(KeystreamFarm, "consume",
                        fault(KeystreamFarm.consume))
    r = run_cell(cell("hera-tiny.tiny-open"), 23, SECONDS)
    assert not r["correct"]
    assert r["checks"]["mismatched_words"]["value"] > 0


def test_control_is_not_correct():
    from control import threefry_everywhere

    r = run_cell(cell("hera-tiny.tiny-bulk"), 5, SECONDS,
                 after_setup=threefry_everywhere)
    assert not r["correct"]
    checks = r["checks"]
    # every keystream word differs, bar one in q by chance
    assert checks["mismatched_words"]["value"] >= \
        0.99 * checks["checked_words"]["value"]


def test_a_per_layer_metric_that_reads_nothing_fails_a_traced_run(
        monkeypatch):
    import chipbench.harness as harness

    real = harness.load_reader

    def reader(name, dirs):
        if name == "blocks_per_request":
            return lambda ctx: None
        return real(name, dirs)

    monkeypatch.setattr(harness, "load_reader", reader)
    r = run_cell(cell("hera-tiny.tiny-open"), 2**31 + 29, SECONDS,
                 trace=True)
    assert r["checks"]["mismatched_words"]["value"] == 0
    assert r["checks"]["per_layer_missing"]["value"] == 1
    assert not r["correct"]
    assert r["metrics"] == {}
