"""The program-span readers on a hand-built trace: a device with idle
gaps, the farm worker's line with ``plane.job`` steps and the
``hhe.device_wait`` steps nested in them, each marked by its two ends,
and the event loop's line.
Each metric's exact value, None without ``plane.job``, 0.0 with no
idle, and the two idle shares within ``device_idle_pct``; on a traced
CPU run of the program itself, that the readers find its steps; and on
a trace recorded on the chip (``fixtures/<cell>.xplane.pb.gz``, with the
run's result line beside it), that every reader of the cell, old and
new, gives again the number that run printed."""

import json
import math
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
EXTRA = BENCH / "tests" / "fixtures" / "extra"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import spans, trace, traffic  # noqa: E402
from chipbench.cells import load_cell, load_peaks, load_reader  # noqa: E402
from chipbench.harness import Context, Sent, run_cell  # noqa: E402
from chipbench.reference import Cipher  # noqa: E402
from test_chipbench_trace import DEV, MS, ctx_for, line  # noqa: E402

SPAN_METRICS = ("worker_busy_pct", "worker_host_us_per_block",
                "idle_worker_host_pct", "idle_worker_free_pct")
US = MS // 1000


def ops(*intervals):
    return line(DEV, trace.OPS_LINE,
                [(f"fusion.{i}", a, b - a)
                 for i, (a, b) in enumerate(intervals)])


def step(name, a, b):
    """A step from a to b as the program marks it: a 1 us mark where it
    starts and another where it ends."""
    return [(name, a, US), (name + ".end", b - US, US)]


def recorded(device=None):
    """10 ms: device ops [0,2) [3,4) [6,9) ms, so its gaps are [2,3) and
    [4,6); two jobs on the worker, [1.5,5) and [5.5,9.5), waiting on the
    device in [1.5,2.5) and [5.5,8); the event loop decodes in a gap."""
    worker = line("/host:CPU", "python", sorted(
        step("plane.job", 1500 * US, 5000 * US)
        + step("hhe.submit", 1500 * US, 5000 * US)
        + step("hhe.device_wait", 1500 * US, 2500 * US)
        + [("hhe.fetch", 2500 * US, 200 * US)]
        + step("plane.job", 5500 * US, 9500 * US)
        + step("hhe.device_wait", 5500 * US, 8000 * US),
        key=lambda e: e[1]))
    loop = line("/host:CPU", "python",
                [("plane.decode", 4000 * US, 1500 * US)]
                + step("hhe.device_wait", 4000 * US, 4500 * US))
    if device is None:
        device = ops((0, 2 * MS), (3 * MS, 4 * MS), (6 * MS, 9 * MS))
    return trace.Trace([device, loop, worker])


def read(name, tr, **kw):
    return load_reader(name)(ctx_for(tr, **kw))


def test_interval_algebra():
    a = [[0, 10], [20, 30]]
    b = [[5, 22], [25, 26]]
    assert spans.intersect(a, b) == [[5, 10], [20, 22], [25, 26]]
    assert spans.subtract(a, b) == [[0, 5], [22, 25], [26, 30]]
    assert spans.subtract(a, []) == a and spans.intersect(a, []) == []
    assert spans.subtract(b, a) == [[10, 20]]


def test_marks_pair_into_steps():
    ln = line("/host:CPU", "python", [
        ("plane.job.end", 0, US),                 # opened before the trace
        *step("plane.job", 2 * US, 10 * US),
        *step("plane.job", 10 * US, 20 * US),     # back to back: merged
        ("hhe.device_wait", 12 * US, US),         # nested in the job...
        ("hhe.device_wait.end", 14 * US, US),     # ...and closed
        ("plane.job", 30 * US, US),               # still open at the stop
    ])
    assert spans.steps(ln, "plane.job") == [[2 * US, 20 * US]]
    assert spans.steps(ln, "hhe.device_wait") == [[12 * US, 15 * US]]
    assert spans.steps(ln, "hhe.fire") == []


def test_the_worker_is_the_line_that_holds_plane_job():
    w = spans.worker(recorded())
    # the event loop's wait span is not the worker's
    assert w.jobs == [[1500 * US, 5000 * US], [5500 * US, 9500 * US]]
    assert w.waits == [[1500 * US, 2500 * US], [5500 * US, 8000 * US]]
    assert w.host == [[2500 * US, 5000 * US], [8000 * US, 9500 * US]]


def test_each_metric_reads_its_exact_value():
    tr = recorded()
    assert math.isclose(read("device_idle_pct", tr), 40.0)
    # jobs 3.5 + 4 ms of the 10 ms window
    assert math.isclose(read("worker_busy_pct", tr), 75.0)
    # 7.5 ms of jobs less 3.5 ms of device wait, over 100 blocks
    assert math.isclose(read("worker_host_us_per_block", tr, blocks=100),
                        40.0)
    # gaps in host steps: [2.5,3) + [4,5) = 1.5 ms
    assert math.isclose(read("idle_worker_host_pct", tr), 15.0)
    # gaps with no job open: [5,5.5) = 0.5 ms
    assert math.isclose(read("idle_worker_free_pct", tr), 5.0)


def test_readers_find_nothing_without_plane_job():
    device = ops((0, 2 * MS), (3 * MS, 4 * MS))
    tr = trace.Trace([device, line("/host:CPU", "python", [
        *step("hhe.device_wait", 0, MS),
        ("PjitFunction(producer)", 2 * MS, 10)])])
    for name in SPAN_METRICS:
        assert read(name, tr) is None
        assert read(name, None) is None


def test_no_idle_reads_zero():
    tr = recorded(device=ops((0, 6 * MS), (5 * MS, 10 * MS)))
    assert read("idle_worker_host_pct", tr) == 0.0
    assert read("idle_worker_free_pct", tr) == 0.0
    assert read("device_idle_pct", tr) == 0.0


@pytest.mark.parametrize("seed", range(6))
def test_idle_shares_sum_to_at_most_device_idle(seed):
    """Random device ops and random, nested worker spans: the two idle
    shares never overlap, and never exceed the device's idle share."""
    rng = np.random.default_rng(seed)
    starts = np.sort(rng.integers(0, 9 * MS, 40))
    device = ops(*[(int(a), int(a + rng.integers(1, MS // 4)))
                   for a in starts])
    events = []
    t = int(rng.integers(0, MS))
    while t < 9 * MS:
        d = int(rng.integers(3 * US, MS))
        events += step("plane.job", t, t + d)
        w0 = t + int(rng.integers(0, d - 2 * US))
        events += step("hhe.device_wait", w0,
                       int(rng.integers(w0 + US, t + d)))
        t += d + int(rng.integers(0, MS // 2))
    events.sort(key=lambda e: e[1])
    tr = trace.Trace([device, line("/host:CPU", "python", events)])
    host = read("idle_worker_host_pct", tr)
    free = read("idle_worker_free_pct", tr)
    idle = read("device_idle_pct", tr)
    assert host >= 0 and free >= 0
    assert host + free <= idle + 1e-9
    busy = read("worker_busy_pct", tr)
    assert 0 < busy <= 100


def test_readers_find_the_programs_own_spans():
    """A traced run of the program on the CPU (no device plane there, so
    no idle gaps): the worker's line is found and its spans are read."""
    cell = load_cell("hera-tiny.tiny-bulk", EXTRA / "BENCHMARK.json",
                     dirs=(EXTRA, BENCH))
    result, ctx = run_cell(cell, 2**33 + 5, 1.0, trace=True,
                           with_context=True)
    assert result["correct"], result["checks"]
    w = spans.worker(ctx.trace)
    assert w is not None and w.waits
    assert 0 < load_reader("worker_busy_pct")(ctx) <= 100
    assert load_reader("worker_host_us_per_block")(ctx) > 0
    assert load_reader("idle_worker_host_pct")(ctx) == 0.0
    assert load_reader("idle_worker_free_pct")(ctx) == 0.0


# ---------------------------------------------------------------------------
# A trace recorded on the chip
# ---------------------------------------------------------------------------
RECORDED = "rubato-128l.bulk-tensors"
FIXTURES = BENCH / "tests" / "fixtures"


@pytest.fixture(scope="module")
def chip_run():
    """The recorded run's result line, and a Context that holds what the
    harness held when it read its metrics: the trace, the traced window
    and the blocks of every answered request."""
    result = json.loads((FIXTURES / f"{RECORDED}.result.json").read_text())
    cell = load_cell(RECORDED, ROOT / "BENCHMARK.json")
    spec = cell.config["cipher"]
    blocks, words = traffic.blocks_for(cell.mix["size"], spec["l"])
    req = traffic.Request(0, 0, 0, "decrypt_tokens",
                          np.zeros((blocks, spec["l"]), np.uint32), words)
    answered = result["attempted"] - result["failed"]
    sent = [Sent(req, 0.0, 0.0, 0.0, {"ok": True})] * answered
    tr = trace.Trace.from_file(FIXTURES / f"{RECORDED}.xplane.pb.gz")
    ctx = Context(cell=cell, seconds=0.0, setup_s=0.0, window=64, sent=sent,
                  t0=0.0, t_end=0.0, stats_before={}, stats_after={},
                  cipher=Cipher(spec),
                  peaks=load_peaks(result["device"]["kind"]), trace=tr,
                  trace_window_s=result["device"]["window_s"],
                  drain_limit=0.0)
    return result, cell, ctx


def test_recorded_trace_is_small_and_from_the_chip(chip_run):
    result, cell, _ = chip_run
    assert (FIXTURES / f"{RECORDED}.xplane.pb.gz").stat().st_size < 1 << 20
    assert result["correct"] and result["device"]["platform"] == "tpu"
    assert set(result["metrics"]) == ({m["name"] for m in cell.per_layer}
                                      | set(SPAN_METRICS))


@pytest.mark.parametrize("name", [
    "producer_us_per_block", "kernel_us_per_block",
    "keystream_kernel_roofline", "device_idle_pct", *SPAN_METRICS])
def test_reader_gives_again_what_the_chip_run_printed(chip_run, name):
    result, _, ctx = chip_run
    assert math.isclose(load_reader(name)(ctx),
                        result["metrics"][name]["value"], rel_tol=1e-9)


def test_recorded_idle_shares_within_device_idle(chip_run):
    _, _, ctx = chip_run
    host = load_reader("idle_worker_host_pct")(ctx)
    free = load_reader("idle_worker_free_pct")(ctx)
    assert 0 < host + free <= load_reader("device_idle_pct")(ctx)


def test_recorded_breakdown_charges_gaps_to_program_steps(chip_run):
    """The breakdown of the recorded run, computed again, and with the
    program's own steps among the host events its gaps are charged to."""
    result, _, ctx = chip_run
    gaps = trace.idle_gaps(ctx.trace)
    assert gaps == result["breakdown"]["idle_gaps"]
    assert any(name.startswith(("hhe.", "farm.", "plane."))
               for name, _ in gaps)
