"""The device-trace readers and the idle share on a hand-built trace with
the planes, lines and event names a TPU trace has: unions of overlapping
ops, module and kernel matching, the HBM roofline share, and idle gaps
charged to host events.  ``idle_gaps`` is held to the plain scan it
replaced, on the trace recorded on the chip and on seeded traces, and
its cost to the size of the trace; a traced run times its phases."""

import math
import pathlib
import re
import sys
import time
from collections import defaultdict

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import trace  # noqa: E402
from chipbench.cells import load_cell, load_reader  # noqa: E402
from chipbench.harness import PHASES, Context, Sent, run_cell  # noqa: E402
from chipbench.reference import Cipher  # noqa: E402
from chipbench.traffic import Request  # noqa: E402

MS = 1_000_000   # ns
DEV = "/device:TPU:0"


def line(plane, name, events):
    names = [e[0] for e in events]
    return trace.Line(plane, name, names,
                      np.array([e[1] for e in events], np.int64),
                      np.array([e[2] for e in events], np.int64))


def recorded():
    """Two windows' worth of device work in a 10 ms trace: each a producer
    module with two fused ops, then the kernel module with a transpose
    and the Pallas call; a host dispatch event sits in each gap."""
    ops = line(DEV, trace.OPS_LINE, [
        ("fusion.1", 0 * MS, 2 * MS),
        ("fusion.2", 1 * MS, 2 * MS),            # overlaps fusion.1
        ("copy.3", 3 * MS, MS // 2),
        ("keystream_kernel_apply.1", 3 * MS + MS // 2, MS // 2),
        ("fusion.1", 6 * MS, 3 * MS),
        ("keystream_kernel_apply.1", 9 * MS, MS // 2),
    ])
    modules = line(DEV, trace.MODULES_LINE, [
        ("jit_producer(17)", 0, 3 * MS),
        ("jit_keystream_kernel_apply(18)", 3 * MS, MS),
        ("jit_producer(17)", 6 * MS, 3 * MS),
        ("jit_keystream_kernel_apply(18)", 9 * MS, MS // 2),
    ])
    host = line("/host:CPU", "hhe-farm", [
        ("whole-thread", 0, 10 * MS),            # umbrella: never charged
        ("PjitFunction(producer)", 4 * MS + MS // 2, MS),
    ])
    return trace.Trace([ops, modules, host])


def ctx_for(tr, blocks=100, window_s=0.010):
    cell = load_cell("rubato-128l.bulk-tensors", ROOT / "BENCHMARK.json")
    l = cell.config["cipher"]["l"]
    req = Request(0, 0, 0, "decrypt_tokens", np.zeros((blocks, l),
                                                       np.uint32), 24576)
    sent = [Sent(req, 0.0, 0.0, 0.001, {"ok": True, "latency_ms": 1.0})]
    return Context(cell=cell, seconds=0.01, setup_s=1.0, window=64,
                   sent=sent, t0=0.0, t_end=0.01, stats_before={},
                   stats_after={}, cipher=Cipher(cell.config["cipher"]),
                   peaks={"hbm_bytes_per_s": 819e9}, trace=tr,
                   trace_window_s=window_s, drain_limit=60.0)


def test_busy_is_the_union_of_device_ops():
    tr = recorded()
    (ops,) = tr.device_ops()
    # [0,3) + [3,4) + [6,9.5) = 7.5 ms, overlaps counted once
    assert math.isclose(trace.busy_s(ops), 0.0075)
    idle = load_reader("device_idle_pct")(ctx_for(tr))
    assert math.isclose(idle, 25.0)


def test_producer_and_kernel_time_per_block():
    ctx = ctx_for(recorded(), blocks=100)
    assert math.isclose(load_reader("producer_us_per_block")(ctx),
                        6000.0 / 100)
    assert math.isclose(load_reader("kernel_us_per_block")(ctx),
                        1000.0 / 100)
    share = load_reader("keystream_kernel_roofline")(ctx)
    assert math.isclose(share, 100.0 * 100 * 1232 / 819e9 / 0.001)
    assert 0 < share < 100


def test_readers_find_nothing_without_a_trace_or_their_events():
    assert load_reader("producer_us_per_block")(ctx_for(None)) is None
    empty = trace.Trace([line(DEV, trace.OPS_LINE, [("fusion.9", 0, 5)])])
    ctx = ctx_for(empty)
    assert load_reader("kernel_us_per_block")(ctx) is None
    assert load_reader("keystream_kernel_roofline")(ctx) is None
    assert load_reader("producer_us_per_block")(ctx) is None


def test_breakdown_ranks_ops_and_charges_gaps_to_host_events():
    tr = recorded()
    top = trace.top_ops(tr.device_ops())
    assert top[0][0] == "fusion.1" and math.isclose(top[0][1], 0.005)
    gaps = dict(trace.idle_gaps(tr))
    # the one gap, [4, 6) ms, overlaps the dispatch event for 1 ms
    assert gaps == {"PjitFunction(producer)": 0.002}


# ---------------------------------------------------------------------------
# idle_gaps against the plain scan it replaced
# ---------------------------------------------------------------------------
RECORDED = BENCH / "tests" / "fixtures" / "rubato-128l.bulk-tensors.xplane.pb.gz"
EXTRA = BENCH / "tests" / "fixtures" / "extra"


def unbounded_idle_gaps(tr, k=10):
    """The plain reference: for each gap, every host event that starts
    within the trace's longest host event before it."""
    ops = tr.device_ops()
    if not ops:
        return []
    iv = trace.union(ops[0].start_ns, ops[0].dur_ns)
    gaps = [(iv[i][1], iv[i + 1][0]) for i in range(len(iv) - 1)
            if iv[i + 1][0] > iv[i][1]]
    if not gaps:
        return []
    hs, he, hn = [], [], []
    for ln in tr.host_lines():
        hs.append(ln.start_ns)
        he.append(ln.start_ns + ln.dur_ns)
        hn.extend(ln.names)
    hs = np.concatenate(hs) if hs else np.zeros(0, np.int64)
    he = np.concatenate(he) if he else np.zeros(0, np.int64)
    order = np.argsort(hs, kind="stable")
    hs, he = hs[order], he[order]
    hn = [hn[i] for i in order.tolist()]
    longest = int((he - hs).max()) if len(hs) else 0
    tot = defaultdict(int)
    for a, b in gaps:
        lo = int(np.searchsorted(hs, a - longest, side="left"))
        hi = int(np.searchsorted(hs, b, side="left"))
        best, best_ov = "no host event", 0
        for j in range(lo, hi):
            s, e = int(hs[j]), int(he[j])
            if e - s > (b - a) + 1_000_000:
                continue
            ov = min(b, e) - max(a, s)
            if ov > best_ov:
                best, best_ov = hn[j], ov
        tot[best] += b - a
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in top]


@pytest.fixture(scope="module")
def chip_trace():
    return trace.Trace.from_file(RECORDED)


def seeded(seed):
    """Device ops with gaps from a microsecond to several milliseconds;
    host events on three lines from nothing long to the whole trace (far
    longer than any gap), some repeated under other names so that two
    overlap a gap equally; and over every tenth gap, two events that
    cover it whole, one just long enough to be charged it and one a
    nanosecond too long."""
    rng = np.random.default_rng(seed)
    t, dev = 0, []
    for i in range(300):
        d = int(rng.integers(1, 200_000))
        dev.append((f"fusion.{i % 7}", t, d))
        t += d + int(rng.choice([0, 1_000, 50_000, 3 * MS]) *
                     rng.random())
    lines = [line(DEV, trace.OPS_LINE, dev)]
    iv = trace.union(lines[0].start_ns, lines[0].dur_ns)
    edges = [(iv[i][1], iv[i + 1][0]) for i in range(0, len(iv) - 1, 10)]
    for name, extra in (("edge.out", 1), ("edge.in", 0)):
        lines.append(line("/host:CPU", name, [
            (name, a - 10, (b - a) + trace.REACH_NS + extra)
            for a, b in edges]))
    for k in range(3):
        starts = np.sort(rng.integers(-5 * MS, t, 500))
        durs = rng.choice([0, 2_000, 300_000, 2 * MS, 50 * MS, t + 10 * MS],
                          500, p=[.05, .4, .3, .15, .07, .03])
        events = [(f"host{k}.{int(rng.integers(12))}", int(a), int(d))
                  for a, d in zip(starts, durs)]
        events += [(f"twin{k}", a, d) for _, a, d in events[::17]]
        lines.append(line(f"/host:CPU{k}", "python",
                          sorted(events, key=lambda e: e[1])))
    return trace.Trace(lines)


def test_idle_gaps_equals_the_unbounded_scan_on_the_chip_trace(chip_trace):
    assert trace.idle_gaps(chip_trace) == unbounded_idle_gaps(chip_trace)


@pytest.mark.parametrize("seed,chunk", [(0, 1 << 22), (1, 1), (2, 7),
                                        (3, 1 << 22), (4, 1000), (5, 64)])
def test_idle_gaps_equals_the_unbounded_scan(seed, chunk, monkeypatch):
    """Seeded traces with host events far longer than any gap, and the
    pairs compared a few at a time as well as all at once."""
    tr = seeded(seed)
    monkeypatch.setattr(trace, "PAIRS_PER_CHUNK", chunk)
    got = trace.idle_gaps(tr, k=50)
    assert got == unbounded_idle_gaps(tr, k=50)
    # a twin ties with the event it repeats, listed before it, which wins
    assert len(got) > 5 and not any(n.startswith("twin") for n, _ in got)
    assert "edge.in" in dict(got)


def test_idle_gaps_cost_does_not_follow_the_longest_host_event(chip_trace):
    """One host event of a second, longer than any gap, on the recorded
    trace: the plain scan would compare each gap with every event before
    it (minutes); the bounded one takes what it took without it."""
    first = min(int(ln.start_ns.min()) for ln in chip_trace.host_lines())
    long_line = line("/host:CPU", "injected", [("umbrella", first, 10**9)])
    tr = trace.Trace(chip_trace.lines + [long_line])
    t = time.perf_counter()
    got = trace.idle_gaps(tr)
    assert time.perf_counter() - t < 10
    assert got == trace.idle_gaps(chip_trace)


def test_traced_run_prints_its_phases(capsys):
    cell = load_cell("hera-tiny.tiny-bulk", EXTRA / "BENCHMARK.json",
                     dirs=(EXTRA, BENCH))
    result = run_cell(cell, 2**32 + 11, 0.5, trace=True)
    assert result["correct"], result["checks"]
    err = capsys.readouterr().err
    (phase_line,) = [ln for ln in err.splitlines()
                     if ln.startswith("phases: ")]
    got = dict(re.findall(r"(\w+)=(\d+\.\d+)", phase_line))
    assert list(got) == list(PHASES)
    assert all(float(v) >= 0 for v in got.values())
    assert float(got["drive_s"]) > 0.5
    assert err.splitlines()[-1].startswith("check ")
