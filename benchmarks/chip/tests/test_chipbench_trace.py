"""The device-trace readers and the idle share on a hand-built trace with
the planes, lines and event names a TPU trace has (no chip trace is
checked in yet): unions of overlapping ops, module and kernel matching,
the HBM roofline share, and idle gaps charged to host events."""

import math
import pathlib
import sys

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import trace  # noqa: E402
from chipbench.cells import load_cell, load_reader  # noqa: E402
from chipbench.harness import Context, Sent  # noqa: E402
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
