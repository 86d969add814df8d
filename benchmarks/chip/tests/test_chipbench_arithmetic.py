"""The arithmetic from requests and counters to metrics: due-time latency
and its tail over a window with a stall, failed requests in the tail,
window occupancy from the stats op's deltas, the rate, and the traffic
generator's stratified schedules."""

import math
import pathlib
import statistics
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
EXTRA = BENCH / "tests" / "fixtures" / "extra"
sys.path.insert(0, str(BENCH))

from chipbench import traffic  # noqa: E402
from chipbench.cells import load_cell, load_reader  # noqa: E402
from chipbench.harness import Context, Sent, passes  # noqa: E402
from chipbench.reference import Cipher  # noqa: E402

CELL = load_cell("hera-128a.small-zipf", EXTRA / "BENCHMARK.json",
                 dirs=(EXTRA, BENCH))
BULK = load_cell("rubato-128l.bulk-tensors", ROOT / "BENCHMARK.json")


def stats(windows):
    return {"per_tenant": {f"t{i}": {"windows_served": w}
                           for i, w in enumerate(windows)}}


def ctx_of(sent, *, cell=CELL, seconds=10.0, before=(0,), after=(0,)):
    return Context(cell=cell, seconds=seconds, setup_s=20.0, window=64,
                   sent=sent, t0=0.0, t_end=seconds,
                   stats_before=stats(before), stats_after=stats(after),
                   cipher=Cipher(cell.config["cipher"]), peaks={},
                   drain_limit=seconds + 60.0)


def request(i, blocks=1, due=0.0, op="decrypt_tokens", words=None):
    l = CELL.config["cipher"]["l"]
    payload = np.zeros((blocks, l), np.uint32)
    return traffic.Request(i, 0, 0, op, payload,
                           blocks * l if words is None else words, due)


def answered(i, due, sent, done, server_ms=5.0, blocks=1):
    return Sent(request(i, blocks, due), due=due, sent=sent, done=done,
                reply={"ok": True, "latency_ms": server_ms})


def test_latency_runs_from_the_due_time_so_a_stall_counts():
    # 100 requests due every 10 ms, each answered 2 ms after it is due;
    # then the generator stalls: the last 10 leave 300 ms late and are
    # answered 2 ms after leaving
    sent = []
    for i in range(100):
        due = i * 0.01
        late = 0.3 if i >= 90 else 0.0
        sent.append(answered(i, due, due + late, due + late + 0.002))
    ctx = ctx_of(sent)
    lat = ctx.latencies_ms()
    assert math.isclose(min(lat), 2.0, rel_tol=1e-6)
    assert math.isclose(max(lat), 302.0, rel_tol=1e-6)
    assert math.isclose(load_reader("p50_ms")(ctx), 2.0, rel_tol=1e-6)
    # nearest rank: the 95th of 100 is the 95th smallest, a stalled one
    assert math.isclose(load_reader("p95_ms")(ctx), 302.0, rel_tol=1e-6)
    # timed from sending, as a send-time bench would, the stall vanishes
    from_send = sorted((s.done - s.sent) * 1e3 for s in sent)
    assert math.isclose(from_send[94], 2.0, rel_tol=1e-6)


def test_failed_and_unanswered_requests_count_as_the_whole_wait():
    sent = [answered(i, i * 0.01, i * 0.01, i * 0.01 + 0.002)
            for i in range(18)]
    sent.append(Sent(request(18, due=0.5), due=0.5, sent=0.5, done=0.501,
                     reply={"ok": False, "error": "saturated"}))
    sent.append(Sent(request(19, due=0.6), due=0.6, sent=0.6))
    ctx = ctx_of(sent)
    lat = sorted(ctx.latencies_ms())
    assert math.isclose(lat[-2], (70.0 - 0.6) * 1e3, rel_tol=1e-9)
    assert math.isclose(lat[-1], (70.0 - 0.5) * 1e3, rel_tol=1e-9)
    assert load_reader("p95_ms")(ctx) > 60_000
    assert ctx.blocks_served() == 18


def test_occupancy_from_stats_deltas():
    # 40 requests of 3 blocks served in 5 windows of 64 lanes, across two
    # tenants, on top of what the tenants had served before the window
    sent = [answered(i, 0.0, 0.0, 0.01, blocks=3) for i in range(40)]
    ctx = ctx_of(sent, before=(7, 2), after=(10, 4))
    assert ctx.windows_served() == 5
    assert math.isclose(load_reader("window_occupancy_pct")(ctx),
                        100.0 * 120 / (5 * 64))
    assert load_reader("window_occupancy_pct")(
        ctx_of(sent, before=(3,), after=(3,))) is None


def test_front_end_and_queue_split_the_latency():
    sent = [answered(i, 0.0, 0.001 * i, 0.001 * i + 0.010 + 0.001 * i,
                     server_ms=4.0 + i) for i in range(5)]
    ctx = ctx_of(sent)
    assert load_reader("queue_ms")(ctx) == 6.0
    rest = [(s.done - s.sent) * 1e3 - s.server_ms for s in sent]
    assert math.isclose(load_reader("frontend_ms")(ctx),
                        statistics.median(rest))


def test_rate_counts_words_answered_inside_the_window():
    l = BULK.config["cipher"]["l"]
    blocks = -(-24576 // l)
    sent = []
    for i in range(11):
        s = Sent(traffic.Request(i, 0, 0, "decrypt_tokens",
                                 np.zeros((blocks, l), np.uint32), 24576),
                 due=i * 0.9, sent=i * 0.9, done=i * 0.9 + 0.85,
                 reply={"ok": True, "latency_ms": 1.0})
        sent.append(s)
    ctx = ctx_of(sent, cell=BULK, seconds=9.5)
    # the 11th, due at 9.0 s, is answered at 9.85 s, after the window
    # closed: 0.5 s of its 0.85 s lie inside, and count
    want = (10 + 0.5 / 0.85) * 24576 / 9.5
    assert math.isclose(load_reader("words_per_s")(ctx), want)
    # back-to-back requests of 0.9 s: wherever the window closes inside
    # the last round, the rate is the steady one, not a whole round off
    steady = [Sent(s.req, due=i * 0.9, sent=i * 0.9, done=(i + 1) * 0.9,
                   reply=s.reply) for i, s in enumerate(sent)]
    for seconds in (9.05, 9.45, 9.85):
        rate = load_reader("words_per_s")(ctx_of(steady, cell=BULK,
                                                 seconds=seconds))
        assert math.isclose(rate, 24576 / 0.9)


def test_rejected_requests_fail_the_comparison():
    """A saturated or shed reply burns no keystream and has nothing to
    compare, yet the cells run below every admission limit: one is a
    failure of the run, counted apart from other error replies."""
    from chipbench.harness import Drive, check
    from chipbench.reference import tenant_key

    spec = CELL.config["cipher"]
    seed = 2**31 + 5
    keys = [tenant_key(spec["preset"], spec["n"], spec["q"], f"t{t}", seed)
            for t in range(int(CELL.config["tenants"]))]

    def drive(replies):
        sent = [Sent(request(i), due=0.0, sent=0.0, done=0.001, reply=r)
                for i, r in enumerate(replies)]
        return Drive(sent=sent, t0=0.0, t_end=1.0, drain_limit=61.0,
                     setup_s=1.0, stats_before={}, stats_after={},
                     keys=keys, rotations=0, engines=["pallas"],
                     trace_window_s=0.0, late_ms=[], window=64)

    checks = check(CELL, seed, drive([{"ok": False, "error": "saturated"},
                                      {"ok": False, "shed": True},
                                      {"ok": False, "error": "boom"}]))
    assert checks["rejected"]["value"] == 2
    assert checks["error_replies"]["value"] == 1
    assert not passes(checks)
    one = check(CELL, seed, drive([{"ok": False, "shed": True}]))
    assert one["rejected"]["value"] == 1 and one["error_replies"]["value"] == 0
    assert not passes(one)


def test_checks_pass_only_within_every_limit():
    ok = {"a": {"value": 0, "limit": 0, "is": "<="},
          "b": {"value": 5, "limit": 1, "is": ">="}}
    assert passes(ok)
    assert not passes(dict(ok, a={"value": 1, "limit": 0, "is": "<="}))
    assert not passes(dict(ok, b={"value": 0, "limit": 1, "is": ">="}))


@pytest.mark.parametrize("seed", [0, 2**31 + 17])
def test_open_schedule_is_the_same_work_in_another_order(seed):
    gen = traffic.Generator(CELL.mix, tenants=8, l=16, q=268369921,
                            seed=seed)
    other = traffic.Generator(CELL.mix, tenants=8, l=16, q=268369921,
                              seed=seed + 1)
    a, b = gen.open_schedule(150, 20.0), other.open_schedule(150, 20.0)
    assert len(a) == len(b) == 3000
    for key in (lambda r: r.tenant, lambda r: r.op, lambda r: r.blocks):
        assert sorted(map(key, a)) == sorted(map(key, b))
    assert [r.tenant for r in a] != [r.tenant for r in b]
    due = np.array([r.due for r in a])
    assert np.all(np.diff(due) > 0) and 0 < due[0] and \
        math.isclose(due[-1], 20.0)
    gaps_a = np.sort(np.diff(np.concatenate([[0.0], due])))
    gaps_b = np.sort(np.diff(np.concatenate(
        [[0.0], [r.due for r in b]])))
    np.testing.assert_allclose(gaps_a, gaps_b, rtol=1e-6, atol=1e-12)
    # exponential gaps: mean 1/rate, coefficient of variation near 1
    assert math.isclose(gaps_a.mean(), 20.0 / 3000, rel_tol=1e-9)
    assert 0.9 < gaps_a.std() / gaps_a.mean() < 1.1
    # Zipf 0.99 over 8 tenants: the hottest takes its exact share
    head = sum(r.tenant == 0 for r in a)
    share = 1.0 / sum(1.0 / (i + 1) ** 0.99 for i in range(8))
    assert abs(head - 3000 * share) <= 1
    # the same seed gives the same requests, payloads included
    again = traffic.Generator(CELL.mix, tenants=8, l=16, q=268369921,
                              seed=seed).open_schedule(150, 20.0)
    assert all(np.array_equal(x.payload, y.payload) and x.due == y.due
               for x, y in zip(a, again))
    assert all(int(r.payload.max()) < 268369921 for r in a)


def test_closed_stream_cuts_a_tensor_into_blocks_of_l():
    gen = traffic.Generator(BULK.mix, tenants=8, l=60, q=33538049, seed=3)
    stream = gen.closed_stream(2, 0)
    r1, r2 = next(stream), next(stream)
    assert r1.blocks == 410 and r1.words == 24576
    assert r1.payload.shape == (410, 60)
    assert not np.array_equal(r1.payload, r2.payload)
    again = next(traffic.Generator(BULK.mix, tenants=8, l=60, q=33538049,
                                   seed=3).closed_stream(2, 0))
    assert np.array_equal(again.payload, r1.payload)


def test_warmup_covers_every_op_and_size_of_the_mix():
    gen = traffic.Generator(CELL.mix, tenants=8, l=16, q=268369921, seed=1)
    shapes = {(r.tenant, r.op, r.blocks) for r in gen.warmup_requests()}
    assert shapes == {(t, op, b) for t in range(8)
                      for op in ("decrypt_tokens", "encrypt_tokens")
                      for b in range(1, 5)}


def test_exact_counts_split_by_largest_remainder():
    assert list(traffic.exact_counts([1, 1, 1], 10)) == [4, 3, 3]
    assert traffic.exact_counts([0.5, 0.3, 0.2], 7).sum() == 7
    assert traffic.nearest_rank([5, 1, 3, 2, 4], 0.5) == 3
