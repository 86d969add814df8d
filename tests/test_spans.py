"""The serving path's program steps, read back from a profiler trace.

Windows of hera-80 are served through ``HHEServer`` and through a socket
round trip to ``ServePlane`` while ``jax.profiler`` traces, with the
options the chip benchmark's ``--trace 1`` run uses.  The steps are read
from the ``.xplane.pb`` it writes: a step of bounded host work is one
span, a step that can wait on the device is a pair of marks
(``hhe_loop.marked``), and every step of the serving path is there under
its plain name, nests as the calls do, carries the request's ``seq`` or
the window's serial, and is entered once per window, never once per
lane.  The chip benchmark's readers match these names, and
``jit_producer``, the producer's module name, pinned here too.
"""

import asyncio
import dataclasses
import os
import pathlib
import socket
import subprocess
import sys
import threading
from collections import Counter

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.cipher import CipherBatch
from repro.core.farm import WindowPlan
from repro.serve.hhe_loop import HHERequest, HHEServer
from repro.serve.tenants import TenantRegistry

SPANS = ("plane.decode", "plane.encode", "plane.resolve", "plane.job",
         "hhe.submit", "hhe.fire", "hhe.carve", "farm.produce",
         "farm.consume", "hhe.materialize", "hhe.device_wait", "hhe.fetch",
         "hhe.scatter", "hhe.respond")
#: spans entered once for every window the scheduler fires
PER_WINDOW = ("hhe.fire", "hhe.carve", "farm.produce", "farm.consume",
              "hhe.materialize", "hhe.device_wait", "hhe.fetch",
              "hhe.scatter")
#: steps that can wait on the device: a mark at each end, not a span
MARKED = ("plane.job", "hhe.submit", "hhe.fire", "hhe.materialize",
          "hhe.device_wait", "hhe.respond")
JOB_KINDS = {"hello", "open_session", "submit", "tick", "rotate", "stats"}
#: the steps a step runs inside, as PERF.md section 3 lists them
PARENTS = {"hhe.submit": ("plane.job",),
           "hhe.fire": ("hhe.submit", "plane.job"),
           "hhe.carve": ("hhe.fire",),
           "farm.produce": ("hhe.fire",),
           "farm.consume": ("hhe.fire", "plane.job"),
           "hhe.materialize": ("hhe.fire", "plane.job"),
           "hhe.device_wait": ("hhe.materialize",),
           "hhe.fetch": ("hhe.materialize",),
           "hhe.scatter": ("hhe.materialize",),
           "hhe.respond": ("hhe.materialize",)}


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int
    stats: dict
    line: tuple          # (plane, index of the line): one thread each

    def inside(self, other: "Span") -> bool:
        return (self.line == other.line and other.start <= self.start
                and self.end <= other.end)


def raw_events(log_dir):
    """The program's own events in the trace under ``log_dir``, as they
    were recorded: spans, and the marks at each end of a marked step."""
    (path,) = pathlib.Path(log_dir).rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for i, ln in enumerate(plane.lines):
            for ev in ln.events:
                if ev.name.split(".")[0] in ("plane", "hhe", "farm"):
                    start = int(ev.start_ns)
                    out.append(Span(ev.name, start,
                                    start + int(ev.duration_ns),
                                    dict(ev.stats), (plane.name, i)))
    return sorted(out, key=lambda s: (s.line, s.start))


def traced(log_dir, fn):
    """Run ``fn`` under the profiler, with the chip benchmark's options,
    and return the program's steps from the trace it writes: spans as
    they are, and each marked step from its first mark, with that mark's
    stats, to the end of its ``.end`` mark."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        out = fn()
    steps, open_ = [], {}
    for ev in raw_events(log_dir):
        if ev.name in MARKED:
            open_.setdefault((ev.line, ev.name), []).append(ev)
        elif ev.name.endswith(".end"):
            begin = open_[(ev.line, ev.name[:-4])].pop()
            steps.append(Span(begin.name, begin.start, ev.end, begin.stats,
                              ev.line))
        else:
            steps.append(ev)
    assert not any(open_.values()), "a marked step was never closed"
    return sorted(steps, key=lambda s: s.start), out


def named(spans, name):
    return [s for s in spans if s.name == name]


# ---------------------------------------------------------------------------
# In process: three requests, three windows, through HHEServer
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def direct(tmp_path_factory):
    cb = CipherBatch("hera-80", seed=5)
    srv = HHEServer(cb, window=8, engine="jax")
    s = srv.open_session()
    srv.warmup()

    def serve():
        for blocks in (5, 9, 4):     # 18 lanes: two fill fires, one flush
            srv.submit(HHERequest(session_id=s.index, blocks=blocks))
        return srv.flush()

    spans, responses = traced(tmp_path_factory.mktemp("direct"), serve)
    return spans, responses, srv


def test_scheduler_spans_count_windows_and_responses(direct):
    spans, responses, srv = direct
    assert srv.windows_served == 3 and len(responses) == 3
    assert len(named(spans, "hhe.materialize")) == srv.windows_served
    assert len(named(spans, "hhe.respond")) == len(responses)
    assert len(named(spans, "hhe.submit")) == 3
    causes = Counter(s.stats["cause"] for s in named(spans, "hhe.fire"))
    assert causes == {"fill": 2, "flush": 1}


def test_materialize_holds_wait_fetch_and_scatter(direct):
    spans, _, _ = direct
    outer = named(spans, "hhe.materialize")
    for name in ("hhe.device_wait", "hhe.fetch", "hhe.scatter"):
        inner = named(spans, name)
        assert len(inner) == len(outer)
        assert all(any(s.inside(m) for m in outer) for s in inner), name


def test_spans_carry_seq_and_window(direct):
    spans, responses, _ = direct
    submits = [s.stats["seq"] for s in named(spans, "hhe.submit")]
    assert submits == [0, 1, 2]
    assert sorted(s.stats["seq"] for s in named(spans, "hhe.respond")) \
        == sorted(r.seq for r in responses)
    fired = [s.stats["window"] for s in named(spans, "hhe.fire")]
    landed = [s.stats["window"] for s in named(spans, "hhe.materialize")]
    assert fired == landed == [0, 1, 2]
    valid = [s.stats["valid"] for s in named(spans, "hhe.fire")]
    assert valid == [8, 8, 2]
    assert [s.stats["lanes"] for s in named(spans, "hhe.scatter")] == valid


@pytest.mark.parametrize("window", [4, 32])
def test_spans_per_window_do_not_grow_with_the_window(tmp_path, window):
    cb = CipherBatch("hera-80", seed=6)
    srv = HHEServer(cb, window=window, engine="jax")
    s = srv.open_session()
    srv.warmup()

    def serve():
        srv.submit(HHERequest(session_id=s.index, blocks=3 * window))
        return srv.flush()

    spans, (resp,) = traced(tmp_path, serve)
    counts = Counter(s.name for s in spans)
    assert {name: counts[name] for name in PER_WINDOW} \
        == dict.fromkeys(PER_WINDOW, 3)
    assert counts["hhe.submit"] == counts["hhe.respond"] == 1
    assert sum(counts.values()) == 3 * len(PER_WINDOW) + 2


@pytest.mark.parametrize("depth, overlapped", [(1, 0), (2, 3)])
def test_overlapped_reads_count_the_reads_with_work_behind_them(
        tmp_path, depth, overlapped):
    """``overlapped_reads`` counts the reads whose ``hhe.materialize`` mark
    has ``behind >= 1``.  17 blocks in windows of 4 at depth 2: the three
    windows read inside the submit and at the flush's push have the next
    producer behind them, the last two, read in the drain, none; depth 1
    reads every window before the next is dispatched."""
    cb = CipherBatch("hera-80", seed=8)
    srv = HHEServer(cb, window=4, engine="jax", depth=depth)
    s = srv.open_session()
    srv.warmup()

    def serve():
        srv.submit(HHERequest(session_id=s.index, blocks=17))
        return srv.flush()

    spans, (resp,) = traced(tmp_path, serve)
    reads = named(spans, "hhe.materialize")
    assert [m.stats["window"] for m in reads] == list(range(5))
    behind = [m.stats["behind"] for m in reads]
    assert srv.latency_stats()["overlapped_reads"] \
        == sum(b >= 1 for b in behind) == overlapped
    assert behind == ([0] * 5 if depth == 1 else [1, 1, 1, 0, 0])


# ---------------------------------------------------------------------------
# Over the wire: one tenant, two requests and a rotation, through ServePlane
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def wire(tmp_path_factory):
    from repro.serve.server import ServeClient, ServePlane

    async def main():
        reg = TenantRegistry("hera-80", capacity=2, window=4, engine="jax",
                             deadline_s=0.01)
        plane = ServePlane(reg, port=0, tick_s=0.002)
        host, port = await plane.start()
        c = ServeClient(host, port, "t0")
        replies = []
        try:
            await c.connect()
            s = await c.open_session()
            rng = np.random.default_rng(3)
            q, l = c.params.mod.q, c.params.l
            toks = rng.integers(0, q, (6, l), dtype=np.uint32)
            replies.append(await c.encrypt_to_server(s, toks))
            await c.rotate(s)
            toks = rng.integers(0, q, (2, l), dtype=np.uint32)
            replies.append((await c.decrypt_from_server(s, toks))[0])
            stats = await c.stats()
        finally:
            await c.close()
            await plane.stop()
        return replies, stats

    return traced(tmp_path_factory.mktemp("wire"),
                  lambda: asyncio.run(main()))


def test_every_span_of_the_serving_path_appears(wire):
    spans, (replies, stats) = wire
    assert all(r["ok"] for r in replies)
    assert set(SPANS) <= {s.name for s in spans}
    assert {s.stats["kind"] for s in named(spans, "plane.job")} == JOB_KINDS
    assert all(s.stats["queued_us"] >= 0 for s in named(spans, "plane.job"))
    assert all(s.stats["bytes"] > 0 for s in named(spans, "plane.decode"))
    assert all(s.stats["bytes"] > 0 for s in named(spans, "plane.encode"))
    assert stats["overlapped_reads"] == sum(
        s.stats["behind"] >= 1 for s in named(spans, "hhe.materialize"))


def test_steps_nest_as_listed(wire):
    spans, _ = wire
    for name, parents in PARENTS.items():
        outer = [s for s in spans if s.name in parents]
        for step in named(spans, name):
            assert any(step.inside(p) for p in outer), (name, step)


def test_wire_spans_count_windows_and_responses(wire):
    spans, (replies, stats) = wire
    # 6 blocks: a fill-fired window and a 2-lane tail; 2 blocks after the
    # rotation: one more; the tails fire on the deadline
    assert stats["windows_served"] == 3
    assert len(named(spans, "hhe.materialize")) == stats["windows_served"]
    assert len(named(spans, "hhe.respond")) == len(replies)
    causes = Counter(s.stats["cause"] for s in named(spans, "hhe.fire"))
    assert causes == {"fill": 1, "deadline": 2}


def test_the_worker_runs_on_its_own_thread(wire):
    spans, _ = wire
    jobs = {s.line for s in named(spans, "plane.job")}
    decodes = {s.line for s in named(spans, "plane.decode")}
    assert len(jobs) == 1 and not jobs & decodes
    # the scheduler's spans all run inside the worker's jobs
    outer = named(spans, "plane.job")
    for s in spans:
        if s.name.startswith(("hhe.", "farm.")):
            assert any(s.inside(j) for j in outer), s


def test_only_steps_of_bounded_host_work_are_spans(tmp_path):
    """No program event lasts through a wait on the device: the marked
    steps are pairs of marks, and no recorded event holds another."""
    cb = CipherBatch("hera-80", seed=7)
    srv = HHEServer(cb, window=4, engine="jax")
    s = srv.open_session()
    srv.warmup()

    def serve():
        srv.submit(HHERequest(session_id=s.index, blocks=9))
        return srv.flush()

    traced(tmp_path, serve)
    raw = raw_events(tmp_path)
    counts = Counter(ev.name for ev in raw)
    for name in MARKED[1:]:
        assert counts[name] == counts[name + ".end"] > 0, name
    assert not [(a.name, b.name) for a in raw for b in raw
                if a is not b and b.inside(a)]


def test_producer_module_keeps_the_name_the_trace_readers_match():
    """The chip benchmark's producer_us_per_block sums the device's module
    events named ``jit_producer(...)``."""
    cb = CipherBatch("rubato-128l", seed=1)
    cb.add_session()
    plan = WindowPlan(np.zeros(4, np.int64), np.arange(4))
    text = cb.producer.jitted("all").lower(
        cb.xof_tables().device, plan.session_ids, plan.block_ctrs).as_text()
    assert "module @jit_producer " in text


def test_profiler_port_serves_live_captures(tmp_path):
    """``--profiler-port`` (off by default) starts JAX's profiler server
    beside the serving plane, for a capture from a running server."""
    from repro.serve.server import build_parser

    assert build_parser().parse_args([]).profiler_port is None
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(src),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.Popen(
        [sys.executable, "-u", "-m", "repro.serve.server", "--port", "0",
         "--profiler-port", str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    timer = threading.Timer(120, proc.kill)
    timer.start()
    try:
        lines = []
        for line in proc.stdout:
            lines.append(line)
            if line.startswith("serving"):
                break
        assert lines and lines[-1].startswith("serving"), lines
        socket.create_connection(("127.0.0.1", port), timeout=10).close()
    finally:
        timer.cancel()
        proc.kill()
        proc.wait(timeout=30)
        proc.stdout.close()
