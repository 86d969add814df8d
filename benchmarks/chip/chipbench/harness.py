"""One run of one cell: set up the serving plane, drive the window, check
what it served against the reference, and reduce to the cell's metrics.

Everything runs in this one process: the serving plane
(``repro.serve.server.ServePlane`` over the configuration's
``TenantRegistry``) on a loopback port, and the clients on the same
event loop, speaking the wire protocol (``chipbench.wire``).
"""

from __future__ import annotations

import asyncio
import dataclasses
import gc
import shutil
import sys
import tempfile
import time
from typing import Callable, Optional

import numpy as np

from chipbench import reference, traffic
from chipbench import trace as tracemod
from chipbench.cells import Cell, load_peaks, load_reader

#: seconds the run waits past the window's close for open requests
DRAIN_S = 60.0
#: closed-loop requests whose replies are checked, drawn from the seed
CHECK_SAMPLE = 16
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the parts of a run timed on stderr's ``phases:`` line: the drive (set-up,
#: window and drain), reading the trace, the metric readers, and the
#: breakdown (``top_ops`` and ``idle_gaps``); a run without a trace reads
#: 0 for the two trace phases
PHASES = ("drive_s", "trace_read_s", "readers_s", "breakdown_s")


class CompileCounter:
    """Counts XLA compilations (persistent-cache hits included) while
    ``phase`` names a phase."""

    def __init__(self):
        self.phase: Optional[str] = None
        self.counts: dict = {}

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT and self.phase is not None:
            self.counts[self.phase] = self.counts.get(self.phase, 0) + 1


_COUNTER: Optional[CompileCounter] = None


def compile_counter() -> CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        import jax

        _COUNTER = CompileCounter()
        jax.monitoring.register_event_duration_secs_listener(_COUNTER)
    return _COUNTER


@dataclasses.dataclass
class Sent:
    """One request of the window and what became of it (loop clock)."""

    req: traffic.Request
    due: float
    sent: float = 0.0
    done: Optional[float] = None
    reply: Optional[dict] = None

    @property
    def ok(self) -> bool:
        return self.reply is not None and bool(self.reply.get("ok"))

    @property
    def server_ms(self) -> float:
        return float(self.reply["latency_ms"])


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    cell: Cell
    seconds: float
    setup_s: float
    window: int                  # lanes per farm window
    sent: list                   # Sent of every request due in the window
    t0: float
    t_end: float
    stats_before: dict
    stats_after: dict
    cipher: reference.Cipher
    peaks: dict
    trace: Optional[tracemod.Trace] = None
    trace_window_s: float = 0.0
    drain_limit: float = 0.0     # loop time past which a request failed

    def latencies_ms(self) -> list:
        """Due-to-reply latency of every request of the window; one that
        failed or never came counts as the whole wait the run allowed."""
        out = []
        for s in self.sent:
            end = s.done if s.ok else self.drain_limit
            out.append((end - s.due) * 1e3)
        return out

    def windows_served(self) -> int:
        def total(st):
            return sum(t["windows_served"]
                       for t in st["per_tenant"].values())
        return total(self.stats_after) - total(self.stats_before)

    def blocks_served(self) -> int:
        return sum(s.req.blocks for s in self.sent if s.ok)


class Plane:
    """The serving plane, its tenants' connections and their sessions."""

    def __init__(self, cell: Cell, seed: int):
        from repro.serve import server

        cfg = cell.config
        self.args = server.build_parser().parse_args(
            list(cfg["server_args"])
            + ["--seed", str(seed), "--host", "127.0.0.1", "--port", "0"])
        self.registry = server.make_registry(self.args)
        self.plane = server.ServePlane(self.registry, host="127.0.0.1",
                                       port=0)
        self.tenants = int(cfg["tenants"])
        self.session_blocks = int(cfg["session_blocks"])
        self.conns: list = []
        self.sessions: list = []
        self.cursor: list = []        # counters reserved per session
        self.keys: list = []          # tenant keys as provisioned
        self.rotations = 0
        self.admin = None

    async def open(self, conns_per_tenant: int) -> None:
        from chipbench.wire import Connection

        host, port = await self.plane.start()
        self.admin = Connection(host, port)
        await self.admin.open()
        for t in range(self.tenants):
            row, srow = [], []
            for _ in range(conns_per_tenant):
                c = Connection(host, port)
                await c.open()
                if not row:
                    hello = await c.call({"op": "hello",
                                          "tenant": f"t{t}"})
                    self.keys.append(np.asarray(hello["key"], np.uint32))
                r = await c.call({"op": "open_session", "tenant": f"t{t}"})
                row.append(c)
                srow.append(int(r["session"]))
            self.conns.append(row)
            self.sessions.append(srow)
            self.cursor.append([0] * conns_per_tenant)

    async def close(self) -> None:
        for row in self.conns:
            for c in row:
                await c.close()
        if self.admin is not None:
            await self.admin.close()
        await self.plane.stop()

    async def stats(self) -> dict:
        return (await self.admin.call({"op": "stats"}))["stats"]

    async def reserve(self, t: int, c: int, blocks: int) -> None:
        """Rotate the session first when the request would not fit its
        counters (the server refuses, and never rotates, a decrypt that
        does not fit)."""
        if self.cursor[t][c] + blocks > self.session_blocks:
            await self.conns[t][c].call({
                "op": "rotate", "tenant": f"t{t}",
                "session": self.sessions[t][c]})
            self.cursor[t][c] = 0
            self.rotations += 1
        self.cursor[t][c] += blocks

    def message(self, req: traffic.Request) -> dict:
        return {"op": "submit", "tenant": f"t{req.tenant}",
                "session": self.sessions[req.tenant][req.conn],
                "hhe_op": req.op, "payload": req.payload}

    def engines(self) -> list:
        return sorted({self.registry.peek(f"t{t}").server.farm.engine.name
                       for t in range(self.tenants)})


async def _send(plane: Plane, s: Sent, loop) -> asyncio.Future:
    await plane.reserve(s.req.tenant, s.req.conn, s.req.blocks)
    fut = plane.conns[s.req.tenant][s.req.conn].send(plane.message(s.req))
    s.sent = loop.time()
    return fut


async def _warm_up(plane: Plane, gen: traffic.Generator) -> None:
    loop = asyncio.get_running_loop()
    futs = []
    for req in gen.warmup_requests():
        futs.append(await _send(plane, Sent(req, 0.0), loop))
    for reply, _ in await asyncio.gather(*futs):
        if not reply.get("ok"):
            raise RuntimeError(f"warm-up request failed: {reply}")


async def _closed_window(plane: Plane, gen: traffic.Generator, t0: float,
                         t_end: float, drain_limit: float) -> list:
    loop = asyncio.get_running_loop()
    sent: list = []

    async def worker(stream):
        due = t0
        while due < t_end:
            s = Sent(next(stream), due)
            sent.append(s)
            fut = await _send(plane, s, loop)
            s.reply, s.done = await fut
            due = s.done

    streams = [gen.closed_stream(t, c) for t in range(plane.tenants)
               for c in range(gen.conns)]
    await asyncio.sleep(max(0.0, t0 - loop.time()))
    tasks = [loop.create_task(worker(st)) for st in streams]
    await asyncio.wait(tasks, timeout=max(0.0, drain_limit - loop.time()))
    for task in tasks:
        task.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return sent


async def _open_window(plane: Plane, schedule: list, t0: float,
                       drain_limit: float) -> list:
    loop = asyncio.get_running_loop()
    sent, pending = [], []

    async def land(s: Sent, fut):
        s.reply, s.done = await fut

    for req in schedule:
        s = Sent(req, t0 + req.due)
        delay = s.due - loop.time()
        if delay > 0:
            await asyncio.sleep(delay)
        sent.append(s)
        pending.append(loop.create_task(land(s, await _send(plane, s,
                                                            loop))))
    if pending:
        await asyncio.wait(pending,
                           timeout=max(0.0, drain_limit - loop.time()))
    for task in pending:
        task.cancel()
    await asyncio.gather(*pending, return_exceptions=True)
    return sent


@dataclasses.dataclass
class Drive:
    sent: list
    t0: float
    t_end: float
    drain_limit: float
    setup_s: float
    stats_before: dict
    stats_after: dict
    keys: list
    rotations: int
    engines: list
    trace_window_s: float
    late_ms: list
    window: int


async def _drive(cell: Cell, seed: int, seconds: float, *,
                 t_start: float, trace_dir: Optional[str],
                 after_setup: Optional[Callable]) -> Drive:
    cfg, mix = cell.config, cell.mix
    counter = compile_counter()
    counter.counts = {}
    gen = traffic.Generator(mix, tenants=int(cfg["tenants"]),
                            l=cfg["cipher"]["l"], q=cfg["cipher"]["q"],
                            seed=seed)
    schedule = None
    if mix["loop"] == "open":
        schedule = gen.open_schedule(float(cell.params["rate_per_s"]),
                                     seconds)
    plane = Plane(cell, seed)
    counter.phase = "setup"
    try:
        await plane.open(gen.conns)
        if after_setup is not None:
            after_setup(plane.registry)
        await _warm_up(plane, gen)
        before = await plane.stats()
        loop = asyncio.get_running_loop()
        t_trace = 0.0
        if trace_dir is not None:
            tracemod.start(trace_dir)
            t_trace = loop.time()
        t0 = loop.time() + 0.05
        t_end = t0 + seconds
        drain_limit = t_end + DRAIN_S
        setup_s = time.time() + (t0 - loop.time()) - t_start
        counter.phase = "window"
        if schedule is None:
            sent = await _closed_window(plane, gen, t0, t_end, drain_limit)
        else:
            sent = await _open_window(plane, schedule, t0, drain_limit)
        counter.phase = "after"
        trace_window_s = 0.0
        if trace_dir is not None:
            trace_window_s = loop.time() - t_trace
            tracemod.stop()
        after = await plane.stats()
        late = [(s.sent - s.due) * 1e3 for s in sent] if schedule else []
        return Drive(sent=sent, t0=t0, t_end=t_end, drain_limit=drain_limit,
                     setup_s=setup_s, stats_before=before,
                     stats_after=after, keys=plane.keys,
                     rotations=plane.rotations, engines=plane.engines(),
                     trace_window_s=trace_window_s, late_ms=late,
                     window=plane.args.window)
    finally:
        counter.phase = None
        await plane.close()


# --------------------------------------------------------------------------
# The comparison that decides `correct`
# --------------------------------------------------------------------------
def check(cell: Cell, seed: int, drive: Drive) -> dict:
    """Every number compared, each with its limit.

    The replies checked are every answered request of an open loop, and
    ``CHECK_SAMPLE`` drawn from the seed in a closed loop.  A reply is
    checked under its echoed (nonce, counters) and the tenant's key: an
    inbound ``decrypt_tokens`` result r of payload p passes where
    ``(r + Z) mod q == p``, an outbound ``encrypt_tokens`` result where
    ``(r - p) mod q == Z``, Z being the reference keystream.
    """
    cfg = cell.config
    spec = cfg["cipher"]
    cipher = reference.Cipher(spec)
    q = cipher.q
    keys = [reference.tenant_key(spec["preset"], cipher.n, q, f"t{t}", seed)
            for t in range(int(cfg["tenants"]))]
    key_mismatch = sum(not np.array_equal(a, b)
                       for a, b in zip(keys, drive.keys))
    answered = [s for s in drive.sent if s.ok]
    unanswered = sum(s.reply is None for s in drive.sent)
    refused = [s for s in drive.sent if s.reply is not None and not s.ok]
    rejected = sum(1 for s in refused if s.reply.get("error") == "saturated"
                   or s.reply.get("shed"))
    errors = len(refused) - rejected
    if cell.mix["loop"] == "closed" and len(answered) > CHECK_SAMPLE:
        rng = np.random.default_rng([seed, 4])
        pick = np.sort(rng.choice(len(answered), CHECK_SAMPLE,
                                  replace=False))
        answered = [answered[i] for i in pick]
    groups: dict = {}
    for s in answered:
        nonce = np.asarray(s.reply["nonce"], np.uint8).tobytes()
        groups.setdefault((s.req.tenant, nonce), []).append(s)
    mismatched = checked = 0
    for (t, nonce), items in groups.items():
        ctrs = [np.asarray(s.reply["ctrs"], np.int64).reshape(-1)
                for s in items]
        z_all = cipher.keystream_for(keys[t], nonce, np.concatenate(ctrs))
        at = 0
        for s, c in zip(items, ctrs):
            z = z_all[at:at + c.size]
            at += c.size
            p = s.req.payload.astype(np.int64)
            r = np.asarray(s.reply["result"]).astype(np.int64)
            checked += p.size
            if r.shape != p.shape or z.shape != p.shape:
                mismatched += p.size
            elif s.req.op == "decrypt_tokens":
                mismatched += int(np.count_nonzero((r + z) % q != p))
            elif s.req.op == "encrypt_tokens":
                mismatched += int(np.count_nonzero((r - p) % q != z))
            else:
                raise ValueError(f"no check for op {s.req.op!r}")
    return {
        "mismatched_words": {"value": mismatched, "limit": 0, "is": "<="},
        "checked_words": {"value": checked, "limit": 1, "is": ">="},
        "unanswered": {"value": unanswered, "limit": 0, "is": "<="},
        "rejected": {"value": rejected, "limit": 0, "is": "<="},
        "error_replies": {"value": errors, "limit": 0, "is": "<="},
        "key_mismatch": {"value": key_mismatch, "limit": 0, "is": "<="},
    }


def passes(checks: dict) -> bool:
    def ok(c):
        if c["is"] == "<=":
            return c["value"] <= c["limit"]
        return c["value"] >= c["limit"]
    return all(ok(c) for c in checks.values())


# --------------------------------------------------------------------------
# One run
# --------------------------------------------------------------------------
def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool = False, *,
             t_start: Optional[float] = None,
             after_setup: Optional[Callable] = None,
             keep_trace: Optional[str] = None, with_context: bool = False):
    """Run ``cell`` once and return the result object (the last line),
    and the metric readers' `Context` too where ``with_context``."""
    import jax

    t_start = time.time() if t_start is None else t_start
    devices = jax.devices()
    dev = devices[0]
    peaks = load_peaks(dev.device_kind) if dev.platform == "tpu" else {}
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace \
        else None
    phases = dict.fromkeys(PHASES, 0.0)
    try:
        t = time.perf_counter()
        drive = asyncio.run(_drive(cell, seed, seconds, t_start=t_start,
                                   trace_dir=trace_dir,
                                   after_setup=after_setup))
        phases["drive_s"] = time.perf_counter() - t
        counts = compile_counter().counts
        memory = dev.memory_stats() or {}
        peak_bytes = int(memory.get("peak_bytes_in_use", 0))
        tr = None
        if trace_dir is not None:
            path = tracemod.find_xplane(trace_dir)
            if keep_trace:
                shutil.copyfile(path, keep_trace)
            t = time.perf_counter()
            tr = tracemod.Trace.from_file(path)
            phases["trace_read_s"] = time.perf_counter() - t
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    gc.collect()

    late = drive.late_ms
    _log(f"window: compiles_in_window={counts.get('window', 0)} "
         f"compiles_in_setup={counts.get('setup', 0)} "
         f"engines={drive.engines} rotations={drive.rotations} "
         f"requests={len(drive.sent)}")
    if late:
        _log("generator lateness ms: "
             f"p50={traffic.nearest_rank(late, 0.5)} "
             f"p99={traffic.nearest_rank(late, 0.99)} max={max(late)}")
    else:
        _log("generator lateness ms: closed loop, each request is due "
             "when the one before it returned")

    checks = check(cell, seed, drive)
    ctx = Context(cell=cell, seconds=seconds, setup_s=drive.setup_s,
                  window=drive.window, sent=drive.sent,
                  t0=drive.t0, t_end=drive.t_end,
                  stats_before=drive.stats_before,
                  stats_after=drive.stats_after,
                  cipher=reference.Cipher(cell.config["cipher"]),
                  peaks=peaks, trace=tr,
                  trace_window_s=drive.trace_window_s,
                  drain_limit=drive.drain_limit)
    metrics = {}
    t = time.perf_counter()
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_reader(m["name"], cell.dirs)(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    phases["readers_s"] = time.perf_counter() - t
    if trace:
        # A declared per-layer metric that finds nothing to read (an event
        # name no longer in the trace, say) fails the run, never vanishes.
        missing = [m["name"] for m in cell.per_layer
                   if m["name"] not in metrics]
        if missing:
            _log(f"per-layer metrics that found nothing to read: {missing}")
        checks["per_layer_missing"] = {"value": len(missing), "limit": 0,
                                       "is": "<="}
    failed = sum(not s.ok for s in drive.sent)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": passes(checks), "attempted": len(drive.sent),
              "failed": failed, "metrics": metrics, "device": device}
    if tr is not None:
        ops = tr.device_ops()
        busy = [tracemod.busy_s(ln) for ln in ops]
        device["busy_s"] = float(np.mean(busy)) if busy else 0.0
        device["window_s"] = drive.trace_window_s
        t = time.perf_counter()
        result["breakdown"] = {"device_ops": tracemod.top_ops(ops),
                               "idle_gaps": tracemod.idle_gaps(tr)}
        phases["breakdown_s"] = time.perf_counter() - t
    _log("phases: " + " ".join(f"{k}={v:.3f}" for k, v in phases.items()))
    for name, c in checks.items():
        _log(f"check {name}: {c['value']} (must be {c['is']} "
             f"{c['limit']})")
    result["checks"] = checks
    return (result, ctx) if with_context else result

