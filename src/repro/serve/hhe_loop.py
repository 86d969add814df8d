"""HHE request loop: event-driven window scheduling over the keystream farm.

The serving shape the ROADMAP targets: many concurrent client sessions
(HHEML-style batched PPML traffic), each submitting encrypt/decrypt/
keystream requests of arbitrary block counts.  The server holds ONE
symmetric key (the enclave role from `data/encrypted.py`) and a
:class:`repro.core.cipher.CipherBatch` session pool; requests are packed
lane-by-lane into fixed-size windows and run through the depth-buffered
:class:`repro.core.farm.KeystreamFarm` pipeline — so an 11-block request
from session A and a 3-block request from session B share one jit'd
dispatch, and the XOF producer for the next window overlaps the current
window's round computation.

Scheduling is EVENT-DRIVEN (PR 10's refactor away from the pull-based
`_flush_queue`): ``submit`` wakes the batcher, and a window fires the
moment the lane buffer fills (``fire_on_fill``) or when the oldest queued
lane crosses the ``deadline_s`` age bound (:meth:`HHEServer.service`, the
timer edge the async front end in `serve/server.py` drives).  Fired
windows flow through ONE long-lived :class:`repro.core.farm.FarmPipeline`,
so producer/consumer overlap spans scheduling events — two windows fired
by different submit wake-ups still double-buffer against each other.
``flush()`` remains the synchronous drain for in-process callers
(launch/serve.py) and returns responses in submission order; the window
packing (and therefore the served bytes) is identical to the old
whole-queue flush because both carve lanes through `core/farm.
pack_windows`' padding rule at the same boundaries.

Admission control: ``max_pending_lanes`` bounds the un-materialized lane
backlog (buffered + in-flight).  Over the bound, policy "reject" raises
:class:`HHEServerSaturated` (the client sees an error and can retry) and
"shed" drops the request before reserving counters (counted, invisible
to the farm).  Queue-depth, shed/reject, and fire-cause counters ride in
:meth:`latency_stats`, which now always returns a fully-populated dict —
a server that served zero windows reports zeroed percentiles instead of
raising.

Read-back: a window's keystream is read back only once more work is
queued on the device behind it.  Each window the pipeline hands back
starts its device-to-host copy at once and waits in a ready queue; the
oldest is materialized only while that queue holds more than
``depth - 1`` windows.  At the default depth 2, window k-1 is read after
producer k+1 and consumer k are dispatched, so the chip runs producer k+1
through the worker's host steps (fetch, scatter, respond, the next carve
and dispatch).  Every drain edge (deadline, flush, rotation, quiesce)
dispatches what is left in the pipeline and then reads every ready
window in order; depth 1 reads each window as soon as it is pushed.
``_respond``'s modular arithmetic runs on the host CPU device, so a
deferred read never queues small device ops behind a producer run.

Latency accounting: a request completes when the window holding its last
lane is materialized; `latency_stats` reports p50/p99 over completed
requests.

Tracing: each scheduler step is marked for ``jax.profiler`` (``hhe.submit``,
``hhe.fire``, ``hhe.carve``, ``hhe.materialize`` with its
``hhe.device_wait``/``hhe.fetch``/``hhe.scatter`` steps, and
``hhe.respond``), tagged with the request's ``seq`` or the window's serial
``window``; ``hhe.materialize`` also carries ``behind``, the windows whose
producer was dispatched after this window's consumer: the device work
queued behind the read (``overlapped_reads`` in `latency_stats` counts
the reads with ``behind >= 1``).  They land in a profiler trace on the
device trace's clock and cost about a microsecond each when no profiler
is running.  A step that does host work of bounded size is one
``TraceAnnotation`` span; a step that can wait on the device is two
instant marks (:func:`marked`), since one span would last as long as the
wait.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
from collections import deque
from typing import Deque, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.cipher import (
    CipherBatch,
    StreamSession,
    decode_fixed,
    encode_fixed,
)
from repro.core.farm import KeystreamFarm, WindowPlan, pack_windows

OPS = ("keystream", "encrypt", "decrypt", "encrypt_tokens", "decrypt_tokens")

#: admission-control policies when the pending-lane bound is hit
OVERLOAD_POLICIES = ("reject", "shed")


@functools.lru_cache(maxsize=None)
def _host_device():
    """The host CPU device ``_respond`` computes on, or None (JAX's default
    device) where JAX runs without its CPU backend."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


@functools.partial(jax.jit, static_argnames=("mod", "op"))
def _finish(mod, op: str, z, payload, delta):
    """A completed request's result from its keystream ``z``: one program
    per op and shape.  ``delta`` is traced, so its division stays one."""
    if op == "encrypt":
        return mod.add(encode_fixed(mod, payload, delta), z)
    if op == "encrypt_tokens":                  # exact Z_q, no encoding
        return mod.add(payload, z)
    mq = mod.sub(payload, z)
    if op == "decrypt_tokens":
        return mq.astype(jnp.int32)
    return decode_fixed(mod, mq, delta)         # decrypt


@contextlib.contextmanager
def marked(name: str, **stats):
    """Trace a step that can wait on the device as two instant profiler
    marks: ``name``, carrying ``stats``, where it starts, and
    ``name + ".end"`` where it ends.  A span would last through the wait,
    tens of milliseconds, and a trace analysis that matches device gaps
    to host events scans as far back as the longest host event."""
    with TraceAnnotation(name, **stats):
        pass
    try:
        yield
    finally:
        with TraceAnnotation(name + ".end"):
            pass


class HHEServerSaturated(RuntimeError):
    """Raised by submit() under the "reject" overload policy: the pending
    window queue is at its configured bound.  Clients should back off and
    retry; nothing was reserved (no counters consumed)."""


@dataclasses.dataclass
class HHERequest:
    """One client request: ``blocks`` keystream blocks on one session.

    op="encrypt":  payload (blocks, l) float32 -> ciphertext (blocks, l) u32.
    op="decrypt":  payload (blocks, l) uint32  -> plaintext (blocks, l) f32.
    op="keystream": no payload -> raw keystream (the transciphering feed).
    op="encrypt_tokens": payload (blocks, l) int token ids (< q) ->
        ciphertext (blocks, l) u32 — exact Z_q encryption, no fixed-point
        encoding (the `launch/serve.py --encrypted` prompt/response path).
    op="decrypt_tokens": payload (blocks, l) u32 -> token ids (blocks, l)
        int32, exact.
    """

    session_id: int
    op: str = "keystream"
    payload: Optional[np.ndarray] = None
    blocks: Optional[int] = None
    delta: float = 1024.0

    def __post_init__(self):
        if self.op not in OPS:
            raise ValueError(f"unknown op {self.op!r}; have {OPS}")
        if self.payload is not None:
            self.payload = np.asarray(self.payload)
            if self.blocks is None:
                self.blocks = self.payload.shape[0]
            if self.payload.shape[0] != self.blocks:
                raise ValueError("payload rows != blocks")
        if self.blocks is None or self.blocks <= 0:
            raise ValueError("request needs blocks > 0 (or a payload)")


@dataclasses.dataclass
class HHEResponse:
    request: HHERequest
    result: np.ndarray        # per-op result, (blocks, l)
    block_ctrs: np.ndarray    # counters consumed (client needs these)
    latency_s: float
    seq: int = 0              # submission sequence (flush() sorts on it)


@dataclasses.dataclass
class _Entry:
    """Book-keeping for one submitted request until its last lane lands."""

    seq: int
    req: HHERequest
    ctrs: np.ndarray
    t_submit: float
    rows: np.ndarray          # (blocks, l) u32, filled window by window
    remaining: int
    # sessions can rotate while a request is queued on the OLD nonce; the
    # response must report the nonce its counters were reserved under
    nonce: bytes = b""
    generation: int = 0


class HHEServer:
    """Single-key HHE endpoint: session pool + event-driven window scheduler.

    ``engine`` picks the farm's consumer backend (any registered
    `repro.core.engine` name or instance); ``consumer``/``interpret`` are
    the legacy spellings; ``depth`` sets the farm's producer→consumer FIFO
    depth, and with it the read-back rule: a window is read back only while
    more than ``depth - 1`` consumed windows wait to be read, so at depth 2
    the next window's producer is queued on the device during every read
    outside a drain, and depth 1 reads each window as it is pushed.
    ``plan`` applies a measured :class:`repro.core.tuner.
    StreamPlan` in one shot — producer, engine, variant, depth, and (when
    ``window`` is not given) window size.  With ``auto_rotate`` (default),
    a session whose counter space cannot fit an incoming request is
    rotated to a fresh nonce (pending lanes on the old nonce materialize
    first), so long-running streams survive counter exhaustion without
    keystream reuse; clients observe rotations via
    ``StreamSession.generation`` and the session's current nonce.

    Scheduler knobs (all optional — defaults reproduce the classic
    submit-then-flush shape):

    * ``fire_on_fill`` (default True): a full window dispatches inside the
      submit that filled it, through the persistent farm pipeline.
    * ``deadline_s``: age bound on the oldest un-materialized lane; when
      it trips, :meth:`service` fires the part-full window (padded via
      `pack_windows`) and drains the pipeline, so tail requests are never
      parked behind an un-filled window.  None = no deadline (drain via
      ``flush``).
    * ``max_pending_lanes`` + ``overload``: admission control — over the
      bound, "reject" raises :class:`HHEServerSaturated`, "shed" drops
      the request (counted in ``latency_stats()["shed"]``) before any
      counters are reserved.
    """

    DEFAULT_WINDOW = 256

    def __init__(self, batch: CipherBatch, window: Optional[int] = None,
                 engine=None, *, consumer: Optional[str] = None, mesh=None,
                 axis: str = "data", interpret: Optional[bool] = None,
                 variant: Optional[str] = None, depth: Optional[int] = None,
                 plan=None, auto_rotate: bool = True,
                 fire_on_fill: bool = True,
                 deadline_s: Optional[float] = None,
                 max_pending_lanes: Optional[int] = None,
                 overload: str = "reject"):
        if window is None:
            window = plan.window if plan is not None else self.DEFAULT_WINDOW
        if window <= 0:
            raise ValueError("window must be positive")
        if overload not in OVERLOAD_POLICIES:
            raise ValueError(
                f"unknown overload policy {overload!r}; "
                f"have {OVERLOAD_POLICIES}")
        if max_pending_lanes is not None and max_pending_lanes < window:
            raise ValueError(
                f"max_pending_lanes={max_pending_lanes} below one window "
                f"({window}): no request could ever complete")
        if deadline_s is not None and deadline_s < 0:
            raise ValueError("deadline_s must be >= 0")
        self.batch = batch
        self.window = window
        self.auto_rotate = auto_rotate
        self.fire_on_fill = fire_on_fill
        self.deadline_s = deadline_s
        self.max_pending_lanes = max_pending_lanes
        self.overload = overload
        self.farm = KeystreamFarm(batch, engine=engine, consumer=consumer,
                                  mesh=mesh, axis=axis, interpret=interpret,
                                  variant=variant, depth=depth, plan=plan)
        # ONE long-lived pipeline: windows fired by different scheduling
        # events still overlap producer-vs-consumer across the FIFO
        self._pipe = self.farm.pipeline()
        # undispatched lanes: [entry, ctrs int64 array, consumed offset]
        self._frags: Deque[list] = deque()
        self._buffered = 0                # lanes in _frags
        self._inflight = 0                # valid lanes dispatched, unmaterialized
        self._pending_windows: Deque[WindowPlan] = deque()
        # consumed windows not yet read back: (plan, keystream, windows
        # pushed when its consumer was dispatched); they stay in
        # _pending_windows and _inflight until _materialize
        self._ready: Deque[tuple] = deque()
        self._completed: List[HHEResponse] = []
        self._seq = 0
        self.latencies: List[float] = []
        self.windows_served = 0
        self.fill_fires = 0
        self.deadline_fires = 0
        self.shed_count = 0
        self.rejected_count = 0
        self.overlapped_reads = 0
        # submit may run on the event-loop thread while service/flush run
        # in an executor (serve/server.py) — one reentrant lock serializes
        # every scheduler mutation
        self._lock = threading.RLock()

    # ------------------------------------------------------------------
    def open_session(self, nonce=None) -> StreamSession:
        return self.batch.add_session(nonce)

    def pending_lanes(self) -> int:
        """Lanes submitted but not yet materialized (buffered + in-flight)."""
        return self._buffered + self._inflight

    def busy(self) -> bool:
        """Whether eviction/teardown would lose work: lanes pending or
        completed responses not yet collected."""
        with self._lock:
            return self.pending_lanes() > 0 or bool(self._completed)

    def warmup(self):
        """Compile the window-size programs before taking traffic (one dummy
        window re-deriving session 0's counter 0 — consumes no counters).
        Compiles against the CURRENT session-pool size; growing the pool
        afterwards retraces the producer on its next dispatch."""
        if not self.batch.sessions:
            raise RuntimeError("open a session before warmup")
        plan = WindowPlan(np.zeros(self.window, np.int64),
                          np.zeros(self.window, np.int64))
        jax.block_until_ready(self.farm.run_one(plan))

    # ------------------------------------------------------------------
    def submit(self, req: HHERequest) -> Optional[np.ndarray]:
        """Admit + queue a request; counters are reserved immediately (the
        client learns them synchronously and can pre-share them).  Returns
        the reserved counters, or None when the request was shed.  If the
        request fills one or more windows and ``fire_on_fill`` is set,
        they dispatch before submit returns — the submit IS the wake-up
        event."""
        with self._lock:
            entry = self.submit_entry(req)
            return None if entry is None else entry.ctrs

    def submit_entry(self, req: HHERequest) -> Optional[_Entry]:
        """submit(), but returns the internal entry (the async front end
        correlates responses by ``entry.seq``)."""
        with self._lock, marked("hhe.submit", seq=self._seq,
                                blocks=req.blocks, op=req.op):
            if not 0 <= req.session_id < len(self.batch.sessions):
                raise KeyError(
                    f"unknown session {req.session_id} (pool has "
                    f"{len(self.batch.sessions)}; open_session() first)"
                )
            # admission control BEFORE any counter reservation: a shed or
            # rejected request must leave no trace in the counter space
            if (self.max_pending_lanes is not None
                    and self.pending_lanes() + req.blocks
                    > self.max_pending_lanes):
                if self.overload == "shed":
                    self.shed_count += 1
                    return None
                self.rejected_count += 1
                raise HHEServerSaturated(
                    f"pending lanes {self.pending_lanes()} + {req.blocks} "
                    f"exceed max_pending_lanes={self.max_pending_lanes}; "
                    "back off and retry")
            sess = self.batch.sessions[req.session_id]
            # fresh-session space, via the cursor so a monkeypatched
            # SESSION_CTR_LIMIT (tests) is honored
            capacity = sess.next_ctr + sess.remaining()
            # Auto-rotation is only sound for server-originated keystream:
            # decrypt payloads are bound to the OLD (nonce, counter) space,
            # so rotating would subtract fresh-nonce keystream and return
            # garbage — for those, fall through and let take_window refuse
            # loudly.
            if (self.auto_rotate and req.blocks > sess.remaining()
                    and req.op not in ("decrypt", "decrypt_tokens")
                    and req.blocks <= capacity):
                # old-nonce lanes must materialize before the table row is
                # replaced — rotation is a materialization boundary; the
                # forced responses surface via flush()/pop_completed()
                self._fire_full("rotate")
                self._fire_partial("rotate")
                self._drain()
                sess = self.batch.rotate_session(req.session_id)
            ctrs = sess.take_window(req.blocks)
            entry = _Entry(
                seq=self._seq, req=req, ctrs=ctrs,
                t_submit=time.perf_counter(),
                rows=np.empty((req.blocks, self.batch.params.l), np.uint32),
                remaining=req.blocks,
                nonce=bytes(sess.nonce), generation=sess.generation,
            )
            self._seq += 1
            self._frags.append([entry, ctrs.astype(np.int64), 0])
            self._buffered += req.blocks
            if self.fire_on_fill:
                self._fire_full()
            return entry

    # ------------------------------------------------------------------
    # window carving and firing
    # ------------------------------------------------------------------
    def _carve(self, count: int) -> WindowPlan:
        """Pop ``count`` buffered lanes into one WindowPlan (padded via
        pack_windows when part-full), tagging per-lane owners in meta."""
        sids = np.empty(count, np.int64)
        ctrs = np.empty(count, np.int64)
        owners = []
        filled = 0
        while filled < count:
            frag = self._frags[0]
            entry, ectrs, off = frag
            take = min(count - filled, ectrs.shape[0] - off)
            sids[filled:filled + take] = entry.req.session_id
            ctrs[filled:filled + take] = ectrs[off:off + take]
            owners.extend((entry, off + j) for j in range(take))
            filled += take
            if off + take == ectrs.shape[0]:
                self._frags.popleft()
            else:
                frag[2] = off + take
        self._buffered -= count
        (plan,) = pack_windows(sids, ctrs, self.window)
        plan.meta = owners
        return plan

    def _push(self, plan: WindowPlan) -> None:
        self._inflight += plan.valid
        self._pending_windows.append(plan)
        self._queue_reads(self._pipe.push(plan))
        while len(self._ready) > self.farm.depth - 1:
            self._materialize(*self._ready.popleft())

    def _queue_reads(self, consumed) -> None:
        """Start each consumed window's copy to the host and queue its read."""
        for plan, z in consumed:
            z.copy_to_host_async()
            self._ready.append((plan, z, self._pushed()))

    def _pushed(self) -> int:
        """Windows pushed to the pipeline so far."""
        return self.windows_served + len(self._pending_windows)

    def _fire(self, cause: str, count: int) -> None:
        """Carve ``count`` buffered lanes into a window and push it; the
        window's serial is the count of windows pushed before it."""
        with marked("hhe.fire", cause=cause, valid=count,
                    window=self._pushed()):
            with TraceAnnotation("hhe.carve", lanes=count):
                plan = self._carve(count)
            self._push(plan)

    def _fire_full(self, cause: str = "fill") -> int:
        """Dispatch every FULL buffered window (the fill event)."""
        fired = 0
        while self._buffered >= self.window:
            self._fire(cause, self.window)
            self.fill_fires += 1
            fired += 1
        return fired

    def _fire_partial(self, cause: str) -> bool:
        """Dispatch the part-full tail window, padded (deadline/flush/
        rotation edges).  No-ops when nothing is buffered — the empty-
        window dispatch the old pull loop could make is structurally
        impossible here."""
        if not self._buffered:
            return False
        self._fire(cause, self._buffered)
        return True

    def _drain(self) -> None:
        self._queue_reads(self._pipe.drain())
        while self._ready:
            self._materialize(*self._ready.popleft())

    def _materialize(self, plan: WindowPlan, z, pushed: int) -> None:
        behind = self._pushed() - pushed
        self.overlapped_reads += behind > 0
        with marked("hhe.materialize", window=self.windows_served,
                    valid=plan.valid, behind=behind):
            with marked("hhe.device_wait"):
                jax.block_until_ready(z)
            with TraceAnnotation("hhe.fetch"):
                z = np.asarray(z)
            t_now = time.perf_counter()
            self._pending_windows.popleft()
            self._inflight -= plan.valid
            self.windows_served += 1
            done = []
            with TraceAnnotation("hhe.scatter", lanes=plan.valid):
                for j in range(plan.valid):
                    entry, row = plan.meta[j]
                    entry.rows[row] = z[j]
                    entry.remaining -= 1
                    if entry.remaining == 0:
                        done.append(entry)
            for entry in done:
                with marked("hhe.respond", seq=entry.seq, op=entry.req.op,
                            blocks=entry.req.blocks):
                    self._completed.append(self._respond(entry, t_now))

    def _respond(self, entry: _Entry, t_done: float) -> HHEResponse:
        req = entry.req
        if req.op == "keystream":
            result = entry.rows
        else:
            dtype = np.float32 if req.op == "encrypt" else np.uint32
            # on the host: a device op here would queue behind the
            # producer run dispatched ahead of this read
            with jax.default_device(_host_device()):
                result = np.asarray(_finish(
                    self.batch.params.mod, req.op, entry.rows,
                    np.asarray(req.payload, dtype), req.delta))
        lat = t_done - entry.t_submit
        self.latencies.append(lat)
        return HHEResponse(request=req, result=result,
                           block_ctrs=entry.ctrs, latency_s=lat,
                           seq=entry.seq)

    # ------------------------------------------------------------------
    # scheduler edges
    # ------------------------------------------------------------------
    def _oldest_pending_t(self) -> Optional[float]:
        if self._pending_windows:
            return self._pending_windows[0].meta[0][0].t_submit
        if self._frags:
            return self._frags[0][0].t_submit
        return None

    def next_due(self) -> Optional[float]:
        """perf_counter() time the deadline edge next trips, or None."""
        with self._lock:
            if self.deadline_s is None:
                return None
            t = self._oldest_pending_t()
            return None if t is None else t + self.deadline_s

    def service(self, now: Optional[float] = None) -> List[HHEResponse]:
        """The timer edge: fire any full windows (for schedulers running
        with ``fire_on_fill=False``), then — if the oldest un-materialized
        lane is older than ``deadline_s`` — fire the part-full window and
        drain the pipeline so everything pending lands.  Returns newly
        completed responses (submission-ordered)."""
        with self._lock:
            self._fire_full()
            if self.deadline_s is not None:
                t = self._oldest_pending_t()
                now = time.perf_counter() if now is None else now
                if t is not None and now - t >= self.deadline_s:
                    self._fire_partial("deadline")
                    self._drain()
                    self.deadline_fires += 1
            return self.pop_completed()

    def flush(self) -> List[HHEResponse]:
        """Force everything pending through the farm; returns responses in
        submission order (including any materialized early by fill or
        deadline fires).  Short-circuits the window dispatch when no lanes
        are pending — a drained server never runs an empty window."""
        with self._lock:
            self.quiesce()
            return self.pop_completed()

    def quiesce(self) -> None:
        """Materialize everything pending WITHOUT collecting responses —
        they stay queued for the next pop_completed()/flush().  The
        rotation/eviction boundary for callers (serve/tenants.py) that
        don't own response delivery."""
        with self._lock:
            if self._buffered:
                self._fire_full("flush")
                self._fire_partial("flush")
            self._drain()

    def pop_completed(self) -> List[HHEResponse]:
        """Collect responses completed since the last collection, in
        submission order."""
        with self._lock:
            out, self._completed = self._completed, []
            out.sort(key=lambda r: r.seq)
            return out

    # ------------------------------------------------------------------
    def latency_stats(self) -> dict:
        """Always fully populated — zeroed percentiles before any window
        has served (the empty-percentile crash is gone), plus scheduler/
        admission counters."""
        with self._lock:
            stats = {"count": 0, "p50_ms": 0.0, "p99_ms": 0.0,
                     "mean_ms": 0.0}
            if self.latencies:
                lat = np.asarray(self.latencies)
                stats = {
                    "count": int(lat.size),
                    "p50_ms": float(np.percentile(lat, 50) * 1e3),
                    "p99_ms": float(np.percentile(lat, 99) * 1e3),
                    "mean_ms": float(lat.mean() * 1e3),
                }
            stats.update(
                queue_depth_lanes=self._buffered,
                inflight_lanes=self._inflight,
                windows_served=self.windows_served,
                fill_fires=self.fill_fires,
                deadline_fires=self.deadline_fires,
                shed=self.shed_count,
                rejected=self.rejected_count,
                overlapped_reads=self.overlapped_reads,
            )
            return stats
