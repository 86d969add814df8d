"""Multi-stream keystream farm: depth-configurable producer→consumer windows.

The paper's T3 ("RNG decoupling") separates the XOF/sampler *producer* from
the round-pipeline *consumer* so the two overlap.  The fused Pallas kernel
already does this at kernel level (BlockSpec double buffering, DMA of block
i+1's constants during block i's rounds).  This module lifts the same
structure to system level for the ROADMAP's many-concurrent-sessions
target:

  * a *window* is a fixed-size batch of lanes, each lane an arbitrary
    (session, block-counter) pair from a :class:`repro.core.cipher.
    CipherBatch` pool — one key, many nonces;
  * :class:`KeystreamFarm` runs a window schedule with a configurable
    pipeline ``depth`` (the paper's FIFO-depth knob lifted to window
    granularity): producers for up to ``depth-1`` windows ahead are
    *dispatched* (async on TPU) before the consumer of window i runs, so
    XOF/sampling for upcoming windows hides behind the current window's
    round computation.  depth=2 is classic double buffering (the
    default); depth=1 serializes producer and consumer (the D1 baseline
    shape); deeper FIFOs absorb producer-latency jitter;
  * the *producer* is the pool's pluggable :class:`repro.core.producer.
    ConstantsProducer` (aes / threefry / cached — see that registry), and
    the *consumer* is a pluggable :class:`repro.core.engine.
    KeystreamEngine` — any registered backend or a pre-bound instance;
    "auto" and the legacy `consumer="kernel"` spelling resolve in
    `repro.core.engine`, the one place backend policy lives;
  * the whole (producer, engine, variant, window, depth) tuple can be
    applied at once from a measured :class:`repro.core.tuner.StreamPlan`
    (``plan=``), the autotuner's unit of selection.

Fixed window sizes keep every producer/consumer call shape-stable —
:func:`pack_windows` pads ragged tails by repeating the last real lane
(outputs trimmed on yield), so the farm compiles exactly two XLA programs
regardless of how many sessions, windows, or stragglers it serves.
`serve/hhe_loop.py` packs ragged request traffic into these windows;
`data/encrypted.py` streams training batches through them.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Iterable, Iterator, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.cipher import CipherBatch, decode_fixed, encode_fixed
from repro.core.engine import EngineSpec


@dataclasses.dataclass
class WindowPlan:
    """One farm step: parallel per-lane (session, counter) arrays.

    ``valid`` counts the real lanes; lanes past it are padding (repeats of
    the last real lane — recomputed keystream, discarded on trim, never
    fresh counters).  Defaults to all lanes.
    """

    session_ids: np.ndarray   # (lanes,) int32
    block_ctrs: np.ndarray    # (lanes,) uint32
    meta: Any = None          # opaque caller tag (e.g. request slices)
    valid: Optional[int] = None

    def __post_init__(self):
        self.session_ids = np.asarray(self.session_ids, np.int32).reshape(-1)
        self.block_ctrs = np.asarray(self.block_ctrs, np.uint32).reshape(-1)
        if self.session_ids.shape != self.block_ctrs.shape:
            raise ValueError("session_ids / block_ctrs length mismatch")
        if self.valid is None:
            self.valid = self.session_ids.shape[0]
        if not 0 < self.valid <= self.session_ids.shape[0]:
            raise ValueError(
                f"valid={self.valid} out of range for "
                f"{self.session_ids.shape[0]} lanes")

    @property
    def lanes(self) -> int:
        return self.session_ids.shape[0]


def pack_windows(session_ids, block_ctrs, window: int) -> List[WindowPlan]:
    """THE window slicer: per-lane arrays -> fixed-size `WindowPlan`s.

    Every window has exactly ``window`` lanes: a non-dividing tail is
    padded by repeating its last real lane (the pad+trim idiom
    `keystream_pallas` uses for ragged lanes), with ``plan.valid`` marking
    where the real lanes end — so ragged totals never force a fresh XLA
    compile for a one-off tail shape.  All slicing-into-windows in the
    farm, the serving loop, and the tuner goes through here, so the
    padding rule lives in exactly one place.
    """
    if window <= 0:
        raise ValueError("window must be positive")
    sids = np.asarray(session_ids).reshape(-1)
    ctrs = np.asarray(block_ctrs).reshape(-1)
    if sids.shape != ctrs.shape:
        raise ValueError("session_ids / block_ctrs length mismatch")
    plans = []
    for i in range(0, sids.shape[0], window):
        s, c = sids[i : i + window], ctrs[i : i + window]
        valid = s.shape[0]
        if valid < window:                      # ragged tail: pad + mark
            pad = window - valid
            s = np.concatenate([s, np.full(pad, s[-1], s.dtype)])
            c = np.concatenate([c, np.full(pad, c[-1], c.dtype)])
        plans.append(WindowPlan(s, c, valid=valid))
    return plans


def plan_windows(sessions, blocks_per_session: int, window: int,
                 interleave: bool = True) -> List[WindowPlan]:
    """Reserve ``blocks_per_session`` counters on each session and pack the
    resulting lanes into fixed-size windows.

    interleave=True round-robins sessions across lanes (many short streams
    per window — the serving traffic shape); False keeps each session's
    lanes contiguous (bulk re-keying shape).  A non-dividing total is
    padded to the window size (`pack_windows`), so every window is
    shape-stable; ``plan.valid`` marks the real lanes of the tail.
    """
    pairs = []
    for s in sessions:
        ctrs = s.take_window(blocks_per_session)
        pairs.append(np.stack(
            [np.full(blocks_per_session, s.index, np.int64), ctrs]))
    stacked = np.stack(pairs)                     # (S, 2, B)
    if interleave:
        flat = stacked.transpose(2, 0, 1).reshape(-1, 2)   # ctr-major
    else:
        flat = stacked.transpose(0, 2, 1).reshape(-1, 2)   # session-major
    return pack_windows(flat[:, 0], flat[:, 1], window)


class KeystreamFarm:
    """Depth-configurable producer→consumer pipeline over a CipherBatch pool.

    ``engine`` selects the consumer backend: any name registered in
    `repro.core.engine` ("ref", "jax", "pallas", "pallas-interpret",
    "sharded"), "auto", or an already-bound :class:`KeystreamEngine`
    instance (the pluggable-consumer path).  ``consumer`` is the legacy
    spelling of the same argument and still accepts "kernel" (+ the
    ``interpret`` flag); both resolve through
    :func:`repro.core.engine.resolve_engine`, so unknown names raise a
    ValueError listing the registered engines.  ``variant`` picks the
    schedule-orientation plan the consumer executes (core/schedule.py;
    "auto" = the backend's preferred one; bit-exact either way).

    ``depth`` sets the producer→consumer FIFO depth: producers for up to
    ``depth-1`` windows ahead are dispatched before each consume (2 =
    double buffering, the default; 1 = serialized).  The producer itself
    is the pool's pluggable `repro.core.producer` backend.

    ``matrix_depth`` is the matrix-plane prefetch depth for stream-
    sourced-MRMC presets (PASTA, whose dense affine matrices are a ~t×
    heavier RNG load than the rc plane): with ``matrix_depth >= 2``,
    matrix-plane-only production for up to ``matrix_depth`` windows ahead
    is dispatched through a second FIFO, *independent* of the
    vector-plane/consumer pipeline, so the heavy plane's XOF + rejection
    sampling hides behind more round computation than ``depth`` alone
    buys.  matrix_depth=1 (the default) keeps the single fused produce;
    presets without matrix planes ignore the knob entirely.  Bit-exact at
    every depth (tests/test_farm.py).

    ``plan`` applies a measured :class:`repro.core.tuner.StreamPlan` in
    one shot — producer (rebound on the pool), engine, variant, depth,
    matrix_depth, and reduction mode — with any explicitly-passed
    argument taking precedence.
    """

    def __init__(self, batch: CipherBatch, engine: Optional[EngineSpec] = None,
                 *, consumer: Optional[str] = None, mesh=None,
                 axis: str = "data", interpret: Optional[bool] = None,
                 variant: Optional[str] = None, depth: Optional[int] = None,
                 matrix_depth: Optional[int] = None,
                 reduction: Optional[str] = None, plan=None):
        if engine is not None and consumer is not None:
            raise ValueError("pass engine= or the legacy consumer=, not both")
        self.plan = plan
        self.window: Optional[int] = None
        if plan is not None:
            if engine is None and consumer is None:
                engine = plan.engine
            if variant is None:
                variant = plan.variant
            if depth is None:
                depth = plan.depth
            if matrix_depth is None:
                matrix_depth = getattr(plan, "matrix_depth", 1)
            if reduction is None:
                reduction = getattr(plan, "reduction", None)
            self.window = plan.window
            batch.set_producer(plan.producer)
        spec = consumer if engine is None else engine
        if spec is None:
            spec = "auto"
        depth = 2 if depth is None else int(depth)
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1 (got {depth})")
        self.depth = depth
        matrix_depth = 1 if matrix_depth is None else int(matrix_depth)
        if matrix_depth < 1:
            raise ValueError(
                f"matrix prefetch depth must be >= 1 (got {matrix_depth})")
        self.matrix_depth = matrix_depth
        self.batch = batch
        self.engine = batch.make_engine(spec, mesh=mesh, axis=axis,
                                        interpret=interpret, variant=variant,
                                        reduction=reduction)
        self.consumer = self.engine.name     # backwards-compatible attr
        self.mesh = mesh
        self.axis = axis

    @property
    def _splits_planes(self) -> bool:
        """Whether run() splits vector/matrix plane production: only for
        stream-sourced-MRMC presets with prefetch actually requested."""
        return (self.matrix_depth > 1
                and self.batch.params.n_matrix_constants > 0)

    # ------------------------------------------------------------------
    def produce(self, plan: WindowPlan, plane: str = "all"):
        """Dispatch the (async) producer for one window — the pool's
        pluggable `ConstantsProducer` (memoizing backends short-circuit
        repeated windows here).  ``plane`` narrows the payload ("vector"
        when the matrix FIFO produces matrices separately).  Traced as
        the span ``farm.produce``."""
        with TraceAnnotation("farm.produce", plane=plane):
            return self.batch.producer.produce(
                self.batch.xof_tables(), plan.session_ids, plan.block_ctrs,
                plane)

    def produce_matrix(self, plan: WindowPlan):
        """Dispatch matrix-plane-only production for one window (the
        prefetch-ahead FIFO's producer half)."""
        with TraceAnnotation("farm.produce", plane="matrix"):
            return self.batch.producer.produce(
                self.batch.xof_tables(), plan.session_ids, plan.block_ctrs,
                "matrix")

    def consume(self, constants):
        """Run the engine consumer on produced constants (the span
        ``farm.consume``)."""
        with TraceAnnotation("farm.consume"):
            return self.engine(constants)

    # ------------------------------------------------------------------
    def pipeline(self) -> "FarmPipeline":
        """A stateful push/drain view of the producer→consumer FIFO.

        :meth:`run` is this object driven by an iterable; event-driven
        callers (`serve/hhe_loop.py`'s scheduler) hold one long-lived
        pipeline instead and push windows as traffic fires them, so the
        FIFO overlap spans scheduling events, not just one flush call.
        """
        return FarmPipeline(self)

    def run(self, plans: Iterable[WindowPlan]
            ) -> Iterator[Tuple[WindowPlan, jnp.ndarray]]:
        """Yield (plan, keystream) per window, pipeline-depth buffered.

        Producers for up to ``self.depth - 1`` windows ahead are
        dispatched *before* window i's consumer runs — on an async
        backend the XOF/sampling of upcoming windows overlaps the current
        round computation (the paper's T3 FIFO, its depth now a knob,
        lifted to window granularity).  depth=1 degenerates to the
        serialized D1 shape.

        For stream-sourced-MRMC presets with ``matrix_depth >= 2`` the
        matrix plane runs through its own prefetch FIFO: matrix-plane
        production is dispatched up to ``matrix_depth`` windows ahead,
        decoupled from the vector-plane/consumer pipeline, and the two
        planes are merged at consume time.  Lane order and keystream bits
        are identical either way.  Implemented over :meth:`pipeline`, the
        incremental form the event-driven serving scheduler drives.
        """
        pipe = self.pipeline()
        for plan in plans:
            yield from pipe.push(plan)
        yield from pipe.drain()

    def run_one(self, plan: WindowPlan) -> jnp.ndarray:
        """Serialized single-window convenience: produce + consume now."""
        return self.consume(self.produce(plan))

    def keystream(self, session_ids, block_ctrs, window: Optional[int] = None):
        """Convenience: full keystream for per-lane pairs, windowed.

        window=None uses the plan's window when one was applied, else runs
        everything as a single window.  Ragged totals are padded to the
        window size and trimmed on return (`pack_windows`), so every
        dispatch is shape-stable.  Returns (lanes, l) uint32, lane order
        preserved.
        """
        sid = np.asarray(session_ids, np.int64).reshape(-1)
        ctr = np.asarray(block_ctrs, np.int64).reshape(-1)
        if window is None:
            window = self.window or sid.shape[0]
        plans = pack_windows(sid, ctr, window)
        outs = [z[: p.valid] for p, z in self.run(plans)]
        return jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]

    # ------------------------------------------------------------------
    def _payload_stream(self, plans_and_payloads):
        """Split (plan, payload) pairs lazily: feed plans to run(), FIFO the
        payloads alongside.  run() reads at most depth-1 plans ahead, so
        the queue never holds more than ``depth`` payloads — the stream
        stays a stream."""
        payloads: deque = deque()

        def plans():
            for plan, payload in plans_and_payloads:
                payloads.append(payload)
                yield plan

        for plan, z in self.run(plans()):
            yield plan, payloads.popleft(), z

    def encrypt_stream(self, plans_and_msgs, delta: float = 1024.0):
        """Streaming encrypt: iterable of (WindowPlan, (lanes, l) float)
        -> yields (plan, ciphertext).  Keystream pipelined as in run().
        """
        mod = self.batch.params.mod
        for plan, m, z in self._payload_stream(plans_and_msgs):
            yield plan, mod.add(encode_fixed(mod, m, delta), z)

    def decrypt_stream(self, plans_and_cts, delta: float = 1024.0):
        """Streaming decrypt: iterable of (WindowPlan, (lanes, l) u32)
        -> yields (plan, plaintext float32)."""
        mod = self.batch.params.mod
        for plan, ct, z in self._payload_stream(plans_and_cts):
            yield plan, decode_fixed(mod, mod.sub(jnp.asarray(ct), z), delta)


class FarmPipeline:
    """Incremental (push-driven) form of :meth:`KeystreamFarm.run`.

    ``push(plan)`` dispatches the window's producer(s) immediately and
    returns any (plan, keystream) pairs whose consumers fired as the FIFO
    reached its depth; ``drain()`` finishes everything in flight.  Driving
    push over an iterable and then draining reproduces ``run()``'s
    dispatch order *exactly* — same producer/consumer interleaving, same
    bits — which tests/test_serve.py pins.  The point of the split: an
    event-driven caller (the serving scheduler) can keep ONE pipeline
    alive across scheduling events, so windows fired by different submit
    wake-ups still overlap producer-vs-consumer like a batch flush would.

    For stream-sourced-MRMC presets with ``matrix_depth >= 2`` the heavy
    matrix plane runs through its own prefetch FIFO ahead of the
    vector/consumer FIFO (the paper's FIFO decoupling applied to the ~t×
    heavier plane); planes merge at consume time.
    """

    def __init__(self, farm: KeystreamFarm):
        self.farm = farm
        self._fifo: deque = deque()     # (plan, in-flight constants[, mats])
        self._mfifo: deque = deque()    # (plan, in-flight matrix plane)

    def in_flight(self) -> int:
        """Windows dispatched (producer running) but not yet consumed."""
        return len(self._fifo) + len(self._mfifo)

    def _promote(self) -> None:
        """Move the oldest matrix-FIFO window into the vector/consumer
        FIFO, dispatching its vector-plane producer."""
        plan, mats = self._mfifo.popleft()
        self._fifo.append((plan, self.farm.produce(plan, "vector"), mats))

    def _consume_one(self):
        entry = self._fifo.popleft()
        if len(entry) == 3:
            plan, consts, mats = entry
            merged = dict(consts)
            merged["mats"] = mats["mats"]
            return plan, self.farm.consume(merged)
        plan, consts = entry
        return plan, self.farm.consume(consts)

    def push(self, plan: WindowPlan) -> List[Tuple[WindowPlan, jnp.ndarray]]:
        out: List[Tuple[WindowPlan, jnp.ndarray]] = []
        if self.farm._splits_planes:
            self._mfifo.append((plan, self.farm.produce_matrix(plan)))
            if len(self._mfifo) >= self.farm.matrix_depth:
                self._promote()
        else:
            self._fifo.append((plan, self.farm.produce(plan)))
        while len(self._fifo) >= self.farm.depth:
            out.append(self._consume_one())
        return out

    def drain(self) -> List[Tuple[WindowPlan, jnp.ndarray]]:
        out: List[Tuple[WindowPlan, jnp.ndarray]] = []
        while self._mfifo:
            self._promote()
            while len(self._fifo) >= self.farm.depth:
                out.append(self._consume_one())
        while self._fifo:
            out.append(self._consume_one())
        return out
