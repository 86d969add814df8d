"""Unified keystream backend layer: the `KeystreamEngine` registry.

The paper's accelerator is ONE datapath (vectorized modules, decoupled RNG,
FIFO-overlapped rounds); this module makes the reproduction expose it the
same way.  Every consumer that turns (key, round constants[, noise]) into
keystream — the pure-jnp reference, the batched-XLA pipeline, the fused
Pallas kernel in compiled or interpret mode, the shard_map lane-sharded
kernel — is a registered engine with declared capabilities, and *all*
backend policy ("auto" selection, legacy `consumer`/`interpret` flag
spellings, availability checks) lives here and nowhere else.

Registered engines (see `registered_engines()` / `engine_caps()`):

  * ``ref``              — eager pure-jnp round pipeline.  The bit-exactness
                           oracle; always available; no jit.
  * ``jax``              — the same pipeline under `jax.jit` (batched XLA).
                           The CPU/GPU fast path and the "auto" fallback.
  * ``pallas``           — the fused Pallas kernel, compiled.  TPU only.
  * ``pallas-interpret`` — the fused kernel in interpret mode.  Correctness
                           tool (slow!), available everywhere; capped lanes.
  * ``sharded``          — the fused kernel lane-sharded over a mesh data
                           axis via shard_map (multi-device farm path).
                           Needs a mesh.

Usage:

    eng = make_engine("auto", params, key)          # policy decided HERE
    z = eng.keystream_from_constants(rc, noise)     # or eng(constants_dict)

`core/farm.py`, `serve/hhe_loop.py`, `data/encrypted.py`,
`launch/serve.py`, and `benchmarks/keystream_farm_bench.py` all route
keystream materialization through engine instances; `core/cipher.py` binds
a default ``ref`` engine per Cipher/CipherBatch.  docs/DESIGN.md §7
documents the layer.

All engines are bit-exact with ``ref`` by contract (tests/test_engine.py
asserts the full engine × cipher-preset × noise × variant matrix, across
all three cipher kinds — hera / rubato / pasta).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple, Type, Union

import jax
import jax.numpy as jnp

from repro.core.params import CipherParams
from repro.core.redplan import DEFAULT_REDUCTION, REDUCTION_MODES
from repro.core.schedule import VARIANTS, build_schedule
from repro.kernels.keystream.keystream import BLK
from repro.kernels.keystream.ops import (
    keystream_kernel_apply,
    keystream_kernel_sharded,
)
from repro.kernels.keystream.ref import keystream_ref


@dataclasses.dataclass(frozen=True)
class EngineCaps:
    """What one backend can do, queried without instantiating it.

    ``available`` answers "can this engine run on the current JAX backend /
    with the given mesh?"; ``reason`` says why not when it can't.
    ``max_lanes`` is a practical per-call lane bound (None = unbounded) —
    exceeded lanes raise instead of silently running for hours (the
    interpret-mode trap).  ``schedule_variants`` lists which orientation
    plans from `core/schedule.py` the backend can execute, and
    ``preferred_variant`` is what "auto" resolves to — the variant the
    backend runs bubble-free (alternating for the unrolled Pallas datapath,
    normal for XLA executors where an orientation flip is a real transpose).
    """

    name: str
    description: str
    available: bool
    reason: str = ""
    supports_noise: bool = True
    max_lanes: Optional[int] = None
    jitted: bool = True
    schedule_variants: Tuple[str, ...] = VARIANTS
    preferred_variant: str = "normal"


class KeystreamEngine:
    """One way to materialize keystream from (key, constants).

    Subclasses implement `_run(rc, noise)`; the base class owns capability
    validation so every backend enforces the same contract.  Engines are
    bound to (params, key) at construction — the farm's consumer, a
    cipher's default consumer, and the bench's per-engine lap are all just
    instances of these classes.
    """

    name: str = "?"

    def __init__(self, params: CipherParams, key, *, mesh=None,
                 axis: str = "data", interpret: Optional[bool] = None,
                 variant: str = "normal",
                 reduction: str = DEFAULT_REDUCTION):
        self.params = params
        self.key = jnp.asarray(key, jnp.uint32)
        self.mesh = mesh
        self.axis = axis
        # only 'sharded' consults it: compiled unless interpret=True is
        # passed explicitly (CPU tests on a host mesh)
        self.interpret = bool(interpret)
        self.caps = type(self).query_caps(mesh=mesh, axis=axis)
        if variant == "auto":
            variant = self.caps.preferred_variant
        if variant not in self.caps.schedule_variants:
            raise ValueError(
                f"engine {self.name!r} does not support schedule variant "
                f"{variant!r} (supports {self.caps.schedule_variants})"
            )
        self.variant = variant
        if reduction not in REDUCTION_MODES:
            raise ValueError(
                f"unknown reduction mode {reduction!r}; expected one of "
                f"{REDUCTION_MODES}"
            )
        #: reduction-scheduling mode ("lazy" | "eager") — bit-exact either
        #: way (core/redplan.py); engines thread the mode string and the
        #: executors rebuild the cached plan inside their traces
        self.reduction = reduction
        #: the declarative round program this engine executes
        self.schedule = build_schedule(params, variant)

    # -- capability reporting (class-level: no instance needed) ------------
    @classmethod
    def query_caps(cls, *, mesh=None, axis: str = "data") -> EngineCaps:
        raise NotImplementedError

    # -- the consumer ------------------------------------------------------
    def _run(self, rc, noise, mats):
        raise NotImplementedError

    def keystream_from_constants(self, rc, noise=None, mats=None):
        """rc: (lanes, n_round_constants) u32; noise: (lanes, l) i32 | None;
        mats: (lanes, n_matrix_constants) u32 | None — dense matrix planes
        for stream-sourced MRMC schedules (PASTA).  Returns (lanes, l) u32
        keystream — bit-exact across engines."""
        if noise is not None and not self.caps.supports_noise:
            raise ValueError(f"engine {self.name!r} does not support noise")
        if self.caps.max_lanes is not None and rc.shape[0] > self.caps.max_lanes:
            raise ValueError(
                f"engine {self.name!r} caps lanes at {self.caps.max_lanes} "
                f"per call (got {rc.shape[0]}); window the request or pick "
                "an uncapped engine"
            )
        if self.schedule.n_matrix_constants and mats is None:
            raise ValueError(
                f"schedule {self.schedule.name} streams its affine matrices "
                "— pass the producer's mats plane"
            )
        return self._run(rc, noise, mats)

    def __call__(self, constants: dict):
        """Consume a producer's dict(rc=..., noise=..., mats=...) directly."""
        return self.keystream_from_constants(
            constants["rc"], constants.get("noise"), constants.get("mats")
        )

    def __repr__(self):
        return f"<KeystreamEngine {self.name} params={self.params.name}>"


# ==========================================================================
# Registry
# ==========================================================================
_REGISTRY: Dict[str, Type[KeystreamEngine]] = {}


def register_engine(cls: Type[KeystreamEngine]) -> Type[KeystreamEngine]:
    """Class decorator: add an engine to the registry under ``cls.name``."""
    if cls.name in _REGISTRY:
        raise ValueError(f"engine {cls.name!r} already registered")
    _REGISTRY[cls.name] = cls
    return cls


def registered_engines() -> Tuple[str, ...]:
    """Names of all registered engines (available or not), sorted."""
    return tuple(sorted(_REGISTRY))


def engine_caps(*, mesh=None, axis: str = "data") -> Dict[str, EngineCaps]:
    """Capability report for every registered engine."""
    return {
        name: cls.query_caps(mesh=mesh, axis=axis)
        for name, cls in sorted(_REGISTRY.items())
    }


def _tuned_engine(params, mesh, axis: str = "data") -> Optional[str]:
    """Consult the StreamPlan cache for a measured engine choice.

    Lazy import (the tuner sits above this module); returns None — never
    raises — when no params context is given, no plan is cached for this
    (preset, host), or the cached engine is unavailable here.  Looked up
    with lanes=None (engines are lane-agnostic at bind time): the nearest
    tuned lane count for the preset decides.  Lane-exact plan application
    is the ``plan=`` path on the farm/server.
    """
    if params is None:
        return None
    try:
        from repro.core.tuner import load_plan

        plan = load_plan(params, lanes=None, mesh=mesh, axis=axis)
    except Exception:
        return None
    if plan is None or plan.engine not in _REGISTRY:
        return None
    caps = _REGISTRY[plan.engine].query_caps(mesh=mesh, axis=axis)
    return plan.engine if caps.available else None


def resolve_engine(spec: str, *, interpret: Optional[bool] = None,
                   mesh=None, params=None, axis: str = "data") -> str:
    """THE single place backend auto-selection lives.

    ``spec`` is an engine name, "auto", or a legacy farm consumer spelling:

      * "auto"   -> with a ``params`` context, the measured `StreamPlan`
        from the tuner cache (`repro.core.tuner.load_plan`) when one
        exists for this (preset, host); otherwise the static preference —
        the fused kernel on TPU ("sharded" when a mesh is given, else
        "pallas"), "jax" elsewhere;
      * "kernel" -> the fused kernel: "sharded" when a mesh is given,
        "pallas" when compiled Pallas can run (TPU, or interpret
        explicitly False), else "pallas-interpret" — exactly the old
        KeystreamFarm(consumer="kernel", mesh=..., interpret=...)
        behavior;
      * "pallas" with interpret=True -> "pallas-interpret".

    Unknown names raise ValueError listing the registered engines.
    """
    if spec == "auto":
        spec = (_tuned_engine(params, mesh, axis)
                or ("kernel" if jax.default_backend() == "tpu" else "jax"))
    if spec == "kernel":  # legacy farm consumer name
        on_tpu = jax.default_backend() == "tpu"
        if mesh is not None:
            spec = "sharded"
        elif interpret is False or (interpret is None and on_tpu):
            spec = "pallas"
        else:
            spec = "pallas-interpret"
    elif spec == "pallas" and interpret is True:
        spec = "pallas-interpret"
    if spec not in _REGISTRY:
        raise ValueError(
            f"unknown keystream engine {spec!r}; registered engines: "
            f"{list(registered_engines())} (plus 'auto' and the legacy "
            "'kernel' alias)"
        )
    return spec


EngineSpec = Union[str, KeystreamEngine]


def make_engine(spec: EngineSpec, params: CipherParams, key, *, mesh=None,
                axis: str = "data", interpret: Optional[bool] = None,
                variant: Optional[str] = None,
                reduction: Optional[str] = None) -> KeystreamEngine:
    """Resolve ``spec`` and bind it to (params, key).

    ``spec`` may already be a KeystreamEngine instance (passed through —
    the pluggable-consumer path), but only if it is bound to the SAME
    (params, key): a consumer keyed differently from the producer would
    emit keystream no session cipher can match, silently.  Raises
    RuntimeError when the resolved engine is not available here (e.g.
    "pallas" off-TPU), with the backend's own reason and a pointer to the
    registry table (``python -m repro.core.engine``).

    ``variant`` picks the schedule orientation plan ("normal" |
    "alternating" | "auto" = the backend's preferred variant; see
    core/schedule.py) — all variants are bit-exact, so this is purely a
    scheduling choice.  None (the default) means "unspecified": newly
    constructed engines get "normal", and a pre-bound instance is accepted
    with whatever plan it already executes; an *explicit* variant that
    contradicts a pre-bound instance raises instead of being silently
    ignored.

    ``reduction`` picks the reduction-scheduling mode ("lazy" | "eager",
    core/redplan.py) with the same None-means-unspecified semantics —
    newly constructed engines default to "lazy"; an explicit mode that
    contradicts a pre-bound instance raises.  Both modes are bit-exact.
    """
    if isinstance(spec, KeystreamEngine):
        if spec.params != params or not bool(
                (spec.key == jnp.asarray(key, jnp.uint32)).all()):
            raise ValueError(
                f"engine {spec.name!r} is bound to different (params, key) "
                f"(engine has {spec.params.name}); rebind it with "
                "make_engine for this pool"
            )
        if variant is not None and variant != "auto" \
                and variant != spec.variant:
            raise ValueError(
                f"engine {spec.name!r} already executes the "
                f"{spec.variant!r} schedule variant; requested {variant!r} "
                "— rebind with make_engine instead of passing the instance"
            )
        if reduction is not None and reduction != spec.reduction:
            raise ValueError(
                f"engine {spec.name!r} already runs the {spec.reduction!r} "
                f"reduction schedule; requested {reduction!r} — rebind "
                "with make_engine instead of passing the instance"
            )
        return spec
    name = resolve_engine(spec, interpret=interpret, mesh=mesh,
                          params=params, axis=axis)
    cls = _REGISTRY[name]
    caps = cls.query_caps(mesh=mesh, axis=axis)
    if not caps.available:
        raise RuntimeError(
            f"keystream engine {name!r} unavailable here: {caps.reason} "
            "(run `python -m repro.core.engine` for the full registry "
            "table)"
        )
    return cls(params, key, mesh=mesh, axis=axis, interpret=interpret,
               variant=variant if variant is not None else "normal",
               reduction=reduction if reduction is not None
               else DEFAULT_REDUCTION)


# ==========================================================================
# Backends
# ==========================================================================
@register_engine
class RefEngine(KeystreamEngine):
    """Eager pure-jnp round pipeline — the oracle every backend must match."""

    name = "ref"

    @classmethod
    def query_caps(cls, *, mesh=None, axis="data") -> EngineCaps:
        return EngineCaps(
            name=cls.name,
            description="eager pure-jnp reference (bit-exactness oracle)",
            available=True,
            jitted=False,
        )

    def _run(self, rc, noise, mats):
        return keystream_ref(self.params, self.key, rc, noise,
                             variant=self.variant, mats=mats,
                             reduction=self.reduction)


@register_engine
class JaxEngine(KeystreamEngine):
    """The reference pipeline under jax.jit: one fused XLA program."""

    name = "jax"

    def __init__(self, params, key, *, mesh=None, axis="data",
                 interpret=None, variant="normal",
                 reduction=DEFAULT_REDUCTION):
        super().__init__(params, key, mesh=mesh, axis=axis,
                         interpret=interpret, variant=variant,
                         reduction=reduction)
        # params/variant/reduction via partial => static; key/rc/noise
        # traced (noise=None is a valid empty pytree, so one jit covers
        # both arities)
        self._fn = jax.jit(functools.partial(keystream_ref, params,
                                             variant=self.variant,
                                             reduction=self.reduction))

    @classmethod
    def query_caps(cls, *, mesh=None, axis="data") -> EngineCaps:
        return EngineCaps(
            name=cls.name,
            description="batched XLA round pipeline (CPU/GPU fast path)",
            available=True,
        )

    def _run(self, rc, noise, mats):
        return self._fn(self.key, rc, noise, mats=mats)


class _PallasBase(KeystreamEngine):
    _interpret: bool     # fixed per registered engine, never inferred

    def _run(self, rc, noise, mats):
        if noise is not None and not self.params.n_noise:
            noise = None    # kernel's 2-input variant
        return keystream_kernel_apply(
            self.params, self.key, rc, noise, interpret=self._interpret,
            variant=self.variant, mats=mats, reduction=self.reduction,
        )


@register_engine
class PallasEngine(_PallasBase):
    """The fused Pallas kernel, compiled — the paper's datapath on TPU."""

    name = "pallas"
    _interpret = False

    @classmethod
    def query_caps(cls, *, mesh=None, axis="data") -> EngineCaps:
        backend = jax.default_backend()
        ok = backend == "tpu"
        return EngineCaps(
            name=cls.name,
            description="fused Pallas kernel, compiled (TPU)",
            available=ok,
            reason="" if ok else (
                f"compiled Pallas needs a TPU backend (have {backend!r}); "
                "use 'pallas-interpret' for correctness or 'jax' for speed"
            ),
            # the unrolled kernel flips orientation for free (Eq. 2): the
            # paper's bubble-free alternating schedule is its native mode
            preferred_variant="alternating",
        )


@register_engine
class PallasInterpretEngine(_PallasBase):
    """The fused kernel in interpret mode: runs anywhere, slowly.

    A correctness tool, not a fast path — lanes are capped so a stray
    "auto" can never turn a serving window into an hour-long interpret run.
    """

    name = "pallas-interpret"
    _interpret = True
    MAX_LANES = 64 * BLK

    @classmethod
    def query_caps(cls, *, mesh=None, axis="data") -> EngineCaps:
        return EngineCaps(
            name=cls.name,
            description="fused Pallas kernel, interpret mode (slow, "
                        "portable correctness tool)",
            available=True,
            max_lanes=cls.MAX_LANES,
            jitted=False,
            preferred_variant="alternating",
        )


@register_engine
class ShardedEngine(KeystreamEngine):
    """Fused kernel with the lane axis shard_map'd over ``mesh[axis]``.

    Key replicated, constants split, no cross-device traffic.  On a 1-wide
    axis this degrades to the plain kernel apply (same numerics), so the
    only hard requirement is a mesh that names the axis.
    """

    name = "sharded"

    @classmethod
    def query_caps(cls, *, mesh=None, axis="data") -> EngineCaps:
        if mesh is None:
            return EngineCaps(
                name=cls.name,
                description="shard_map lane-sharded fused kernel",
                available=False,
                reason="needs a mesh (pass mesh=/axis= to make_engine)",
                preferred_variant="alternating",
            )
        if axis not in mesh.shape:
            return EngineCaps(
                name=cls.name,
                description="shard_map lane-sharded fused kernel",
                available=False,
                reason=f"mesh has no axis {axis!r} (axes: "
                       f"{tuple(mesh.shape)})",
                preferred_variant="alternating",
            )
        return EngineCaps(
            name=cls.name,
            description=f"shard_map lane-sharded fused kernel "
                        f"({mesh.shape[axis]} device(s) on {axis!r})",
            available=True,
            preferred_variant="alternating",
        )

    def _run(self, rc, noise, mats):
        if noise is not None and not self.params.n_noise:
            noise = None
        return keystream_kernel_sharded(
            self.params, self.key, rc, noise, mesh=self.mesh,
            axis=self.axis, interpret=self.interpret, variant=self.variant,
            mats=mats, reduction=self.reduction,
        )


# ==========================================================================
# Introspection CLI: `python -m repro.core.engine`
# ==========================================================================
def describe(*, mesh=None, axis: str = "data") -> str:
    """The engine registry as a table: one row per backend, with
    availability (and the reason when unavailable), schedule variants,
    lane caps, and the "auto" resolution on this host."""
    caps = engine_caps(mesh=mesh, axis=axis)
    rows = [("engine", "available", "variants (pref)", "max lanes",
             "description / reason")]
    for name, c in caps.items():
        variants = "/".join(c.schedule_variants) + f" ({c.preferred_variant})"
        lanes = str(c.max_lanes) if c.max_lanes is not None else "-"
        detail = c.description if c.available else f"UNAVAILABLE: {c.reason}"
        rows.append((name, "yes" if c.available else "no", variants, lanes,
                     detail))
    widths = [max(len(r[i]) for r in rows) for i in range(4)]
    lines = []
    for i, r in enumerate(rows):
        lines.append("  ".join(r[j].ljust(widths[j]) for j in range(4))
                     + "  " + r[4])
        if i == 0:
            lines.append("  ".join("-" * w for w in widths) + "  " + "-" * 24)
    lines.append("")
    lines.append(f"backend: {jax.default_backend()}   "
                 f"auto resolves to: {resolve_engine('auto')!r}   "
                 "(legacy alias 'kernel' also accepted)")
    return "\n".join(lines)


if __name__ == "__main__":
    print(describe())
