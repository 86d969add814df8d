"""Public jit'd wrappers for the fused keystream kernel.

`keystream_kernel_apply` — kernel consumer with explicit constants (matches
ref.py signature).  `keystream_kernel_sharded` — the same consumer with its
lane axis sharded over a mesh data axis via shard_map (the farm's
multi-device path: each device runs the fused kernel on its lane slice, key
replicated, no cross-device traffic).  `presto_keystream` — the full D3
pipeline: pure-JAX XOF producer (decoupled RNG) feeding the fused Pallas
consumer.

These wrappers are the *mechanism*; backend *policy* (which consumer runs
where, interpret-or-compiled, lane sharding) lives in one place:
`repro.core.engine`.  Callers that want a consumer should go through an
engine instance rather than passing interpret flags around.
"""

from __future__ import annotations

import functools
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.params import CipherParams
from repro.core.redplan import DEFAULT_REDUCTION
from repro.core.schedule import build_schedule
from repro.kernels.keystream.keystream import keystream_pallas

if TYPE_CHECKING:  # annotation only — core.engine imports this module
    from repro.core.cipher import Cipher


@functools.partial(jax.jit, static_argnames=("params", "interpret", "variant",
                                             "reduction"))
def keystream_kernel_apply(params: CipherParams, key, rc, noise=None, *,
                           interpret: bool, variant: str = "normal",
                           mats=None, reduction: str = DEFAULT_REDUCTION):
    """key: (n,) u32; rc: (lanes, n_round_constants) u32; noise: (lanes, l)
    int32 or None; mats: (lanes, n_matrix_constants) u32 or None (dense
    matrix planes for stream-sourced MRMC schedules).  Returns (lanes, l)
    u32 keystream blocks.

    ``variant`` selects the schedule orientation plan ("normal" |
    "alternating", see core/schedule.py) — bit-exact either way.
    ``reduction`` selects the reduction-scheduling mode ("lazy" | "eager",
    core/redplan.py) — also bit-exact; it is a static jit argument, so the
    plan is rebuilt (cached) inside the trace.  Ragged lane counts are
    padded/trimmed inside :func:`keystream_pallas`.
    """
    sched = build_schedule(params, variant)
    rc_p = rc.T                                       # (n_consts, lanes)
    noise_p = None
    if noise is not None and params.n_noise:
        noise_p = noise.T                             # (l, lanes)
    mats_p = None
    if mats is not None and sched.n_matrix_constants:
        mats_p = mats.T                               # (n_mat, lanes)
    out = keystream_pallas(
        params, key[:, None], rc_p, noise_p, interpret=interpret,
        schedule=sched, mats_ml=mats_p, reduction=reduction,
    )
    return out.T


def keystream_kernel_sharded(params: CipherParams, key, rc, noise=None, *,
                             mesh=None, axis: str = "data",
                             interpret: bool, variant: str = "normal",
                             mats=None, reduction: str = DEFAULT_REDUCTION):
    """Lane-sharded fused consumer: rc/noise/mats split over ``mesh[axis]``.

    Same signature/semantics as :func:`keystream_kernel_apply`; lanes are
    padded to a multiple of the axis size, each device runs the fused kernel
    on its slice (key replicated), and the padding is stripped on the way
    out.  With no mesh (or a 1-wide axis) this is the plain kernel apply.
    """
    if mesh is None or mesh.shape.get(axis, 1) == 1:
        return keystream_kernel_apply(params, key, rc, noise,
                                      interpret=interpret, variant=variant,
                                      mats=mats, reduction=reduction)
    ndev = mesh.shape[axis]
    lanes = rc.shape[0]
    pad = (-lanes) % ndev
    rc_p = jnp.pad(rc, ((0, pad), (0, 0)))
    args = [key, rc_p]
    in_specs = [P(), P(axis, None)]
    with_noise = noise is not None and params.n_noise
    if with_noise:
        args.append(jnp.pad(noise, ((0, pad), (0, 0))))
        in_specs.append(P(axis, None))
    with_mats = mats is not None and params.n_matrix_constants
    if with_mats:
        args.append(jnp.pad(mats, ((0, pad), (0, 0))))
        in_specs.append(P(axis, None))

    def shard_fn(key_s, rc_s, *extra):
        extra = list(extra)
        noise_s = extra.pop(0) if with_noise else None
        mats_s = extra.pop(0) if with_mats else None
        return keystream_kernel_apply(
            params, key_s, rc_s, noise_s,
            interpret=interpret, variant=variant, mats=mats_s,
            reduction=reduction,
        )

    out = shard_map(
        shard_fn, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=P(axis, None), check_vma=False,
    )(*args)
    return out[:lanes]


def presto_keystream(cipher: Cipher, block_ctrs, *, interpret: bool):
    """Full accelerator pipeline: XOF producer -> fused kernel consumer.

    Backend selection is engine-routed: ``interpret`` picks between the
    registered "pallas" and "pallas-interpret" engines.
    """
    from repro.core.engine import make_engine  # runtime: engine imports us

    eng = make_engine("pallas-interpret" if interpret else "pallas",
                      cipher.params, cipher.key)
    consts = cipher.round_constant_stream(block_ctrs)
    return eng.keystream_from_constants(consts["rc"], consts["noise"],
                                        consts.get("mats"))
