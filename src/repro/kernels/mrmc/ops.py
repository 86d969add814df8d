"""Public jit'd wrapper for the MRMC kernel: row-major (lanes, n) API,
lane padding, layout transform to/from the kernel's lane-major (v, v, BLK)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.params import CipherParams
from repro.kernels.mrmc.mrmc import BLK, mrmc_pallas


@functools.partial(jax.jit, static_argnames=("params", "interpret"))
def mrmc_kernel_apply(params: CipherParams, x, *, interpret: bool):
    """x: (lanes, n) uint32 row-major states -> (lanes, n) MRMC output.

    Branch-aware: a multi-branch state (PASTA, n = branches·v²) applies the
    same per-branch matrix, so branches fold into the kernel's lane axis —
    (lanes, b, v, v) becomes a (v, v, lanes·b) lane-major block and the
    kernel is oblivious to where lanes end and branches begin.
    """
    lanes, n = x.shape
    v, b = params.v, params.branches
    assert n == params.n
    pad = (-lanes) % BLK
    lp = lanes + pad
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    # (lanes_p, n) -> (v, v, lanes_p·b): row-major branch states onto
    # sublanes, (lane, branch) pairs on the vector lane axis
    x_vvl = xp.reshape(lp, b, v, v).transpose(2, 3, 0, 1).reshape(v, v, -1)
    o = mrmc_pallas(params, x_vvl, interpret=interpret)
    out = o.reshape(v, v, lp, b).transpose(2, 3, 0, 1).reshape(lp, n)
    return out[:lanes]
