"""Public jit'd wrapper for the AES-CTR Pallas kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.aes.aes import BLK, aes_ctr_pallas


@functools.partial(jax.jit, static_argnames=("interpret",))
def aes_ctr_kernel_apply(round_keys, nonce12, counters, *,
                         interpret: bool):
    """round_keys: (11,16) u8/u32; nonce12: (12,) u8/u32; counters: (lanes,)
    u32.  Returns (lanes, 16) uint8 keystream blocks."""
    rk = jnp.asarray(round_keys, jnp.uint32)[..., None]      # (11,16,1)
    nonce = jnp.asarray(nonce12, jnp.uint32)[:, None]        # (12,1)
    counters = jnp.asarray(counters, jnp.uint32)
    lanes = counters.shape[0]
    pad = (-lanes) % BLK
    c = jnp.pad(counters, (0, pad))[None, :]                 # (1, lanes_p)
    out = aes_ctr_pallas(rk, nonce, c, interpret=interpret)  # (16, lanes_p)
    return out.T[:lanes].astype(jnp.uint8)
