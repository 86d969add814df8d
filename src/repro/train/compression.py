"""Int8 error-feedback gradient compression for cross-pod reduction.

At multi-pod scale the pod-to-pod links are the scarcest bandwidth; the
standard mitigation is quantized all-reduce with error feedback (the
quantization residual is carried to the next step, so the compression is
unbiased over time).  Implemented with shard_map over the "pod" axis:

    g_local   -> q8(g_local + err)            (int8 + per-row scale)
    q8 psum over pods (int32 accumulate)      (8x fewer bytes on the link)
    g_hat     -> dequant / n_pods
    err'      = (g_local + err) - g_hat_own_contribution

Used by wrapping the gradient tree between backward and the optimizer; the
error buffer lives in the train state.  CPU dry-runs exercise the same
collective graph.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _q8(x):
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def init_error_buffers(grads_like):
    return jax.tree.map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads_like
    )


def compressed_pod_reduce(grads, err, mesh, axis: str = "pod"):
    """All-reduce ``grads`` over ``axis`` in int8 with error feedback.

    grads: pytree of f32, already reduced within a pod (i.e. the natural
    GSPMD output); err: matching error-feedback buffers.
    Returns (reduced_grads, new_err).
    """
    npods = mesh.shape[axis]

    def leaf(g, e):
        def body(gl, el):
            x = gl + el
            q, s = _q8(x)
            qsum = jax.lax.psum(q.astype(jnp.int32), axis)
            ssum = jax.lax.psum(s, axis)  # conservative shared scale
            ghat = qsum.astype(jnp.float32) * (ssum / npods) / npods
            new_e = x - q.astype(jnp.float32) * s
            return ghat, new_e

        spec = P()  # grads replicated across pods at this point
        return shard_map(
            body, mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec),
            check_vma=False,
        )(g, e)

    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = tdef.flatten_up_to(err)
    out = [leaf(g, e) for g, e in zip(flat_g, flat_e)]
    return (jax.tree.unflatten(tdef, [o[0] for o in out]),
            jax.tree.unflatten(tdef, [o[1] for o in out]))
