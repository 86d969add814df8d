"""Capacity-based top-k Mixture-of-Experts (GShard-style token choice).

Dispatch is **sort-based**: the (token, slot) -> expert assignments are
flattened (slot-major, so first choices win capacity ties), stably sorted by
expert id, and each assignment's position inside its expert's capacity
buffer is its rank within the sorted run.  Nothing of shape (N, E) is ever
materialized — the working set is O(N·k) indices plus the (E, C, D) expert
buffers, which matters at train_4k scale (N=1M, E=128 would make an (N, E)
cumsum a 537 GB tensor).

Compiled FLOPs stay proportional to *active* experts
(capacity_factor × top_k / E of the dense equivalent), keeping the roofline
useful-ratio honest.  Expert weights shard over the tp axes (sharding.py
`_expert_axes`); arctic runs 8 experts/chip.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.configs.base import ModelConfig


def _wsc(x, shardings, name):
    if shardings is not None and shardings.get(name) is not None:
        return jax.lax.with_sharding_constraint(x, shardings[name])
    return x


# ---------------------------------------------------------------------------
# shard_map expert-parallel path (production)
# ---------------------------------------------------------------------------
def _local_positions(e_local, k, n_loc, E, capacity):
    """Sort-based positions for the local token slice (slot-major priority)."""
    e_flat = e_local.T.reshape(n_loc * k)
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    starts = jnp.searchsorted(e_sorted, jnp.arange(E), side="left")
    rank_sorted = jnp.arange(n_loc * k) - starts[e_sorted]
    pos_flat = jnp.zeros((n_loc * k,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))
    keep_flat = pos_flat < capacity
    return (jnp.where(keep_flat, pos_flat, 0).reshape(k, n_loc),
            keep_flat.reshape(k, n_loc))


def moe_ffn_sharded(cfg: ModelConfig, x, router_w, wi_g, wi_u, wo, policy):
    """Expert-parallel MoE under shard_map (docs/DESIGN.md §5).

    Key observation: activations are dp-sharded and tp-REPLICATED in this
    framework, so every expert owner already holds every local token —
    dispatch needs NO communication.  Each device computes its E_loc experts
    on its data shard's tokens (capacity enforced per (expert, data-shard)),
    and ONE psum over the tp axes both sums expert contributions and
    completes the feature-sharded matmul — exactly the collective a dense
    TP MLP needs.  No GSPMD scatter partitioning involved.

    FSDP: expert weights arrive data-sharded on D and are all-gathered
    in-body (AD turns that into the reduce-scatter of gradients).
    """
    mesh = policy.mesh
    E, k = cfg.num_experts, cfg.top_k
    B, T, D = x.shape
    e_axes, f_axes = policy.expert_axes(cfg)
    e_axes = e_axes or ()
    f_axes = f_axes or ()
    ws = policy.weight_stationary
    dp = policy.dp if not policy.seq_shard_data else ()
    fs = "data" if policy.fsdp else None
    tp_all = tuple(a for a in ("tp_a", "tp_b", "sp") if mesh.shape[a] > 1)
    if ws:
        f_axes = tuple(f_axes) + ("data",)
        psum_axes = tp_all + ("data",)
    else:
        psum_axes = tp_all
    e_loc = E
    for a in e_axes:
        e_loc //= mesh.shape[a]

    from jax.sharding import PartitionSpec as P

    # chunk the expert FFN feature dim when the FSDP-gathered weights would
    # otherwise dominate per-device residency (jamba: 3x0.4 GB per layer)
    f_loc = cfg.d_ff
    for a in f_axes:
        f_loc //= mesh.shape[a]
    n_f_chunks = 1
    while e_loc * D * (f_loc // n_f_chunks) > 2**28 and n_f_chunks < 8:
        n_f_chunks *= 2
    while f_loc % n_f_chunks:
        n_f_chunks //= 2

    def body(xb, rw, wg, wu, wod):
        # xb: (B_loc, T, D); rw: (D/fs, E); w*: (E_loc, D/fs, F_loc)
        n_loc = xb.shape[0] * xb.shape[1]
        xf = xb.reshape(n_loc, D)
        if fs:
            rw = jax.lax.all_gather(rw, fs, axis=0, tiled=True)

        logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32),
                            rw.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gates, eidx = jax.lax.top_k(probs, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

        capacity = max(1, math.ceil(n_loc * k * cfg.capacity_factor / E))
        pos, keep = _local_positions(eidx, k, n_loc, E, capacity)

        # my expert range from the tp coordinates
        lin = jnp.zeros((), jnp.int32)
        for a in e_axes:
            lin = lin * mesh.shape[a] + jax.lax.axis_index(a)
        e0 = lin * e_loc

        xe = jnp.zeros((e_loc, capacity, D), x.dtype)
        for s in range(k):
            e_rel = eidx[:, s] - e0
            mine = keep[s] & (e_rel >= 0) & (e_rel < e_loc)
            contrib = jnp.where(mine[:, None], xf, 0)
            xe = xe.at[jnp.where(mine, e_rel, 0), pos[s]].add(contrib)

        def ffn_chunk(carry, ws):
            wg_c, wu_c, wo_c = ws
            if fs:
                wg_c = jax.lax.all_gather(wg_c, fs, axis=1, tiled=True)
                wu_c = jax.lax.all_gather(wu_c, fs, axis=1, tiled=True)
                wo_c = jax.lax.all_gather(wo_c, fs, axis=2, tiled=True)
            h = jax.nn.silu(
                jnp.einsum("ecd,edf->ecf", xe, wg_c)
            ) * jnp.einsum("ecd,edf->ecf", xe, wu_c)
            return carry + jnp.einsum(
                "ecf,efd->ecd", h, wo_c).astype(jnp.float32), None

        if n_f_chunks > 1:
            split = lambda w, ax: jnp.stack(
                jnp.split(w, n_f_chunks, axis=ax), axis=0)
            ye0 = jnp.zeros((e_loc, capacity, D), jnp.float32)
            xs = (split(wg, 2), split(wu, 2), split(wod, 1))
            if cfg.probe_unroll:
                ye = ye0
                for i in range(n_f_chunks):
                    ye, _ = ffn_chunk(ye, jax.tree.map(lambda a: a[i], xs))
            else:
                ye, _ = jax.lax.scan(jax.checkpoint(ffn_chunk), ye0, xs)
            ye = ye.astype(x.dtype)
        else:
            if fs:
                wg = jax.lax.all_gather(wg, fs, axis=1, tiled=True)
                wu = jax.lax.all_gather(wu, fs, axis=1, tiled=True)
                wod = jax.lax.all_gather(wod, fs, axis=2, tiled=True)
            h = jax.nn.silu(
                jnp.einsum("ecd,edf->ecf", xe, wg)
            ) * jnp.einsum("ecd,edf->ecf", xe, wu)
            ye = jnp.einsum("ecf,efd->ecd", h, wod)  # (E_loc, C, D)

        y = jnp.zeros((n_loc, D), jnp.float32)
        for s in range(k):
            e_rel = eidx[:, s] - e0
            mine = keep[s] & (e_rel >= 0) & (e_rel < e_loc)
            part = ye[jnp.where(mine, e_rel, 0), pos[s]].astype(jnp.float32)
            y = y + part * (gates[:, s] * mine)[:, None]
        if psum_axes:
            y = jax.lax.psum(y, psum_axes)           # experts + F partials

        # load-balance aux (local f/P are unbiased estimates; average over dp)
        f = jnp.zeros((E,), jnp.float32).at[eidx.reshape(-1)].add(1.0) / (
            n_loc * k)
        aux = E * jnp.sum(f * probs.mean(0))
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return y.reshape(xb.shape).astype(x.dtype), aux

    in_specs = (
        P(dp or None, None, None),               # x
        P(fs, None),                             # router
        P(e_axes or None, fs, f_axes or None),   # wi_g
        P(e_axes or None, fs, f_axes or None),   # wi_u
        P(e_axes or None, f_axes or None, fs),   # wo
    )
    out_specs = (P(dp, None, None), P())
    y, aux = shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(x, router_w, wi_g, wi_u, wo)
    return y, aux


def moe_ffn(cfg: ModelConfig, x, router_w, wi_g, wi_u, wo, shardings=None):
    """x: (B, T, D).  router_w: (D, E).  expert weights: (E, D, F)/(E, F, D).

    Returns (y, aux_loss)."""
    B, T, D = x.shape
    E, k = cfg.num_experts, cfg.top_k
    N = B * T
    xf = x.reshape(N, D)

    logits = jnp.einsum(
        "nd,de->ne", xf.astype(jnp.float32), router_w.astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)          # (N, E)
    gates, eidx = jax.lax.top_k(probs, k)            # (N, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    capacity = max(1, math.ceil(N * k * cfg.capacity_factor / E))

    # ---- sort-based positions: slot-major flatten => first choices win ----
    e_flat = eidx.T.reshape(N * k)                   # (k*N,) slot-major
    order = jnp.argsort(e_flat, stable=True)         # tokens grouped by expert
    e_sorted = e_flat[order]
    starts = jnp.searchsorted(e_sorted, jnp.arange(E), side="left")
    rank_sorted = jnp.arange(N * k) - starts[e_sorted]
    pos_flat = jnp.zeros((N * k,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32)
    )
    keep_flat = pos_flat < capacity
    pos_flat = jnp.where(keep_flat, pos_flat, 0)
    pos = pos_flat.reshape(k, N)
    keep = keep_flat.reshape(k, N)
    e_slot = eidx.T                                   # (k, N)

    # ---- dispatch into (E, C, D) buffers ----
    xe = jnp.zeros((E, capacity, D), x.dtype)
    for s in range(k):
        contrib = jnp.where(keep[s][:, None], xf, 0)
        xe = xe.at[e_slot[s], pos[s]].add(contrib)
    xe = _wsc(xe, shardings, "moe_xe")

    # ---- expert FFN (SwiGLU), dense per-expert batches ----
    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", xe, wi_g)
    ) * jnp.einsum("ecd,edf->ecf", xe, wi_u)
    h = _wsc(h, shardings, "moe_h")
    ye = jnp.einsum("ecf,efd->ecd", h, wo)           # (E, C, D)
    ye = _wsc(ye, shardings, "moe_xe")

    # ---- combine ----
    y = jnp.zeros((N, D), jnp.float32)
    for s in range(k):
        part = ye[e_slot[s], pos[s]].astype(jnp.float32)
        w = (gates[:, s] * keep[s])[:, None]
        y = y + part * w

    # ---- load-balance aux loss (Switch): E * sum_e f_e * P_e ----
    f = jnp.zeros((E,), jnp.float32).at[e_flat].add(1.0) / (N * k)
    p_mean = probs.mean(0)
    aux = E * jnp.sum(f * p_mean)

    return y.reshape(B, T, D).astype(x.dtype), aux
