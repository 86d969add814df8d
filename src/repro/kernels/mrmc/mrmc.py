"""Pallas kernel: fused MixRows∘MixColumns (MRMC) = M_v · X · M_vᵀ mod q.

The paper's T2+T4 in kernel form:

  * T2 (transposition-invariance / bubble elimination): MixColumns and
    MixRows execute back-to-back on a VMEM-resident state — there is no
    transpose materialization, relayout, or HBM round-trip between them
    (the FPGA design's "bubble" maps to exactly those on TPU).
  * T4 (shift-add): M_v entries ∈ {1,2,3}, so every "multiplication" is an
    add chain with branchless conditional-subtract reduction — the kernel
    contains no integer multiply at all.

Layout: lane-major — state block is (v, v, BLK) uint32 with the keystream
lane on the 128-wide vector lane axis, state rows/cols unrolled on sublanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.params import CipherParams
from repro.crypto.modmath import Modulus

BLK = 128  # keystream lanes per grid step (one full vector-lane width)


def _scale_small(mod: Modulus, x, c: int, in_bound: int | None = None,
                 reduce_out: bool = True):
    """c·x mod q for c ∈ {0..3} as adds + conditional subtract (no multiply).

    ``reduce_out=False`` keeps the raw add chain (< c·in_bound) for a lazy
    accumulator; ``in_bound`` (default q) is the operand's exclusive bound.
    """
    b = mod.q if in_bound is None else in_bound
    if c == 0:
        return jnp.zeros_like(x)
    acc = x
    for _ in range(c - 1):
        acc = acc + x
    return mod.reduce(acc, c * b) if reduce_out else acc


def _combine(mod: Modulus, terms, bounds=None):
    """Sum of terms with interleaved reduction and ONE terminal reduce.

    ``bounds`` gives each term's exclusive static bound (default: already
    reduced, < q each — the eager policy; the reduction plan's lazy
    policy passes the raw c·in_bound term bounds instead)."""
    acc, bound = None, 0
    for i, t in enumerate(terms):
        tb = mod.q if bounds is None else bounds[i]
        if acc is None:
            acc, bound = t, tb
        else:
            if bound + tb >= 2**32:
                acc = mod.reduce(acc, bound)
                bound = mod.q
            acc = acc + t
            bound += tb
    return mod.reduce(acc, bound)


def mrmc_matrix_apply(mod: Modulus, mat: np.ndarray, x,
                      transpose_out: bool = False,
                      in_bound: int | None = None, lazy: bool = False):
    """Apply M·X·Mᵀ to x of shape (v, v, ...) — shared by this kernel and
    the fused keystream kernel (state stays wherever it lives; VMEM here).

    ``transpose_out=True`` emits (M·X·Mᵀ)ᵀ instead — the schedule IR's
    orientation flip (core/schedule.py).  Because the state dims are fully
    unrolled, the flip is a static relabeling of the output stacking axis:
    zero extra compute, no relayout — the TPU form of the paper's Eq. 2
    bubble elimination (MRMC commutes with transposition, so either
    orientation runs the identical shift-add datapath).

    ``lazy=True`` is the reduction plan's lazy-accumulate policy
    (core/redplan.py): shift-add terms stay raw and each row fires one
    terminal reduce, with MixColumns accepting operands up to
    ``in_bound`` (MixRows always sees the reduced MixColumns output).
    Same policy, hence same proof, as `Modulus.matvec_small(lazy=True)`.
    """
    v = mat.shape[0]
    if lazy:
        ib = mod.q if in_bound is None else in_bound
        a = [
            _combine(mod,
                     [_scale_small(mod, x[j], int(mat[i, j]), in_bound=ib,
                                   reduce_out=False) for j in range(v)],
                     bounds=[int(mat[i, j]) * ib for j in range(v)])
            for i in range(v)
        ]
        a = jnp.stack(a, axis=0)  # (v, v, ...), reduced
        y = [
            _combine(mod,
                     [_scale_small(mod, a[:, j], int(mat[c, j]),
                                   reduce_out=False) for j in range(v)],
                     bounds=[int(mat[c, j]) * mod.q for j in range(v)])
            for c in range(v)
        ]
        return jnp.stack(y, axis=0 if transpose_out else 1)
    # MixColumns: a[i] = Σ_j M[i,j] · x[j]   (x[j] is state row j: (v, ...))
    a = [
        _combine(mod, [_scale_small(mod, x[j], int(mat[i, j])) for j in range(v)])
        for i in range(v)
    ]
    a = jnp.stack(a, axis=0)  # (v, v, ...)
    # MixRows: y[:, c] = Σ_j M[c,j] · a[:, j]
    y = [
        _combine(mod, [_scale_small(mod, a[:, j], int(mat[c, j])) for j in range(v)])
        for c in range(v)
    ]
    # y[c] is the c-th *column* of M·X·Mᵀ: stacking on axis 1 lays columns
    # out as columns (normal); axis 0 lays them out as rows (transposed)
    return jnp.stack(y, axis=0 if transpose_out else 1)


def mrmc_dense_apply(mod: Modulus, m_ttl, x_tl,
                     x_bound: int | None = None, lazy: bool = False):
    """Per-lane dense matvec: y[i, lane] = Σ_j M[i, j, lane]·x[j, lane] mod q.

    The stream-sourced MRMC datapath (PASTA's per-block random affine
    matrices, docs/DESIGN.md §8.7): each keystream lane carries its own
    (t, t) matrix, delivered through the constants FIFO in storage order
    (`Schedule.mat_storage_perm`), so unlike the circulant path there is
    no shared host matrix and the multiplies are full modmuls.

    m_ttl: (t, t, lanes) uint32 matrix plane, entries < q;
    x_tl:  (t, lanes) uint32 state, entries < q.  Returns (t, lanes).

    Accumulation mirrors `Modulus.matvec_dense` (the lane-minor sibling):
    products < q sum raw (uint32 bits, see `_sum_u32`) in
    `Modulus.dense_chunk_schedule` chunks (a reshape, one fused sum per
    level) with one reduce per chunk, then one raw fold of the reduced
    partials — the ONE shared overflow policy
    `Modulus.dense_accumulate_sites` proves safe.
    ``lazy=True`` is the reduction plan's lazy-dense policy: each
    product's final reduce is deferred (raw values < 3q) and the chunk
    width shrinks to match; ``x_bound`` relaxes the state-operand
    contract through the limb multiply.  Output is reduced either way.
    """
    t = x_tl.shape[0]
    if lazy:
        prods = mod.mul(m_ttl, x_tl[None, :, :], y_bound=x_bound,
                        reduce_out=False)             # (t, t, lanes), < 3q
        pb = 3 * mod.q
    else:
        prods = mod.mul(m_ttl, x_tl[None, :, :])      # (t, t, lanes), < q
        pb = mod.q
    ch, nch = mod.dense_chunk_schedule(t, pb)
    lanes = prods.shape[-1]
    s = _sum_u32(prods.reshape(t, nch, ch, lanes), axis=2)  # (t, nch, lanes)
    s = mod.reduce(s, ch * pb)                        # each < q
    if nch == 1:
        return s[:, 0]
    return mod.reduce(_sum_u32(s, axis=1), nch * mod.q)


def _sum_u32(x, axis: int):
    """uint32 sum along ``axis`` computed on the int32 view.

    Mosaic (the TPU kernel compiler) implements no unsigned reductions.
    Two's-complement addition wraps exactly like uint32 addition, so the
    bits equal the uint32 sum; the overflow proof
    (`Modulus.dense_accumulate_sites`) keeps that sum below 2^32 anyway.
    """
    s = jnp.sum(jax.lax.bitcast_convert_type(x, jnp.int32), axis=axis,
                dtype=jnp.int32)
    return jax.lax.bitcast_convert_type(s, jnp.uint32)


def _mrmc_kernel(mat: np.ndarray, q: int, x_ref, o_ref):
    mod = Modulus(q)
    o_ref[...] = mrmc_matrix_apply(mod, mat, x_ref[...])


def mrmc_pallas(params: CipherParams, x_vvl, *, interpret: bool):
    """x_vvl: (v, v, lanes) uint32, lanes % BLK == 0.  Returns same shape."""
    v = params.v
    lanes = x_vvl.shape[-1]
    if lanes % BLK != 0:
        raise ValueError(
            f"mrmc_pallas needs lanes % {BLK} == 0 (got {lanes}); use "
            "mrmc_kernel_apply, which pads and trims ragged lane counts"
        )
    grid = (lanes // BLK,)
    kernel = functools.partial(_mrmc_kernel, params.mix_matrix(), params.mod.q)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((v, v, BLK), lambda i: (0, 0, i))],
        out_specs=pl.BlockSpec((v, v, BLK), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((v, v, lanes), jnp.uint32),
        interpret=interpret,
    )(x_vvl)
