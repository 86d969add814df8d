"""Pallas kernel: fully fused HERA/Rubato/PASTA stream-key generation.

This is the accelerator itself (paper §IV), re-architected for TPU — the
T1–T4 technique mapping below is documented in docs/DESIGN.md §3:

  * T1 (vectorization + function overlapping) → the *entire* r-round cipher
    is one kernel; the state lives in VMEM/vregs from initial ARK to final
    output.  Between "functional modules" (ARK, MRMC, Cube/Feistel) there is
    no HBM traffic at all — the strongest possible form of the paper's
    module-overlap: on TPU, modules are fused ops on register-resident data.
  * T2 (MRMC transposition-invariance) → MixColumns/MixRows execute as one
    algebraic unit M_v·X·M_vᵀ with no transpose materialization or relayout
    (see kernels/mrmc/mrmc.py, shared implementation).
  * T3 (RNG decoupling) → round constants are an *input* streamed through
    `BlockSpec` grid pipelining.  Pallas double-buffers input blocks: while
    block i computes, block i+1's constants are DMA'd HBM→VMEM — the FIFO
    between the AES producer and the round consumer, depth 2, in hardware.
  * T4 (shift-add) → no integer multiply in the linear layers; the modular
    multiplies that remain (key schedule, Cube/Feistel) use the 14-bit limb
    scheme, uint32 only.

The kernel body is a *schedule interpreter*: it executes the declarative
round program from `core/schedule.py` — the same `build_schedule(params)`
ops the pure-JAX reference interprets — so there is ONE code path for all
three ciphers (HERA, Rubato, PASTA) and any future scheme is a schedule,
not a new kernel.  PASTA exercises the IR's generalizations: key-initial
state (the key column broadcast across lanes replaces the iota ic), the
affine MRMC (per-branch matrix + additive storage-order constants + the
two-branch mix), and per-branch Feistel.  Orientation handling (the
paper's alternating MixColumns/MixRows order, Eq. 2):

  * a transposed-orientation MRMC is the identical shift-add datapath with
    the output stacking relabeled (`mrmc_matrix_apply(transpose_out=...)`)
    — no relayout, the TPU bubble elimination;
  * transposed ARKs read constants the wrapper pre-permuted into storage
    order (`Schedule.rc_storage_perm`) — the RNG FIFO delivers constants in
    exactly the order the datapath consumes them — and a second, permuted
    key column rides along in the (n, 2) key input;
  * transposed Feistel is a static row/column shift of the (v, v, BLK)
    view (logical neighbors sit one sublane-row up).

Layout: lane-major (state dim on sublanes, keystream lanes on vector lanes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import redplan as RP
from repro.core import schedule as S
from repro.core.params import CipherParams
from repro.core.schedule import Schedule, build_schedule, state_transpose_perm
from repro.crypto.modmath import Modulus
from repro.kernels.mrmc.mrmc import mrmc_dense_apply, mrmc_matrix_apply

BLK = 128  # keystream lanes per grid step


def _feistel(mod: Modulus, x):
    """y_1 = x_1; y_i = x_i + x_{i-1}^2 — on (n, BLK) lane-major state."""
    sq = mod.mul(x[:-1], x[:-1])
    shifted = jnp.concatenate([jnp.zeros_like(x[:1]), sq], axis=0)
    return mod.add(x, shifted)


def _feistel_transposed(mod: Modulus, v: int, x):
    """Feistel on transposed-stored (n, BLK) state via static shifts of the
    (v, v, BLK) view: stored row c*v+r holds logical element r*v+c, so the
    logical predecessor is one view-row up, wrapping to (v-1, r-1)."""
    sq = mod.mul(x, x).reshape(v, v, -1)          # axes (c, r, lane)
    row0 = jnp.concatenate(
        [jnp.zeros_like(sq[:1, :1]), sq[v - 1:, : v - 1]], axis=1
    )
    shifted = jnp.concatenate([row0, sq[: v - 1]], axis=0).reshape(x.shape)
    return mod.add(x, shifted)


def _keystream_kernel(params: CipherParams, sched: Schedule, plan,
                      with_noise: bool, with_mats: bool, *refs):
    """One grid step: interpret the schedule program on a (n, BLK) block.

    ``plan`` is the `core.redplan.ReductionPlan` for this program — the
    kernel honors the same per-op reduce deferrals the pure-JAX
    interpreter does (bit-exact either way; only the conditional-subtract
    placement moves)."""
    refs = list(refs)
    key_ref, rc_ref = refs[:2]
    o_ref = refs[-1]
    extra = refs[2:-1]
    noise_ref = extra.pop(0) if with_noise else None
    mats_ref = extra.pop(0) if with_mats else None

    p = params
    mod = p.mod
    mat = p.mix_matrix()
    n, v = p.n, p.v
    nb = sched.branches
    t = n // nb

    key2 = key_ref[...]         # (n, 2): col 0 normal, col 1 transposed
    rc = rc_ref[...]            # (n_round_constants, BLK), STORAGE order
    if sched.init == "key":
        # PASTA: the keyed permutation — the key column IS the state
        x = jnp.broadcast_to(key2[:, :1], (n, rc.shape[-1]))
    else:
        # ic = (1, ..., n) built in-kernel (n < q, so no reduction needed);
        # programs always start in normal orientation
        x = jax.lax.broadcasted_iota(
            jnp.uint32, (n, rc.shape[-1]), 0
        ) + jnp.uint32(1)

    for oi, op in enumerate(sched.ops):
        p_i = plan.ops[oi]
        if isinstance(op, S.ARK):
            a, b = op.rc_slice
            col = 1 if op.orientation == S.TRANSPOSED else 0
            k = key2[:, col : col + 1][: op.key_len]
            m_ = mod.mul(k, rc[a:b])
            # defer-out: the raw sum (< in_bound + q) flows into the next
            # MRMC's lazy shift-add accumulator
            x = x + m_ if p_i.has(RP.DEFER_OUT) else mod.add(x, m_)
        elif isinstance(op, S.MRMC):
            if op.streams_matrix:
                # dense per-lane matrix plane, delivered storage-permuted
                # (`mat_storage_perm`): stored-state in -> stored-state out,
                # so there is no flip handling here at all
                # (per-branch ref slices: the whole plane is never loaded)
                ma, _ = op.mat_slice
                lazy_d = p_i.has(RP.LAZY_DENSE)
                x = jnp.concatenate([
                    mrmc_dense_apply(
                        mod,
                        mats_ref[ma + i * t * t : ma + (i + 1) * t * t, :]
                        .reshape(t, t, -1),
                        x[i * t : (i + 1) * t],
                        x_bound=p_i.in_bound if lazy_d else None,
                        lazy=lazy_d,
                    )
                    for i in range(nb)
                ], axis=0)
            else:
                flip = op.orientation != op.out_orientation
                lazy_a = p_i.has(RP.LAZY_ACCUMULATE)
                x = jnp.concatenate([
                    mrmc_matrix_apply(
                        mod, mat, x[i * t : (i + 1) * t].reshape(v, v, -1),
                        transpose_out=flip, in_bound=p_i.in_bound,
                        lazy=lazy_a,
                    ).reshape(t, -1)
                    for i in range(nb)
                ], axis=0) if nb > 1 else mrmc_matrix_apply(
                    mod, mat, x.reshape(v, v, -1), transpose_out=flip,
                    in_bound=p_i.in_bound, lazy=lazy_a,
                ).reshape(n, -1)
            fold = p_i.has(RP.FOLD_MIX)
            if op.has_rc:
                a, b = op.rc_slice
                # storage order: already oriented; fold-mix keeps the sum
                # raw (< 2q) and defers into the mix's terminal reduce
                x = x + rc[a:b] if fold else mod.add(x, rc[a:b])
            if op.mix_branches:
                L, R_ = x[:t], x[t:]
                if fold:
                    mix_in = mod.q * (2 if op.has_rc else 1)
                    s = L + R_                      # < 2·mix_in
                    x = mod.reduce(
                        jnp.concatenate([s + L, s + R_], axis=0),
                        3 * mix_in)                 # ONE terminal reduce
                else:
                    s = mod.add(L, R_)  # (2L + R, L + 2R) = (s + L, s + R)
                    x = jnp.concatenate([mod.add(s, L), mod.add(s, R_)],
                                        axis=0)
        elif isinstance(op, S.NONLINEAR):
            if op.kind == "cube":
                x = mod.cube(x)
            elif op.orientation == S.TRANSPOSED:
                x = jnp.concatenate([
                    _feistel_transposed(mod, v, x[i * t : (i + 1) * t])
                    for i in range(nb)
                ], axis=0)
            else:
                x = jnp.concatenate([
                    _feistel(mod, x[i * t : (i + 1) * t]) for i in range(nb)
                ], axis=0)
        elif isinstance(op, S.TRUNCATE):
            x = x[: op.keep]
        elif isinstance(op, S.AGN) and noise_ref is not None:
            # the signed->canonical fold already lands in [0, q) (|e| < q),
            # so the one bounded add is the only reduce this path needs
            e = noise_ref[...]
            x = mod.add(x, jnp.where(
                e < 0, e + jnp.int32(mod.q), e).astype(jnp.uint32))
    o_ref[...] = x


def keystream_pallas(params: CipherParams, key_n1, rc_cl, noise_ll=None, *,
                     interpret: bool, schedule: Schedule | None = None,
                     mats_ml=None, reduction: str = RP.DEFAULT_REDUCTION,
                     plan=None):
    """key_n1: (n, 1) u32; rc_cl: (n_consts, lanes) u32 in logical order;
    noise_ll: (l, lanes) int32 or None; mats_ml: (n_matrix_constants,
    lanes) u32 or None — dense matrix planes in logical order for
    stream-sourced MRMC schedules (PASTA).  Returns (l, lanes) u32
    keystream (lane-major).

    Ragged lane counts are padded up to a BLK multiple and trimmed on the
    way out, so any farm window size compiles (the pad lanes compute junk
    keystream that is discarded).  ``schedule`` defaults to the normal
    variant of ``build_schedule(params)``.  ``reduction`` picks the
    reduction-scheduling mode ("lazy"/"eager", core/redplan.py; bit-exact
    either way); an explicit ``plan`` overrides it and is validated
    against the terminal-reduction law first.
    """
    p = params
    if schedule is None:
        schedule = build_schedule(p)
    if plan is None:
        plan = RP.plan_reductions(p, schedule, reduction)
    plan.validate(schedule)
    n_mat = schedule.n_matrix_constants
    if n_mat and (mats_ml is None or mats_ml.shape[0] != n_mat):
        got = None if mats_ml is None else mats_ml.shape[0]
        raise ValueError(
            f"schedule {schedule.name} streams its affine matrices: "
            f"mats_ml first dim {got} != {n_mat}"
        )
    lanes = rc_cl.shape[-1]
    pad = (-lanes) % BLK
    if pad:
        rc_cl = jnp.pad(rc_cl, ((0, 0), (0, pad)))
        if noise_ll is not None:
            noise_ll = jnp.pad(noise_ll, ((0, 0), (0, pad)))
        if n_mat:
            mats_ml = jnp.pad(mats_ml, ((0, 0), (0, pad)))
    padded = lanes + pad
    nc = p.n_round_constants

    # deliver constants in storage order (transposed ARK slices pre-permuted
    # — the RNG-FIFO ordering the datapath consumes) and both key
    # orientations; static gathers on tiny host-visible arrays, outside the
    # kernel
    rc_perm = schedule.rc_storage_perm()
    if rc_perm is not None:
        rc_cl = rc_cl[rc_perm]
    # matrix planes ride the same storage-order FIFO: each stream op's
    # (t, t) blocks are pre-permuted so stored-state in -> stored-state out
    if n_mat:
        mat_perm = schedule.mat_storage_perm()
        if mat_perm is not None:
            mats_ml = mats_ml[mat_perm]
    key_n2 = jnp.concatenate(
        [key_n1,
         key_n1[np.asarray(state_transpose_perm(p.v, schedule.branches))]],
        axis=1,
    )

    with_noise = noise_ll is not None
    with_mats = bool(n_mat)
    grid = (padded // BLK,)

    in_specs = [
        pl.BlockSpec((p.n, 2), lambda i: (0, 0)),       # key: replicated
        pl.BlockSpec((nc, BLK), lambda i: (0, i)),      # constants: streamed
    ]
    args = [key_n2, rc_cl]
    if with_noise:
        in_specs.append(pl.BlockSpec((p.l, BLK), lambda i: (0, i)))
        args.append(noise_ll)
    if with_mats:
        # matrix planes: streamed per grid step exactly like rc — the
        # double-buffered constants FIFO, ~t× deeper
        in_specs.append(pl.BlockSpec((n_mat, BLK), lambda i: (0, i)))
        args.append(mats_ml)

    kernel = functools.partial(_keystream_kernel, p, schedule, plan,
                               with_noise, with_mats)
    out_block = (p.l, BLK)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(out_block, lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((p.l, padded), jnp.uint32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit(
            [s.block_shape for s in in_specs] + [out_block])),
        interpret=interpret,
    )(*args)
    return out[:, :lanes] if pad else out


#: scoped VMEM the kernel body's own temporaries may take beyond its
#: pipeline buffers (v5e's default scoped limit)
_VMEM_HEADROOM = 16 << 20
#: v5e has 128 MiB of VMEM; leave room for the compiler's internal scratch
_VMEM_CAP = 100 << 20


def _vmem_limit(block_shapes) -> int:
    """Scoped-VMEM limit for one keystream call: every block (32-bit words)
    double-buffered by the Pallas pipeline, plus headroom.  PASTA-128L's
    (32768, BLK) matrix plane alone is 16 MiB per buffer, over the
    default limit."""
    buffered = sum(2 * 4 * int(np.prod(s)) for s in block_shapes)
    return min(buffered + _VMEM_HEADROOM, _VMEM_CAP)
