"""Pallas flash attention for TPU (forward + custom-VJP backward).

The dense-attention hot path materializes the [L, L] score matrix in HBM;
this kernel keeps score blocks in VMEM and streams K/V blocks through the
MXU with the online-softmax recurrence, so attention memory is O(L·D) and
the score traffic never leaves the chip (pallas_guide.md: HBM→VMEM→MXU).

Layout: q/k/v are [BH, L, D] (batch×heads flattened outside).  The grid is
(BH, q_blocks, k_blocks) with the k dimension innermost — on TPU the grid is
executed sequentially per core, so VMEM scratch (the running max ``m``,
normalizer ``l``, and output accumulator) persists across the k sweep of one
q block (initialized at k==0, finalized at the last k).

Backward implements the standard flash recurrence from the saved
logsumexp rows: two kernels, one accumulating dQ over the k sweep and one
accumulating dK/dV over the q sweep, both recomputing P blocks on-chip.

Supports causal masking (upper-triangle k blocks are skipped entirely, not
just masked) and a [B, L] key-padding mask.  ``interpret=True`` runs the
same kernels through the pallas interpreter (used for CPU tests).

Used via ``make_flash_attention()`` as a drop-in ``attention_fn`` for
``stoke_tpu.models.bert`` — composable with the ring transform (ring for
cross-device sequence sharding, flash for the on-chip block math).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

#: block-size candidates, best first — on v5e the 512x512 blocking is ~3.5x
#: faster than 128x128 (K/V HBM refetch traffic scales as L^2·D/block_q;
#: measured 2026-07-29 under an older JAX, PERF.md "Before the benchmark";
#: not re-measured since — ROADMAP S6)
_BLOCK_CANDIDATES = (512, 256, 128, 64)
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30


def _pick_block(requested: Optional[int], L: int, default: int,
                lane_aligned: bool = False) -> int:
    """Resolve a block size: explicit request wins (clamped to L); when
    L ≤ default a single full-length block is used (always legal, one grid
    step); otherwise the largest candidate ≤ default dividing L.

    ``lane_aligned`` restricts the candidates to multiples of 128: a key
    block that also blocks the [BH, 1, L] key mask sits on the mask's lane
    axis, where Mosaic takes only 128-multiples (or the whole axis)."""
    if requested is not None:
        return min(requested, L)
    if L <= default:
        return L
    candidates = tuple(
        c for c in _BLOCK_CANDIDATES if not lane_aligned or c % 128 == 0
    )
    for c in candidates:
        if c <= default and L % c == 0:
            return c
    step = candidates[-1]
    raise ValueError(
        f"flash attention auto block selection: no candidate in "
        f"{candidates} divides sequence length {L}"
        f"{' (key-masked call)' if lane_aligned else ''}. Pad the sequence "
        f"to a multiple of one of the candidates (e.g. "
        f"{step * -(-L // step)}), or pass an explicit block size that "
        f"divides L."
    )


# --------------------------------------------------------------------------- #
# partitioning under a sharded jit
# --------------------------------------------------------------------------- #


#: (mesh, row axes) of the multi-device program being traced, set by
#: :func:`partition_kernels_over`
_KERNEL_PARTITION: contextvars.ContextVar = contextvars.ContextVar(
    "stoke_kernel_partition", default=None
)


@contextlib.contextmanager
def partition_kernels_over(mesh, axis_names):
    """Tell the Pallas kernels traced inside this scope that they are part
    of a program partitioned over ``mesh``, with the batch sharded over
    ``axis_names``.

    Mosaic kernels cannot be partitioned automatically: lowering a bare
    ``pallas_call`` inside a multi-device ``jit`` (``distributed="dp"``,
    fsdp, ...) raises on the chip ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map").  Inside this scope
    :func:`flash_attention` wraps its kernels in a ``shard_map`` over the
    mesh, each device taking its share of the batch×heads rows.  The step
    and serving engines enter it around every model forward; enter it
    yourself when you call the kernels under your own sharded ``jit``."""
    token = _KERNEL_PARTITION.set((mesh, tuple(axis_names)))
    try:
        yield
    finally:
        _KERNEL_PARTITION.reset(token)


def _current_partition():
    """The (mesh, row axes) a kernel traced NOW must shard_map itself over;
    None on one device and inside a ``shard_map`` body (the ring/Ulysses
    transforms), where the kernel already sees per-device arrays."""
    part = _KERNEL_PARTITION.get()
    if part is None or part[0].size == 1:
        return None
    if jax.sharding.get_abstract_mesh().manual_axes:
        return None
    return part


def _partition_rows(call, part, n_rows: int):
    """``call`` (arrays in, arrays out, every one leading with the same
    axis of independent batch×heads rows) as it must appear in the program:
    bare without a partition, else ``shard_map``-ped over the mesh with the
    rows split over the partition's axes (kept whole, every device
    computing all of them, when they do not divide)."""
    if part is None:
        return call
    mesh, axes = part
    axes = tuple(a for a in axes if a in mesh.axis_names)
    ways = math.prod(mesh.shape[a] for a in axes)
    spec = P(axes) if axes and n_rows % ways == 0 else P()
    return jax.shard_map(
        call, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False
    )


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #


def _fwd_kernel(mask_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc, m_sc, l_sc, *, scale, causal, block_q, block_k, L):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    qi = pl.program_id(1)
    run = True
    if causal:
        # a k block strictly above the diagonal contributes nothing
        run = qi * block_q + block_q - 1 >= ki * block_k

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(jnp.float32)
        kb = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k]
        if mask_ref is not None:
            valid = mask_ref[0] > 0  # [1, block_k] row, broadcasts over q
            s = jnp.where(valid, s, _NEG_INF)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        m_prev = m_sc[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(s > _NEG_INF * 0.5, p, 0.0)
        corr = jnp.exp(m_prev - m_new)  # [block_q, 1]
        l_sc[:, 0:1] = l_sc[:, 0:1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_sc[:, 0:1] = m_new
        pv = jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc[:] = acc[:] * corr + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_sc[:, 0:1]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc[:] / safe_l).astype(o_ref.dtype)
        # logsumexp rows for the backward pass; fully-masked rows get -inf.
        # lse is laid out [BH, L, 1] (column blocks) so the block shape
        # (1, block_q, 1) satisfies the Mosaic (8, 128)-or-full tiling rule
        # and the backward kernels read it as the [block_q, 1] column they
        # subtract from score blocks — no relayout on either side.
        lse = m_sc[:, 0:1] + jnp.log(safe_l)
        lse_ref[0] = jnp.where(l > 0, lse, _NEG_INF)


def _flash_forward(q, k, v, mask, scale, causal, block_q, block_k, interpret,
                   part):
    """q/k/v ``[BH, L, D]``; ``mask`` ``None`` or ``[BH, 1, L]`` (one row per
    batch×head row, so every operand shares the row axis
    :func:`_partition_rows` shards; the unit middle axis keeps the
    ``(1, 1, block_k)`` mask block legal under the Mosaic tiling rule — see
    the lse layout note in ``_fwd_kernel``)."""
    BH, L, D = q.shape
    nq, nk = pl.cdiv(L, block_q), pl.cdiv(L, block_k)
    kernel = functools.partial(
        _fwd_kernel if mask is not None else
        functools.partial(_fwd_kernel, None),
        scale=scale, causal=causal, block_q=block_q, block_k=block_k, L=L,
    )
    in_specs = []
    if mask is not None:
        in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda bh, qi, ki: (bh, 0, ki))
        )
    in_specs += [
        pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
        pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
    ]

    def call(*args):  # ([mask], q, k, v), all with this device's rows
        rows = args[-1].shape[0]
        return pl.pallas_call(
            kernel,
            grid=(rows, nq, nk),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
                pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((rows, L, D), q.dtype),
                jax.ShapeDtypeStruct((rows, L, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, D), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
            ],
            interpret=interpret,
            name="flash_fwd",
        )(*args)

    args = ([mask] if mask is not None else []) + [q, k, v]
    out, lse = _partition_rows(call, part, BH)(*args)
    return out, lse


# --------------------------------------------------------------------------- #
# backward
# --------------------------------------------------------------------------- #


def _recompute_p(q_ref, k_ref, lse_col, mask_ref, qi, ki, *, scale, causal,
                 block_q, block_k):
    """Recompute the softmax block P from saved logsumexp rows.

    ``lse_col`` is the [block_q, 1] column slice of the [BH, L, 1] lse;
    ``mask_ref`` blocks are [1, 1, block_k] rows — both broadcast against
    the [block_q, block_k] score block without any relayout."""
    q = q_ref[0].astype(jnp.float32)
    kb = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if mask_ref is not None:
        s = jnp.where(mask_ref[0] > 0, s, _NEG_INF)
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(qpos >= kpos, s, _NEG_INF)
    p = jnp.exp(s - lse_col)
    return jnp.where(s > _NEG_INF * 0.5, p, 0.0)


def _dq_kernel(mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_acc, *, scale, causal, block_q, block_k):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = True
    if causal:
        run = qi * block_q + block_q - 1 >= ki * block_k

    @pl.when(run)
    def _block():
        p = _recompute_p(
            q_ref, k_ref, lse_ref[0], mask_ref, qi, ki,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        )
        do = do_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0])
        dq_acc[:] += scale * jax.lax.dot_general(
            ds, k_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, block_q,
                block_k):
    qi = pl.program_id(2)  # innermost: sweep over q blocks
    nq = pl.num_programs(2)
    ki = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = qi * block_q + block_q - 1 >= ki * block_k

    @pl.when(run)
    def _block():
        p = _recompute_p(
            q_ref, k_ref, lse_ref[0], mask_ref, qi, ki,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        )
        do = do_ref[0].astype(jnp.float32)
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0])
        dk_acc[:] += scale * jax.lax.dot_general(
            ds, q_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(res, g, scale, causal, block_q, block_k, interpret, part,
                    dlse=None):
    q, k, v, mask, out, lse = res
    do = g
    BH, L, D = q.shape
    nq, nk = pl.cdiv(L, block_q), pl.cdiv(L, block_k)
    # delta_i = rowsum(dO_i * O_i), stored [BH, L, 1] like lse
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
    )
    if dlse is not None:
        # when lse is itself an output (ring-attention hop composition), its
        # cotangent folds into the same kernels: d lse_i/d s_ij = p_ij, so
        # ds = p*(dp - delta + dlse) = p*(dp - (delta - dlse))
        delta = delta - dlse.astype(jnp.float32)

    def specs(grid_inner_is_k):
        idx_q = (lambda bh, a, b: (bh, a, 0)) if grid_inner_is_k else (
            lambda bh, a, b: (bh, b, 0))
        idx_k = (lambda bh, a, b: (bh, b, 0)) if grid_inner_is_k else (
            lambda bh, a, b: (bh, a, 0))
        sp = []
        if mask is not None:
            sp.append(pl.BlockSpec((1, 1, block_k), lambda bh, a, b: (
                bh, 0, b if grid_inner_is_k else a)))
        sp += [
            pl.BlockSpec((1, block_q, D), idx_q),   # q
            pl.BlockSpec((1, block_k, D), idx_k),   # k
            pl.BlockSpec((1, block_k, D), idx_k),   # v
            pl.BlockSpec((1, block_q, D), idx_q),   # do
            pl.BlockSpec((1, block_q, 1), idx_q),   # lse [BH, L, 1]
            pl.BlockSpec((1, block_q, 1), idx_q),   # delta [BH, L, 1]
        ]
        return sp

    args = ([mask] if mask is not None else []) + [q, k, v, do, lse, delta]

    dq_kernel = functools.partial(
        _dq_kernel if mask is not None else functools.partial(_dq_kernel, None),
        scale=scale, causal=causal, block_q=block_q, block_k=block_k,
    )

    def dq_call(*args):
        rows = args[-1].shape[0]
        return pl.pallas_call(
            dq_kernel,
            grid=(rows, nq, nk),
            in_specs=specs(grid_inner_is_k=True),
            out_specs=pl.BlockSpec(
                (1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)
            ),
            out_shape=jax.ShapeDtypeStruct((rows, L, D), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
            interpret=interpret,
            name="flash_bwd_dq",
        )(*args)

    dq = _partition_rows(dq_call, part, BH)(*args)

    dkv_kernel = functools.partial(
        _dkv_kernel if mask is not None else functools.partial(_dkv_kernel, None),
        scale=scale, causal=causal, block_q=block_q, block_k=block_k,
    )

    def dkv_call(*args):
        rows = args[-1].shape[0]
        return pl.pallas_call(
            dkv_kernel,
            grid=(rows, nk, nq),
            in_specs=specs(grid_inner_is_k=False),
            out_specs=[
                pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
                pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((rows, L, D), k.dtype),
                jax.ShapeDtypeStruct((rows, L, D), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, D), jnp.float32),
            ],
            interpret=interpret,
            name="flash_bwd_dkv",
        )(*args)

    dk, dv = _partition_rows(dkv_call, part, BH)(*args)
    return dq, dk, dv, None


# --------------------------------------------------------------------------- #
# public API
# --------------------------------------------------------------------------- #


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, mask, scale, causal, block_q, block_k, interpret, part):
    out, _ = _flash_forward(
        q, k, v, mask, scale, causal, block_q, block_k, interpret, part
    )
    return out


def _flash_fwd_rule(q, k, v, mask, scale, causal, block_q, block_k, interpret,
                    part):
    out, lse = _flash_forward(
        q, k, v, mask, scale, causal, block_q, block_k, interpret, part
    )
    return out, (q, k, v, mask, out, lse)


def _flash_bwd_rule(scale, causal, block_q, block_k, interpret, part, res, g):
    return _flash_backward(
        res, g, scale, causal, block_q, block_k, interpret, part
    )


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_with_lse(q, k, v, mask, scale, causal, block_q, block_k,
                    interpret, part):
    """Like ``_flash`` but also returns the [BH, L, 1] logsumexp rows —
    the composition hook for ring attention (hop outputs are re-weighted by
    their lse, so lse needs a real gradient path)."""
    return _flash_forward(
        q, k, v, mask, scale, causal, block_q, block_k, interpret, part
    )


def _flash_lse_fwd_rule(q, k, v, mask, scale, causal, block_q, block_k,
                        interpret, part):
    out, lse = _flash_forward(
        q, k, v, mask, scale, causal, block_q, block_k, interpret, part
    )
    return (out, lse), (q, k, v, mask, out, lse)


def _flash_lse_bwd_rule(scale, causal, block_q, block_k, interpret, part, res,
                        g):
    do, dlse = g
    return _flash_backward(
        res, do, scale, causal, block_q, block_k, interpret, part, dlse=dlse
    )


_flash_with_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


def flash_attention(
    q, k, v, mask=None, *, causal: bool = False,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    interpret: Optional[bool] = None, return_lse: bool = False,
):
    """Flash attention on [B, H, L, D] inputs with optional [B, L] key mask.

    ``return_lse=True`` additionally returns the [B, H, L] logsumexp rows
    (fully-masked rows get the ``_NEG_INF`` sentinel) — used by ring
    attention to merge per-hop partial attentions; gradients flow through
    both outputs.

    ``interpret=None`` auto-selects the pallas interpreter off-TPU (tests).
    ``block_q``/``block_k=None`` auto-selects the largest block in
    ``_BLOCK_CANDIDATES`` that divides L (bigger q blocks cut the K/V HBM
    refetch factor — the measured optimum on v5e is 512x512).  L must be
    divisible by the resolved block sizes.
    """
    if q.ndim != 4:
        raise ValueError(f"expected [B, H, L, D] inputs, got {q.shape}")
    if q.shape != k.shape or k.shape != v.shape:
        raise ValueError(
            f"q/k/v shapes must match, got {q.shape}/{k.shape}/{v.shape}"
        )
    B, H, L, D = q.shape
    if mask is not None and mask.shape != (B, L):
        raise ValueError(f"mask must be [B, L] = {(B, L)}, got {mask.shape}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = _pick_block(block_q, L, DEFAULT_BLOCK_Q)
    block_k = _pick_block(
        block_k, L, DEFAULT_BLOCK_K, lane_aligned=mask is not None
    )
    if L % block_q or L % block_k:
        raise ValueError(
            f"sequence length {L} must be divisible by block sizes "
            f"({block_q}, {block_k})"
        )
    flat = lambda t: t.reshape(B * H, L, D)
    # one mask row per batch×head row ([BH, 1, L], see _flash_forward)
    mask3 = None if mask is None else jnp.repeat(mask, H, axis=0).reshape(
        B * H, 1, L
    )
    # read at FORWARD trace time and handed down as a static argument: the
    # backward rule is traced later, outside any scope
    part = _current_partition()
    if return_lse:
        out, lse = _flash_with_lse(
            flat(q), flat(k), flat(v), mask3, 1.0 / (D**0.5), causal,
            block_q, block_k, interpret, part,
        )
        return out.reshape(B, H, L, D), lse.reshape(B, H, L)
    out = _flash(
        flat(q), flat(k), flat(v), mask3, 1.0 / (D**0.5), causal,
        block_q, block_k, interpret, part,
    )
    return out.reshape(B, H, L, D)


#: numerics-contract tolerances for validating the kernel against the dense
#: reference at bf16 inputs (tests/test_flash_tpu.py holds the compiled
#: kernel to them on the chip)
FWD_ATOL_BF16 = 2e-2
BWD_RTOL_BF16 = 0.05


def dense_reference(q, k, v, mask=None, causal=False):
    """O(L²) dense attention in fp32 — the ground truth the flash kernel is
    validated against ([B, H, L, D] inputs, optional [B, L] key mask)."""
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) / (q.shape[-1] ** 0.5)
    L = q.shape[2]
    if mask is not None:
        s = jnp.where(mask[:, None, None, :] > 0, s, _NEG_INF)
    if causal:
        qpos = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
        s = jnp.where((qpos >= kpos)[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


def _one_layer_pool(pages):
    """``[NB, BS, H, D]`` pages of one layer as the ``[1, NB, BS, H*D]`` pool
    the serving cache stores (:func:`paged_pool_attention`'s layout)."""
    NB, BS, H, D = pages.shape
    return pages.reshape(1, NB, BS, H * D)


def paged_pool_attention(q, k_pool, v_pool, layer, block_tables, positions):
    """Attention of ``q`` over one layer of the serving cache's page pool,
    in the layout the pool is stored in (the implementation behind
    :func:`paged_decode_attention`, :func:`paged_prefill_chunk_attention`
    and :func:`paged_verify_attention`).

    The pool is ``[n_layers, NB, BS, H*D]``: a cached token is one row of
    ``H*D`` lanes, heads side by side (``serving/kv_cache.py``).  Each
    slot's window is ONE gather at ``(layer, block_tables)`` out of the
    whole pool — no layer plane is sliced out first, and the table's
    entries are promised in bounds (the allocator hands out nothing else;
    unused entries point at the scratch block 0), so no select runs over
    the window.  The arithmetic stays on those flat rows: the queries are
    laid out block-diagonally, row ``(s, h)`` holding head ``h``'s query in
    head ``h``'s lanes and zeros elsewhere, so one ``[S*H, H*D] x [H*D, W]``
    matmul per slot gives every head's scores and one ``[S*H, W] x [W,
    H*D]`` matmul every head's values (of whose ``H*D`` output lanes row
    ``(s, h)`` keeps head ``h``'s).  The zeros cost ``H`` times the MXU
    work and save reshaping the window to ``[.., H, D]``, which on the
    device is a copy of the window into a padded tiling; the window is the
    larger by far.  The softmax is fp32 and masked by position.

    A single query row (decode) multiplies at ``Precision.HIGHEST``, so the
    float32 cache is read with float32 products (the one-row case is a
    multiply-reduce in exact arithmetic; a default-precision matmul would
    round K, V and the probabilities to bf16).  Several rows (chunk,
    verify) are matmuls proper and run at the default precision.

    Args:
        q: ``[B, H, S, D]`` queries.
        k_pool / v_pool: ``[n_layers, NB, BS, H*D]`` page pools.
        layer: static layer index into the pools.
        block_tables: ``[B, MAX_BLOCKS] int32`` per-slot block ids.
        positions: ``[B, S] int32`` — query row ``s`` of slot ``b`` attends
            window positions ``<= positions[b, s]``.

    Returns ``[B, H, S, D]`` attention outputs in the query dtype.
    """
    B, H, S, D = q.shape
    HD = H * D
    if k_pool.shape != v_pool.shape or k_pool.ndim != 4:
        raise ValueError(
            f"k_pool/v_pool must be identical [n_layers, NB, BS, H*D] "
            f"pools, got {k_pool.shape}/{v_pool.shape}"
        )
    if k_pool.shape[3] != HD:
        raise ValueError(
            f"the pool's rows are {k_pool.shape[3]} wide; the query's "
            f"heads x head_dim is {H} x {D}"
        )
    MB = block_tables.shape[1]
    W = MB * k_pool.shape[2]

    def window(pool):  # [B, MB, BS, HD] -> [B, W, HD]: merges whole tiles
        rows = pool.at[layer, block_tables].get(mode="promise_in_bounds")
        return rows.reshape(B, W, HD).astype(jnp.float32)

    precision = jax.lax.Precision.HIGHEST if S == 1 else None
    # lane j belongs to head j // D
    own = (
        jnp.arange(HD, dtype=jnp.int32)[None, :] // D
        == jnp.arange(H, dtype=jnp.int32)[:, None]
    ).astype(jnp.float32)  # [H, HD]
    q_rows = jnp.swapaxes(q, 1, 2).reshape(B, S, 1, HD).astype(jnp.float32)
    q_diag = (q_rows * own).reshape(B, S * H, HD)
    s = jnp.einsum(
        "brj,bwj->brw", q_diag, window(k_pool), precision=precision
    ) / (D**0.5)
    w_pos = jnp.arange(W, dtype=jnp.int32)
    valid = w_pos[None, None, :] <= positions[:, :, None]  # [B, S, W]
    s = jnp.where(jnp.repeat(valid, H, axis=1), s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("brw,bwj->brj", p, window(v_pool), precision=precision)
    # row (s, h) keeps head h's lanes; the rows of one s then sum to [HD]
    out = (out.reshape(B, S, H, HD) * own).sum(axis=2).reshape(B, S, H, D)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_tables, context_lens):
    """Decode-mode attention over a paged KV-cache (ISSUE 9 serving path).

    Single-token decode is HBM-bandwidth-bound, not MXU-bound: each query
    attends over its own sequence's cached K/V, which lives scattered
    across a block pool addressed by a per-request block table (the
    vLLM-style layout, sized so freed blocks refill mid-flight —
    ``stoke_tpu.serving.kv_cache``).  Each request's blocks are gathered
    from the pool and scored with an fp32 masked softmax — the flash
    recurrence degenerates at q-length 1 (one online-softmax row), so the
    gather IS the whole memory schedule
    (:func:`paged_decode_attention_pallas` streams the blocks through VMEM
    itself, with this function as its reference semantics).  A thin
    wrapper: the pages are viewed as a one-layer pool and
    :func:`paged_pool_attention`, which the serving engine calls on its
    whole pool, does the work.

    Args:
        q: ``[B, H, 1, D]`` current-token queries (one per decode slot).
        k_pages / v_pages: ``[NB, BS, H, D]`` block pool for ONE layer
            (NB blocks of BS tokens).
        block_tables: ``[B, MAX_BLOCKS] int32`` — each slot's block ids
            into the pool, in sequence order; unused entries point at a
            legal block (the reserved scratch block 0 by convention) —
            they are masked by ``context_lens``.
        context_lens: ``[B] int32`` — valid tokens per slot INCLUDING the
            current one (positions ``>= context_lens[b]`` are masked).

    Returns ``[B, H, 1, D]`` attention outputs in the query dtype.
    """
    if q.shape[2] != 1:
        raise ValueError(
            f"paged_decode_attention is single-token decode; got q-length "
            f"{q.shape[2]} (prefill goes through flash_attention/"
            f"dense_attention)"
        )
    return paged_pool_attention(
        q, _one_layer_pool(k_pages), _one_layer_pool(v_pages), 0,
        block_tables, context_lens.astype(jnp.int32)[:, None] - 1,
    )


#: KV pages the decode and verify kernels fetch HBM→VMEM per kernel step
#: when the caller names no step (serving never does) — bigger groups
#: amortize per-step overhead
_PAGES_PER_STEP = 8


def _pick_divisor(requested: Optional[int], total: int) -> int:
    """Largest divisor of ``total`` that is <= the requested value (default
    :data:`_PAGES_PER_STEP`) — the pages fetched per step must tile the
    block table exactly, so a step that does not divide the table width
    degrades to the nearest legal size."""
    want = _PAGES_PER_STEP if requested is None else int(requested)
    want = max(1, min(want, total))
    while total % want:
        want -= 1
    return want


def _paged_attention_kernel(tables_ref, qpos_ref, q_ref, *refs, heads, n_q,
                            block_size, pages_per_block, scale):
    """Streaming paged-attention body shared by decode (``n_q == 1``) and
    speculative verify (``n_q == k+1``): one ``(request, page group)`` grid
    cell.

    The page pool is viewed ``[NB, BS*H, D]`` (row ``t*H + h`` = token ``t``
    of the page, head ``h``) and the block table rides as a scalar-prefetch
    argument, so each of the ``pages_per_block`` K and V inputs is an
    ordinary BlockSpec whose index map reads the table — the Pallas
    pipeline double-buffers the page DMAs itself, whole pages at a time
    (Mosaic cannot slice one head out of the ``(H, D)`` tile a page is
    stored in).  Queries are ``[H*n_q, D]`` (row ``h*n_q + s``).  Per page:
    one ``[rows, D] x [D, BS*H]`` score matmul against ALL heads' keys,
    masked down to each row's own head and to window positions
    ``<= qpos`` before the online softmax, then ``P @ V`` — masked
    probabilities are exact zeros, so the other heads' values contribute
    nothing.  The fp32 running max / normalizer / accumulator live in VMEM
    scratch across the page-group sweep (init at the first group, finalize
    at the last), exactly like ``_fwd_kernel``.  Inactive table entries
    point at the reserved scratch block 0 (a legal fetch); their positions
    are masked, so they contribute nothing (the same dead-block traffic the
    jnp reference gather pays)."""
    k_refs = refs[:pages_per_block]
    v_refs = refs[pages_per_block:2 * pages_per_block]
    o_ref, acc, m_sc, l_sc = refs[2 * pages_per_block:]
    j = pl.program_id(1)
    rows = heads * n_q
    cols = block_size * heads

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    q = q_ref[0].astype(jnp.float32) * scale  # [rows, D]
    qpos = qpos_ref[0]  # [rows, 1] last window position each row may see
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    own_head = (col % heads) == (row // n_q)
    tok = col // heads  # token offset of the column inside its page
    for p in range(pages_per_block):
        s = jax.lax.dot_general(
            q, k_refs[p][...].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [rows, BS*H]
        base = (j * pages_per_block + p) * block_size
        valid = own_head & (base + tok <= qpos)
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_sc[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        prob = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_sc[:, 0:1] = l_sc[:, 0:1] * corr + jnp.sum(
            prob, axis=-1, keepdims=True
        )
        m_sc[:, 0:1] = m_new
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            prob, v_refs[p][...].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l = l_sc[:, 0:1]
        o_ref[0] = (acc[:] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def _paged_attention_pallas(q, k_pages, v_pages, block_tables, positions,
                            pages_per_block, interpret):
    """Shared driver of the two Pallas paged-attention entry points: query
    row ``s`` of request ``b`` attends window positions
    ``<= positions[b, s]``."""
    B, H, S, D = q.shape
    if k_pages.shape != v_pages.shape or k_pages.ndim != 4:
        raise ValueError(
            f"k_pages/v_pages must be identical [NB, BS, H, D] pools, got "
            f"{k_pages.shape}/{v_pages.shape}"
        )
    if k_pages.shape[2] != H or k_pages.shape[3] != D:
        raise ValueError(
            f"page pool heads/dim {k_pages.shape[2:]} do not match the "
            f"query's {(H, D)}"
        )
    if block_tables.ndim != 2 or block_tables.shape[0] != B:
        raise ValueError(
            f"block_tables must be [B={B}, MAX_BLOCKS], got "
            f"{block_tables.shape}"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    NB, BS = int(k_pages.shape[0]), int(k_pages.shape[1])
    MB = int(block_tables.shape[1])
    ppb = _pick_divisor(pages_per_block, MB)
    rows = H * S
    kernel = functools.partial(
        _paged_attention_kernel,
        heads=H, n_q=S, block_size=BS, pages_per_block=ppb,
        scale=1.0 / (D**0.5),
    )
    # row h*S + s of the flattened queries carries positions[b, s]
    qpos = jnp.tile(positions.astype(jnp.int32), (1, H)).reshape(B, rows, 1)
    # the kernel's view of a page: one row per (token, head).  The serving
    # cache stores a token as one H*D-wide row, so from its pool these
    # reshapes are copies of the layer's pages into a D-minor tiling
    k_flat = k_pages.reshape(NB, BS * H, D)
    v_flat = v_pages.reshape(NB, BS * H, D)

    def page_spec(p):
        return pl.BlockSpec(
            (None, BS * H, D), lambda b, j, tbl: (tbl[b, j * ppb + p], 0, 0)
        )

    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # the block table
            grid=(B, MB // ppb),
            in_specs=[
                pl.BlockSpec((1, rows, 1), lambda b, j, tbl: (b, 0, 0)),
                pl.BlockSpec((1, rows, D), lambda b, j, tbl: (b, 0, 0)),
            ] + [page_spec(p) for p in range(ppb)] * 2,
            out_specs=pl.BlockSpec((1, rows, D), lambda b, j, tbl: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows, D), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, rows, D), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )
    part = _current_partition()
    if part is not None:
        # nothing here is split: every device walks the whole slot batch
        # over its own replica of the pool
        part = (part[0], ())
    out = _partition_rows(call, part, B)(
        block_tables.astype(jnp.int32), qpos, q.reshape(B, rows, D),
        *([k_flat] * ppb), *([v_flat] * ppb),
    )
    return out.reshape(B, H, S, D)


def paged_decode_attention_pallas(
    q, k_pages, v_pages, block_tables, context_lens, *,
    pages_per_block: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Pallas paged-decode attention (ISSUE 13), with
    :func:`paged_decode_attention` as its pinned reference semantics.

    Decode attention is HBM-bandwidth-bound: the whole job is moving each
    request's cached K/V past the compute units once.  The jnp reference
    leaves the memory schedule to XLA's gather lowering; this kernel owns
    it — grid ``(batch, table_width / pages_per_block)``, the block table
    scalar-prefetched, the page pool left in HBM and fetched whole pages at
    a time by table-indexed BlockSpecs into the fp32 online-softmax
    accumulation (:func:`_paged_attention_kernel`).  Same contract as the
    reference: positions >= ``context_lens[b]`` are masked, unused table
    entries point at the reserved scratch block 0 (a legal fetch, a masked
    contribution), output in the query dtype.  A slot with
    ``context_lens[b] == 0`` returns zeros (the reference returns a
    meaningless mean; callers discard inactive slots either way).

    Args mirror :func:`paged_decode_attention`; the extra knobs:

    Args:
        pages_per_block: KV pages fetched per kernel step (clamped to the
            largest divisor of the table width; ``None`` = up to
            ``_PAGES_PER_STEP``, which is what serving runs).
        interpret: run through the pallas interpreter (``None`` =
            auto-select off-TPU, like :func:`flash_attention` — the CPU
            parity mode the tests pin against the reference).
    """
    if q.ndim != 4 or q.shape[2] != 1:
        raise ValueError(
            f"paged_decode_attention_pallas is single-token decode "
            f"([B, H, 1, D]); got q shape {q.shape}"
        )
    # decode is verify with one query row at the last cached position
    positions = context_lens.astype(jnp.int32).reshape(-1, 1) - 1
    return _paged_attention_pallas(
        q, k_pages, v_pages, block_tables, positions, pages_per_block,
        interpret,
    )


def paged_prefill_chunk_attention(q, k_pages, v_pages, block_tables,
                                  positions):
    """Chunked-prefill attention over a paged KV-cache (ISSUE 13).

    A prompt chunk's queries attend over everything already cached for the
    request — the earlier chunks' K/V (written to the block pool by prior
    chunk dispatches) plus this chunk's own (written by the hook before
    attention runs, exactly like decode writes the fresh token first).
    Causality is positional: query at global position ``p`` attends cache
    window positions ``<= p``, which covers both the intra-chunk causal
    mask and the inter-chunk prefix in one predicate.  The generalization
    of :func:`paged_decode_attention` to q-length C (its C == 1, positions
    == context_lens - 1 special case) and the reference semantics for a
    future Pallas chunk kernel.

    Args:
        q: ``[B, H, C, D]`` chunk queries.
        k_pages / v_pages: ``[NB, BS, H, D]`` block pool for one layer.
        block_tables: ``[B, MAX_BLOCKS] int32`` per-request block ids.
        positions: ``[B, C] int32`` global token positions of the chunk's
            queries (padding rows past the prompt end may hold clamped
            positions — their outputs are discarded by the caller).

    Returns ``[B, H, C, D]`` attention outputs in the query dtype.
    """
    return paged_pool_attention(
        q, _one_layer_pool(k_pages), _one_layer_pool(v_pages), 0,
        block_tables, positions,
    )


def paged_verify_attention(q, k_pages, v_pages, block_tables, positions):
    """Speculative-verify attention over a paged KV-cache (ISSUE 17).

    The verify program scores a request's next token plus its k draft
    continuations in ONE dispatch: S = k+1 query rows per request, each
    attending the cache window at its own global position.  Semantically
    this IS :func:`paged_prefill_chunk_attention` — multi-token queries
    over the paged prefix with the positional causal predicate — applied
    at decode time, which is exactly why the chunk program shape pins the
    verify semantics (ROADMAP item 2).  Kept as its own named entry point
    so the Pallas fast path (:func:`paged_verify_attention_pallas`) has
    pinned reference semantics independent of future chunk changes.

    Args:
        q: ``[B, H, S, D]`` verify queries (S = speculative_k + 1).
        k_pages / v_pages: ``[NB, BS, H, D]`` block pool for one layer.
        block_tables: ``[B, MAX_BLOCKS] int32`` per-request block ids.
        positions: ``[B, S] int32`` global positions of the verify
            queries; padding rows (requests with short drafts) carry
            clamped positions and their outputs are discarded.

    Returns ``[B, H, S, D]`` attention outputs in the query dtype.
    """
    return paged_prefill_chunk_attention(
        q, k_pages, v_pages, block_tables, positions
    )


def paged_verify_attention_pallas(
    q, k_pages, v_pages, block_tables, positions, *,
    pages_per_block: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Pallas verify attention: the k-token speculative-decode kernel
    (ISSUE 17), with :func:`paged_verify_attention` as its pinned
    reference semantics.

    The memory schedule of :func:`paged_decode_attention_pallas` (the two
    share :func:`_paged_attention_kernel`), but each grid cell scores
    S = k+1 query rows against every fetched page, so the per-dispatch HBM
    traffic (the decode bottleneck) is amortized over up to k+1 emitted
    tokens.  Masking is positional per query row
    (``w_pos <= positions[b, s]``), the chunk-attention predicate.

    Args:
        q: ``[B, H, S, D]`` verify queries.
        k_pages / v_pages: ``[NB, BS, H, D]`` pools for one layer.
        block_tables: ``[B, MAX_BLOCKS] int32`` per-request block ids
            (unused entries at the reserved scratch block 0).
        positions: ``[B, S] int32`` per-query global positions.
        pages_per_block: KV pages fetched per kernel step (clamped to a
            divisor like the decode kernel's; ``None`` = the same default).
        interpret: pallas interpreter toggle (``None`` = auto off-TPU).
    """
    if q.ndim != 4:
        raise ValueError(f"expected [B, H, S, D] queries, got {q.shape}")
    if positions.shape != (q.shape[0], q.shape[2]):
        raise ValueError(
            f"positions must be [B={q.shape[0]}, S={q.shape[2]}], got "
            f"{positions.shape}"
        )
    return _paged_attention_pallas(
        q, k_pages, v_pages, block_tables, positions, pages_per_block,
        interpret,
    )


#: bytes of latent pages :func:`latent_paged_attention` holds in each of its
#: two VMEM buffers: 32 pages of [16, 640] bfloat16, so a step's two
#: products run over 512 positions.  Swept on the v5e (PERF.md section 6,
#: PR 30): a step's cost is the products' fixed part, not the page DMAs, and
#: 512 positions is where long and short contexts both stop gaining.
_LATENT_GROUP_BYTES = 640 * 1024


def _latent_paged_kernel(tables_ref, lens_ref, q_ref, plane_ref, o_ref,
                         buf, sems, acc, m_sc, l_sc, *, layer, block_size,
                         group, max_blocks, scale):
    """One slot of :func:`latent_paged_attention`: walk the slot's live
    blocks, ``group`` pages a step, fetching step ``g + 1``'s pages from
    the plane in HBM into one half of ``buf`` while step ``g`` is scored
    out of the other.  A page is fetched once and serves both products:
    every head's scores against its rows, then the probabilities against
    the same rows (whose leading lanes are the values).  The fp32 running
    max / normalizer / accumulator live in VMEM scratch, as in
    ``_paged_attention_kernel``.  Blocks past the slot's length cost
    neither a DMA nor a step."""
    b = pl.program_id(0)
    n_ctx = lens_ref[b]
    n_blocks = jnp.minimum(pl.cdiv(n_ctx, block_size), max_blocks)
    n_groups = pl.cdiv(n_blocks, group)
    tokens = group * block_size

    def live_pages(g, half, act):
        # a loop and not ``group`` unrolled copies: the kernel is traced
        # once a layer, and 3 x 32 guarded DMAs a trace cost the set-up 20 s
        first = g * group

        def page(p, carry):
            at = pl.multiple_of(p * block_size, block_size)
            act(pltpu.make_async_copy(
                plane_ref.at[layer, tables_ref[b * max_blocks + first + p]],
                buf.at[half, pl.ds(at, block_size)],
                sems.at[half],
            ))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(group, n_blocks - first), page, None)

    @pl.when(b == 0)
    def _():
        # the last step of a slot scores its unfetched pages too (masked to
        # exact zeros): what lies there must be finite, so start from zeros
        # and from then on it is rows fetched earlier
        buf[...] = jnp.zeros_like(buf)

    acc[...] = jnp.zeros_like(acc)
    m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)

    @pl.when(n_groups > 0)
    def _():
        live_pages(0, 0, lambda dma: dma.start())

    q = q_ref[...]  # [H, row]

    def step(g, carry):
        half = g % 2

        @pl.when(g + 1 < n_groups)
        def _():
            live_pages(g + 1, 1 - half, lambda dma: dma.start())

        live_pages(g, half, lambda dma: dma.wait())
        page = buf[half].astype(q.dtype)  # [tokens, row]
        s = jax.lax.dot_general(
            q, page, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [H, tokens]
        pos = g * tokens + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = pos < n_ctx
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_sc[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        prob = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_sc[:, 0:1] = l_sc[:, 0:1] * corr + jnp.sum(
            prob, axis=-1, keepdims=True
        )
        m_sc[:, 0:1] = m_new
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            prob.astype(q.dtype), page, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return carry

    jax.lax.fori_loop(0, n_groups, step, None)
    l = l_sc[:, 0:1]
    o_ref[...] = (acc[...] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def latent_paged_attention(q_row, plane, layer, block_tables, context_lens,
                           scale, *, interpret: Optional[bool] = None):
    """Decode attention of absorbed query rows over the latent cache's
    plane, read in place: each slot's LIVE blocks stream through VMEM
    once, with an online softmax, and no window is ever materialised.
    ``models/decoder.py`` ``absorbed_attention`` over the slot's gathered
    window is its pinned reference semantics.

    Latent attention keeps one row a token that every head shares, so a
    page is one ``[BS, row]`` tile-aligned block and the score product is
    ``[H, row] x [row, tokens]``.  The block table and the lengths ride as
    scalar-prefetch arguments; the plane stays in HBM (``pl.ANY``), whole:
    no layer is sliced out and nothing is reshaped, either of which would
    copy it.  Grid over the slots; inside, a loop to the slot's own block
    count with double-buffered page DMAs (:func:`_latent_paged_kernel`).
    How many pages a step takes follows from the page's bytes
    (``_LATENT_GROUP_BYTES``).

    Args:
        q_row: ``[B, H, row]`` absorbed queries (``q_nope W_UK``, the roped
            part, zeros up to the row's width), in the compute dtype.
        plane: ``[n_layers, NB, BS, row]`` latent plane of the pool.
        layer: static layer index into the plane.
        block_tables: ``[B, MAX_BLOCKS] int32`` per-slot block ids; only
            the first ``ceil(context_lens[b] / BS)`` of a slot are read.
        context_lens: ``[B] int32`` valid positions a slot INCLUDING the
            current one; at most ``MAX_BLOCKS * BS``.
        scale: softmax scale, applied to the fp32 scores.
        interpret: run through the pallas interpreter (``None`` = auto
            off-TPU, as :func:`flash_attention`).

    Returns ``[B, H, row]`` in the query dtype: the probabilities times the
    whole rows (the caller keeps the value lanes).  A slot with
    ``context_lens[b] == 0`` returns zeros.
    """
    B, H, row = q_row.shape
    if plane.ndim != 4 or plane.shape[3] != row:
        raise ValueError(
            f"the plane must be [n_layers, NB, BS, row={row}], got "
            f"{plane.shape}"
        )
    if block_tables.ndim != 2 or block_tables.shape[0] != B:
        raise ValueError(
            f"block_tables must be [B={B}, MAX_BLOCKS], got "
            f"{block_tables.shape}"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    BS, MB = int(plane.shape[2]), int(block_tables.shape[1])
    group = max(1, min(
        MB, _LATENT_GROUP_BYTES // (BS * row * plane.dtype.itemsize)))
    kernel = functools.partial(
        _latent_paged_kernel, layer=int(layer), block_size=BS, group=group,
        max_blocks=MB, scale=float(scale),
    )

    slot_rows = pl.BlockSpec((None, H, row), lambda b, tbl, lens: (b, 0, 0))
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # the flat block table, the lengths
            grid=(B,),
            in_specs=[slot_rows, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=slot_rows,
            scratch_shapes=[
                pltpu.VMEM((2, group * BS, row), plane.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((H, row), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, row), q_row.dtype),
        interpret=interpret,
        name="latent_paged_attention",
    )
    part = _current_partition()
    if part is not None:
        # as the MHA kernel: every device walks the whole slot batch over
        # its own replica of the pool
        part = (part[0], ())
    return _partition_rows(call, part, B)(
        block_tables.astype(jnp.int32).reshape(-1),
        context_lens.astype(jnp.int32), q_row, plane,
    )


def make_flash_attention(
    causal: bool = False, block_q: Optional[int] = None,
    block_k: Optional[int] = None, interpret: Optional[bool] = None,
):
    """Build a flash ``attention_fn`` pluggable into
    ``BertEncoder(attention_fn=...)`` (same contract as ``dense_attention``)."""

    def attention_fn(q, k, v, bias, dropout_rng=None, dropout_rate=0.0,
                     deterministic=True):
        if dropout_rate > 0.0 and not deterministic:
            raise NotImplementedError(
                "flash attention does not support attention-prob dropout; "
                "set attention dropout to 0 (residual dropout is fine)"
            )
        mask = None
        if bias is not None:
            mask = (bias[:, 0, 0, :] > -1e8).astype(jnp.int32)
        return flash_attention(
            q, k, v, mask, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret,
        )

    return attention_fn


def grouped_query_attention(q, k, v, key_valid, impl: str = "dense"):
    """Causal softmax attention of ``q [B, L, H, D]`` over ``k``, ``v [B, L,
    G, D]``, query head ``h`` reading key-value head ``h // (H / G)``,
    scaled by ``D ** -0.5``: the training and prefill form of a ``gqa``
    layer.  ``key_valid [B, L]`` bool masks padding keys.  ``impl="flash"``
    runs the repo's flash kernel, which wants as many key heads as query
    heads: each key-value head is repeated for its ``H / G`` query heads
    (the kernel then fetches a key-value head ``H / G`` times).  Returns
    ``[B, L, H, D]``."""
    B, L, H, D = q.shape
    G = k.shape[2]
    dtype = q.dtype
    if impl == "flash":
        heads_first = functools.partial(jnp.swapaxes, axis1=1, axis2=2)
        k, v = (jnp.repeat(heads_first(t), H // G, axis=1) for t in (k, v))
        out = flash_attention(
            heads_first(q), k, v, key_valid.astype(jnp.int32), causal=True)
        return heads_first(out)
    if impl != "dense":
        raise ValueError(f"unknown attention impl {impl!r}")
    qg = q.reshape(B, L, G, H // G, D)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                   preferred_element_type=jnp.float32) * D ** -0.5
    allow = jnp.tril(jnp.ones((L, L), bool))[None, None, None] & (
        key_valid[:, None, None, None, :])
    p = jax.nn.softmax(jnp.where(allow, s, _NEG_INF), axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, L, H, D).astype(dtype)
