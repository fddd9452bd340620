"""Sequence-parallel attention: ring attention and Ulysses all-to-all.

Long-context support the reference does not have (SURVEY.md §2.8).  Both
transforms shard the SEQUENCE dimension over a mesh axis so context length
scales with the number of devices; both are drop-in ``attention_fn``s for
``stoke_tpu.models.bert`` (same signature as ``dense_attention``).

- **Ring attention** (arxiv 2310.01889 pattern): Q stays put; K/V blocks
  rotate around the mesh axis via ``lax.ppermute`` while a flash-style
  online-softmax accumulator (running max ``m``, normalizer ``l``, weighted
  sum ``o``) folds in one K/V block per hop.  Peak memory per device is
  O(L_shard²) instead of O(L²), and the ppermute rides ICI neighbor links —
  the topology's cheapest collective.

- **Ulysses** (DeepSpeed-Ulysses pattern, arxiv 2309.14509): one
  ``all_to_all`` re-shards [B, H, L/n, D] → [B, H/n, L, D] (heads sharded,
  sequence gathered), runs ordinary dense attention locally, and a second
  ``all_to_all`` restores sequence sharding.  Cheaper collectives for
  moderate L; requires heads divisible by the axis size.

Both are written against ``shard_map`` (explicit per-shard code + explicit
collectives) and compose with the jit-GSPMD data-parallel engine: the mesh
carries ("data", "seq") axes and batch arrays are sharded over both.

**Inner kernel** (``inner=`` on every entry point): ``"flash"`` runs the
on-chip math through the Pallas flash kernel (``ops/flash_attention.py``) —
ring hops call flash with ``return_lse`` and merge partial attentions with
a log-sum-exp combine (per-device attention memory O(L·D·H/n), no score
materialization, vs the dense inner's O((L/n)²·H) score blocks); Ulysses
runs one flash call over the gathered sequence after the all-to-all, so
local memory is O(L·D·H/n) not O(L²·H/n).  ``"dense"`` keeps the einsum
inner math (useful for debugging and as the numerics reference).  The
default ``"auto"`` picks flash whenever the local length fits the flash
block ladder (L ≤ 512 or divisible by a candidate) and dense otherwise, so
pre-existing call sites keep working for any L.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .flash_attention import DEFAULT_BLOCK_Q, _pick_block, flash_attention


def shard_map(f, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` with this repo's default: no varying-manual-axes
    check (the per-shard bodies here mix replicated and sharded values)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


_NEG_INF = -1e30


def _resolve_inner(inner: str, L: int) -> str:
    """Resolve the inner-kernel choice.  ``"auto"`` (the default) uses flash
    when the flash block picker supports the local length L and falls back
    to the dense einsum otherwise (flash needs L ≤ 512 or L divisible by a
    128-multiple block candidate); explicit ``"flash"``/``"dense"`` are honored verbatim
    (flash will raise its actionable block error for unsupported L)."""
    if inner not in ("auto", "flash", "dense"):
        raise ValueError(
            f"inner must be 'auto', 'flash' or 'dense', got {inner!r}"
        )
    if inner != "auto":
        return inner
    try:
        # the ring hops and key-padded calls carry a key mask, whose block
        # must be lane-aligned
        _pick_block(None, L, DEFAULT_BLOCK_Q, lane_aligned=True)
        return "flash"
    except ValueError:
        return "dense"


def _resolve_batch_axis(q, mesh, axis_name, batch_axis) -> Optional[str]:
    """Shard the batch over ``batch_axis`` when possible; replicate when the
    axis is absent or the batch is not divisible (e.g. tiny init-tracing
    batches).  The sequence axis is mandatory — raise if L doesn't divide."""
    if q.shape[2] % mesh.shape[axis_name] != 0:
        raise ValueError(
            f"sequence length {q.shape[2]} not divisible by mesh axis "
            f"'{axis_name}' size {mesh.shape[axis_name]}; pad the sequence"
        )
    if not batch_axis or batch_axis not in mesh.axis_names:
        return None
    if q.shape[0] % mesh.shape[batch_axis] != 0:
        return None
    return batch_axis


def _online_softmax_block(o, m, l, scores, v):
    """Fold one [.., Lq, Lk_blk] score block into the flash accumulator."""
    m_blk = jnp.max(scores, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # correction for previously accumulated blocks
    corr = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    # fully-masked blocks: exp(-inf - (-inf)) would be 1; force true zeros
    p = jnp.where(scores > _NEG_INF * 0.5, p, 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(p.dtype)
    )
    return o_new, m_new, l_new


def _ring_shard(q, k, v, kmask, *, axis_name, causal, scale):
    """Per-shard ring attention body (runs inside shard_map).

    q: [B, H, Lq, D] (this device's query block, stays resident)
    k, v: [B, H, Lk, D] (rotating blocks)
    kmask: [B, Lk] 0/1 key-validity (rotates with k/v), or None
    """
    size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    qf = q.astype(jnp.float32)
    scale = jnp.float32(scale)

    q_pos = my_idx * Lq + jnp.arange(Lq)  # global query positions

    o0 = jnp.zeros((B, H, Lq, D), jnp.float32)
    m0 = jnp.full((B, H, Lq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Lq), jnp.float32)
    perm = [(i, (i + 1) % size) for i in range(size)]

    def body(step, carry):
        o, m, l, k, v, kmask = carry
        # which shard's K/V do we currently hold?
        src = (my_idx - step) % size
        scores = jnp.einsum("bhqd,bhkd->bhqk", qf, k.astype(jnp.float32)) * scale
        if kmask is not None:
            scores = jnp.where(kmask[:, None, None, :] > 0, scores, _NEG_INF)
        if causal:
            k_pos = src * Lk + jnp.arange(Lk)
            scores = jnp.where(
                q_pos[:, None] >= k_pos[None, :], scores, _NEG_INF
            )
        o, m, l = _online_softmax_block(o, m, l, scores, v)
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        if kmask is not None:
            kmask = lax.ppermute(kmask, axis_name, perm)
        return o, m, l, k, v, kmask

    o, m, l, *_ = lax.fori_loop(0, size, body, (o0, m0, l0, k, v, kmask))
    # fully-masked rows (all padding) have l == 0; emit zeros, not NaN
    safe_l = jnp.where(l > 0, l, 1.0)
    return (o / safe_l[..., None]).astype(q.dtype)


def _rotate_kv(k, v, km, axis_name, perm):
    """One ring hop: pass K/V (and the rotating key mask) to the neighbor."""
    k = lax.ppermute(k, axis_name, perm)
    v = lax.ppermute(v, axis_name, perm)
    if km is not None:
        km = lax.ppermute(km, axis_name, perm)
    return k, v, km


def _seq_shard_map(body, mesh, qkv_spec, mask_spec, q, k, v, kmask):
    """Dispatch a per-shard attention body through shard_map with the
    standard (q, k, v[, kmask]) signature (kmask=None drops the operand)."""
    if kmask is None:
        fn = shard_map(
            lambda q, k, v: body(q, k, v, None),
            mesh, in_specs=(qkv_spec, qkv_spec, qkv_spec), out_specs=qkv_spec,
        )
        return fn(q, k, v)
    fn = shard_map(
        lambda q, k, v, km: body(q, k, v, km),
        mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec,
    )
    return fn(q, k, v, kmask)


def _lse_merge(o, lse, o_hop, lse_hop):
    """Log-sum-exp merge of two partial attentions.  The finite ``-NEG_INF``
    sentinel keeps every term finite (fully-masked hops get weight
    ``exp(-huge) == 0.0`` exactly)."""
    lse_new = jnp.logaddexp(lse, lse_hop)
    o_new = (
        o * jnp.exp(lse - lse_new)[..., None]
        + o_hop.astype(jnp.float32) * jnp.exp(lse_hop - lse_new)[..., None]
    )
    return o_new, lse_new


def _ring_shard_flash(q, k, v, kmask, *, axis_name, causal, size):
    """Per-shard ring attention with the Pallas flash kernel as the hop math.

    Each hop runs flash attention on the resident Q block against the
    currently-held K/V block (``return_lse``), and partial attentions merge
    via the log-sum-exp combine ``o = o·e^{lse-lse'} + o_hop·e^{lse_hop-lse'}``.
    Gradients flow through both flash outputs (the lse cotangent folds into
    the flash backward kernels — see ``_flash_backward``).

    Hop 0 (the diagonal — this device's own K/V block) runs outside the loop
    so the causal flag can be static (causal-local flash); hops 1..size-1
    share ONE flash instance inside a ``fori_loop`` — compile time and
    executable size stay constant in the axis size.  At hop ``step`` this
    device holds the K/V block of source shard ``(my_idx - step) % size``,
    which for a causal mask contributes fully iff ``step <= my_idx`` (all
    its positions are strictly earlier) — enforced with a traced key mask
    that zeroes non-contributing hops (flash emits lse = -NEG_INF for
    fully-masked rows, making the merge a no-op).
    """
    my_idx = lax.axis_index(axis_name)
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    perm = [(i, (i + 1) % size) for i in range(size)]

    # hop 0: diagonal block, static causal flag
    o_hop, lse_hop = flash_attention(
        q, k, v, kmask, causal=causal, return_lse=True
    )
    o = o_hop.astype(jnp.float32)
    lse = lse_hop

    def body(step, carry):
        o, lse, k, v, km = carry
        k, v, km = _rotate_kv(k, v, km, axis_name, perm)
        hop_mask = km
        if causal:
            valid = (step <= my_idx).astype(jnp.int32)
            vm = jnp.broadcast_to(valid, (B, Lk))
            hop_mask = vm if hop_mask is None else hop_mask * vm
        o_hop, lse_hop = flash_attention(
            q, k, v, hop_mask, causal=False, return_lse=True
        )
        o, lse = _lse_merge(o, lse, o_hop, lse_hop)
        return o, lse, k, v, km

    if size > 1:
        # carry km as an explicit array only when a mask exists; fori_loop
        # needs a uniform carry structure
        if kmask is not None:
            o, lse, *_ = lax.fori_loop(1, size, body, (o, lse, k, v, kmask))
        else:
            def body_nomask(step, carry):
                o, lse, k, v = carry
                o, lse, k2, v2, _ = body(step, (o, lse, k, v, None))
                return o, lse, k2, v2

            o, lse, *_ = lax.fori_loop(1, size, body_nomask, (o, lse, k, v))
    return o.astype(q.dtype)


def ring_attention(
    q, k, v, kmask=None, *, mesh: Mesh, axis_name: str = "seq",
    causal: bool = False, batch_axis: Optional[str] = "data",
    inner: str = "auto",
):
    """Ring attention over sequence shards.

    Args:
        q, k, v: [B, H, L, D] logically-global arrays (sharded over
            ``axis_name`` on the L dim and optionally ``batch_axis`` on B).
        kmask: optional [B, L] key-validity mask (1 = attend).
        mesh: the device mesh holding ``axis_name`` (and ``batch_axis``).
        causal: apply a causal (autoregressive) mask using global positions.
        inner: per-hop kernel — "auto" (flash when the per-shard length
            supports it, else dense), "flash" (Pallas, blockwise), or
            "dense" (einsum reference).

    Returns [B, H, L, D] with the same sharding as ``q``.
    """
    inner = _resolve_inner(inner, q.shape[2] // mesh.shape[axis_name])
    ba = _resolve_batch_axis(q, mesh, axis_name, batch_axis)
    qkv_spec = P(ba, None, axis_name, None)
    mask_spec = P(ba, axis_name)
    if inner == "flash":
        body = functools.partial(
            _ring_shard_flash,
            axis_name=axis_name,
            causal=causal,
            size=mesh.shape[axis_name],
        )
    else:
        body = functools.partial(
            _ring_shard,
            axis_name=axis_name,
            causal=causal,
            scale=1.0 / (q.shape[-1] ** 0.5),
        )
    return _seq_shard_map(body, mesh, qkv_spec, mask_spec, q, k, v, kmask)


# --------------------------------------------------------------------------- #
# zigzag ring attention (causal load balance)
# --------------------------------------------------------------------------- #


def zigzag_permutation(L: int, size: int):
    """Index permutation mapping the natural sequence order to the zigzag
    layout: with 2·size blocks of length L/(2·size), device d's shard is
    ``concat(block_d, block_{2·size-1-d})``.  Apply with
    ``x.take(perm, axis=seq_axis)``; invert with ``inverse_permutation``."""
    if L % (2 * size):
        raise ValueError(
            f"zigzag layout needs L divisible by 2*axis_size = {2 * size}, "
            f"got {L}"
        )
    Lb = L // (2 * size)
    blocks = []
    for d in range(size):
        blocks.append(np.arange(d * Lb, (d + 1) * Lb))
        hi = 2 * size - 1 - d
        blocks.append(np.arange(hi * Lb, (hi + 1) * Lb))
    return np.concatenate(blocks)


def inverse_permutation(perm):
    """Inverse of an index permutation: ``x[perm][inverse_permutation(perm)]
    == x`` (used to undo the zigzag sequence layout host-side)."""
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm))
    return inv


def _zigzag_shard(q, k, v, kmask, *, axis_name, size):
    """Per-shard zigzag causal ring (flash inner).

    The contiguous causal ring is load-IMBALANCED: at hop ``step`` only
    devices with index ≥ step contribute, so half the hop FLOPs are masked
    away on average.  In the zigzag layout device d holds sequence blocks
    ``(d, 2n-1-d)`` — one early, one late — so every device does the same
    causal work at every hop (the ring-flash-attention / striped-attention
    balance trick).

    Per hop the held K/V pair (two blocks) meets the resident Q pair:
    block-level causality is whole-block (full / none) except the two
    diagonal pairs of hop 0, which run as static causal-local flash calls.
    Later hops are three square flash calls — q_lo x k_lo, q_hi x k_lo,
    q_hi x k_hi — with traced whole-block validity masks; the fourth pair
    (q_lo x k_hi) is STATICALLY invisible (a hi key block 2n-1-src >= n can
    never precede a lo query block my <= n-1) and is skipped entirely.
    """
    my = lax.axis_index(axis_name)
    B, H, Lq2, D = q.shape
    Lb = Lq2 // 2
    n = size
    perm = [(i, (i + 1) % n) for i in range(n)]

    my_lo = my
    my_hi = 2 * n - 1 - my

    q_lo, q_hi = q[:, :, :Lb], q[:, :, Lb:]

    def flash_lse(qh, kk, vv, mask, causal_flag):
        return flash_attention(qh, kk, vv, mask, causal=causal_flag,
                               return_lse=True)

    # ---- hop 0: own blocks.  lo×lo and hi×hi are the causal diagonals;
    # hi×lo is fully visible (my_hi > my_lo always); lo×hi contributes
    # nothing.
    k_lo, k_hi = k[:, :, :Lb], k[:, :, Lb:]
    v_lo, v_hi = v[:, :, :Lb], v[:, :, Lb:]
    m_lo = None if kmask is None else kmask[:, :Lb]
    m_hi = None if kmask is None else kmask[:, Lb:]
    o_lo, lse_lo = flash_lse(q_lo, k_lo, v_lo, m_lo, True)
    o_hi, lse_hi = flash_lse(q_hi, k_hi, v_hi, m_hi, True)
    o_hi = o_hi.astype(jnp.float32)
    o_hi, lse_hi = _lse_merge(
        o_hi, lse_hi, *flash_lse(q_hi, k_lo, v_lo, m_lo, False)
    )
    o_lo = o_lo.astype(jnp.float32)

    # ---- hops 1..n-1: held blocks are (src, 2n-1-src); all visibility is
    # whole-block (full or none — a traced scalar), so each (Q half,
    # K half) pair is one square flash call whose key mask broadcasts the
    # pair's validity (an invisible pair yields lse = -NEG_INF and the
    # merge is an exact no-op).
    def body(step, carry):
        o_lo, lse_lo, o_hi, lse_hi, k, v, km = carry
        k, v, km = _rotate_kv(k, v, km, axis_name, perm)
        src = (my - step) % n
        src_blks = (src, 2 * n - 1 - src)
        k_halves = (k[:, :, :Lb], k[:, :, Lb:])
        v_halves = (v[:, :, :Lb], v[:, :, Lb:])
        km_halves = (None, None) if km is None else (km[:, :Lb], km[:, Lb:])

        def pair(o, lse, qh, q_blk, half):
            vis = (src_blks[half] < q_blk).astype(jnp.int32)
            mask = jnp.broadcast_to(vis, (B, Lb))
            if km_halves[half] is not None:
                mask = mask * km_halves[half]
            return _lse_merge(
                o, lse,
                *flash_lse(qh, k_halves[half], v_halves[half], mask, False),
            )

        # q_lo sees only lo key blocks (hi blocks are statically later)
        o_lo, lse_lo = pair(o_lo, lse_lo, q_lo, my_lo, 0)
        o_hi, lse_hi = pair(o_hi, lse_hi, q_hi, my_hi, 0)
        o_hi, lse_hi = pair(o_hi, lse_hi, q_hi, my_hi, 1)
        return o_lo, lse_lo, o_hi, lse_hi, k, v, km

    if n > 1:
        if kmask is not None:
            o_lo, lse_lo, o_hi, lse_hi, *_ = lax.fori_loop(
                1, n, body, (o_lo, lse_lo, o_hi, lse_hi, k, v, kmask)
            )
        else:
            def body_nomask(step, carry):
                o_lo, lse_lo, o_hi, lse_hi, k, v = carry
                o_lo, lse_lo, o_hi, lse_hi, k2, v2, _ = body(
                    step, (o_lo, lse_lo, o_hi, lse_hi, k, v, None)
                )
                return o_lo, lse_lo, o_hi, lse_hi, k2, v2

            o_lo, lse_lo, o_hi, lse_hi, *_ = lax.fori_loop(
                1, n, body_nomask, (o_lo, lse_lo, o_hi, lse_hi, k, v)
            )
    return jnp.concatenate([o_lo, o_hi], axis=2).astype(q.dtype)


def zigzag_ring_attention(
    q, k, v, kmask=None, *, mesh: Mesh, axis_name: str = "seq",
    batch_axis: Optional[str] = "data",
):
    """Load-balanced CAUSAL ring attention over the zigzag layout.

    Inputs must already be in zigzag order along the sequence dim (use
    :func:`zigzag_permutation` once at the data layer — positions/RoPE and
    targets must be permuted consistently); the output is returned in the
    same layout.  Requires ``L % (2·axis_size) == 0``.  Always causal
    (the zigzag layout exists to balance the causal mask's work) and always
    flash-inner.  ``kmask`` follows the same layout.
    """
    L = q.shape[2]
    size = mesh.shape[axis_name]
    if L % (2 * size):
        raise ValueError(
            f"zigzag layout needs L divisible by 2*axis_size = {2 * size}, "
            f"got {L}"
        )
    ba = _resolve_batch_axis(q, mesh, axis_name, batch_axis)
    qkv_spec = P(ba, None, axis_name, None)
    mask_spec = P(ba, axis_name)
    body = functools.partial(_zigzag_shard, axis_name=axis_name, size=size)
    return _seq_shard_map(body, mesh, qkv_spec, mask_spec, q, k, v, kmask)


def _ulysses_shard(q, k, v, kmask, *, axis_name, causal, scale, inner):
    """Per-shard Ulysses body: all_to_all to head-sharding, local attention
    (flash or dense), all_to_all back.  q/k/v: [B, H, Ls, D] with H the FULL
    head count."""
    # [B, H, Ls, D] -> [B, H/n, L, D]: split heads (axis 1), concat seq (axis 2)
    qh = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2, tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2, tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2, tiled=True)
    km = None
    if kmask is not None:
        km = lax.all_gather(kmask, axis_name, axis=1, tiled=True)  # [B, L]
    if inner == "flash":
        # local attention is a full flash call: no [L, L] score tensor, so
        # per-device memory after the all-to-all is O(L·D·H/n) not O(L²·H/n)
        out = flash_attention(qh, kh, vh, km, causal=causal)
    else:
        L = qh.shape[2]
        scores = (
            jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32),
                       kh.astype(jnp.float32)) * scale
        )
        if km is not None:
            scores = jnp.where(km[:, None, None, :] > 0, scores, _NEG_INF)
        if causal:
            pos = jnp.arange(L)
            scores = jnp.where(pos[:, None] >= pos[None, :], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh.astype(jnp.float32))
    # [B, H/n, L, D] -> [B, H, Ls, D]
    out = lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1, tiled=True)
    return out.astype(q.dtype)


def ulysses_attention(
    q, k, v, kmask=None, *, mesh: Mesh, axis_name: str = "seq",
    causal: bool = False, batch_axis: Optional[str] = "data",
    inner: str = "auto",
):
    """DeepSpeed-Ulysses-style all-to-all sequence parallelism (head count
    must be divisible by the mesh axis size).  Same contract as
    :func:`ring_attention`; ``inner`` selects the local attention kernel
    after the all-to-all over the full gathered length ("auto" default =
    flash when supported, "flash", or "dense")."""
    inner = _resolve_inner(inner, q.shape[2])
    size = mesh.shape[axis_name]
    if q.shape[1] % size != 0:
        raise ValueError(
            f"ulysses_attention: heads ({q.shape[1]}) not divisible by "
            f"mesh axis '{axis_name}' size ({size})"
        )
    ba = _resolve_batch_axis(q, mesh, axis_name, batch_axis)
    qkv_spec = P(ba, None, axis_name, None)
    mask_spec = P(ba, axis_name)
    body = functools.partial(
        _ulysses_shard,
        axis_name=axis_name,
        causal=causal,
        scale=1.0 / (q.shape[-1] ** 0.5),
        inner=inner,
    )
    return _seq_shard_map(body, mesh, qkv_spec, mask_spec, q, k, v, kmask)


def _as_model_attention(impl, mesh, axis_name, batch_axis, causal, inner):
    """Adapt ring/ulysses to the ``dense_attention`` signature used by
    stoke_tpu.models.bert (q/k/v [B,H,L,D] + additive bias)."""

    def attention_fn(q, k, v, bias, dropout_rng=None, dropout_rate=0.0,
                     deterministic=True):
        if dropout_rate > 0.0 and not deterministic:
            raise NotImplementedError(
                "sequence-parallel attention does not support attention-prob "
                "dropout; set attention dropout to 0 (residual dropout is fine)"
            )
        kmask = None
        if bias is not None:
            if bias.shape[-2] > 1:
                # a full [.., L, L] bias (an in-model causal mask) would be
                # silently misread as a key mask of its first row — refuse
                raise ValueError(
                    "sequence-parallel attention received a full [.., L, L] "
                    "attention bias (an in-model causal mask?); these "
                    "adapters support only [B, 1, 1, L] key-padding biases "
                    "— set attention_is_causal=True on the model and let "
                    "the attention enforce causality"
                )
            # recover the [B, L] key mask from the additive [B,1,1,L] bias
            kmask = (bias[:, 0, 0, :] > -1e8).astype(jnp.int32)
        return impl(
            q, k, v, kmask, mesh=mesh, axis_name=axis_name,
            causal=causal, batch_axis=batch_axis, inner=inner,
        )

    return attention_fn


def make_ring_attention(
    mesh: Mesh, axis_name: str = "seq", batch_axis: str = "data",
    causal: bool = False, inner: str = "auto",
) -> Callable:
    """Build a ring-attention ``attention_fn`` pluggable into
    ``BertEncoder(attention_fn=...)``."""
    return _as_model_attention(
        ring_attention, mesh, axis_name, batch_axis, causal, inner
    )


def make_ulysses_attention(
    mesh: Mesh, axis_name: str = "seq", batch_axis: str = "data",
    causal: bool = False, inner: str = "auto",
) -> Callable:
    """Build a Ulysses ``attention_fn`` pluggable into
    ``BertEncoder(attention_fn=...)``."""
    return _as_model_attention(
        ulysses_attention, mesh, axis_name, batch_axis, causal, inner
    )


def make_zigzag_ring_attention(
    mesh: Mesh, axis_name: str = "seq", batch_axis: str = "data",
) -> Callable:
    """Build a zigzag-ring ``attention_fn`` (always causal, flash-inner).

    The MODEL must run on zigzag-ordered sequences: permute tokens/masks
    with :func:`zigzag_permutation` at the data layer and pass the
    permutation as the model's position ids (``GPT(..., positions=perm)``)
    so position embeddings follow original positions.  Set
    ``attention_is_causal=True`` — causality is enforced here, by original
    positions."""

    def impl(q, k, v, kmask, *, mesh, axis_name, causal, batch_axis, inner):
        # zigzag is always causal and flash-inner; the extra kwargs exist
        # only to fit the shared adapter signature
        return zigzag_ring_attention(
            q, k, v, kmask, mesh=mesh, axis_name=axis_name,
            batch_axis=batch_axis,
        )

    return _as_model_attention(
        impl, mesh, axis_name, batch_axis, causal=True, inner="flash"
    )
