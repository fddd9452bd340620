"""Block-sparse attention over the paged cache (InfLLM-v2, as in the
MiniCPM4 report, arXiv:2506.07900): each query of a key-value group attends
the ``topk`` 64-token blocks its group chose from pooled keys.

The selection.  Compressed keys, per key-value head: ``c_j = mean(k_{S j}
... k_{S j + K - 1})`` (``K`` the kernel, ``S`` the stride); ``c_j`` exists
for a query at ``t`` once ``S j + K - 1 <= t``.  Per group ``g`` the scores
``s_tj = sum_{h in g} softmax_j(q_th . c_j / sqrt(D))`` over the compressed
keys that exist; a block's score is the largest ``s_tj`` of the windows that
overlap it.  The first ``init_blocks`` blocks and the blocks that hold the
last ``window_size`` positions are always chosen and count toward the
``topk``; the rest are the highest-scored, ties to the lower block.  A
context of at most ``dense_len`` attends every block.  Attention is then
softmax over the positions ``<= t`` of the chosen blocks.

Kernels (each with a plain ``jax.numpy`` form the tests hold it to, and
interpreted off the TPU):

- :func:`compressed_scores` (decode): every slot's one query against the
  slot's compressed keys, read in place to the slot's own length from a store
  of ``[max_seqs, windows, G D]``; returns ``[B, G, windows]``;
- :func:`block_sparse_attention` (decode): each slot and group over the
  chosen blocks of the row plane, a block a page, fetched whole (both
  groups' lanes: one DMA a block) and only once; it counts the words it
  fetched;
- :func:`block_sparse_flash` (prefill): a query tile of ``PREFILL_TILE``
  queries of one group (``PREFILL_TILE x heads a group`` rows) over the union
  of the blocks its queries chose, each query masked to its own selection.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from stoke_tpu.ops.flash_attention import _NEG_INF
from stoke_tpu.ops.sparse_attention import selection_mask

#: chosen blocks a step of the decode and prefill kernels fetches
BLOCKS_PER_STEP = 8
#: queries of a tile of :func:`block_sparse_flash` (times the heads of a
#: group: 256 rows at 16)
PREFILL_TILE = 16
#: queries the prefill selection scores at a time
SELECT_QUERIES = 512
#: compressed keys :func:`compressed_scores` fetches a DMA
_SCORE_CHUNK = 256


def _interpret(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


@dataclasses.dataclass(frozen=True)
class BlockSparse:
    """The selection's settings (``sparse_config``)."""

    kernel_size: int
    kernel_stride: int
    block_size: int
    topk: int
    init_blocks: int
    window_size: int
    dense_len: int

    def windows(self, context):
        """Compressed keys that exist at a context of ``context`` keys."""
        return jnp.maximum(
            (context - self.kernel_size) // self.kernel_stride + 1, 0)

    @property
    def list_len(self) -> int:
        """Entries of a decode choice list: the most blocks a query
        attends, ``topk`` or every block of a dense context."""
        return max(self.topk, -(-self.dense_len // self.block_size))


# --------------------------------------------------------------------------- #
# the selection, plain
# --------------------------------------------------------------------------- #


def compress_keys(k, cfg: BlockSparse):
    """``k [L, G, D]`` -> float32 ``[L / S, G, D]``: ``c_j`` for every ``j``
    (windows that run past ``L`` are left incomplete; nothing reads them)."""
    L = k.shape[0]
    S, K = cfg.kernel_stride, cfg.kernel_size
    if L % S or K % S:
        raise ValueError(f"compress_keys: {L} keys, stride {S}, kernel {K}")
    part = k.astype(jnp.float32).reshape(L // S, S, *k.shape[1:]).sum(1)
    total = part
    for i in range(1, K // S):
        total = total + jnp.pad(part[i:], ((0, i), (0, 0), (0, 0)))
    return total / K


def block_scores(scores, n_blocks: int, cfg: BlockSparse):
    """``scores [..., W]`` (``-inf`` where a compressed key does not exist)
    -> ``[..., n_blocks]``: block ``i``'s score, the largest of the windows
    overlapping its positions ``[i B, i B + B - 1]``."""
    W, B, S, K = scores.shape[-1], cfg.block_size, cfg.kernel_stride, \
        cfg.kernel_size
    rows = []
    for i in range(n_blocks):
        lo = max(0, -(-(i * B - K + 1) // S))
        hi = min(W - 1, (i * B + B - 1) // S)
        rows.append([j for j in range(lo, hi + 1)])
    span = max(max(len(r) for r in rows), 1)
    index = np.full((n_blocks, span), W, np.int32)
    for i, r in enumerate(rows):
        index[i, :len(r)] = r
    padded = jnp.concatenate(
        [scores, jnp.full(scores.shape[:-1] + (1,), -jnp.inf, scores.dtype)],
        axis=-1)
    return padded[..., index].max(-1)


def choose_blocks(scores, context, cfg: BlockSparse):
    """bool ``[..., nb]``: the blocks a query with ``context`` keys (``[...]``
    int32, its position + 1) attends, from its block scores ``[..., nb]``."""
    nb = scores.shape[-1]
    i = jnp.arange(nb, dtype=jnp.int32)
    ctx = context[..., None].astype(jnp.int32)
    live = i * cfg.block_size < ctx
    forced = live & ((i < cfg.init_blocks) | (
        (i + 1) * cfg.block_size > ctx - cfg.window_size))
    key = jnp.where(forced, jnp.inf, jnp.where(live, scores, -jnp.inf))
    flat = key.reshape(-1, nb).astype(jnp.float32)
    top = selection_mask(flat, cfg.topk).reshape(key.shape)
    return jnp.where(ctx <= cfg.dense_len, live, top & live)


def window_scores(q, c, context, cfg: BlockSparse):
    """The scores ``s [..., G, W]`` float32 of queries ``q [..., H, D]``
    against compressed keys ``c [W, G, D]`` (each query's own ``context``
    ``[...]``), ``-inf`` where a compressed key does not exist: the plain
    form of :func:`compressed_scores`."""
    H, D = q.shape[-2:]
    W, G = c.shape[:2]
    qg = q.reshape(q.shape[:-2] + (G, H // G, D))
    logits = jnp.einsum("...ghd,wgd->...ghw", qg, c.astype(q.dtype),
                        preferred_element_type=jnp.float32) * D ** -0.5
    exists = (jnp.arange(W)[None, :]
              < cfg.windows(context.reshape(-1, 1))).reshape(
                  context.shape + (1, 1, W))
    logits = jnp.where(exists, logits, -jnp.inf)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.where(exists, jnp.exp(logits - m), 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return jnp.where(exists[..., 0, :], p.sum(-2), -jnp.inf)


def choice_lists(chosen, length: int):
    """bool ``[..., nb]`` -> ``(blocks [..., length]`` int32, the chosen in
    increasing order then zeros, ``count [...]`` int32)."""
    order = jnp.argsort(~chosen, axis=-1, stable=True)[..., :length]
    count = chosen.sum(-1, dtype=jnp.int32)
    live = jnp.arange(length)[None] < count.reshape(-1, 1)
    return (jnp.where(live.reshape(order.shape), order, 0).astype(jnp.int32),
            count)


# --------------------------------------------------------------------------- #
# decode: the scores of the compressed keys
# --------------------------------------------------------------------------- #


def compressed_scores_reference(q, store, lengths, cfg: BlockSparse):
    """Plain form of :func:`compressed_scores`: ``q [B, H, D]``, ``store
    [B, W, G D]``, ``lengths [B]``."""
    B, W, GD = store.shape
    D = q.shape[-1]
    c = store.reshape(B, W, GD // D, D)
    return jax.vmap(lambda qb, cb, n: window_scores(qb, cb, n, cfg))(
        q, c, lengths.astype(jnp.int32))


def _scores_kernel(len_ref, q_ref, c_ref, o_ref, buf, sem, *, groups, D,
                   chunk, cfg):
    b = pl.program_id(0)
    n = cfg.windows(len_ref[b])
    n_chunks = pl.cdiv(n, chunk)

    @pl.when(b == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)

    def copy(c):
        at = pl.multiple_of(c * chunk, chunk)
        return pltpu.make_async_copy(c_ref.at[b, pl.ds(at, chunk)],
                                     buf.at[pl.ds(at, chunk)], sem)

    jax.lax.fori_loop(0, n_chunks, lambda c, _: copy(c).start(), None)
    jax.lax.fori_loop(0, n_chunks, lambda c, _: copy(c).wait(), None)
    q = q_ref[...]  # [H, D]
    hpg = q.shape[0] // groups
    W = buf.shape[0]
    exists = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1) < n
    for g in range(groups):
        logits = jax.lax.dot_general(
            q[g * hpg:(g + 1) * hpg], buf[:, g * D:(g + 1) * D].astype(q.dtype),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * D ** -0.5  # [hpg, W]
        logits = jnp.where(exists, logits, _NEG_INF)
        m = jnp.max(logits, axis=-1, keepdims=True)
        p = jnp.where(exists, jnp.exp(logits - m), 0.0)
        p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        o_ref[g:g + 1, :] = jnp.where(
            exists, jnp.sum(p, axis=0, keepdims=True), -jnp.inf)


def compressed_scores(q, store, lengths, cfg: BlockSparse, *,
                      interpret: Optional[bool] = None):
    """Each slot's query against its compressed keys, read in place to the
    slot's own length: ``q [B, H, D]`` (normed, compute dtype), ``store [B,
    W, G D]`` (one layer's compressed keys, a row a window, the groups side
    by side), ``lengths [B]`` keys a slot.  Returns float32 ``[B, G, W]``,
    ``-inf`` where a compressed key does not exist."""
    B, H, D = q.shape
    W, GD = store.shape[1:]
    G = GD // D
    chunk = max(c for c in range(1, min(W, _SCORE_CHUNK) + 1) if W % c == 0)
    kernel = functools.partial(_scores_kernel, groups=G, D=D, chunk=chunk,
                               cfg=cfg)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[pl.BlockSpec((None, H, D), lambda b, n: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, G, W), lambda b, n: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((W, GD), store.dtype),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((B, G, W), jnp.float32),
        interpret=_interpret(interpret),
        name="compressed_scores",
    )(lengths.astype(jnp.int32), q, store)


# --------------------------------------------------------------------------- #
# decode: attention over the chosen blocks
# --------------------------------------------------------------------------- #


def block_sparse_attention_reference(q, plane, layer, blocks, logical, count,
                                     lengths, scale):
    """Plain form of :func:`block_sparse_attention`: the chosen pages
    gathered, each query head over its group's."""
    B, H, D = q.shape
    G = plane.shape[-1] // (2 * D)
    BS = plane.shape[2]
    rows = plane[layer][blocks]  # [B, G, K, BS, 2 G D]
    K = blocks.shape[-1]
    keys = jnp.stack([rows[:, g, ..., g * D:(g + 1) * D]
                      for g in range(G)], 1)  # [B, G, K, BS, D]
    vals = jnp.stack([rows[:, g, ..., (G + g) * D:(G + g + 1) * D]
                      for g in range(G)], 1)
    pos = logical[..., None] * BS + jnp.arange(BS)  # [B, G, K, BS]
    live = (jnp.arange(K)[None, None, :, None] < count[..., None, None]) & (
        pos < lengths[:, None, None, None])
    qg = q.reshape(B, G, H // G, D)
    s = jnp.einsum("bghd,bgkpd->bghkp", qg, keys.astype(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(live[:, :, None], s, -jnp.inf)
    s = s.reshape(B, G, H // G, K * BS)
    p = jax.nn.softmax(s, axis=-1).reshape(B, G, H // G, K, BS)
    out = jnp.einsum("bghkp,bgkpd->bghd", p.astype(q.dtype),
                     vals.astype(q.dtype), preferred_element_type=jnp.float32)
    return out.reshape(B, H, D).astype(q.dtype)


def _attend_block(q, k, v, valid, acc, m_sc, l_sc, scale):
    """A run of keys ``k``, ``v [N, D]`` into the online softmax of ``q [R,
    D]``, ``valid [R, N]`` the pairs it may attend."""
    s = jax.lax.dot_general(q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid, s, _NEG_INF)
    m_prev = m_sc[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_sc[:, 0:1] = l_sc[:, 0:1] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_sc[:, 0:1] = m_new
    acc[...] = acc[...] * corr + jax.lax.dot_general(
        p.astype(q.dtype), v.astype(q.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _decode_kernel(len_ref, blk_ref, log_ref, cnt_ref, q_ref, plane_ref,
                   o_ref, words_ref, buf, sems, acc, m_sc, l_sc, issued, *,
                   layer, groups, D, step_blocks, scale):
    """One slot: for each group its chosen pages, ``step_blocks`` a step,
    step ``s + 1``'s fetched while step ``s`` is attended; a page is one DMA
    of the whole ``[BS, 2 G D]`` page, whose words the slot's count adds."""
    b = pl.program_id(0)
    ctx = len_ref[b]
    BS, width = plane_ref.shape[2], plane_ref.shape[3]
    rows = step_blocks * BS
    page_words = BS * width * jnp.dtype(plane_ref.dtype).itemsize // 4
    hpg = q_ref.shape[0] // groups
    issued[0] = 0

    @pl.when(b == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)

    for g in range(groups):
        n = cnt_ref[0, g]
        n_steps = pl.cdiv(n, step_blocks)

        def copies(s, half, act, g=g, n=n):
            for i in range(step_blocks):
                e = s * step_blocks + i

                @pl.when(e < n)
                def _():
                    act(pltpu.make_async_copy(
                        plane_ref.at[layer, blk_ref[g, e]], buf.at[half, i],
                        sems.at[half]))

        def start(dma):
            dma.start()
            issued[0] += page_words

        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

        @pl.when(n_steps > 0)
        def _():
            copies(0, 0, start)

        q = q_ref[g * hpg:(g + 1) * hpg, :]

        def step(s, carry, g=g, n=n, n_steps=n_steps, copies=copies):
            half = s % 2

            @pl.when(s + 1 < n_steps)
            def _():
                copies(s + 1, 1 - half, start)

            copies(s, half, lambda dma: dma.wait())
            # the step's pages as one run of keys: a column's position is
            # its block's first plus its offset; a block past the count
            # starts at the length and is masked whole
            col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
            first = jnp.zeros((1, rows), jnp.int32)
            for i in range(step_blocks):
                e = s * step_blocks + i
                first = jnp.where(col // BS == i, jnp.where(
                    e < n, log_ref[g, e] * BS, ctx), first)
            keys = buf[half, :, :, pl.ds(g * D, D)].reshape(rows, D)
            vals = buf[half, :, :, pl.ds((groups + g) * D, D)].reshape(
                rows, D)
            _attend_block(q, keys, vals, first + col % BS < ctx, acc, m_sc,
                          l_sc, scale)
            return carry

        jax.lax.fori_loop(0, n_steps, step, None)
        l = l_sc[:, 0:1]
        o_ref[g * hpg:(g + 1) * hpg, :] = jnp.where(
            l > 0, acc[...] / jnp.maximum(l, 1e-30), 0.0).astype(o_ref.dtype)
    words_ref[...] = jnp.full(words_ref.shape, issued[0], jnp.int32)


def block_sparse_attention(q, plane, layer, blocks, logical, count, lengths,
                           scale, *, interpret: Optional[bool] = None):
    """Each slot's query heads over the blocks their group chose, read in
    place from the row plane.

    Args:
        q: ``[B, H, D]`` normed queries, compute dtype.
        plane: ``[layers, NB, BS, 2 G D]``: a token's keys, then its values,
            the groups side by side.
        layer: static row of the plane.
        blocks: ``[B, G, K] int32`` pool blocks of the chosen blocks, the
            first ``count`` real.
        logical: ``[B, G, K] int32`` the chosen blocks' places in the
            sequence.
        count: ``[B, G] int32``.  lengths: ``[B]`` keys a slot.
        scale: on the float32 scores.

    Returns ``([B, H, D]`` in the query dtype, ``[B] int32`` the words of
    the plane the kernel's DMAs fetched for each slot)."""
    B, H, D = q.shape
    G = blocks.shape[1]
    K = blocks.shape[2]
    SB = BLOCKS_PER_STEP
    pad = -K % SB
    blocks, logical = (jnp.pad(t.astype(jnp.int32), ((0, 0), (0, 0), (0, pad)))
                       for t in (blocks, logical))
    BS, width = plane.shape[2], plane.shape[3]
    kernel = functools.partial(_decode_kernel, layer=int(layer), groups=G,
                               D=D, step_blocks=SB, scale=float(scale))
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    out, words = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[
                smem((None, G, K + pad), lambda b, n: (b, 0, 0)),
                smem((None, G, K + pad), lambda b, n: (b, 0, 0)),
                smem((None, 1, G), lambda b, n: (b, 0, 0)),
                pl.BlockSpec((None, H, D), lambda b, n: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((None, H, D), lambda b, n: (b, 0, 0)),
                pl.BlockSpec((None, 1, 128), lambda b, n: (b, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, SB, BS, width), plane.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((H // G, D), jnp.float32),
                pltpu.VMEM((H // G, 128), jnp.float32),
                pltpu.VMEM((H // G, 128), jnp.float32),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, D), q.dtype),
                   jax.ShapeDtypeStruct((B, 1, 128), jnp.int32)],
        interpret=_interpret(interpret),
        name="block_sparse_attention",
    )(lengths.astype(jnp.int32), blocks, logical,
      count.astype(jnp.int32).reshape(B, 1, G), q, plane)
    return out, words[:, 0, 0]


# --------------------------------------------------------------------------- #
# prefill: the selection a query tile at a time, and flash attention over it
# --------------------------------------------------------------------------- #


def prefill_choices(q, c, length, cfg: BlockSparse, tile: int = PREFILL_TILE):
    """The blocks each query tile of a prompt visits.

    ``q [L, H, D]`` normed queries, ``c [L / S, G, D]`` the prompt's
    compressed keys as cached, ``length`` (int32 scalar) the prompt's
    length; the queries at and past it choose nothing.  ``SELECT_QUERIES``
    queries at a time, in a loop: their scores ``[queries, G, W]``, block
    scores and choice; no ``[L, L]`` or ``[L, L / S]`` array.

    Returns ``(visited [G, T, nb]`` int32, each tile's union of the blocks
    its queries chose in increasing order then zeros, ``words [G, T, nb]``
    int32: bit ``r`` of entry ``e`` says whether query ``r`` of the tile
    chose block ``visited[..., e]``, ``count [G, T]`` int32, ``tiles [2]``
    int32: the tiles visited and the causal tiles, summed over the groups
    and the tiles that hold a query of the prompt)."""
    L, H, D = q.shape
    G = c.shape[1]
    BS = cfg.block_size
    nb = L // BS
    rows = min(SELECT_QUERIES, L)
    if L % rows or rows % tile or L % BS:
        raise ValueError(f"prefill_choices: {L} queries, blocks of {rows}, "
                         f"tiles of {tile}, key blocks of {BS}")

    def one(i):
        t = i * rows + jnp.arange(rows, dtype=jnp.int32)
        qb = jax.lax.dynamic_slice_in_dim(q, i * rows, rows, axis=0)
        s = window_scores(qb, c, t + 1, cfg)  # [rows, G, W]
        chosen = choose_blocks(block_scores(s, nb, cfg),
                               jnp.broadcast_to((t + 1)[:, None], (rows, G)),
                               cfg) & (t < length)[:, None, None]
        bits = chosen.reshape(rows // tile, tile, G, nb).astype(jnp.int32)
        return (bits << jnp.arange(tile, dtype=jnp.int32)[None, :, None, None]
                ).sum(1)  # [rows / tile, G, nb]

    words = jax.lax.map(one, jnp.arange(L // rows, dtype=jnp.int32))
    words = jnp.moveaxis(words.reshape(L // tile, G, nb), 1, 0)
    visited, count = choice_lists(words != 0, nb)
    words = jnp.take_along_axis(words, visited, axis=-1)
    T = L // tile
    first = jnp.arange(T, dtype=jnp.int32) * tile
    real = first < length
    causal = (jnp.minimum(first + tile, length) - 1) // BS + 1
    tiles = jnp.stack([count.sum(dtype=jnp.int32),
                       G * jnp.where(real, causal, 0).sum(dtype=jnp.int32)])
    return visited, words, count, tiles


def block_sparse_flash_reference(q, k, v, visited, words, count, scale,
                                 block_size: int):
    """Plain form of :func:`block_sparse_flash`: ``q [L, G, hpg, D]``, ``k``,
    ``v [G, L, D]``, the lists of :func:`prefill_choices`; each query over
    the positions ``<=`` its own of the blocks its bit marks."""
    L, G, hpg, D = q.shape
    T, nb = visited.shape[1:]
    tile = L // T
    live = jnp.arange(nb)[None, None] < count[..., None]  # [G, T, e]
    bit = (words[..., None] >> jnp.arange(tile)) & 1  # [G, T, e, tile]
    entry = (visited[..., None] == jnp.arange(nb)) & live[..., None]
    chosen = jnp.einsum("gteb,gter->gtrb", entry.astype(jnp.int32),
                        bit.astype(jnp.int32)) > 0  # [G, T, tile, nb]
    mask = jnp.repeat(chosen.reshape(G, L, nb), block_size, axis=-1)
    mask = mask & jnp.tril(jnp.ones((L, L), bool))[None]
    s = jnp.einsum("lghd,gkd->glhk", q, k.astype(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[:, :, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask[:, :, None], jnp.exp(s - jnp.where(
        jnp.isfinite(m), m, 0.0)), 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("glhk,gkd->lghd", p.astype(q.dtype), v.astype(q.dtype),
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _flash_kernel(cnt_ref, vis_ref, w_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf,
                  vbuf, sems, acc, m_sc, l_sc, *, tiles, tile, BS,
                  step_blocks, scale):
    g, t = pl.program_id(0), pl.program_id(1)
    n = cnt_ref[g * tiles + t]
    n_steps = pl.cdiv(n, step_blocks)
    _, hpg, D = q_ref.shape
    R = tile * hpg

    @pl.when((g == 0) & (t == 0))
    def _():
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def copies(s, half, act):
        for i in range(step_blocks):
            e = s * step_blocks + i

            @pl.when(e < n)
            def _():
                at = pl.multiple_of(vis_ref[0, e] * BS, BS)
                act(pltpu.make_async_copy(k_hbm.at[g, pl.ds(at, BS)],
                                          kbuf.at[half, i], sems.at[0, half]))
                act(pltpu.make_async_copy(v_hbm.at[g, pl.ds(at, BS)],
                                          vbuf.at[half, i], sems.at[1, half]))

    acc[...] = jnp.zeros_like(acc)
    m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)

    @pl.when(n_steps > 0)
    def _():
        copies(0, 0, lambda dma: dma.start())

    q = q_ref[...].reshape(R, D)
    row = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) // hpg
    qpos = t * tile + row
    keys_a_step = step_blocks * BS
    col = jax.lax.broadcasted_iota(jnp.int32, (1, keys_a_step), 1)

    def step(s, carry):
        half = s % 2

        @pl.when(s + 1 < n_steps)
        def _():
            copies(s + 1, 1 - half, lambda dma: dma.start())

        copies(s, half, lambda dma: dma.wait())
        # the step's blocks as one run of keys: each column takes its
        # block's word and first position
        word = jnp.zeros((1, keys_a_step), jnp.int32)
        first = jnp.zeros((1, keys_a_step), jnp.int32)
        for i in range(step_blocks):
            e = s * step_blocks + i
            mine = col // BS == i
            word = jnp.where(mine, jnp.where(e < n, w_ref[0, e], 0), word)
            first = jnp.where(mine, vis_ref[0, jnp.minimum(e, n - 1)] * BS,
                              first)
        chosen = jax.lax.shift_right_logical(
            jnp.broadcast_to(word, (R, keys_a_step)),
            jnp.broadcast_to(row, (R, keys_a_step))) & 1
        _attend_block(q, kbuf[half].reshape(keys_a_step, D),
                      vbuf[half].reshape(keys_a_step, D),
                      (chosen > 0) & (first + col % BS <= qpos), acc, m_sc,
                      l_sc, scale)
        return carry

    jax.lax.fori_loop(0, n_steps, step, None)
    l = l_sc[:, 0:1]
    out = jnp.where(l > 0, acc[...] / jnp.maximum(l, 1e-30), 0.0)
    o_ref[...] = out.reshape(tile, hpg, D).astype(o_ref.dtype)


def block_sparse_flash(q, k, v, visited, words, count, scale, block_size: int,
                       *, interpret: Optional[bool] = None):
    """Causal attention of a prompt's queries over the blocks they chose:
    ``q [L, G, hpg, D]`` (the heads in their groups, as projected), ``k``,
    ``v [G, L, D]``, and :func:`prefill_choices`'s lists.  Grid (group, query
    tile); a tile fetches the blocks of its union, ``BLOCKS_PER_STEP`` a
    step, double buffered, and masks each query to its own choice and
    causality.  A query that chose nothing reads zeros.  Returns ``[L, G,
    hpg, D]``."""
    L, G, hpg, D = q.shape
    T, nb = visited.shape[1:]
    tile = L // T
    SB = BLOCKS_PER_STEP
    kernel = functools.partial(_flash_kernel, tiles=T, tile=tile,
                               BS=block_size, step_blocks=SB,
                               scale=float(scale))
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(G, T),
            in_specs=[
                smem((None, 1, nb), lambda g, t, c: (g * T + t, 0, 0)),
                smem((None, 1, nb), lambda g, t, c: (g * T + t, 0, 0)),
                pl.BlockSpec((tile, None, hpg, D),
                             lambda g, t, c: (t, g, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tile, None, hpg, D),
                                   lambda g, t, c: (t, g, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, SB, block_size, D), k.dtype),
                pltpu.VMEM((2, SB, block_size, D), v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((tile * hpg, D), jnp.float32),
                pltpu.VMEM((tile * hpg, 128), jnp.float32),
                pltpu.VMEM((tile * hpg, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((L, G, hpg, D), q.dtype),
        interpret=_interpret(interpret),
        name="block_sparse_flash",
    )(count.astype(jnp.int32).reshape(-1), visited.reshape(G * T, 1, nb),
      words.reshape(G * T, 1, nb), q, k, v)
