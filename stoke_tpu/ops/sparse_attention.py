"""Learned sparse attention (DeepSeek-V3.2's lightning indexer, "DSA") over
the paged cache: the indexer's scores of a query against its keys, the exact
top-k selection, and latent attention over the chosen rows only.

An indexer scores query ``t`` against every key ``s <= t``: ``I_ts =
scale * sum_j w_tj ReLU(q_tj . k_s)`` over its heads ``j``; the attention then
reads the ``index_topk`` keys of highest score, ties to the lower position.
Three Pallas kernels, each with the plain ``jax.numpy`` form the tests hold it
to, and interpreted off the TPU:

- :func:`index_scores` (decode): every slot's one query against the keys of
  the indexer plane ``[layers, NB, BS, D]``, read in place to the slot's own
  length, a group of pages a step, double-buffered as
  ``flash_attention.latent_paged_attention`` reads the latent plane.  Returns
  ``[B, 1, MAX_BLOCKS * BS]`` float32 scores, ``-inf`` past the length;
- :func:`sparse_latent_attention` (decode): absorbed MLA plus a sink a head
  over the CHOSEN rows of the latent plane, each row fetched by its own DMA.
  A row DMA must cover whole tiles of the array's layout, and a bfloat16
  plane ``[..., BS, 576-640]`` is tiled 8 (or 2) rows deep: a row alone is
  no legal slice of it.  So the plane a sparse model reads row by row is
  kept as 32-bit words, one row a ``[1, W]`` slice (tiled ``(1, 128)``: the
  row is whole tiles), two 16-bit values a word
  (:func:`pack_rows`); the kernel unpacks a word into its two values
  exactly.  A step takes a group of rows, all issued on one semaphore and
  waited for once.  Issuing the rows, not their bytes, bounds the kernel,
  so a row costs a few scalar operations: its place a multiply and an add
  in the plane viewed as ``[layers, NB * BS, 1, W]``, its address clamped
  to the plane in place of Mosaic's bounds checks, and the starts written
  out ``SPARSE_ISSUE_UNROLL`` to a trip of the issue loop, by hand, because
  Mosaic lowers ``fori_loop(..., unroll=k)`` only for ``k`` 1 or the whole
  loop;
- :func:`sparse_flash_attention` (prefill): causal flash attention of
  expanded heads with the selection as a bit mask, 32 queries a word
  (:func:`selection_bits`), and a sink a head.

The selection is exact (``jax.lax.top_k``, which returns equal values lower
index first), not ``approx_max_k``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from stoke_tpu.ops.flash_attention import (
    _NEG_INF,
    _current_partition,
    _partition_rows,
)

#: bytes of indexer pages :func:`index_scores` holds in each of its two VMEM
#: buffers (128 pages of [16, 128] bfloat16)
_INDEX_GROUP_BYTES = 512 * 1024
#: rows :func:`sparse_latent_attention` fetches a step
SPARSE_ROWS_PER_STEP = 128
#: row DMAs one trip of a step's issue loop starts, written out at most
#: (:func:`issue_unroll`): a whole step's, the fewest scalar bundles a row
SPARSE_ISSUE_UNROLL = 128
#: query and key block of :func:`sparse_flash_attention`
_FLASH_BLOCK_Q = 512
_FLASH_BLOCK_K = 1024


def _interpret(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


# --------------------------------------------------------------------------- #
# the plane of 32-bit words
# --------------------------------------------------------------------------- #


def packed_width(values: int, dtype) -> int:
    """Words a row of ``values`` values of ``dtype`` takes in a packed plane:
    two 16-bit values a word or one 32-bit value, up to whole 128-lane
    tiles."""
    per_word = 4 // jnp.dtype(dtype).itemsize
    if per_word not in (1, 2):
        raise ValueError(f"a packed plane holds 16- or 32-bit values, not "
                         f"{jnp.dtype(dtype)}")
    return -(-values // per_word // 128) * 128


def pack_rows(rows, width: int, dtype):
    """``rows [N, C]`` as ``uint32 [N, width]``, in ``dtype``: for a 16-bit
    ``dtype`` word ``j`` holds value ``j`` in its low half and value ``j +
    width`` in its high half; for a 32-bit one it is value ``j``'s bits.
    Values past ``C`` are zeros."""
    dtype = jnp.dtype(dtype)
    per_word = 4 // dtype.itemsize
    rows = rows.astype(dtype)
    rows = jnp.pad(rows, ((0, 0), (0, per_word * width - rows.shape[-1])))
    if per_word == 1:
        return jax.lax.bitcast_convert_type(rows, jnp.uint32)
    half = jax.lax.bitcast_convert_type(rows, jnp.uint16).astype(jnp.uint32)
    return half[:, :width] | (half[:, width:] << 16)


def unpack_rows(words, dtype):
    """:func:`pack_rows` undone: ``[..., width]`` words -> ``[..., per_word *
    width]`` values of ``dtype``."""
    dtype = jnp.dtype(dtype)
    if dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(words, dtype)
    lo = (words & 0xFFFF).astype(jnp.uint16)
    hi = (words >> 16).astype(jnp.uint16)
    return jax.lax.bitcast_convert_type(
        jnp.concatenate([lo, hi], axis=-1), dtype)


def _word_values(words, pairs: bool, dtype):
    """In a kernel: ``[G, W]`` words -> the tuple of ``[G, W]`` value blocks
    they hold (low halves, then high halves), exactly, in ``dtype``."""
    if not pairs:
        return (jax.lax.bitcast_convert_type(words, jnp.float32).astype(dtype),)
    lo = jax.lax.bitcast_convert_type(words << 16, jnp.float32)
    hi = jax.lax.bitcast_convert_type(words & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return lo.astype(dtype), hi.astype(dtype)


def _write_rows_kernel(addr_ref, rows_ref, plane_in, plane_ref, sem, *,
                       layer, block_size, n):
    del plane_in  # aliased to ``plane_ref``

    # a row's place by block and offset, not through the pool-row view that
    # :func:`_sparse_kernel` reads: the interpreter writes through no
    # reshaped ref, and a few rows a step cost nothing seen
    def one(i, carry):
        a = addr_ref[i]
        pltpu.make_async_copy(
            rows_ref.at[i], plane_ref.at[layer, a // block_size,
                                         a % block_size], sem).start()
        return carry

    jax.lax.fori_loop(0, n, one, None)
    # the semaphore counts bytes: one wait for all ``n`` rows
    pltpu.make_async_copy(rows_ref, rows_ref, sem).wait()


def write_rows(words, layer, addr, rows, *,
               interpret: Optional[bool] = None):
    """``words [layers, NB, BS, 1, W]`` with ``rows [N, W]`` (uint32,
    :func:`pack_rows`) written at pool rows ``addr [N]`` (``block * BS +
    offset``) of ``layer``, in place: the caller donates ``words``.  A row
    a DMA.  XLA's scatter would drop the unit dimension and work on the
    plane in another tiling, copying all of it twice a call."""
    N, W = rows.shape
    kernel = functools.partial(_write_rows_kernel, layer=int(layer),
                               block_size=int(words.shape[2]), n=N)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct(words.shape, words.dtype),
        input_output_aliases={2: 0},
        interpret=_interpret(interpret),
        name="write_latent_rows",
    )
    part = _current_partition()
    part = None if part is None else (part[0], ())
    return _partition_rows(call, part, 1)(
        addr.astype(jnp.int32), rows[:, None, :], words)


# --------------------------------------------------------------------------- #
# selection
# --------------------------------------------------------------------------- #


def select_topk(scores, lengths, k: int):
    """``scores [N, T]`` float32 (``-inf`` past each row's length) ->
    ``(positions [N, k] int32, count [N] int32)``: the ``k`` highest, equal
    scores lower position first; ``count = min(lengths, k)`` of them are
    real, the rest are past the length."""
    k = min(k, scores.shape[-1])
    _, positions = jax.lax.top_k(scores, k)
    return positions.astype(jnp.int32), jnp.minimum(
        lengths.astype(jnp.int32), k)


def row_addresses(positions, count, block_tables, block_size: int):
    """Where the chosen positions lie in the pool: ``block * BS + offset``
    a row (``[N, k]`` int32), row 0 of the scratch block past ``count``."""
    blocks = jnp.take_along_axis(block_tables, positions // block_size,
                                 axis=1)
    addr = blocks * block_size + positions % block_size
    live = jnp.arange(positions.shape[1])[None, :] < count[:, None]
    return jnp.where(live, addr, 0).astype(jnp.int32)


def _ordered_keys(scores):
    """float32 -> uint32 in the same order (``-inf`` lowest)."""
    u = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def selection_mask(scores, k: int):
    """``scores [N, T]`` (``-inf`` where not a candidate) -> bool ``[N, T]``:
    the top ``k`` of each row, equal scores lower position first; every
    candidate where a row has no more than ``k``.

    The ``k``-th highest score is found exactly by bisection over the bits
    of the scores' order-keeping 32-bit keys (32 passes of compare-and-count
    over the row); the equal scores after the higher ones are taken from
    the lowest position.  A sort (``jax.lax.top_k``) of ``[512, 24,576]``
    took 20 ms on the v5e, this about a tenth of it."""
    k = min(k, scores.shape[-1])
    keys = _ordered_keys(scores)

    def bit(i, kth):
        trial = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        many = (keys >= trial).sum(axis=-1, keepdims=True, dtype=jnp.int32)
        return jnp.where(many >= k, trial, kth)

    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(keys.shape[:-1] + (1,), jnp.uint32))
    above = keys > kth
    tied = keys == kth
    # of the scores equal to the k-th, the lowest positions fill what the
    # higher scores leave of k
    room = k - above.sum(axis=-1, keepdims=True, dtype=jnp.int32)
    take = tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= room)
    return (above | take) & (scores > -jnp.inf)


def query_block(L: int) -> int:
    """Queries of one block of :func:`sparse_flash_attention` and of one
    word block of :func:`selection_bits` over a prompt of ``L``."""
    block = min(_FLASH_BLOCK_Q, L)
    if L % block or block % 256:
        raise ValueError(f"a prompt of {L} positions is no multiple of its "
                         f"query block {block} (a multiple of 256)")
    return block


def selection_bits(mask, block: int):
    """bool ``[Lq, Lk]`` -> int32 ``[Lq / 32, Lk]``: of each block of
    ``block`` queries (:func:`query_block`), word row ``w`` holds in bit
    ``j`` query ``j * block / 32 + w``'s selection of the key, so that a
    kernel that reads the block's ``block / 32`` rows and stacks ``(words >>
    j) & 1`` over ``j`` has the queries in order."""
    Lq, Lk = mask.shape
    R = block // 32
    m = mask.reshape(Lq // block, 32, R, Lk).astype(jnp.uint32)
    bits = (m << jnp.arange(32, dtype=jnp.uint32)[None, :, None, None]).sum(
        axis=1, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(bits, jnp.int32).reshape(
        Lq // 32, Lk)


def bits_mask(bits, block: int):
    """:func:`selection_bits` undone: bool ``[Lq, Lk]``."""
    R = block // 32
    words = jax.lax.bitcast_convert_type(bits, jnp.uint32).reshape(
        -1, 1, R, bits.shape[-1])
    j = jnp.arange(32, dtype=jnp.uint32)[None, :, None, None]
    return ((words >> j) & 1).astype(bool).reshape(-1, bits.shape[-1])


# --------------------------------------------------------------------------- #
# the indexer's scores, decode
# --------------------------------------------------------------------------- #


def index_scores_reference(q, w, plane, layer, block_tables, context_lens,
                           scale):
    """Plain form of :func:`index_scores`: each slot's window gathered."""
    B, MB = block_tables.shape
    BS = plane.shape[2]
    keys = plane[layer][block_tables].reshape(B, MB * BS, -1)
    s = jnp.einsum("bhd,btd->bht", q, keys.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    s = (jnp.maximum(s, 0.0) * w).sum(axis=1) * scale
    valid = jnp.arange(MB * BS)[None, :] < context_lens[:, None]
    return jnp.where(valid, s, -jnp.inf)[:, None, :]


def _index_kernel(tables_ref, lens_ref, q_ref, w_ref, plane_ref, o_ref,
                  buf, sems, *, layer, block_size, group, max_blocks, scale):
    """One slot of :func:`index_scores`: the slot's live pages of the
    indexer plane, ``group`` a step, step ``g + 1``'s fetched while step
    ``g`` is scored; positions past the length read ``-inf``."""
    b = pl.program_id(0)
    n_ctx = lens_ref[b]
    n_blocks = jnp.minimum(pl.cdiv(n_ctx, block_size), max_blocks)
    n_groups = pl.cdiv(n_blocks, group)
    tokens = group * block_size

    def live_pages(g, half, act):
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
        buf[...] = jnp.zeros_like(buf)

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)

    @pl.when(n_groups > 0)
    def _():
        live_pages(0, 0, lambda dma: dma.start())

    q = q_ref[...]  # [heads, D]
    w = w_ref[...]  # [heads, 1] float32

    def step(g, carry):
        half = g % 2

        @pl.when(g + 1 < n_groups)
        def _():
            live_pages(g + 1, 1 - half, lambda dma: dma.start())

        live_pages(g, half, lambda dma: dma.wait())
        s = jax.lax.dot_general(
            q, buf[half].astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [heads, tokens]
        row = jnp.sum(jnp.maximum(s, 0.0) * w, axis=0, keepdims=True) * scale
        pos = g * tokens + jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
        start = pl.multiple_of(g * tokens, tokens)
        o_ref[:, pl.ds(start, tokens)] = jnp.where(pos < n_ctx, row, -jnp.inf)
        return carry

    jax.lax.fori_loop(0, n_groups, step, None)


def index_scores(q, w, plane, layer, block_tables, context_lens, scale, *,
                 interpret: Optional[bool] = None):
    """The indexer's score of each slot's one query against the slot's keys,
    read in place from ``plane``: the decode form.

    Args:
        q: ``[B, heads, D]`` indexer queries (roped), in the compute dtype.
        w: ``[B, heads, 1]`` float32 head weights.
        plane: ``[layers, NB, BS, D]`` indexer plane of the pool.
        layer: static row of the plane.
        block_tables: ``[B, MAX_BLOCKS] int32``.
        context_lens: ``[B] int32`` keys a slot, the current one included.
        scale: on the float32 scores.

    Returns float32 ``[B, 1, MAX_BLOCKS * BS]``, ``-inf`` at and past each
    slot's length."""
    B, heads, D = q.shape
    BS, MB = int(plane.shape[2]), int(block_tables.shape[1])
    budget = max(1, _INDEX_GROUP_BYTES // (BS * D * plane.dtype.itemsize))
    group = max(g for g in range(1, min(MB, budget) + 1) if MB % g == 0)
    kernel = functools.partial(
        _index_kernel, layer=int(layer), block_size=BS, group=group,
        max_blocks=MB, scale=float(scale))
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((None, heads, D), lambda b, tbl, lens: (b, 0, 0)),
                pl.BlockSpec((None, heads, 1), lambda b, tbl, lens: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, 1, MB * BS),
                                   lambda b, tbl, lens: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, group * BS, D), plane.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, 1, MB * BS), jnp.float32),
        interpret=_interpret(interpret),
        name="index_scores",
    )
    part = _current_partition()
    part = None if part is None else (part[0], ())
    return _partition_rows(call, part, B)(
        block_tables.astype(jnp.int32).reshape(-1),
        context_lens.astype(jnp.int32), q, w.astype(jnp.float32), plane,
    )


# --------------------------------------------------------------------------- #
# latent attention over the chosen rows, decode
# --------------------------------------------------------------------------- #


def sparse_latent_reference(q_row, words, layer, addr, count, sinks, scale):
    """Plain form of :func:`sparse_latent_attention`: the chosen rows
    gathered and unpacked, softmax with a sink a head."""
    L, NB, BS = words.shape[:3]
    flat = words[layer].reshape(NB * BS, -1)
    rows = unpack_rows(flat[addr], q_row.dtype)  # [B, K, 2W or W]
    s = jnp.einsum("bhc,bkc->bhk", q_row, rows,
                   preferred_element_type=jnp.float32) * scale
    live = jnp.arange(addr.shape[1])[None, None, :] < count[:, None, None]
    s = jnp.where(live, s, -jnp.inf)
    m = jnp.maximum(s.max(-1, keepdims=True), sinks[None, :, None])
    p = jnp.where(live, jnp.exp(s - m), 0.0)
    denom = p.sum(-1, keepdims=True) + jnp.exp(sinks[None, :, None] - m)
    out = jnp.einsum("bhk,bkc->bhc", (p / denom).astype(q_row.dtype), rows,
                     preferred_element_type=jnp.float32)
    return out.astype(q_row.dtype)


def issue_unroll(group: int) -> int:
    """Row DMAs one trip of :func:`sparse_latent_attention`'s issue loop
    starts for a step of ``group`` rows: the largest divisor of ``group``
    not above ``SPARSE_ISSUE_UNROLL``."""
    return max(u for u in range(1, min(SPARSE_ISSUE_UNROLL, group) + 1)
               if group % u == 0)


def _sparse_kernel(count_ref, addr_ref, q_ref, sink_ref, plane_ref, o_ref,
                   issued_ref, buf, sems, acc, m_sc, l_sc, issued, *, layer,
                   group, unroll, pairs, scale):
    """One slot of :func:`sparse_latent_attention`: its chosen rows,
    ``group`` a step, each row by its own DMA and the step's rows waited for
    at once (the semaphore counts bytes); step ``g + 1``'s rows fetched while
    step ``g`` is scored.  The last step's rows past ``count`` are row 0 of
    the scratch block (``row_addresses``), fetched and masked.  Every row
    DMA started adds the words of its source to the slot's count, written
    to ``issued_ref``.

    Issuing a row is what bounds the kernel, not its bytes, so a row costs
    a few scalar operations.  Its place is a multiply and an add: the plane
    is viewed as ``[layers, NB * BS, 1, W]`` and indexed by pool row, which
    moves nothing (blocks and offsets are untiled leading dimensions) and
    needs no block and offset: those take a signed division and remainder
    with a dozen sign fix-ups.  ``unroll`` starts are written out in each trip of the
    issue loop, by hand: Mosaic lowers ``fori_loop(..., unroll=k)`` only for
    ``k`` 1 or the whole loop.  And the kernel is compiled without Mosaic's
    bounds checks, which took more scalar operations a row than the rest of
    its issue: the one index no loop bounds, a row's address, is clamped to
    the plane as an unsigned number instead, so no DMA reads outside it (the
    kernel only reads the plane)."""
    b = pl.program_id(0)
    n = count_ref[b]
    n_groups = pl.cdiv(n, group)
    issued[0] = 0
    L, NB, BS, one, W = plane_ref.shape
    plane = plane_ref.reshape(L, NB * BS, one, W)
    last_row = jnp.uint32(NB * BS - 1)

    def fetch(g, half):
        first = g * group

        def rows(t, words):
            at = t * unroll
            for i in range(unroll):
                a = addr_ref[0, first + at + i].astype(jnp.uint32)
                src = plane.at[layer,
                               jnp.minimum(a, last_row).astype(jnp.int32)]
                pltpu.make_async_copy(src, buf.at[half, at + i],
                                      sems.at[half]).start()
                words += math.prod(src.shape)
            return words

        issued[0] += jax.lax.fori_loop(0, group // unroll, rows, 0)

    @pl.when(b == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)

    acc[...] = jnp.zeros_like(acc)
    m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)

    @pl.when(n_groups > 0)
    def _():
        fetch(0, 0)

    q = q_ref[...]  # [H, per_word * W]

    def step(g, carry):
        half = g % 2

        @pl.when(g + 1 < n_groups)
        def _():
            fetch(g + 1, 1 - half)

        pltpu.make_async_copy(buf.at[half], buf.at[half], sems.at[half]).wait()
        parts = _word_values(buf[half][:, 0, :], pairs, q.dtype)
        s = sum(
            jax.lax.dot_general(
                q[:, i * W:(i + 1) * W], part, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            for i, part in enumerate(parts)) * scale  # [H, group]
        live = g * group + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < n
        s = jnp.where(live, s, _NEG_INF)
        m_prev = m_sc[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        prob = jnp.where(live, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_sc[:, 0:1] = l_sc[:, 0:1] * corr + jnp.sum(prob, axis=-1,
                                                     keepdims=True)
        m_sc[:, 0:1] = m_new
        prob = prob.astype(q.dtype)
        for i, part in enumerate(parts):
            acc[:, i * W:(i + 1) * W] = acc[:, i * W:(i + 1) * W] * corr + (
                jax.lax.dot_general(prob, part, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32))
        return carry

    jax.lax.fori_loop(0, n_groups, step, None)
    m = m_sc[:, 0:1]
    l = l_sc[:, 0:1] + jnp.exp(sink_ref[...] - m)
    o_ref[...] = (acc[...] / l).astype(o_ref.dtype)
    issued_ref[...] = jnp.full(issued_ref.shape, issued[0], jnp.int32)


def sparse_latent_attention(q_row, words, layer, addr, count, sinks, scale,
                            *, interpret: Optional[bool] = None):
    """Absorbed MLA of each slot's query over the rows its selection chose,
    with a sink a head: ``p_s = e^{z_s} / (e^{sink_h} + sum e^{z})``.

    Args:
        q_row: ``[B, H, per_word * W]`` absorbed queries in the compute
            dtype, values in their natural order (zeros past the row).
        words: ``[layers, NB, BS, 1, W]`` uint32 latent plane
            (:func:`pack_rows`, ``per_word`` values a word).
        layer: static row of the plane.
        addr: ``[B, K] int32`` pool rows (``block * BS + offset``) of the
            chosen positions (:func:`row_addresses`).
        count: ``[B] int32`` real entries of ``addr`` a slot.
        sinks: ``[H]`` float32.
        scale: on the float32 scores.

    Returns ``([B, H, per_word * W]`` in the query dtype: the probabilities
    times the whole rows (the caller keeps the latent lanes), ``[B] int32``:
    the words of ``words`` the kernel's DMAs fetched for each slot).  The
    count is the kernel's own, summed over the copies it started, each at
    its source's size: it is whatever its grid and steps fetched."""
    B, H, width = q_row.shape
    W = int(words.shape[-1])
    pairs = width == 2 * W
    K = addr.shape[1]
    group = min(SPARSE_ROWS_PER_STEP, K)
    pad = -K % group
    addr = jnp.pad(addr.astype(jnp.int32), ((0, 0), (0, pad)))[:, None, :]
    kernel = functools.partial(
        _sparse_kernel, layer=int(layer), group=group,
        unroll=issue_unroll(group), pairs=pairs, scale=float(scale))
    slot_rows = pl.BlockSpec((None, H, width), lambda b, n: (b, 0, 0))
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((None, 1, K + pad), lambda b, n: (b, 0, 0),
                             memory_space=pltpu.SMEM),
                slot_rows,
                pl.BlockSpec((H, 1), lambda b, n: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                slot_rows,
                pl.BlockSpec((None, 1, 128), lambda b, n: (b, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, group, 1, W), jnp.uint32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((H, width), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, width), q_row.dtype),
            jax.ShapeDtypeStruct((B, 1, 128), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(disable_bounds_checks=True),
        interpret=_interpret(interpret),
        name="sparse_latent_attention",
    )
    part = _current_partition()
    part = None if part is None else (part[0], ())
    out, issued = _partition_rows(call, part, B)(
        count.astype(jnp.int32), addr, q_row,
        sinks.astype(jnp.float32).reshape(H, 1), words,
    )
    return out, issued[:, 0, 0]


# --------------------------------------------------------------------------- #
# prefill: the selection, and flash attention under it
# --------------------------------------------------------------------------- #


def _prefill_index_kernel(q0_ref, q_ref, w_ref, k_ref, o_ref, acc, *,
                          scale, rows, block_k):
    """Scores of ``rows`` queries (positions ``q0`` ...) against one block
    of keys: a head at a time, ``ReLU(q_j k^T) w_j`` summed; keys after the
    query ``-inf``."""
    ki = pl.program_id(0)
    kb = k_ref[...]  # [block_k, D]
    acc[...] = jnp.zeros_like(acc)

    def head(j, carry):
        s = jax.lax.dot_general(
            q_ref[j], kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [rows, block_k]
        acc[...] += jnp.maximum(s, 0.0) * w_ref[j]
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0], head, None)
    qpos = q0_ref[0] + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k),
                                                   1)
    o_ref[...] = jnp.where(kpos <= qpos, acc[...] * scale, -jnp.inf)


def prefill_index_scores(q, w, k, q0, scale, *,
                         interpret: Optional[bool] = None):
    """Scores ``[rows, L]`` float32 of the queries ``q [heads, rows, D]``
    at positions ``q0 + arange(rows)`` (``q0`` an int32 scalar) with head
    weights ``w [heads, rows, 1]`` against the keys ``k [L, D]``, ``-inf``
    after each query's position."""
    heads, rows, D = q.shape
    L = k.shape[0]
    block_k = min(L, 512)
    kernel = functools.partial(_prefill_index_kernel, scale=float(scale),
                               rows=rows, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(L // block_k,),
            in_specs=[
                pl.BlockSpec((heads, rows, D), lambda ki, q0: (0, 0, 0)),
                pl.BlockSpec((heads, rows, 1), lambda ki, q0: (0, 0, 0)),
                pl.BlockSpec((block_k, D), lambda ki, q0: (ki, 0)),
            ],
            out_specs=pl.BlockSpec((rows, block_k), lambda ki, q0: (0, ki)),
            scratch_shapes=[pltpu.VMEM((rows, block_k), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, L), jnp.float32),
        interpret=_interpret(interpret),
        name="prefill_index_scores",
    )(jnp.asarray(q0, jnp.int32).reshape(1), q, w.astype(jnp.float32), k)


def prefill_selection(q, w, k, k_top: int, scale, *,
                      interpret: Optional[bool] = None):
    """The selection of every query of a prompt, as
    :func:`selection_bits`: ``q [heads, L, D]``, ``w [heads, L, 1]``, ``k
    [L, D]``.  A query block (:func:`query_block`) at a time, in a loop
    and not unrolled: its scores (:func:`prefill_index_scores`), its top
    ``k_top``, its bits."""
    heads, L, D = q.shape
    rows = query_block(L)

    def block(i):
        q0 = i * rows
        s = prefill_index_scores(
            jax.lax.dynamic_slice_in_dim(q, q0, rows, axis=1),
            jax.lax.dynamic_slice_in_dim(w, q0, rows, axis=1), k, q0, scale,
            interpret=interpret)
        return selection_bits(selection_mask(s, k_top), rows)

    bits = jax.lax.map(block, jnp.arange(L // rows, dtype=jnp.int32))
    return bits.reshape(L // 32, L)


def sparse_flash_reference(q, k, v, mask, sinks, scale):
    """Plain form of :func:`sparse_flash_attention`: ``q``, ``k [H, L, D]``,
    ``v [H, L, Dv]``, bool ``mask [L, L]``, ``sinks [H]``."""
    s = jnp.einsum("hqd,hkd->hqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[None], s, -jnp.inf)
    m = jnp.maximum(s.max(-1, keepdims=True), sinks[:, None, None])
    p = jnp.exp(s - m)
    p = p / (p.sum(-1, keepdims=True) + jnp.exp(sinks[:, None, None] - m))
    return jnp.einsum("hqk,hkd->hqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _sparse_flash_kernel(bits_ref, sink_ref, q_ref, k_ref, v_ref, o_ref,
                         acc, m_sc, l_sc, *, scale, block_q, block_k):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
    def _():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [block_q, block_k]
        words = bits_ref[...]  # [block_q / 32, block_k]
        chosen = jnp.concatenate(
            [jax.lax.shift_right_logical(words, j) & 1 for j in range(32)],
            axis=0) > 0  # query j * block_q / 32 + w: in order
        s = jnp.where(chosen, s, _NEG_INF)
        m_prev = m_sc[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(chosen, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_sc[:, 0:1] = l_sc[:, 0:1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_sc[:, 0:1] = m_new
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        m = m_sc[:, 0:1]
        l = l_sc[:, 0:1] + jnp.exp(sink_ref[0][:, 0:1] - m)
        o_ref[0] = (acc[...] / l).astype(o_ref.dtype)


def sparse_flash_attention(q, k, v, bits, sinks, scale, *,
                           interpret: Optional[bool] = None):
    """Causal flash attention under a selection, with a sink a head: query
    ``t`` attends the keys its bit marks (:func:`selection_bits`; a
    selection of keys after ``t`` is the caller's fault).  ``q``, ``k [H, L,
    D]``, ``v [H, L, Dv]``, ``bits [L / 32, L] int32``, ``sinks [H]``
    float32.  Grid (head, query block, key block); key blocks after a query
    block are skipped and not fetched.  Returns ``[H, L, Dv]``."""
    H, L, D = q.shape
    Dv = v.shape[-1]
    block_q, block_k = query_block(L), min(_FLASH_BLOCK_K, L)
    if L % block_k:
        raise ValueError(f"sparse_flash_attention: length {L} is no "
                         f"multiple of its key block {block_k}")

    def last_k(qi):  # the last key block a query block reads
        return (qi * block_q + block_q - 1) // block_k

    def kv_block(h, qi, ki):
        return (h, jnp.minimum(ki, last_k(qi)), 0)

    kernel = functools.partial(_sparse_flash_kernel, scale=float(scale),
                               block_q=block_q, block_k=block_k)
    sinks = jnp.broadcast_to(sinks.astype(jnp.float32)[:, None, None],
                             (H, 1, 128))
    return pl.pallas_call(
        kernel,
        grid=(H, L // block_q, L // block_k),
        in_specs=[
            pl.BlockSpec((block_q // 32, block_k),
                         lambda h, qi, ki: (qi, jnp.minimum(ki, last_k(qi)))),
            pl.BlockSpec((1, 1, 128), lambda h, qi, ki: (h, 0, 0)),
            pl.BlockSpec((1, block_q, D), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((1, block_k, D), kv_block),
            pl.BlockSpec((1, block_k, Dv), kv_block),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dv), lambda h, qi, ki: (h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((H, L, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=_interpret(interpret),
        name="sparse_flash_attention",
    )(bits, sinks, q, k, v)
