"""Paged KV-cache: block pool + per-request block tables + attention hook.

ISSUE 9 pillar 1.  Serving memory is dominated by the KV-cache, and naive
per-request contiguous caches fragment HBM so badly that batch size — the
thing TPU serving throughput actually scales with (arXiv:2605.25645) — is
capped by the WORST-case sequence length.  The paged layout (vLLM lineage)
fixes that: one pool of fixed-size blocks, per-request block tables mapping
sequence position -> (block, offset), freed blocks refilling mid-flight as
requests complete.

The pieces:

- :class:`BlockAllocator` — host-side free list over the pool.  Block 0 is
  RESERVED as a scratch block: inactive decode slots write their (discarded)
  K/V there, so the compiled decode program always runs the full fixed-shape
  slot batch with no active-mask branching.
- :class:`PagedKVCache` — the device arrays: ``[n_layers, n_blocks,
  block_size, heads * head_dim]`` K and V page pools, created zeroed on the
  target device/mesh.  The serving engine threads them functionally through
  its compiled programs (donated, so updates are in-place in HBM).
- :class:`PagedAttentionHook` — the per-trace bridge into ``models/gpt.py``:
  ``GPT(..., kv_cache=hook)`` asks it for one attention fn per layer.  In
  prefill mode the fn writes the prompt's K/V into the slot's blocks and
  runs ordinary causal attention (dense or the flash kernel) over the
  prompt; in decode mode it writes the single fresh token's K/V and attends
  over the gathered cached blocks
  (:func:`stoke_tpu.ops.flash_attention.paged_pool_attention`).  The hook
  carries the updated page arrays across layers within one trace; the
  caller reads them back after ``apply`` and returns them from the jitted
  program.  :class:`LatentAttentionHook` is the same bridge for a
  latent-attention model's one plane.
- the second store and :class:`HybridCacheHook` — a model whose layers keep
  different things (``CacheSpec.layer_kinds``): planes for the layers that
  cache a row a token, and slot-indexed state arrays for the layers that
  keep a constant state a slot (a linear-attention layer's recurrent state,
  its convolution's last inputs), which cache no rows and get no plane;
  with block-sparse row layers (``CacheSpec.block_sparse``) each also
  keeps its compressed keys in a store addressed by slot
  (``CacheSpec.slot_rows``), and its queries attend the pages they chose.
- :class:`SparseLatentHook` — a model with learned sparse attention: a
  packed latent plane read row by row, and a plane of indexer keys that
  only the layers with an indexer have.
"""

from __future__ import annotations

from collections import deque
from functools import partial
from typing import Deque, List, Optional, Sequence

import jax
import jax.numpy as jnp

from stoke_tpu.models.bert import dense_attention
from stoke_tpu.ops.block_sparse import (
    block_scores,
    block_sparse_attention,
    block_sparse_flash,
    choice_lists,
    choose_blocks,
    compress_keys,
    compressed_scores,
    prefill_choices,
)
from stoke_tpu.ops.flash_attention import (
    flash_attention,
    grouped_query_attention,
    latent_paged_attention,
    paged_decode_attention_pallas,
    paged_pool_attention,
    paged_verify_attention_pallas,
)
from stoke_tpu.ops.sparse_attention import (
    counted_index_scores,
    pack_rows,
    packed_width,
    prefill_selection,
    row_addresses,
    select_topk,
    sparse_flash_attention,
    sparse_latent_attention,
    write_rows,
)

#: block id every unused block-table entry (and every inactive slot) points
#: at — allocated to no request, read by nothing meaningful
SCRATCH_BLOCK = 0

#: heads :class:`SparseLatentHook` expands and attends at a time in prefill
PREFILL_HEADS = 16


class BlockAllocator:
    """Host-side free list over the KV block pool (block 0 reserved).

    Pure bookkeeping — never touches a device.  The scheduler allocates a
    request's FULL worst-case block budget at admission (prompt + token
    cap), so a mid-flight decode step can never fail on an empty pool;
    freed blocks return to the tail and are reused by later admissions
    (tests assert occupancy returns to 0 after drain).

    The order of reuse is first in, first out: ``alloc`` takes from the
    head, ``free`` appends to the tail in the order given, so a sequence of
    calls hands out the same ids whatever the pool's size.  ``_free`` keeps
    only that order; whether a block is free is one flag a block
    (``_is_free``), so ``alloc`` and ``free`` cost the blocks of the one
    request and nothing that grows with the pool: an eviction runs inside
    the engine's step with the device idle and every other slot waiting.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError(
                f"BlockAllocator needs >= 2 blocks (one is the reserved "
                f"scratch block {SCRATCH_BLOCK}), got {num_blocks}"
            )
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self._free: Deque[int] = deque(range(1, num_blocks))
        self._is_free = bytearray([0]) + bytearray([1]) * (num_blocks - 1)

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` cache entries."""
        return -(-max(int(n_tokens), 1) // self.block_size)

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        """Blocks currently owned by requests (scratch excluded)."""
        return (self.num_blocks - 1) - len(self._free)

    @property
    def capacity(self) -> int:
        """Allocatable blocks (pool minus the scratch block)."""
        return self.num_blocks - 1

    @property
    def occupancy(self) -> float:
        """Fraction of the allocatable pool currently owned (the
        ``serve/kv_block_occupancy`` gauge)."""
        return self.used_blocks / max(self.capacity, 1)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Take ``n`` blocks, or None (allocator unchanged) when the pool
        cannot supply them — the scheduler then keeps the request queued."""
        if n > len(self._free):
            return None
        taken = [self._free.popleft() for _ in range(n)]
        for b in taken:
            self._is_free[b] = 0
        return taken

    def free(self, blocks: Sequence[int]) -> None:
        """Give ``blocks`` back, to the tail in the order given.  Raises
        ``ValueError`` and leaves the allocator as it found it when one of
        them is the scratch block, no block of the pool, or free already
        (twice in ``blocks`` included)."""
        blocks = [int(b) for b in blocks]
        for i, b in enumerate(blocks):
            if 0 < b < self.num_blocks and not self._is_free[b]:
                self._is_free[b] = 1
                continue
            for marked in blocks[:i]:
                self._is_free[marked] = 0
            if b == SCRATCH_BLOCK:
                why = "cannot free the reserved scratch block"
            elif 0 < b < self.num_blocks:
                why = f"double free of KV block {b}"
            else:
                why = (f"KV block {b} is outside the pool's "
                       f"1..{self.num_blocks - 1}")
            raise ValueError(why)
        self._free.extend(blocks)


class PagedKVCache:
    """The device-side block pool: the cache planes of every layer.

    A plane is ``[n_layers, n_blocks, block_size, width]``: a cached token
    is ONE row.  Which planes there are is the model's to say
    (``CacheSpec.planes``): multi-head attention keeps two, K and V, of
    ``heads * head_dim`` values, a token's heads side by side; latent
    attention keeps one, the normed latent and the roped shared key side
    by side.  The minor dimension is then
    whole 128-lane tiles at every MHA width in use (1024, 768, the tests'
    128) and ``block_size`` whole sublanes, so row-major is the layout the
    device keeps the pool in at rest AND the one every program computes
    on: nothing is padded, no program converts the pool on its way in or
    out, and the donated pool is updated in place.  (With a trailing
    ``[heads, head_dim]`` the 64-wide minor dimension makes the device
    keep the block index in the lanes at rest, and every program copies K
    and V to a ``head_dim``-padded tiling and back: four copies of the
    pool a dispatch.)  A width that is no multiple of 128 is still
    correct, and is copied the same way: read from the entry layouts of
    programs compiled for the v5e (a row scatter and a block gather on a
    bfloat16 plane), widths 576 and 64 are kept ``{1,3,2,0:T(8,128)(2,1)}``
    at rest with two whole-plane copies a dispatch, widths 640, 512 and 128
    ``{3,2,1,0:T(8,128)(2,1)}`` with none.  So the 576-value latent row of
    the DeepSeek-V3 family is ONE plane of 640, 64 zero lanes at its end
    (what the tiled layout pads it to anyway), and not 512 + 64 as two,
    whose second plane would be copied, nor 512 + 128, which costs a second
    gather and scatter a layer for the same bytes.  Layer
    outermost, block next: a write is one scatter of rows at ``(layer,
    block, offset)``; the MHA window is one gather of whole blocks at
    ``(layer, block_table)``, and the latent plane's decode kernel fetches
    a slot's live blocks at ``(layer, block)``, one ``[block_size,
    width]`` tile-aligned page each.

    ``planes`` is the model's ``CacheSpec.planes``: ``(("k", 768), ("v",
    768))`` for 12 heads of 64, ``(("latent", 640),)`` for the latent row.
    A third element of an entry gives the plane's options: ``"layers"``,
    the model's layers that keep a row of it (a plane a layer SET: the
    indexer keys of a sparse model's ``full`` layers; default every row
    layer), and ``"packed"``: the row's ``width`` values kept as 32-bit
    words, ``[layers, NB, BS, 1, words]`` ``uint32``
    (``ops/sparse_attention.py`` ``pack_rows``), so that one row is a whole
    tile of the array's layout and a kernel can fetch it alone.

    ``n_layers`` counts the layers that cache rows.  A model whose other
    layers keep a constant state a slot (``CacheSpec.layer_kinds``,
    ``CacheSpec.state``: a linear-attention layer's recurrent state and its
    convolution's last inputs) gets the second store beside the planes:
    ``state``, one ``[max_seqs, *shape]`` array an entry a state layer,
    addressed by slot and not through the block tables; a plane for such a
    layer would be spent on rows it never caches.

    ``sharding`` (optional ``jax.sharding.Sharding``) places the pool on
    the serving mesh — replicated by default (data-parallel serving
    replicas each own a full pool; a model-sharded pool over a heads axis
    is a placement change here, not a layout change).
    """

    def __init__(
        self,
        n_layers: int,
        num_blocks: int,
        block_size: int,
        planes: Sequence,
        dtype=jnp.float32,
        sharding=None,
        state: Sequence = (),
        state_layers: int = 0,
        max_seqs: int = 0,
        slot_rows: Sequence = (),
        max_seq_len: int = 0,
        slot_state: Sequence = (),
    ):
        self.n_layers = int(n_layers)
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.dtype = jnp.dtype(dtype)
        self.plane_names = tuple(plane[0] for plane in planes)

        def zeros(shape, dtype):
            array = jnp.zeros(shape, dtype)
            return (array if sharding is None
                    else jax.device_put(array, sharding))

        def plane(name, width, options=None):
            options = options or {}
            layers = len(options.get("layers", range(n_layers)))
            if options.get("packed"):
                return zeros((layers, num_blocks, block_size, 1,
                              packed_width(width, self.dtype)), jnp.uint32)
            return zeros((layers, num_blocks, block_size, width), dtype)

        #: the planes, in the model's order, then the row layers' slot
        #: stores (``CacheSpec.slot_rows``: ``[max_seqs, ceil(max_seq_len /
        #: stride), width]`` each, store by store and row layer by row
        #: layer), then their per-slot state (``CacheSpec.slot_state``:
        #: ``[max_seqs, *shape]`` each, in the same order); the serve
        #: programs take them (donated) and hand them back
        self.pages = tuple(plane(*spec) for spec in planes) + tuple(
            zeros((max_seqs, -(-max_seq_len // stride), width), dtype)
            for _, stride, width in slot_rows for _ in range(n_layers)
        ) + tuple(
            zeros((max_seqs,) + tuple(shape), jnp.dtype(kind))
            for _, shape, kind in slot_state for _ in range(n_layers))
        #: the second store: what the layers that cache no rows keep, a
        #: constant size a slot and addressed by slot (``CacheSpec.state``):
        #: ``[max_seqs, *shape]`` each, an array an entry a state layer
        #: (layer by layer, the entries in the model's order), threaded
        #: through the serve programs behind the planes, donated like them.
        #: An array a layer, so that a decode step replaces each whole: of
        #: one ``[state layers, max_seqs, ...]`` array the layers' updates
        #: were slices written in place one after the other, and the v5e's
        #: compiler, short of memory at 256 slots, rematerialised the first
        #: layer's update three times over the aliased buffer, applying it
        #: three times (PERF.md section 6, PR 33)
        self.state = tuple(
            zeros((max_seqs,) + tuple(shape),
                  self.dtype if kind == "cache" else jnp.dtype(kind))
            for _ in range(state_layers) for _, shape, kind in state
        )

    @property
    def arrays(self) -> tuple:
        """Everything the serve programs thread: planes, then state."""
        return self.pages + self.state

    @property
    def k_pages(self):
        return self.pages[self.plane_names.index("k")]

    @property
    def v_pages(self):
        return self.pages[self.plane_names.index("v")]

    @property
    def nbytes(self) -> int:
        """HBM footprint of the pool (all planes and slot stores,
        unpadded)."""
        return sum(int(p.size) * p.dtype.itemsize for p in self.pages)

    @property
    def state_nbytes(self) -> int:
        """HBM footprint of the per-slot state arrays."""
        return sum(int(a.size) * a.dtype.itemsize for a in self.state)

    @property
    def bytes_per_token(self) -> int:
        """Cached bytes a token, over all planes, each at its own layers."""
        return self.nbytes // (self.num_blocks * self.block_size)


def _write_targets(block_tables, positions, block_size: int, lengths=None):
    """``(blocks, offs)``, each ``[B*L]``: where this call's tokens at
    ``positions [B, L]`` land in the pool.  A token at a position below its
    slot's ``lengths`` (or any token, without ``lengths``: decode) lands at
    ``(block_table[b, pos // BS], pos % BS)``; the others land in the
    scratch block."""
    B, L = positions.shape
    pos = positions.reshape(-1)  # [B*L]
    slot = jnp.repeat(jnp.arange(B, dtype=jnp.int32), L)
    blk_idx = pos // block_size
    if lengths is not None:
        # chunk rows past the prompt end (the last chunk's padding)
        # carry clamped positions >= the prompt length, so the same
        # predicate steers them to scratch; verify's lengths bound
        # the real write window (context + draft + 1) the same way
        valid = (
            positions < lengths[:, None].astype(positions.dtype)
        ).reshape(-1)
    else:
        valid = jnp.ones_like(pos, dtype=bool)
    # clamp the table column so padding positions past the allocated
    # window index legally, then steer invalid writes to scratch
    blk_idx = jnp.minimum(blk_idx, block_tables.shape[1] - 1)
    blocks = block_tables[slot, blk_idx]
    blocks = jnp.where(valid, blocks, SCRATCH_BLOCK)
    return blocks, pos % block_size


def _token_rows(t):
    """[B, H, L, D] attention layout -> [B*L, H*D] rows as the pool stores
    them."""
    B, H, L, D = t.shape
    return jnp.swapaxes(t, 1, 2).reshape(B * L, H * D)


class PagedAttentionHook:
    """Per-trace cache bridge for ``GPT(..., kv_cache=hook)``.

    Constructed INSIDE the serving engine's jitted prefill/decode programs
    around the (donated) page arrays; ``layer_attention(i)`` returns the
    attention fn layer ``i``'s transformer block calls.  Page updates are
    functional (``.at[].set``) and threaded through ``self.k_pages`` /
    ``self.v_pages`` so the program returns the updated pool.

    Args:
        k_pages / v_pages: ``[n_layers, NB, BS, H*D]`` page pools
            (:class:`PagedKVCache`'s layout).  Every access addresses the
            WHOLE pool at ``(layer, block, ...)``: no layer's pages are
            sliced out, and every index is promised in bounds (block ids
            come from the allocator or are the scratch block).
        block_tables: ``[B, MAX_BLOCKS] int32`` per-slot block ids.
        positions: ``[B, L] int32`` token positions being written this
            call (prefill: ``arange`` rows; decode: each slot's current
            position, L == 1).
        mode: ``"prefill"``, ``"chunk"`` (chunked prefill, ISSUE 13),
            ``"decode"``, or ``"verify"`` (speculative k-token verify,
            ISSUE 17 — chunk-style positional writes/attention, plus
            save-before-write so :meth:`rollback` can restore rejected
            draft positions exactly).
        lengths: ``[B] int32`` — prefill/chunk: true prompt lengths
            (padding positions write to the scratch block and are
            masked); decode: context lengths INCLUDING the fresh token;
            verify: context + draft length + 1 (the write budget —
            padding query rows past it steer to scratch).
        attention_impl: prefill kernel, ``"dense"`` or ``"flash"``.
        decode_impl: decode kernel — ``"reference"`` (the jnp
            gathered-block :func:`paged_pool_attention`) or
            ``"pallas"`` (the ISSUE 13 streaming kernel
            :func:`paged_decode_attention_pallas`, which takes one layer's
            pages reshaped to ``[NB, BS, H, D]``).
        decode_interpret: run the pallas kernel through the interpreter
            (``None`` = auto off-TPU — the CPU parity mode).
            ``decode_impl`` selects reference vs pallas for verify too —
            both kernels share the streaming memory schedule and take
            their pages-per-step from the table's width.
    """

    def __init__(
        self,
        k_pages,
        v_pages,
        block_tables,
        positions,
        *,
        mode: str,
        lengths,
        attention_impl: str = "dense",
        decode_impl: str = "reference",
        decode_interpret: Optional[bool] = None,
    ):
        if mode not in ("prefill", "chunk", "decode", "verify"):
            raise ValueError(f"unknown PagedAttentionHook mode {mode!r}")
        if decode_impl not in ("reference", "pallas"):
            raise ValueError(
                f"unknown PagedAttentionHook decode_impl {decode_impl!r}; "
                f"valid: ['reference', 'pallas']"
            )
        self.k_pages = k_pages
        self.v_pages = v_pages
        self.block_tables = block_tables
        self.positions = positions
        self.mode = mode
        self.lengths = lengths
        self.attention_impl = attention_impl
        self.decode_impl = decode_impl
        self.decode_interpret = decode_interpret
        self.block_size = int(k_pages.shape[2])
        # verify mode: per-layer (blocks, offs, old_k, old_v) snapshots
        # taken before each write, consumed by rollback()
        self._saved: List[tuple] = []

    @property
    def pages(self) -> tuple:
        """The planes as the serve programs thread them."""
        return (self.k_pages, self.v_pages)

    # ------------------------------ writes ----------------------------- #

    def _write_layer(self, layer: int, k, v) -> None:
        """Scatter this call's fresh K/V into layer ``layer``'s planes.

        Valid (position < budget) tokens land at ``(block_table[b,
        pos // BS], pos % BS)``; invalid ones — prompt padding, inactive
        decode slots are steered by their all-scratch block tables — land
        in the scratch block, which nothing reads.  Distinct live slots
        own distinct blocks, so in-batch writes never collide.
        """
        blocks, offs = _write_targets(
            self.block_tables, self.positions, self.block_size,
            self.lengths if self.mode in ("prefill", "chunk", "verify")
            else None,
        )
        if self.mode == "verify":
            # snapshot what the write clobbers so rollback() can undo the
            # rejected tail exactly — acceptance is only known after the
            # forward, but the chunk-attention semantics need the draft
            # K/V resident DURING it
            old_k, old_v = (
                pool.at[layer, blocks, offs].get(mode="promise_in_bounds")
                for pool in (self.k_pages, self.v_pages)
            )
            self._saved.append((blocks, offs, old_k, old_v))
        self._set_rows(layer, blocks, offs, _token_rows(k), _token_rows(v))

    def _set_rows(self, layer: int, blocks, offs, k_rows, v_rows) -> None:
        """Scatter ``[N, H*D]`` rows into both pools at ``(layer,
        blocks[n], offs[n])``."""
        self.k_pages, self.v_pages = (
            pool.at[layer, blocks, offs].set(
                rows.astype(pool.dtype), mode="promise_in_bounds"
            )
            for pool, rows in ((self.k_pages, k_rows), (self.v_pages, v_rows))
        )

    def rollback(self, n_keep) -> None:
        """Restore every verify write PAST the accepted window (ISSUE 17).

        Called after acceptance is computed, inside the same trace: query
        row ``i`` of slot ``b`` keeps its written K/V iff ``i <
        n_keep[b]``; every other row's destination is restored to the
        snapshot ``_write_layer`` took.  Restores are steered like
        writes: kept rows' restore targets flip to the scratch block
        (their old values land somewhere nothing reads), so the scatter
        stays fixed-shape with no branching, and rejected draft
        positions never dirty the cache across dispatches.

        Args:
            n_keep: ``[B] int32`` accepted-row counts (the sampling
                layer's ``n_emit``).
        """
        if self.mode != "verify":
            raise ValueError(
                f"rollback() is a verify-mode operation; hook mode is "
                f"{self.mode!r}"
            )
        B, L = self.positions.shape
        within = jnp.tile(jnp.arange(L, dtype=jnp.int32), B)
        slot = jnp.repeat(jnp.arange(B, dtype=jnp.int32), L)
        keep = within < n_keep.astype(jnp.int32)[slot]
        for layer, (blocks, offs, old_k, old_v) in enumerate(self._saved):
            blocks_r = jnp.where(keep, SCRATCH_BLOCK, blocks)
            self._set_rows(layer, blocks_r, offs, old_k, old_v)

    # ----------------------------- attention --------------------------- #

    def layer_attention(self, layer: int):
        """The ``attention_fn`` (bert.py signature) for layer ``layer``."""

        def attention_fn(q, k, v, bias, dropout_rng=None, dropout_rate=0.0,
                         deterministic=True):
            if dropout_rate > 0.0 and not deterministic:
                raise NotImplementedError(
                    "paged-cache attention is inference-only; attention "
                    "dropout is not supported"
                )
            self._write_layer(layer, k, v)
            if self.mode != "prefill":
                # decode: one query row at the last cached position.
                # chunk: the chunk's K/V were just written, so attention is
                # one paged gather masked causally by GLOBAL position —
                # earlier chunks' prefix and the intra-chunk causal mask
                # fall out of the same predicate.  verify: S = k+1 query
                # rows over the paged prefix (draft K/V just written)
                # under that same predicate.
                positions = (
                    self.lengths.astype(jnp.int32)[:, None] - 1
                    if self.mode == "decode"
                    else self.positions
                )
                if self.decode_impl == "pallas" and self.mode != "chunk":
                    # the kernel streams one layer's [NB, BS, H, D] pages
                    # once for all query rows
                    NB, BS = self.k_pages.shape[1:3]
                    k_l, v_l = (
                        pool[layer].reshape(NB, BS, q.shape[1], q.shape[3])
                        for pool in (self.k_pages, self.v_pages)
                    )
                    if self.mode == "decode":
                        return paged_decode_attention_pallas(
                            q, k_l, v_l, self.block_tables, self.lengths,
                            interpret=self.decode_interpret,
                        )
                    return paged_verify_attention_pallas(
                        q, k_l, v_l, self.block_tables, positions,
                        interpret=self.decode_interpret,
                    )
                return paged_pool_attention(
                    q, self.k_pages, self.v_pages, layer,
                    self.block_tables, positions,
                )
            # prefill: ordinary causal attention over the (padded) prompt
            # — the pages were just written for DECODE's benefit; the
            # prompt itself is fully in registers/VMEM here, so the
            # training-side kernels serve it unchanged
            B, H, L, D = q.shape
            key_valid = (
                jnp.arange(L, dtype=jnp.int32)[None, :]
                < self.lengths[:, None].astype(jnp.int32)
            )  # [B, L]
            if self.attention_impl == "flash":
                return flash_attention(
                    q, k, v, key_valid.astype(jnp.int32), causal=True
                )
            causal = jnp.tril(jnp.ones((L, L), bool))
            allow = causal[None, None, :, :] & key_valid[:, None, None, :]
            pbias = jnp.where(allow, 0.0, -1e9).astype(q.dtype)
            return dense_attention(q, k, v, pbias)

        return attention_fn


class LatentAttentionHook:
    """Per-trace cache bridge for a latent-attention model
    (``Decoder(..., kv_cache=hook)``): one plane, one row a token a latent
    sublayer, the normed latent and the roped shared key side by side.

    ``latent_attention(k)`` returns latent sublayer ``k``'s ``attend(q_nope,
    q_rope, c, k_rope, w_kvb, scale)``: ``k`` is the row of the plane, a
    model's layer index where a layer has one latent attention, ``2 l + j``
    for sublayer ``j`` of a double layer ``l`` (``CacheSpec.layers`` counts
    sublayers).  It writes the call's rows into the slot's
    blocks (the same steering as :class:`PagedAttentionHook`: padding and
    idle slots land in the scratch block), then attends: in ``"prefill"``
    mode the expanded form, causal over the padded prompt, through
    ``attention_impl`` (``"flash"`` or ``"dense"``); in ``"decode"`` mode
    the absorbed form (``models/decoder.py`` holds both) through the
    latent cache's one decode path, the Pallas kernel
    ``latent_paged_attention``: it reads each slot's live blocks of the
    whole plane in place, to the slot's own length, and gathers no window
    (interpreted off the TPU).  The chunk and verify modes have no latent
    program yet.

    Args as :class:`PagedAttentionHook`'s, with ``pages`` the pool's one
    plane ``[n_layers, NB, BS, >= C + dr]`` (the row's values, then zeros up
    to whole 128-lane tiles: ``DecoderConfig.latent_row_width``).
    """

    def __init__(self, pages, block_tables, positions, *, mode: str,
                 lengths, attention_impl: str = "dense"):
        if mode not in ("prefill", "decode"):
            raise NotImplementedError(
                f"LatentAttentionHook has no {mode!r} mode: the latent "
                f"cache is written and read by the serve_prefill and "
                f"serve_decode programs only"
            )
        self.latent_pages = pages
        self.block_tables = block_tables
        self.positions = positions
        self.mode = mode
        self.lengths = lengths
        self.attention_impl = attention_impl
        self.block_size = int(pages.shape[2])

    @property
    def pages(self) -> tuple:
        return (self.latent_pages,)

    def latent_attention(self, layer: int):
        from stoke_tpu.models.decoder import (
            absorbed_paged_attention,
            expanded_attention,
        )

        def attend(q_nope, q_rope, c, k_rope, w_kvb, scale):
            B, L = self.positions.shape
            rows = jnp.concatenate([c, k_rope], axis=-1).reshape(B * L, -1)
            pool = self.latent_pages
            # the row as stored: its values, then zeros up to whole tiles
            rows = jnp.pad(
                rows, ((0, 0), (0, pool.shape[-1] - rows.shape[-1])))
            blocks, offs = _write_targets(
                self.block_tables, self.positions, self.block_size,
                self.lengths if self.mode == "prefill" else None,
            )
            self.latent_pages = pool.at[layer, blocks, offs].set(
                rows.astype(pool.dtype), mode="promise_in_bounds"
            )
            if self.mode == "prefill":
                key_valid = (
                    jnp.arange(L, dtype=jnp.int32)[None, :]
                    < self.lengths[:, None].astype(jnp.int32)
                )
                return expanded_attention(
                    q_nope, q_rope, c, k_rope, w_kvb, scale, key_valid,
                    self.attention_impl,
                )
            return absorbed_paged_attention(
                q_nope, q_rope, self.latent_pages, layer, self.block_tables,
                self.lengths, w_kvb, scale,
            )

        return attend


class _LayerState:
    """What :meth:`HybridCacheHook.layer_state` hands a state layer: the
    hook's ``mode`` and ``lengths``, ``read()`` for the slots' state and
    ``write(...)`` to give it back."""

    def __init__(self, hook, mine: slice):
        self._hook, self._mine = hook, mine
        self.mode, self.lengths = hook.mode, hook.lengths

    def read(self) -> tuple:
        """Decode: every slot's ``(state, conv)`` rows of this layer."""
        return self._hook.state[self._mine]

    def write(self, *rows) -> None:
        """Decode: every slot's rows back, each array replaced whole;
        prefill: the one request's rows (``[1, ...]`` each) into its
        slot's."""
        hook = self._hook
        if hook.mode == "decode":
            new = [r.astype(a.dtype) for a, r in zip(self.read(), rows)]
        else:
            new = [a.at[hook.slot[0]].set(r[0].astype(a.dtype))
                   for a, r in zip(self.read(), rows)]
        state = list(hook.state)
        state[self._mine] = new
        hook.state = tuple(state)


class HybridCacheHook:
    """Per-trace cache bridge for a model whose layers keep different
    things (``CacheSpec.kind == "hybrid"``): grouped-query attention layers
    that cache a row a token in the pool's one plane, and layers that keep a
    constant state a slot in the state arrays.  One hook serves both.

    ``layer_attention(i)`` returns a row layer's ``attend(q [B, L, H, D], k,
    v [B, L, G, D])``.  It writes the call's rows, a token's keys then its
    values side by side (``2 G D`` lanes), into the slot's blocks with the
    steering of :class:`PagedAttentionHook` (padding and idle slots land in
    the scratch block), then attends: in ``"prefill"`` mode causally over
    the padded prompt (``ops/flash_attention.py`` ``grouped_query_attention``,
    ``attention_impl`` ``"flash"`` or ``"dense"``); in ``"decode"`` mode
    through the Pallas kernel ``latent_paged_attention``, which reads each
    slot's live blocks of the plane in place, to the slot's own length:
    query head ``h`` rides as a row that holds its ``D`` values in the key
    lanes of key-value head ``h // (H / G)`` and zeros elsewhere, so its
    score against a cached row is ``q_h . k_g``, and of the probabilities
    times the rows it keeps that head's value lanes.  The kernel's cost is
    the pages it streams, the same bytes for any ``H``; the zeros ride in
    the matrix unit's spare rows.

    ``layer_state(i)`` returns a state layer's accessor
    (:class:`_LayerState`): in ``"decode"`` mode every slot's rows of the
    layer's state arrays, out and back in; in ``"prefill"`` mode the layer
    starts from zero state and writes the one request's rows at ``slot``.
    An idle slot's rows may hold anything finite: its prefill overwrites
    them.

    With ``block_sparse`` (``ops/block_sparse.py`` ``BlockSparse``) a row
    layer's queries attend the blocks their group chose, and each row layer
    keeps its compressed keys in a store of its own, ``[max_seqs, windows,
    G D]`` addressed by slot, each written once when its window completes,
    and beside it the float32 key sums of the strides of each slot's window
    in progress: in ``"prefill"`` mode every window of the prompt and the
    sums at its end, the selection of every
    query a block of queries at a time (``prefill_choices``) and flash
    attention over each query tile's union of chosen blocks
    (``block_sparse_flash``), whose tiles visited and causal tiles add to
    ``key_tiles``; in ``"decode"`` mode the fresh token's keys added to its
    stride's sum and the window it completes written as the mean of its
    strides' sums (no key is read back), each slot's query scored against
    its compressed keys in place (``compressed_scores``), the exact choice
    with the forced blocks, and attention over the chosen pages
    (``block_sparse_attention``), whose fetched words add to
    ``words_fetched``.

    Args as :class:`PagedAttentionHook`'s, with ``pages`` the one ``kv``
    plane ``[row layers, NB, BS, 2 G D]`` and then the row layers'
    compressed-key stores and window sums, ``state`` the state arrays
    (``[max_seqs, ...]`` each, layer by layer), ``layer_kinds`` the model's
    ``CacheSpec.layer_kinds`` and ``slot [1] int32`` the prefilled
    request's slot.
    """

    def __init__(self, pages, state, block_tables, positions, *, mode: str,
                 lengths, layer_kinds, slot=None,
                 attention_impl: str = "dense", block_sparse=None):
        if mode not in ("prefill", "decode"):
            raise NotImplementedError(
                f"HybridCacheHook has no {mode!r} mode: the state arrays "
                f"are written and read by the serve_prefill and "
                f"serve_decode programs only"
            )
        kinds = tuple(layer_kinds)
        self.kv_pages, *slot_arrays = pages
        rows = kinds.count("rows") if slot_arrays else 0
        #: the row layers' compressed-key stores, then the float32 sums of
        #: the key strides of each slot's last window so far (``[max_seqs,
        #: kernel_size / kernel_stride, G D]``: the strides before the
        #: current one, oldest first, then the current one's keys so far);
        #: block-sparse only
        self.compressed = tuple(slot_arrays[:rows])
        self.window_sums = tuple(slot_arrays[rows:])
        self.block_sparse = block_sparse
        #: ``[B] int32``: the words of the plane the block-sparse decode
        #: kernels fetched for each slot, summed over the layers
        self.words_fetched = jnp.zeros(block_tables.shape[:1], jnp.int32)
        #: ``[2] int32``: the prefill's key tiles visited and causal tiles,
        #: summed over the block-sparse layers
        self.key_tiles = jnp.zeros((2,), jnp.int32)
        self.state = tuple(state)
        self.block_tables = block_tables
        self.positions = positions
        self.mode = mode
        self.lengths = lengths
        self.slot = slot
        self.attention_impl = attention_impl
        self.block_size = int(self.kv_pages.shape[2])
        # layer i's place in the plane or in the state arrays
        self._place = [kinds[:i].count(kind) for i, kind in enumerate(kinds)]
        self._kinds = kinds

    @property
    def pages(self) -> tuple:
        return (self.kv_pages,) + self.compressed + self.window_sums

    def layer_state(self, layer: int) -> _LayerState:
        if self._kinds[layer] != "state":
            raise ValueError(f"layer {layer} caches rows, not a state")
        # the layer's arrays: the state arrays lie layer by layer
        n = len(self.state) // self._kinds.count("state")
        place = self._place[layer]
        return _LayerState(self, slice(place * n, (place + 1) * n))

    def layer_attention(self, layer: int):
        if self._kinds[layer] != "rows":
            raise ValueError(f"layer {layer} keeps a state, not rows")
        place = self._place[layer]
        if self.block_sparse is not None:
            return partial(self._sparse_attend, place)

        def attend(q, k, v):
            B, L, H, D = q.shape
            G = k.shape[2]
            self._write_rows(place, k, v)
            if self.mode == "prefill":
                key_valid = (
                    jnp.arange(L, dtype=jnp.int32)[None, :]
                    < self.lengths[:, None].astype(jnp.int32)
                )
                return grouped_query_attention(
                    q, k, v, key_valid, self.attention_impl)
            # head h's query in the key lanes of its key-value head
            own = (jnp.arange(H)[:, None] // (H // G)
                   == jnp.arange(G)[None, :])  # [H, G]
            q_row = jnp.where(
                own[None, :, :, None], q[:, 0, :, None, :], 0
            ).reshape(B, H, G * D)
            q_row = jnp.pad(q_row, ((0, 0), (0, 0), (0, G * D)))
            o_row = latent_paged_attention(
                q_row, self.kv_pages, place, self.block_tables,
                self.lengths, D ** -0.5)
            values = o_row[..., G * D:].reshape(B, H, G, D)
            return jnp.where(own[None, :, :, None], values, 0).sum(
                axis=2)[:, None].astype(q.dtype)

        return attend

    def _write_rows(self, place, k, v):
        """A row layer's keys and values, a token's side by side, into its
        plane at the tokens' places (padding and idle slots to scratch)."""
        B, L, G, D = k.shape
        rows = jnp.concatenate(
            [k.reshape(B * L, G * D), v.reshape(B * L, G * D)], axis=-1)
        blocks, offs = _write_targets(
            self.block_tables, self.positions, self.block_size,
            self.lengths if self.mode == "prefill" else None,
        )
        self.kv_pages = self.kv_pages.at[place, blocks, offs].set(
            rows.astype(self.kv_pages.dtype), mode="promise_in_bounds")

    def _sparse_attend(self, place, q, k, v):
        """A block-sparse row layer: ``q [B, L, H, D]``, ``k``, ``v [B, L,
        G, D]`` (normed, no positions)."""
        cfg = self.block_sparse
        B, L, H, D = q.shape
        G = k.shape[2]
        self._write_rows(place, k, v)
        store, sums = self.compressed[place], self.window_sums[place]
        S, m = cfg.kernel_stride, cfg.kernel_size // cfg.kernel_stride
        # the keys as the plane holds them, in float32
        keys = k.astype(self.kv_pages.dtype).astype(jnp.float32).reshape(
            B, L, G * D)
        if self.mode == "prefill":
            with jax.named_scope("compress"):
                c = compress_keys(k[0].astype(store.dtype), cfg).astype(
                    store.dtype)  # [L / S, G, D]
                n = min(c.shape[0], store.shape[1])
                store = jax.lax.dynamic_update_slice(
                    store, c[:n].reshape(1, n, G * D),
                    (self.slot[0], 0, 0))
                # the slot's window sums at the prompt's end: the sums of
                # its last m - 1 whole strides and of the stride it has
                # begun (zeros before the first stride and past the end)
                length = self.lengths[0].astype(jnp.int32)
                real = jnp.where(
                    jnp.arange(L, dtype=jnp.int32)[:, None] < length,
                    keys[0], 0.0)
                strides = jnp.pad(real, ((0, -L % S), (0, 0))).reshape(
                    -1, S, G * D).sum(axis=1)
                strides = jnp.pad(strides, ((m - 1, 1), (0, 0)))
                sums = sums.at[self.slot[0]].set(jax.lax.dynamic_slice_in_dim(
                    strides, length // S, m))
            with jax.named_scope("select"):
                visited, words, count, tiles = prefill_choices(
                    q[0], c, self.lengths[0], cfg)
            self.key_tiles = self.key_tiles + tiles
            with jax.named_scope("sparse_attn"):
                out = block_sparse_flash(
                    q[0].reshape(L, G, H // G, D),
                    jnp.swapaxes(k[0], 0, 1).astype(q.dtype),
                    jnp.swapaxes(v[0], 0, 1).astype(q.dtype),
                    visited, words, count, D ** -0.5, cfg.block_size)
            out = out.reshape(1, L, H, D)
        else:
            with jax.named_scope("compress"):
                # the fresh token at ``lengths - 1`` joins its stride's
                # sum; where it ends a stride that ends a window, the
                # window's mean is the sum of its strides' (a slot that
                # completes none writes past the store, which drops it),
                # and the sums move on a stride
                length = self.lengths.astype(jnp.int32)
                sums = sums.at[:, m - 1].add(keys[:, 0])
                ended = length % S == 0
                window = jnp.where(ended & (length >= cfg.kernel_size),
                                   length // S - m, store.shape[1])
                c = sums.sum(axis=1) / cfg.kernel_size
                store = store.at[jnp.arange(B), window].set(
                    c.astype(store.dtype), mode="drop")
                sums = jnp.where(ended[:, None, None], jnp.pad(
                    sums[:, 1:], ((0, 0), (0, 1), (0, 0))), sums)
            with jax.named_scope("select"):
                scores = compressed_scores(q[:, 0], store, self.lengths, cfg)
                nb = self.block_tables.shape[1]
                chosen = choose_blocks(
                    block_scores(scores, nb, cfg),
                    jnp.broadcast_to(self.lengths[:, None], (B, G)), cfg)
                logical, count = choice_lists(chosen, min(cfg.list_len, nb))
                blocks = jnp.take_along_axis(
                    self.block_tables[:, None, :], logical, axis=2)
            with jax.named_scope("sparse_attn"):
                out, words = block_sparse_attention(
                    q[:, 0], self.kv_pages, place, blocks, logical, count,
                    self.lengths, D ** -0.5)
            self.words_fetched = self.words_fetched + words
            out = out[:, None]
        compressed, window_sums = list(self.compressed), list(self.window_sums)
        compressed[place], window_sums[place] = store, sums
        self.compressed = tuple(compressed)
        self.window_sums = tuple(window_sums)
        return out


class SparseLatentHook:
    """Per-trace cache bridge for a model with learned sparse attention
    (``CacheSpec.kind == "sparse_latent"``, ``Decoder`` of the DSA family):
    the latent rows of every layer in a packed plane (32-bit words, a row a
    whole-tile ``[1, words]`` slice), the indexer's keys of the layers that
    have an indexer in a second plane that only those layers have
    (``index_layers``: the model's layer of each of its rows).

    ``sparse_attention(i)`` returns layer ``i``'s ``attend(q_nope, q_rope,
    c, k_rope, w_kvb, scale, sinks, index)``.  It writes the call's latent
    rows (and, where ``index = (q, k, w)`` is given, the indexer keys) into
    the slot's blocks with :class:`PagedAttentionHook`'s steering, then:

    - a layer with an indexer makes the selection, which the layers after
      it without one reuse within the same program: in ``"decode"`` mode
      each slot's query scored against its keys read in place to the slot's
      own length (``counted_index_scores``, whose fetched words add to
      ``index_words``), the exact top ``index_topk`` (``select_topk``) and
      their rows' places in the pool; in
      ``"prefill"`` mode the prompt's queries a block at a time
      (``prefill_selection``), as bits;
    - attends: in ``"decode"`` mode the absorbed form over the chosen rows,
      each fetched alone (``sparse_latent_attention``); in ``"prefill"``
      mode the expanded form under the selection (``sparse_flash_attention``,
      flash attention that reads the bits), a sink a head in both.

    Args as :class:`LatentAttentionHook`'s, with ``pages`` the pool's two
    planes ``(latent words [layers, NB, BS, 1, W], index keys [index
    layers, NB, BS, D])``.
    """

    def __init__(self, pages, block_tables, positions, *, mode: str,
                 lengths, index_topk: int, index_layers):
        if mode not in ("prefill", "decode"):
            raise NotImplementedError(
                f"SparseLatentHook has no {mode!r} mode: the sparse cache "
                f"is written and read by the serve_prefill and serve_decode "
                f"programs only"
            )
        self.latent_words, self.index_keys = pages
        self.block_tables = block_tables
        self.positions = positions
        self.mode = mode
        self.lengths = lengths
        self.index_topk = int(index_topk)
        self._index_row = {layer: row for row, layer in enumerate(index_layers)}
        self.block_size = int(self.index_keys.shape[2])
        #: the last selection made: ``(addresses, counts)`` in decode, the
        #: bits in prefill
        self.selection = None
        #: ``[B] int32``: the words of latent plane the decode kernels'
        #: row DMAs fetched for each slot, summed over the layers
        self.words_fetched = jnp.zeros(block_tables.shape[:1], jnp.int32)
        #: ``[B] int32``: the words of indexer plane the decode kernels'
        #: page DMAs fetched for each slot, summed over the layers
        self.index_words = jnp.zeros(block_tables.shape[:1], jnp.int32)

    @property
    def pages(self) -> tuple:
        return (self.latent_words, self.index_keys)

    def _select(self, row, q, k, w):
        if self.mode == "decode":
            scores, words = counted_index_scores(
                q[:, 0], w[:, 0, :, None], self.index_keys, row,
                self.block_tables, self.lengths, q.shape[-1] ** -0.5)
            self.index_words = self.index_words + words
            chosen, count = select_topk(scores[:, 0], self.lengths,
                                        self.index_topk)
            return row_addresses(chosen, count, self.block_tables,
                                 self.block_size), count
        return prefill_selection(
            jnp.swapaxes(q[0], 0, 1), jnp.swapaxes(w[0], 0, 1)[..., None],
            k[0], self.index_topk, q.shape[-1] ** -0.5)

    def _prefill_attention(self, q_nope, q_rope, c, k_rope, w_kvb, scale,
                           sinks):
        """The expanded form under the selection, ``PREFILL_HEADS`` heads
        at a time: a group's keys and values, expanded from the latent over
        the whole prompt, are what a call holds (all 64 heads' would be 1.4
        GB at a 24,576-token prompt)."""
        dn, H = q_nope.shape[-1], q_nope.shape[2]
        dv = w_kvb.shape[-1] - dn
        group = max(g for g in range(1, min(H, PREFILL_HEADS) + 1)
                    if H % g == 0)
        heads_first = partial(jnp.swapaxes, axis1=0, axis2=1)
        c, q_nope, q_rope, k_rope = c[0], q_nope[0], q_rope[0], k_rope[0]
        L = c.shape[0]

        def heads(g, out):
            # one group at a time, in a loop: unrolled, the compiler keeps
            # several groups' keys and values at once
            take = partial(jax.lax.dynamic_slice_in_dim, start_index=g * group,
                           slice_size=group)
            kv = jnp.einsum("lc,chd->lhd", c,
                            take(w_kvb, axis=1).astype(c.dtype),
                            preferred_element_type=jnp.float32
                            ).astype(c.dtype)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(
                    k_rope[:, None, :], kv.shape[:-1] + k_rope.shape[-1:])],
                axis=-1)
            q = jnp.concatenate([take(q_nope, axis=1), take(q_rope, axis=1)],
                                axis=-1)
            o = sparse_flash_attention(
                heads_first(q), heads_first(k), heads_first(kv[..., dn:]),
                self.selection, take(sinks, axis=0), scale)
            return jax.lax.dynamic_update_slice_in_dim(out, o, g * group, 0)

        out = jax.lax.fori_loop(0, H // group, heads,
                                jnp.zeros((H, L, dv), c.dtype))
        return heads_first(out)[None]

    def sparse_attention(self, layer: int):
        from stoke_tpu.models.decoder import _absorbed_output, _absorbed_query

        def attend(q_nope, q_rope, c, k_rope, w_kvb, scale, sinks, index):
            B, L = self.positions.shape
            dtype = self.index_keys.dtype
            blocks, offs = _write_targets(
                self.block_tables, self.positions, self.block_size,
                self.lengths if self.mode == "prefill" else None,
            )
            words = self.latent_words
            rows = jnp.concatenate([c, k_rope], axis=-1).reshape(B * L, -1)
            self.latent_words = write_rows(
                words, layer, blocks * self.block_size + offs,
                pack_rows(rows, words.shape[-1], dtype))
            if index is not None:
                q, k, w = index
                row = self._index_row[layer]
                self.index_keys = self.index_keys.at[row, blocks, offs].set(
                    k.reshape(B * L, -1).astype(dtype),
                    mode="promise_in_bounds")
                with jax.named_scope("select"):
                    self.selection = self._select(row, q, k, w)
            if self.mode == "decode":
                per_word = 4 // jnp.dtype(dtype).itemsize
                q_row = _absorbed_query(q_nope, q_rope, w_kvb,
                                        per_word * words.shape[-1])
                addr, count = self.selection
                o_row, issued = sparse_latent_attention(
                    q_row[:, 0], self.latent_words, layer, addr, count,
                    sinks, scale)
                self.words_fetched = self.words_fetched + issued
                return _absorbed_output(o_row[:, None], w_kvb,
                                        q_nope.shape[-1])
            return self._prefill_attention(q_nope, q_rope, c, k_rope, w_kvb,
                                           scale, sinks)

        return attend
