"""GPT-style decoder-only causal language model.

Rounds out the model library (the reference ships no models; SURVEY.md §2.7
has only the CIFAR example).  Reuses the transformer blocks from
``stoke_tpu.models.bert`` with causal attention; works with dense attention
(causal bias built in-model), the pallas flash kernel
(``make_flash_attention(causal=True)``), or sequence-parallel ring/Ulysses
(``make_ring_attention(..., causal=True)``) — set ``attention_is_causal``
when the attention_fn enforces causality itself.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from stoke_tpu.models.bert import (
    BERT_SIZES,
    BertSize,
    CacheSpec,
    TransformerBlock,
    dense_attention,
)


class GPT(nn.Module):
    """Decoder-only LM: learned token+position embeddings, pre-LN-free
    (reuses the post-LN blocks), weight-tied LM head.

    Args:
        size_name: one of BERT_SIZES ("tiny"…"large") — decoder uses the
            same width table.
        attention_is_causal: True when ``attention_fn`` already applies the
            causal mask (flash/ring/ulysses built with ``causal=True``);
            False (default) builds an additive causal bias for dense
            attention.
        tie_embeddings: LM head = transpose of the token embedding.
    """

    vocab_size: int = 50257
    size_name: str = "tiny"
    max_len: int = 1024
    dropout_rate: float = 0.1
    attention_fn: Callable = dense_attention
    attention_is_causal: bool = False
    tie_embeddings: bool = True
    remat: bool = False
    # sparse-FFN option: replace the dense FFN with a switch MoE in every
    # `moe_every`-th block (0 experts = dense everywhere); shard experts
    # with moe_expert_parallel_rules() for expert parallelism
    moe_num_experts: int = 0
    moe_every: int = 2
    moe_capacity_factor: float = 1.25
    moe_router_noise: float = 0.0  # needs the "router" rng stream when > 0
    moe_top_k: int = 1  # experts per token (1 = Switch, 2 = GShard-style)
    # return (hidden, embedding) instead of logits so the loss can run
    # chunked over the sequence (ops/chunked_ce.py) — the [B, L, V] logits
    # tensor is never materialized; requires tie_embeddings
    chunked_head: bool = False

    def cache_spec(self) -> CacheSpec:
        """The serving contract's cache description: two planes (K, V) of
        ``heads * head_dim`` a layer.  Raises for the options the paged
        serving path has no forward for."""
        if self.chunked_head:
            raise ValueError(
                "ServingEngine needs logits from the forward; construct the "
                "serving GPT with chunked_head=False (params are identical)"
            )
        if self.moe_num_experts > 0:
            raise NotImplementedError(
                "ServingEngine supports dense-FFN GPT only (no MoE)"
            )
        size: BertSize = BERT_SIZES[self.size_name]
        return CacheSpec(
            layers=size.num_layers,
            planes=(("k", size.hidden), ("v", size.hidden)),
            kind="mha",
            heads=size.heads,
            head_dim=size.hidden // size.heads,
            max_len=self.max_len,
        )

    @nn.compact
    def __call__(self, input_ids, train: bool = True, positions=None,
                 decode: bool = False, kv_cache=None):
        """``positions`` (optional [L] or [B, L] int) overrides the default
        ``arange`` position ids — required when the sequence is laid out in
        a non-natural order (the zigzag layout of
        ``ops.zigzag_ring_attention``, packed sequences): the position
        embedding must follow each token's ORIGINAL position.  The causal
        attention mask is the attention_fn's job in that case
        (``attention_is_causal=True``).

        Serving path (ISSUE 9): ``kv_cache`` is a per-trace paged-cache
        hook (``stoke_tpu.serving.kv_cache.PagedAttentionHook``) supplying
        one attention fn per layer via ``layer_attention(i)`` — each
        writes that layer's fresh K/V into the block pool and (in decode
        mode) attends over the gathered cached blocks.  ``decode=True``
        marks the incremental single-token forward: ``input_ids`` is
        ``[B, 1]``, ``positions`` carries each slot's current position,
        and the hook's updated page arrays are read back by the caller
        after apply (the hook threads them functionally through one
        trace).  Incremental decode matches the full-sequence forward
        token-for-token (tests/test_serving.py decode-parity).  The
        causal mask is the hook's job, so no in-model bias is built."""
        size: BertSize = BERT_SIZES[self.size_name]
        B, L = input_ids.shape
        if decode and kv_cache is None:
            raise ValueError(
                "GPT: decode=True needs a kv_cache hook — the incremental "
                "forward reads/writes the paged KV-cache "
                "(stoke_tpu.serving.kv_cache.PagedAttentionHook)"
            )
        if decode and L != 1:
            raise ValueError(
                f"GPT: decode=True is single-token incremental decode; got "
                f"sequence length {L} (prefill runs kv_cache without decode)"
            )
        if decode and positions is None:
            raise ValueError(
                "GPT: decode=True needs explicit positions (each slot's "
                "current cache position selects its position embedding)"
            )
        if kv_cache is not None and self.moe_num_experts > 0:
            raise NotImplementedError(
                "GPT: the paged KV-cache serving path supports dense FFN "
                "blocks only (no MoE routing state in the cache)"
            )
        if L > self.max_len:
            # XLA would silently clamp out-of-range position indices,
            # collapsing every position past max_len onto one embedding
            raise ValueError(
                f"GPT: sequence length {L} exceeds max_len={self.max_len}"
            )
        tok_emb = nn.Embed(self.vocab_size, size.hidden, name="tok_emb")
        h = tok_emb(input_ids)
        if positions is None:
            pos = jnp.arange(L)[None, :]
        else:
            # concrete position ids are validated eagerly — XLA's gather
            # would silently CLAMP out-of-range ids onto the max_len-1
            # embedding (same failure mode as the L > max_len guard above);
            # traced positions cannot be checked without a device sync
            if not isinstance(positions, jax.core.Tracer):
                pmax = int(np.max(np.asarray(positions)))
                if pmax >= self.max_len:
                    raise ValueError(
                        f"GPT: positions contain id {pmax} >= "
                        f"max_len={self.max_len}"
                    )
            pos = jnp.asarray(positions)
            if pos.ndim == 1:
                pos = pos[None, :]
        h = h + nn.Embed(self.max_len, size.hidden, name="pos_emb")(pos)
        h = nn.Dropout(self.dropout_rate)(h, deterministic=not train)
        if self.attention_is_causal or kv_cache is not None:
            # cache-aware attention (serving): masking — causal + prompt
            # padding in prefill, context-length in decode — is the
            # kv_cache hook's job, exactly like a causal attention_fn's
            bias = None
        else:
            causal = jnp.tril(jnp.ones((L, L), bool))
            bias = jnp.where(causal, 0.0, -1e9)[None, None, :, :].astype(h.dtype)
        block = TransformerBlock
        moe_block = None
        if self.moe_num_experts > 0:
            from stoke_tpu.models.moe import MoETransformerBlock

            if self.moe_every < 1:
                raise ValueError(
                    f"GPT: moe_every must be >= 1, got {self.moe_every}"
                )
            if size.num_layers // self.moe_every == 0:
                raise ValueError(
                    f"GPT: moe_every={self.moe_every} selects no layer in a "
                    f"{size.num_layers}-layer model — the MoE option would "
                    f"silently train fully dense"
                )
            moe_block = MoETransformerBlock
        if self.remat:
            block = nn.remat(TransformerBlock, static_argnums=(3,))
            if moe_block is not None:
                moe_block = nn.remat(MoETransformerBlock, static_argnums=(3,))
        for i in range(size.num_layers):
            use_moe = (
                moe_block is not None and (i + 1) % self.moe_every == 0
            )
            if use_moe:
                h = moe_block(
                    size.hidden, size.heads, size.ff, self.moe_num_experts,
                    self.dropout_rate, self.moe_capacity_factor,
                    self.attention_fn, self.moe_router_noise,
                    self.moe_top_k, name=f"layer_{i}",
                )(h, bias, not train)
            else:
                # cache-aware serving: each layer gets its OWN attention fn
                # from the hook (it addresses that layer's page plane) —
                # attention_fn is not a parameter, so the param tree is
                # identical to the training forward's
                attn_fn = (
                    self.attention_fn
                    if kv_cache is None
                    else kv_cache.layer_attention(i)
                )
                h = block(
                    size.hidden, size.heads, size.ff, self.dropout_rate,
                    attn_fn, name=f"layer_{i}",
                )(h, bias, not train)
        h = nn.LayerNorm(epsilon=1e-5, name="ln_final")(h)
        if self.chunked_head:
            if not self.tie_embeddings:
                raise ValueError(
                    "GPT: chunked_head requires tie_embeddings=True (the "
                    "chunked loss re-applies the tied embedding per chunk)"
                )
            return h, tok_emb.embedding
        if self.tie_embeddings:
            return tok_emb.attend(h)
        return nn.Dense(self.vocab_size, name="lm_head")(h)


GPTTiny = partial(GPT, size_name="tiny")
GPTBase = partial(GPT, size_name="base")


def causal_lm_loss(logits, input_ids, mask=None):
    """Next-token cross entropy: predict token t+1 from positions ≤ t.
    ``mask`` (optional [B, L] 0/1) excludes padding targets."""
    import optax

    targets = input_ids[:, 1:]
    logits = logits[:, :-1]
    losses = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    if mask is not None:
        w = mask[:, 1:].astype(losses.dtype)
        return (losses * w).sum() / jnp.maximum(w.sum(), 1.0)
    return losses.mean()
