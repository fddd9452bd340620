"""BERT-style transformer encoder for sequence classification.

The reference's capability config #5 (BASELINE.md) is "BERT-base seq-cls with
BucketedDistributedSampler + grad-accum/clip"; the reference itself ships no
model code for it (stoke wraps user models).  This module provides the model
as a first-class flax implementation, TPU-native:

- NHWC-free: everything is [batch, seq, hidden] matmuls → MXU-friendly.
- Attention is pluggable (``attention_fn``) so the same encoder runs dense
  attention today and ring/flash attention (stoke_tpu.ops) for long context.
- Padding-aware: additive attention masks from an int mask, mean/CLS pooling.

Sizes follow the standard family table (base: 12 layers, hidden 768, 12
heads, ff 3072).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class BertSize:
    num_layers: int
    hidden: int
    heads: int
    ff: int


@dataclass(frozen=True)
class CacheSpec:
    """What a model tells ``ServingEngine`` about the cache it serves
    through (the serving contract beside ``__call__(input_ids, train,
    positions, decode, kv_cache)``): the engine builds the page pool from
    it and reads no width table.

    ``planes`` are the pool's arrays, ``(name, row width)`` each, one
    ``[row layers, blocks, block_size, width]`` array a plane; ``values`` is
    how many of a token's row, over all planes, are cached values (None: all
    of it; a model may pad a row to whole 128-lane tiles, which is the form
    the device keeps row-major at rest).  ``kind`` says
    which hook method the model's layers call: ``"mha"`` (``layer_attention``:
    per-head K and V rows, two planes of ``heads * head_dim``),
    ``"latent"`` (``latent_attention``: one row a token, the normed latent
    and the roped shared key side by side; ``layers`` then counts latent
    sublayers, two a layer in a model of double layers) or ``"hybrid"``
    (``layer_attention`` for the layers that cache rows, ``layer_state`` for
    the others).

    ``layer_kinds`` says what each of the ``layers`` keeps: ``"rows"`` (a
    row a token in the planes, growing with the context) or ``"state"`` (a
    constant per-slot state, in the ``state`` arrays); None means every
    layer caches rows.  ``state`` lists the per-slot arrays a state layer
    keeps, ``(name, shape a slot, dtype name)`` each, held as ``[max_seqs,
    *shape]``, an array a state layer; a ``cache`` dtype is the pool's
    (``ServeConfig.kv_dtype``).

    A plane may carry options as a third element (``PagedKVCache``):
    ``"layers"``, the layers that keep a row of it, and ``"packed"``.
    ``"sparse_latent"`` (``sparse_attention``) is the latent cache of a
    model with learned sparse attention: a packed latent plane in every
    layer and an ``index`` plane in the layers with an indexer; each query
    attends ``index_topk`` of its keys."""

    layers: int
    planes: tuple
    kind: str
    heads: int
    head_dim: int
    max_len: int
    values: Optional[int] = None
    layer_kinds: Optional[tuple] = None
    state: tuple = ()
    index_topk: int = 0

    def plane_layers(self, name: str) -> tuple:
        """The layers that keep a row of plane ``name``."""
        for plane in self.planes:
            if plane[0] == name:
                options = plane[2] if len(plane) > 2 else {}
                return tuple(options.get("layers", range(self.layers)))
        raise KeyError(name)

    @property
    def values_per_token(self) -> int:
        """Cached values a token a row layer, all planes together."""
        if self.values is not None:
            return self.values
        return sum(plane[1] for plane in self.planes)

    def layers_of(self, kind: str) -> tuple:
        """The indices of the layers that keep ``kind`` (``"rows"`` or
        ``"state"``), in order: layer ``i``'s place in the planes or in the
        state arrays is its position in this tuple."""
        kinds = self.layer_kinds or ("rows",) * self.layers
        return tuple(i for i, k in enumerate(kinds) if k == kind)


BERT_SIZES = {
    "tiny": BertSize(2, 128, 2, 512),
    "mini": BertSize(4, 256, 4, 1024),
    "small": BertSize(4, 512, 8, 2048),
    "medium": BertSize(8, 512, 8, 2048),
    "base": BertSize(12, 768, 12, 3072),
    "large": BertSize(24, 1024, 16, 4096),
}


def dense_attention(q, k, v, bias, dropout_rng=None, dropout_rate=0.0,
                    deterministic=True):
    """Standard softmax attention: q/k/v [B, H, L, D], bias broadcastable to
    [B, H, L, L].  The default ``attention_fn``; long-context variants
    (ring attention over a mesh seq axis) plug in with the same signature."""
    depth = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(depth).astype(q.dtype)
    if bias is not None:
        scores = scores + bias
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_rate > 0.0 and not deterministic:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = probs * keep / (1.0 - dropout_rate)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


class MultiHeadAttention(nn.Module):
    hidden: int
    heads: int
    dropout_rate: float = 0.1
    attention_fn: Callable = dense_attention

    @nn.compact
    def __call__(self, x, bias, deterministic: bool):
        B, L, H = x.shape
        head_dim = self.hidden // self.heads
        qkv = nn.DenseGeneral((3, self.heads, head_dim), name="qkv")(x)
        q, k, v = jnp.moveaxis(qkv, 2, 0)  # 3 × [B, L, heads, D]
        q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))  # [B, H, L, D]
        rng = None
        if not deterministic and self.dropout_rate > 0.0:
            rng = self.make_rng("dropout")
        out = self.attention_fn(
            q, k, v, bias,
            dropout_rng=rng, dropout_rate=self.dropout_rate,
            deterministic=deterministic,
        )
        out = jnp.swapaxes(out, 1, 2).reshape(B, L, self.hidden)
        return nn.DenseGeneral(self.hidden, name="out")(out)


class TransformerBlock(nn.Module):
    hidden: int
    heads: int
    ff: int
    dropout_rate: float = 0.1
    attention_fn: Callable = dense_attention

    @nn.compact
    def __call__(self, x, bias, deterministic: bool):
        y = MultiHeadAttention(
            self.hidden, self.heads, self.dropout_rate, self.attention_fn,
            name="attention",
        )(x, bias, deterministic)
        y = nn.Dropout(self.dropout_rate)(y, deterministic=deterministic)
        x = nn.LayerNorm(epsilon=1e-12, name="ln_attn")(x + y)
        y = nn.Dense(self.ff, name="ff_in")(x)
        y = nn.gelu(y)
        y = nn.Dense(self.hidden, name="ff_out")(y)
        y = nn.Dropout(self.dropout_rate)(y, deterministic=deterministic)
        return nn.LayerNorm(epsilon=1e-12, name="ln_ff")(x + y)


class BertEncoder(nn.Module):
    """Token + position + segment embeddings, N transformer blocks.

    ``layer_drop_rate`` enables progressive layer drop — stochastic depth
    with a linearly increasing drop probability over depth (layer i is kept
    with probability ``1 - rate * (i+1)/N`` during training).  This is the
    TPU-native counterpart of the reference's DeepSpeed PLD passthrough
    (configs.py:375-388, distributed.py:876-896); needs the ``layer_drop``
    rng stream (pass ``model_rng_keys=("dropout", "layer_drop")`` to Stoke).

    The reference PLD additionally exposes a theta/gamma TIME schedule
    (``DeepspeedPLDConfig``, configs.py:375-388): the global keep ratio
    warms from 1 toward ``theta`` as ``theta_bar(t) = (1-theta) *
    exp(-gamma*t) + theta``.  Set ``layer_drop_theta``/``layer_drop_gamma``
    and pass the current optimizer step as the ``global_step`` call kwarg
    (a traced scalar, so the scanned multi-step paths can feed a per-step
    value); the depth-linear drop fraction then becomes
    ``(1 - theta_bar(t)) * (i+1)/N``.  Without ``global_step`` (or with
    ``layer_drop_theta=None``) the static ``layer_drop_rate`` applies.
    """

    vocab_size: int
    size: BertSize
    max_len: int = 512
    dropout_rate: float = 0.1
    attention_fn: Callable = dense_attention
    remat: bool = False
    layer_drop_rate: float = 0.0
    layer_drop_theta: Optional[float] = None
    layer_drop_gamma: float = 0.001

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None,
                 train: bool = True, global_step=None):
        B, L = input_ids.shape
        h = nn.Embed(self.vocab_size, self.size.hidden, name="tok_emb")(input_ids)
        pos = jnp.arange(L)[None, :]
        h = h + nn.Embed(self.max_len, self.size.hidden, name="pos_emb")(pos)
        if token_type_ids is not None:
            h = h + nn.Embed(2, self.size.hidden, name="seg_emb")(token_type_ids)
        h = nn.LayerNorm(epsilon=1e-12, name="ln_emb")(h)
        h = nn.Dropout(self.dropout_rate)(h, deterministic=not train)
        if attention_mask is None:
            bias = None
        else:
            # additive mask: [B, 1, 1, L]; large negative on padding
            bias = jnp.where(attention_mask[:, None, None, :] > 0, 0.0, -1e9).astype(
                h.dtype
            )
        block = TransformerBlock
        if self.remat:
            block = nn.remat(TransformerBlock, static_argnums=(3,))
        pld_on = train and (
            self.layer_drop_rate > 0.0 or self.layer_drop_theta is not None
        )
        drop_keys = None
        drop_frac = None
        if pld_on:
            drop_keys = jax.random.split(
                self.make_rng("layer_drop"), self.size.num_layers
            )
            if self.layer_drop_theta is not None and global_step is None:
                raise ValueError(
                    "Stoke -- layer_drop_theta is set (PLD theta/gamma time "
                    "schedule) but the forward was called without the "
                    "global_step kwarg; the schedule would silently never "
                    "engage.  Pass global_step=<optimizer step> (a traced "
                    "scalar), or use the static layer_drop_rate instead."
                )
            if self.layer_drop_theta is not None:
                # reference theta/gamma schedule (DeepspeedPLDConfig,
                # configs.py:375-388): keep ratio decays 1 -> theta
                theta = jnp.float32(self.layer_drop_theta)
                theta_bar = (1.0 - theta) * jnp.exp(
                    -jnp.float32(self.layer_drop_gamma)
                    * jnp.asarray(global_step, jnp.float32)
                ) + theta
                drop_frac = 1.0 - theta_bar
            else:
                drop_frac = jnp.float32(self.layer_drop_rate)
        for i in range(self.size.num_layers):
            h_new = block(
                self.size.hidden, self.size.heads, self.size.ff,
                self.dropout_rate, self.attention_fn, name=f"layer_{i}",
            )(h, bias, not train)
            if drop_keys is not None:
                keep_p = 1.0 - drop_frac * (i + 1) / self.size.num_layers
                keep = jax.random.bernoulli(drop_keys[i], keep_p)
                h = jnp.where(keep, h_new, h)
            else:
                h = h_new
        return h


class BertForSequenceClassification(nn.Module):
    """Encoder + tanh pooler over [CLS] + classifier head (BERT seq-cls)."""

    vocab_size: int = 30522
    num_classes: int = 2
    size_name: str = "base"
    max_len: int = 512
    dropout_rate: float = 0.1
    attention_fn: Callable = dense_attention
    remat: bool = False
    layer_drop_rate: float = 0.0
    layer_drop_theta: Optional[float] = None
    layer_drop_gamma: float = 0.001

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None,
                 train: bool = True, global_step=None):
        size = BERT_SIZES[self.size_name]
        h = BertEncoder(
            self.vocab_size, size, self.max_len, self.dropout_rate,
            self.attention_fn, self.remat, self.layer_drop_rate,
            self.layer_drop_theta, self.layer_drop_gamma,
            name="encoder",
        )(input_ids, attention_mask, token_type_ids, train, global_step)
        cls = nn.tanh(nn.Dense(size.hidden, name="pooler")(h[:, 0]))
        cls = nn.Dropout(self.dropout_rate)(cls, deterministic=not train)
        return nn.Dense(self.num_classes, name="classifier")(cls)


BertBase = partial(BertForSequenceClassification, size_name="base")
BertTiny = partial(BertForSequenceClassification, size_name="tiny")


def bert_tensor_parallel_rules(model_axis: str = "model"):
    """Megatron-style tensor-parallel partition rules for the BERT family
    (for ``PartitionRulesConfig``; requires a mesh with ``model_axis`` and
    heads/ff divisible by its size).

    Column-parallel: qkv projection (split over heads) and ff_in (split over
    the ff dim); row-parallel: attention output and ff_out (split over the
    input dim).  GSPMD derives the all-reduces after the row-parallel
    matmuls from these placements.
    """
    return (
        (r"attention/qkv/kernel", (None, None, model_axis, None)),
        (r"attention/qkv/bias", (None, model_axis, None)),
        (r"attention/out/kernel", (model_axis, None)),
        (r"ff_in/kernel", (None, model_axis)),
        (r"ff_in/bias", (model_axis,)),
        (r"ff_out/kernel", (model_axis, None)),
    )
