"""A pre-norm decoder assembled from a ``config.json``-shaped description.

Four families' blocks, keyed by their sources' own names, so a published
configuration builds the model as it stands.  All: a final RMSNorm; an
untied ``lm_head``; no biases.  The first two: per layer ``h +=
Attn(RMSNorm(h))``, ``h += FFN(RMSNorm(h))``, the feed-forward a dense
SwiGLU in the first ``first_k_dense_replace`` layers and an expert layer
after (:class:`stoke_tpu.models.moe.ExpertShareFFN`: the router's published
width, the experts this chip holds, the shared expert).  The third
(``attention_method``, ``ffn_hidden_size``, ``zero_expert_num`` ...; the
LongCat-Flash family) is made of *double layers*
(:class:`ShortcutDoubleLayer`): two latent-attention sublayers and two
dense feed-forwards in series and ONE expert layer that reads the first
sublayer's output and is added back at the double layer's end, its router a
softmax over the routed and the zero-compute experts.  The attention kind
of layer ``i`` is :meth:`DecoderConfig.layer_kind`:

- ``"mla"``, the DeepSeek-V3 family's (``q_lora_rank``, ``kv_lora_rank``,
  ``qk_nope_head_dim``, ``rope_scaling`` ...; A.X-K1, ``model_type:
  "axk1"``): multi-head latent attention, queries through a low-rank pair
  ``W_qb RMSNorm(W_qa x)``, split per head into a ``nope`` and a ``rope``
  part; keys and values from one latent a token, ``[c, k_r] = W_kva x``
  with ``c`` RMS-normed and ``k_r`` roped once for all heads, ``[k_nope, v]
  = W_kvb c``.  Rotary positions with YaRN frequencies, pairs ``(2i,
  2i+1)``.  Under ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` the query
  is scaled by ``(hidden_size / q_lora_rank) ** 0.5`` and the normed latent
  by ``(hidden_size / kv_lora_rank) ** 0.5`` (the roped key is not);
- ``"gqa"`` and ``"kda"``, a hybrid keyed by ``gqa_layers`` and
  ``linear_attn_config`` (Solar-Open2, ``model_type: "solar_open2"``): the
  layers in ``gqa_layers`` are softmax attention without positions,
  ``num_attention_heads`` query heads over ``num_key_value_heads`` key-value
  heads, the output gated elementwise by ``sigmoid(W_g x)``
  (:class:`GroupedQueryAttention`); the others are gated delta-rule linear
  attention with a decay a channel (Kimi Delta Attention, arXiv:2510.26692;
  :class:`DeltaRuleAttention`): a ``[d_k, d_v]`` float32 state a head in
  place of cached rows, a depthwise causal convolution on q, k and v.

The fourth (``use_dsa``, ``indexer_types``, ``hc_mult`` ...; Hy4,
``model_type: "hy_v4"``) is the first's latent attention made sparse and
its residual path widened (:class:`StreamLayer`): each query attends only
the ``index_topk`` keys a learned indexer scores highest (DeepSeek-V3.2's
lightning indexer, :class:`Indexer`; a ``full`` layer has one, the
``shared`` layers after it reuse its choice), a sink a head joins the
softmax's denominator, the attended values are gated elementwise, the
residual stream is ``hc_mult`` rows mixed around each sublayer by
manifold-constrained hyper-connections (:class:`HyperConnection`, Sinkhorn
on the mixing matrix), every SwiGLU is limited (``swiglu_limit``), the
router chooses by sigmoid scores plus a bias, and the head is float32.

The same ``__call__(input_ids, train, positions, decode, kv_cache)``
contract as :class:`stoke_tpu.models.gpt.GPT`, and :meth:`Decoder.cache_spec`
for ``ServingEngine``.  A latent model caches one row a token a latent
sublayer (``kv_lora_rank + qk_rope_head_dim`` values, stored padded to whole
128-lane tiles; a double layer has two sublayers, so two rows of the plane,
``2 l`` and ``2 l + 1``); a hybrid one a row of keys and values a token in its
``gqa`` layers only and a constant state a slot in its ``kda`` layers
(``CacheSpec.layer_kinds``, ``CacheSpec.state``).  With a cache hook each
layer's attention is the hook's (``kv_cache.latent_attention(i)``: the row
written once, then the expanded form in prefill and the absorbed form in
decode; ``kv_cache.layer_attention(i)`` for a ``gqa`` layer;
``kv_cache.layer_state(i)`` hands a ``kda`` layer its slots' state and
takes it back; ``kv_cache.sparse_attention(i)`` a DSA layer its attention
over the chosen rows, ``CacheSpec.kind == "sparse_latent"``); without one
it is the whole-sequence form.

Parameters are ``param_dtype``, products take ``dtype`` inputs and
accumulate in float32; router, softmax, RMSNorm statistics and the whole of
the delta rule's recurrence (decay, state, its products) are float32.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from stoke_tpu.models.bert import CacheSpec
from stoke_tpu.models.moe import ExpertShareFFN, SwiGLU
from stoke_tpu.ops.delta_rule import delta_rule_step
from stoke_tpu.ops.flash_attention import grouped_query_attention
from stoke_tpu.ops.sparse_attention import selection_mask

_NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """The keys of the source's ``config.json`` the decoder reads, under
    the source's names.  ``n_routed_experts`` is the router's width.  The
    latent-attention keys are needed where a layer is ``"mla"``, the
    ``gqa_layers`` / ``linear_*`` ones where the model is a hybrid."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    intermediate_size: int = 0
    n_group: int = 1
    topk_group: int = 1
    first_k_dense_replace: int = 1
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    max_position_embeddings: int = 4096
    # YaRN, from ``rope_scaling`` (factor 1: plain rotary frequencies)
    rope_factor: float = 1.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    rope_original_max_position_embeddings: int = 4096
    # the hybrid: which layers are softmax attention (None: every layer is
    # latent attention), its key-value heads and head size, its gate; the
    # delta-rule layers' heads, head size and convolution, from
    # ``linear_attn_config``
    gqa_layers: Optional[Tuple[int, ...]] = None
    num_key_value_heads: int = 0
    head_dim: int = 0
    use_gqa_gate: bool = False
    linear_num_heads: int = 0
    linear_head_dim: int = 0
    linear_conv_kernel: int = 4
    kda_allow_neg_eigval: bool = False
    # the double-layer family: every layer two latent sublayers, two dense
    # feed-forwards and a shortcut expert branch; its router's scoring, the
    # zero-compute outputs after the routed ones, the learned bias on the
    # choice; the two scales of its latent attention
    shortcut_double_layers: bool = False
    scoring_func: str = "sigmoid"
    zero_expert_num: int = 0
    router_choice_bias: bool = False
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    # the DSA family (``use_dsa``, ``index_*``, ``hc_*``; Hy4, ``model_type:
    # "hy_v4"``): a learned sparse selection of each query's keys (an
    # indexer in the ``full`` layers of ``indexer_types``, whose selection
    # the ``shared`` layers after it reuse), an elementwise gate and a sink a
    # head on the latent attention, the residual stream as ``hc_mult``
    # hyper-connections, a limit on every SwiGLU, a float32 head
    use_dsa: bool = False
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    indexer_types: Optional[Tuple[str, ...]] = None
    mla_gate: bool = False
    learnable_sink: bool = False
    hc_mult: int = 0
    hc_magnitude: float = 2.0
    hc_eps: float = 1e-6
    swiglu_limit: float = 0.0
    lm_head_fp32: bool = False

    @classmethod
    def from_dict(cls, config: dict) -> "DecoderConfig":
        """From a dict with the source's key names; keys the decoder does
        not read (``model_type``, ``seq_aux``, ``ep_size`` ...) are left.
        What the decoder cannot build is an error, not a default."""
        if config.get("hidden_act", "silu") != "silu":
            raise ValueError(f"hidden_act {config['hidden_act']!r}: the "
                             f"feed-forward here is SwiGLU")
        if config.get("scoring_func", "sigmoid") != "sigmoid":
            raise ValueError("the router here scores with a sigmoid")
        if config.get("moe_layer_freq", 1) != 1:
            raise ValueError("every layer after the dense ones is an "
                             "expert layer here (moe_layer_freq 1)")
        if config.get("tie_word_embeddings", False):
            raise ValueError("the head here is untied")
        fields = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in config.items() if k in fields}
        scaling = config.get("rope_scaling")
        if scaling:
            if scaling.get("type", scaling.get("rope_type")) != "yarn":
                raise ValueError(f"rope_scaling {scaling!r}: only yarn")
            for key in ("factor", "beta_fast", "beta_slow", "mscale",
                        "mscale_all_dim", "original_max_position_embeddings"):
                if key in scaling:
                    kwargs["rope_" + key] = scaling[key]
        if "gqa_layers" in config:
            kwargs.update(cls._hybrid_keys(config))
        else:
            needed = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                      "qk_rope_head_dim", "v_head_dim")
            if "attention_method" in config:
                kwargs.update(cls._double_layer_keys(config))
            else:
                needed += ("n_group", "topk_group")
            if config.get("use_dsa"):
                kwargs.update(cls._sparse_keys(config))
                needed += ("index_n_heads", "index_head_dim", "index_topk",
                           "indexer_types", "hc_mult")
            missing = [k for k in needed if k not in config]
            if missing:
                raise ValueError(
                    f"latent attention needs {missing}: a configuration "
                    f"without gqa_layers is the DeepSeek-V3 family's, or "
                    f"with attention_method the double-layer family's")
        made = cls(**kwargs)
        if made.first_k_dense_replace and not made.intermediate_size:
            raise ValueError("the dense layers need intermediate_size")
        return made

    @staticmethod
    def _hybrid_keys(config: dict) -> dict:
        """The hybrid's keys, checked: softmax layers without positions and
        delta-rule layers with the low-rank decay projection are what is
        built here."""
        if config.get("use_rope", False):
            raise ValueError("use_rope: the softmax layers of a hybrid "
                             "carry no positions here")
        if config.get("kda_use_full_proj", False):
            raise ValueError("kda_use_full_proj: the decay here goes "
                             "through the low-rank pair")
        linear = config.get("linear_attn_config")
        if not linear:
            raise ValueError("gqa_layers without linear_attn_config: the "
                             "other layers are delta-rule layers")
        if linear.get("num_kv_heads") not in (None, linear["num_heads"]):
            raise ValueError("linear_attn_config.num_kv_heads: the "
                             "delta-rule layers keep a state a head")
        for key in ("num_key_value_heads", "head_dim"):
            if not config.get(key):
                raise ValueError(f"grouped-query attention needs {key}")
        if config["num_attention_heads"] % config["num_key_value_heads"]:
            raise ValueError("the query heads do not divide over the "
                             "key-value heads")
        # a configuration cut in depth keeps the source's list
        layers = tuple(int(i) for i in config["gqa_layers"]
                       if int(i) < config["num_hidden_layers"])
        return {
            "gqa_layers": layers,
            "linear_num_heads": int(linear["num_heads"]),
            "linear_head_dim": int(linear["head_dim"]),
            "linear_conv_kernel": int(linear["short_conv_kernel_size"]),
        }

    @staticmethod
    def _double_layer_keys(config: dict) -> dict:
        """The double-layer family's keys (``num_layers``,
        ``ffn_hidden_size``, ``expert_ffn_hidden_size``, ``moe_topk``,
        ``zero_expert_num`` ...) under the fields the decoder reads, checked:
        latent attention and identity zero-compute experts are what is
        built here.  The family's defaults where the source is silent: a
        softmax router without a bias on its logits, weights not
        renormalised, a learned bias on the choice, no shared expert."""
        if config["attention_method"] != "MLA":
            raise ValueError(
                f"attention_method {config['attention_method']!r}: the "
                f"double layer's two attentions are latent attention here")
        if config.get("zero_expert_type", "identity") != "identity":
            raise ValueError(
                f"zero_expert_type {config['zero_expert_type']!r}: a "
                f"zero-compute expert here is the identity")
        if config.get("router_bias", False):
            raise ValueError("router_bias: the router's logits carry no "
                             "bias here")
        return {
            "num_hidden_layers": int(config["num_layers"]),
            "intermediate_size": int(config["ffn_hidden_size"]),
            "moe_intermediate_size": int(config["expert_ffn_hidden_size"]),
            "num_experts_per_tok": int(config["moe_topk"]),
            "shortcut_double_layers": True,
            "scoring_func": "softmax",
            "router_choice_bias": True,
            "norm_topk_prob": bool(config.get("norm_topk_prob", False)),
            "n_shared_experts": 0,
            "first_k_dense_replace": 0,
        }

    @staticmethod
    def _sparse_keys(config: dict) -> dict:
        """The DSA family's keys (``use_dsa``, ``index_n_heads``,
        ``indexer_types``, ``gated_mla`` / ``gating_type``,
        ``learnable_sink``, ``hc_mult`` ..., ``swiglu_limit``,
        ``enable_lm_head_fp32``, ``rope_parameters``, ``mlp_layer_types``)
        under the fields the decoder reads, checked: latent attention in
        every layer, an elementwise gate, ``full`` and ``shared`` indexer
        layers with a ``full`` one first, leading dense feed-forwards, plain
        rotary frequencies and hyper-connections around both sublayers are
        what is built here."""
        n = int(config["num_hidden_layers"])
        if not config.get("use_mla", True):
            raise ValueError("use_mla false: the attention here is latent")
        kinds = set(config.get("layer_types", ["deepseek_sparse_attention"]))
        if kinds != {"deepseek_sparse_attention"}:
            raise ValueError(f"layer_types {sorted(kinds)}: every layer is "
                             f"deepseek_sparse_attention here")
        rope = config.get("rope_parameters") or {}
        if rope.get("rope_type", "default") != "default":
            raise ValueError(f"rope_parameters {rope!r}: plain rotary "
                             f"frequencies only")
        qk = config.get("qk_head_dim")
        if qk is not None and qk != (config["qk_nope_head_dim"]
                                     + config["qk_rope_head_dim"]):
            raise ValueError(f"qk_head_dim {qk}: the nope and rope parts "
                             f"make the query-key head")
        gated = bool(config.get("gated_mla", False))
        if gated and config.get("gating_type", "elementwise") != "elementwise":
            raise ValueError(f"gating_type {config['gating_type']!r}: the "
                             f"gate here is elementwise")
        mlp = list(config.get("mlp_layer_types", ["dense"] + ["sparse"] * n))
        dense = next((i for i, t in enumerate(mlp) if t != "dense"), len(mlp))
        if set(mlp[dense:]) - {"sparse"}:
            raise ValueError("mlp_layer_types: the dense feed-forwards lead "
                             "and every later layer is an expert layer here")
        out = {
            "first_k_dense_replace": dense,
            "mla_gate": gated,
            "learnable_sink": bool(config.get("learnable_sink", False)),
            "swiglu_limit": float(config.get("swiglu_limit") or 0.0),
            "lm_head_fp32": bool(config.get("enable_lm_head_fp32", False)),
            "router_choice_bias": True,
        }
        if "rope_theta" in rope:
            out["rope_theta"] = float(rope["rope_theta"])
        if "indexer_types" in config:
            types = tuple(config["indexer_types"])[:n]
            if set(types) - {"full", "shared"}:
                raise ValueError(f"indexer_types {sorted(set(types))}: an "
                                 f"indexer layer is full or shared here")
            if types[0] != "full":
                raise ValueError("indexer_types: the first layer is shared "
                                 "and has no full layer to reuse")
            out["indexer_types"] = types
        if not config.get("hc_mult") or not config.get("enable_ihc", True):
            raise ValueError("hyper-connections around both sublayers of "
                             "every layer (hc_mult, enable_ihc) are what "
                             "the DSA family is built with here")
        return out

    def layer_kind(self, i: int) -> str:
        """Layer ``i``'s attention: ``"mla"``, ``"gqa"`` or ``"kda"``."""
        if self.gqa_layers is None:
            return "mla"
        return "gqa" if i in self.gqa_layers else "kda"

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(map(self.layer_kind, range(self.num_hidden_layers)))

    @property
    def kv_row_width(self) -> int:
        """A ``gqa`` layer's cached row: a token's keys, then its values,
        every key-value head's side by side."""
        return 2 * self.num_key_value_heads * self.head_dim

    @property
    def conv_width(self) -> int:
        """Channels the delta rule's convolution runs over: q, k and v."""
        return 3 * self.linear_num_heads * self.linear_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def indexes(self, i: int) -> bool:
        """Whether layer ``i`` has an indexer of its own (a ``full`` layer of
        a DSA model); a ``shared`` layer reuses the selection of the nearest
        ``full`` layer before it."""
        return self.use_dsa and self.indexer_types[i] == "full"

    @property
    def full_layers(self) -> Tuple[int, ...]:
        """The layers that have an indexer, in order."""
        return tuple(i for i in range(self.num_hidden_layers)
                     if self.indexes(i))

    @property
    def latent_sublayers(self) -> int:
        """Latent-attention sublayers, each a row of the latent plane: two
        a double layer, else one a layer."""
        return self.num_hidden_layers * (
            2 if self.shortcut_double_layers else 1)

    @property
    def latent_width(self) -> int:
        """Values cached a token a layer: the normed latent and the roped
        shared key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row_width(self) -> int:
        """The cached row as stored: ``latent_width`` values, then zeros up
        to whole 128-lane tiles (576 -> 640).  A 576-wide plane the device
        keeps with the block index in the lanes at rest and copies whole,
        twice a dispatch; a 640-wide one it keeps row-major, as computed
        on."""
        return -(-self.latent_width // 128) * 128


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cfg: DecoderConfig) -> float:
    """``qk_head_dim ** -0.5``, times the square of YaRN's attention factor
    over all dimensions (the family's convention)."""
    m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return cfg.qk_head_dim ** -0.5 * m * m


def rope_inv_freq(cfg: DecoderConfig):
    """``(float32[qk_rope_head_dim / 2] rotary frequencies, the factor on
    cos and sin)``: YaRN's blend of the plain and the ``factor``-times-
    interpolated frequencies, by a linear ramp between the dimensions that
    turn ``beta_fast`` and ``beta_slow`` times over the original context;
    the factor is ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)``, 1 where the two are equal."""
    dim, base = cfg.qk_rope_head_dim, float(cfg.rope_theta)
    plain = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if cfg.rope_factor <= 1.0:
        return plain, 1.0

    def correction_dim(rotations):
        return (dim * math.log(cfg.rope_original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    span = (high - low) or 0.001
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / span, 0.0, 1.0
    )
    inv_freq = plain / cfg.rope_factor * ramp + plain * (1.0 - ramp)
    cos_sin_scale = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / (
        _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return inv_freq, cos_sin_scale


def apply_rope(x, positions, cfg: DecoderConfig):
    """Rotate the pairs ``(2i, 2i+1)`` of ``x [..., qk_rope_head_dim]`` by
    ``positions * inv_freq[i]``; ``positions`` broadcasts against ``x``'s
    leading dimensions.  Angles and the rotation are float32."""
    inv_freq, m = rope_inv_freq(cfg)
    angle = positions[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angle) * m, jnp.sin(angle) * m
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class RMSNorm(nn.Module):
    """``gain`` (a constant, not a parameter) multiplies the normed values
    in float32, before they are rounded to ``dtype``."""

    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    gain: float = 1.0

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           self.param_dtype)
        y = x.astype(jnp.float32)
        y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + self.eps)
        y = y * scale.astype(jnp.float32)
        if self.gain != 1.0:
            y = y * self.gain
        return y.astype(self.dtype)


# --------------------------------------------------------------------------- #
# the two forms of one attention
# --------------------------------------------------------------------------- #


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def expanded_attention(q_nope, q_rope, c, k_rope, w_kvb, scale, key_valid,
                       impl: str = "dense"):
    """MLA with keys and values expanded per head, causal over one padded
    sequence: the training and prefill form.

    ``q_nope [B, L, H, dn]``, ``q_rope [B, L, H, dr]`` (roped), ``c [B, L,
    C]`` (normed latent), ``k_rope [B, L, dr]`` (roped, shared by the
    heads), ``w_kvb [C, H, dn + dv]``, ``key_valid [B, L]`` bool.  Returns
    ``[B, L, H, dv]``.  ``impl="flash"`` runs the repo's flash kernel, which
    wants one head size for q, k and v and scales by its inverse root: the
    values are zero-padded from ``dv`` to ``dn + dr`` (a third more
    ``P V`` work than the algorithm needs) and YaRN's factor goes on q."""
    dn = q_nope.shape[-1]
    dtype = q_nope.dtype
    kv = _einsum("blc,chd->blhd", c, w_kvb.astype(dtype)).astype(dtype)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  k_nope.shape[:-1] + k_rope.shape[-1:])],
        axis=-1,
    )
    D, dv = q.shape[-1], v.shape[-1]
    if impl == "flash":
        from stoke_tpu.ops.flash_attention import flash_attention

        q = (q * (scale * D ** 0.5)).astype(dtype)
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, D - dv)))
        heads_first = partial(jnp.swapaxes, axis1=1, axis2=2)
        out = flash_attention(
            heads_first(q), heads_first(k), heads_first(v),
            key_valid.astype(jnp.int32), causal=True,
        )
        return heads_first(out)[..., :dv]
    if impl != "dense":
        raise ValueError(f"unknown attention impl {impl!r}")
    L = q.shape[1]
    s = _einsum("bqhd,bkhd->bhqk", q, k) * scale
    allow = jnp.tril(jnp.ones((L, L), bool))[None, None] & (
        key_valid[:, None, None, :])
    p = jax.nn.softmax(jnp.where(allow, s, _NEG_INF), axis=-1)
    return _einsum("bhqk,bkhd->bqhd", p.astype(dtype), v).astype(dtype)


def _absorbed_query(q_nope, q_rope, w_kvb, width):
    """``[B, S, H, width]`` rows the cached rows are scored against:
    ``q_nope W_UK``, the roped part, zeros up to the cached row's width."""
    dn = q_nope.shape[-1]
    dtype = q_nope.dtype
    q_lat = _einsum(
        "bshd,chd->bshc", q_nope, w_kvb[..., :dn].astype(dtype)
    ).astype(dtype)
    q_row = jnp.concatenate([q_lat, q_rope], axis=-1)  # [B, S, H, C + dr]
    return jnp.pad(q_row, ((0, 0),) * 3 + ((0, width - q_row.shape[-1]),))


def _absorbed_output(o_row, w_kvb, dn):
    """``(softmax . rows) W_UV``: of the attended rows ``[B, S, H, >= C]``
    the latent lanes, through the value half of ``w_kvb``.  The key and
    padding lanes rode along and are dropped here; slicing them off the
    cached rows first would copy those."""
    dtype = o_row.dtype
    return _einsum(
        "bshc,chd->bshd", o_row[..., : w_kvb.shape[0]],
        w_kvb[..., dn:].astype(dtype),
    ).astype(dtype)


def absorbed_attention(q_nope, q_rope, window, positions, w_kvb, scale):
    """MLA over cached latent rows with ``W_kvb`` absorbed into the query
    and the output: the decode form, over a window gathered out of the
    cache.  It defines what :func:`absorbed_paged_attention` computes and
    is what the tests compare that with.  ``W_UK`` and ``W_UV`` are the two
    halves of ``w_kvb``, not new parameters.

    ``q_nope [B, S, H, dn]``, ``q_rope [B, S, H, dr]`` (roped), ``window
    [B, W, >= C + dr]`` (a slot's cached rows in position order: normed
    latent, roped shared key, then the row's zero padding, which the query
    is padded to meet), ``positions [B, S]``: query row ``s`` attends
    window positions ``<= positions[b, s]``.  ``q_lat = q_nope W_UK``;
    scores are ``q_lat . c + q_r . k_r``, one product over the whole row;
    ``o = (softmax . c) W_UV``.  Returns ``[B, S, H, dv]``."""
    dtype = q_nope.dtype
    q_row = _absorbed_query(q_nope, q_rope, w_kvb, window.shape[-1])
    window = window.astype(dtype)
    s = _einsum("bshj,bwj->bshw", q_row, window) * scale
    W = window.shape[1]
    valid = (jnp.arange(W, dtype=jnp.int32)[None, None, None, :]
             <= positions[:, :, None, None])
    p = jax.nn.softmax(jnp.where(valid, s, _NEG_INF), axis=-1)
    o_row = _einsum("bshw,bwj->bshj", p.astype(dtype), window).astype(dtype)
    return _absorbed_output(o_row, w_kvb, q_nope.shape[-1])


def absorbed_paged_attention(q_nope, q_rope, plane, layer, block_tables,
                             context_lens, w_kvb, scale):
    """:func:`absorbed_attention` of one query row a slot (``S == 1``) at
    its last cached position, over the latent plane ``[n_layers, NB, BS,
    row]`` read in place: the Pallas kernel
    ``ops/flash_attention.py`` ``latent_paged_attention`` streams each
    slot's ``ceil(context_lens[b] / BS)`` live blocks of
    ``block_tables[b]`` and gathers no window.  Same products in the same
    precisions.  Returns ``[B, 1, H, dv]``."""
    from stoke_tpu.ops.flash_attention import latent_paged_attention

    q_row = _absorbed_query(q_nope, q_rope, w_kvb, plane.shape[-1])
    o_row = latent_paged_attention(
        q_row[:, 0], plane, layer, block_tables, context_lens, scale)
    return _absorbed_output(o_row[:, None], w_kvb, q_nope.shape[-1])


# --------------------------------------------------------------------------- #
# learned sparse attention: the indexer's selection, and attention over it
# --------------------------------------------------------------------------- #


def indexer_selection(q, k, w, k_top: int, key_valid):
    """The keys each query attends (DeepSeek-V3.2's lightning indexer), the
    whole-sequence form: ``q [B, L, Hi, D]``, ``k [B, L, D]`` (both roped),
    ``w [B, L, Hi]`` float32, ``key_valid [B, L]``.  ``I_ts = D^-0.5 sum_j
    w_tj ReLU(q_tj . k_s)`` for ``s <= t``; a query attends its ``k_top``
    keys of highest ``I``, equal scores lower position first (all of them
    while it has no more).  Returns bool ``[B, L, L]``."""
    B, L, _, D = q.shape
    s = jnp.einsum("bqhd,bkd->bqhk", q, k.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    s = (jnp.maximum(s, 0.0) * w[..., None]).sum(axis=2) * D ** -0.5
    allow = jnp.tril(jnp.ones((L, L), bool))[None] & key_valid[:, None, :]
    s = jnp.where(allow, s, -jnp.inf)
    return selection_mask(s.reshape(B * L, L), k_top).reshape(B, L, L)


def sparse_expanded_attention(q_nope, q_rope, c, k_rope, w_kvb, scale, sinks,
                              selection):
    """:func:`expanded_attention` over each query's selected keys only
    (``selection [B, L, L]`` bool), with a sink a head (``sinks [H]``
    float32, ``-inf`` for none): ``p_ts = e^{z_ts} / (e^{sink} + sum_{s in
    S_t} e^{z_ts})``.  The whole-sequence form; returns ``[B, L, H, dv]``."""
    dn = q_nope.shape[-1]
    dtype = q_nope.dtype
    kv = _einsum("blc,chd->blhd", c, w_kvb.astype(dtype)).astype(dtype)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  k_nope.shape[:-1] + k_rope.shape[-1:])],
        axis=-1,
    )
    s = _einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(selection[:, None], s, -jnp.inf)
    sink = sinks[None, :, None, None]
    m = jnp.maximum(s.max(-1, keepdims=True), sink)
    p = jnp.exp(s - m)
    p = p / (p.sum(-1, keepdims=True) + jnp.exp(sink - m))
    return _einsum("bhqk,bkhd->bqhd", p.astype(dtype), v).astype(dtype)


#: Sinkhorn iterations of a hyper-connection's residual mix: mHC's t_max
#: (the configuration has no key for it)
SINKHORN_ITERS = 20


def sinkhorn(logits, iters: int, eps: float):
    """``exp(logits)`` ``[..., n, n]`` made doubly stochastic by ``iters``
    alternations of row and column normalisation (Sinkhorn-Knopp, ``eps`` in
    each denominator)."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


# --------------------------------------------------------------------------- #
# the delta rule with a decay a channel: one token, and a whole prompt
# --------------------------------------------------------------------------- #

#: positions a step of :func:`delta_rule_chunked` takes.  The pairwise decay
#: ``exp(G_t - G_j)`` of a step is ``[heads, C, C, d_k]`` float32 (32 MB at
#: 64 heads of 128), and its exponentials grow with ``L * C``.
DELTA_RULE_CHUNK = 32

_HIGHEST = jax.lax.Precision.HIGHEST


def delta_rule_chunked(q, k, v, g, beta):
    """:func:`delta_rule_step` over the ``L`` positions of each sequence
    from zero state, ``DELTA_RULE_CHUNK`` positions a step of the scan: the
    whole-prompt form.  Exactly the recurrence, regrouped; float32, its
    matrix products at ``Precision.HIGHEST``.

    ``q``, ``k``, ``g [B, L, H, dk]``, ``v [B, L, H, dv]``, ``beta [B, L,
    H]``.  A position with ``beta = 0`` and ``g = 0`` leaves the state as it
    was (how a caller masks padding).  Returns ``(o [B, L, H, dv], state
    after the last position)``.

    Within a chunk, with ``G_t`` the running sum of ``g`` from the chunk's
    start and ``S_0`` the state before it, the rule unrolls to ``S_t =
    diag(e^{G_t}) S_0 + sum_{j <= t} diag(e^{G_t - G_j}) k_j u_j^T`` where
    the ``u`` solve the unit lower-triangular system ``u_t + beta_t sum_{j <
    t} A_tj u_j = beta_t (v_t - S_0^T (e^{G_t} k_t))``, ``A_tj = sum_d k_td
    k_jd e^{G_td - G_jd}``.  Every exponent taken is ``<= 0``: the pairwise
    decay is formed explicitly (no division by a running product, which
    overflows where a channel decays fast).  ``(I + N)^{-1}`` of the
    nilpotent ``N = diag(beta) A`` is the product of ``I + (-N)^{2^i}``."""
    B, L, H, dk = q.shape
    dv = v.shape[-1]
    C = min(DELTA_RULE_CHUNK, L)
    pad = -L % C
    if pad:
        q, k, v, g = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for t in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (L + pad) // C

    def chunks(t):  # [B, n*C, H, ...] -> float32 [n, B, H, C, ...]
        t = jnp.asarray(t, jnp.float32).reshape((B, n, C) + t.shape[2:])
        return jnp.moveaxis(jnp.swapaxes(t, 2, 3), 1, 0)

    mm = partial(jnp.einsum, precision=_HIGHEST,
                 preferred_element_type=jnp.float32)
    eye = jnp.eye(C, dtype=jnp.float32)
    strictly_lower = jnp.tril(jnp.ones((C, C), bool), -1)
    lower = jnp.tril(jnp.ones((C, C), bool))

    def step(S0, xs):
        qc, kc, vc, gc, bc = xs  # [B, H, C, dk] ..., bc [B, H, C]
        G = jnp.cumsum(gc, axis=2)
        # e^{G_t - G_j} for j <= t; above the diagonal the exponent is
        # positive and unused
        diff = G[:, :, :, None, :] - G[:, :, None, :, :]
        decay = jnp.exp(jnp.where(lower[:, :, None], diff, -jnp.inf))
        kd = kc[:, :, None, :, :] * decay  # [B, H, t, j, dk]
        A = (kc[:, :, :, None, :] * kd).sum(-1)
        Bq = (qc[:, :, :, None, :] * kd).sum(-1)
        N = jnp.where(strictly_lower, A, 0.0) * bc[..., None]
        inv, power = eye - N, N
        for _ in range(max(C - 1, 1).bit_length() - 1):
            power = mm("bhij,bhjk->bhik", power, power)
            inv = mm("bhij,bhjk->bhik", inv, eye + power)
        eG = jnp.exp(G)
        rhs = bc[..., None] * (vc - mm("bhck,bhkv->bhcv", kc * eG, S0))
        U = mm("bhij,bhjv->bhiv", inv, rhs)
        o = mm("bhck,bhkv->bhcv", qc * eG, S0) + mm(
            "bhij,bhjv->bhiv", jnp.where(lower, Bq, 0.0), U)
        to_end = jnp.exp(G[:, :, -1:, :] - G)  # e^{G_C - G_j}
        S1 = eG[:, :, -1, :, None] * S0 + mm(
            "bhck,bhcv->bhkv", kc * to_end, U)
        return S1, o

    state, o = jax.lax.scan(
        step, jnp.zeros((B, H, dk, dv), jnp.float32),
        tuple(chunks(t) for t in (q, k, v, g, beta)),
    )
    o = jnp.swapaxes(jnp.moveaxis(o, 0, 1), 2, 3).reshape(B, n * C, H, dv)
    return o[:, :L], state


# --------------------------------------------------------------------------- #
# modules
# --------------------------------------------------------------------------- #


class Indexer(nn.Module):
    """DeepSeek-V3.2's lightning indexer, the ``q``, ``k`` and ``w`` of
    :func:`indexer_selection`: ``q = W_q c_q`` (``c_q`` the normed query
    latent), ``index_n_heads`` heads of ``index_head_dim``; ``k =
    LayerNorm(W_k x)``, one head; ``w = W_w x * index_n_heads^-0.5``,
    float32; rotary positions on the first ``qk_rope_head_dim`` values of
    ``q`` and ``k``."""

    cfg: DecoderConfig
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, q_latent, positions):
        cfg = self.cfg
        B, L, _ = x.shape
        Hi, D, dr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype)
        q = dense(Hi * D, name="wq")(q_latent).reshape(B, L, Hi, D)
        k = nn.LayerNorm(epsilon=1e-6, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="k_norm")(
            dense(D, name="wk")(x))
        w = nn.Dense(Hi, use_bias=False, dtype=jnp.float32,
                     param_dtype=self.param_dtype, name="weights_proj")(
            x) * Hi ** -0.5
        q = jnp.concatenate(
            [apply_rope(q[..., :dr], positions[:, :, None], cfg),
             q[..., dr:]], axis=-1)
        k = jnp.concatenate(
            [apply_rope(k[..., :dr], positions, cfg), k[..., dr:]], axis=-1)
        return q, k, w


class LatentAttention(nn.Module):
    """Multi-head latent attention (the module docstring's ``"mla"``).  The
    two scales of ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` ride on the
    norms they follow: ``W_qb`` is linear, so ``s_q W_qb n = W_qb (s_q n)``,
    and the latent is scaled before it is cached, so the cached row is
    ``[s_kv RMSNorm(c~), rope(k_r)]`` and both forms of the attention read
    it as it is.

    In the DSA family (``use_dsa``) each query attends the keys its layer's
    selection chose: an ``indexer`` layer makes the selection
    (:class:`Indexer`), a layer without one is given the selection of the
    ``full`` layer before it; a sink a head joins the softmax's denominator
    (``learnable_sink``) and the attended values are gated elementwise,
    ``o = W_o (attn * sigmoid(W_gate x))`` (``mla_gate``).  Returns ``(out,
    selection)`` there (the selection of the whole-sequence form; None
    through a cache hook, which keeps its own)."""

    cfg: DecoderConfig
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    attention: str = "dense"
    indexer: bool = False

    @nn.compact
    def __call__(self, x, positions, attend=None, selection=None):
        cfg = self.cfg
        s_q = ((cfg.hidden_size / cfg.q_lora_rank) ** 0.5
               if cfg.mla_scale_q_lora else 1.0)
        s_kv = ((cfg.hidden_size / cfg.kv_lora_rank) ** 0.5
                if cfg.mla_scale_kv_lora else 1.0)
        B, L, _ = x.shape
        H, dn, dr = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim)
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype)
        norm = partial(RMSNorm, cfg.rms_norm_eps, self.dtype,
                       self.param_dtype)
        q = dense(cfg.q_lora_rank, name="q_a")(x)
        q_latent = norm(gain=s_q, name="q_a_norm")(q)
        q = dense(H * cfg.qk_head_dim, name="q_b")(q_latent)
        q = q.reshape(B, L, H, cfg.qk_head_dim)
        q_nope = q[..., :dn]
        q_rope = apply_rope(q[..., dn:], positions[:, :, None], cfg)
        kv = dense(cfg.latent_width, name="kv_a")(x)
        c = norm(gain=s_kv, name="kv_a_norm")(kv[..., : cfg.kv_lora_rank])
        k_rope = apply_rope(kv[..., cfg.kv_lora_rank:], positions, cfg)
        w_kvb = self.param(
            "kv_b", nn.initializers.lecun_normal(),
            (cfg.kv_lora_rank, H * (dn + cfg.v_head_dim)), self.param_dtype,
        ).reshape(cfg.kv_lora_rank, H, dn + cfg.v_head_dim)
        scale = softmax_scale(cfg)
        if not cfg.use_dsa:
            if attend is None:
                out = expanded_attention(
                    q_nope, q_rope, c, k_rope, w_kvb, scale,
                    jnp.ones((B, L), bool), self.attention,
                )
            else:
                out = attend(q_nope, q_rope, c, k_rope, w_kvb, scale)
            return dense(cfg.hidden_size, name="o")(
                out.reshape(B, L, H * cfg.v_head_dim))
        index = None
        if self.indexer:
            with jax.named_scope("index"):
                index = Indexer(cfg, self.dtype, self.param_dtype,
                                name="indexer")(x, q_latent, positions)
        sinks = (self.param("sinks", nn.initializers.zeros, (H,),
                            jnp.float32)
                 if cfg.learnable_sink else jnp.full((H,), -jnp.inf))
        if attend is None:
            if index is not None:
                with jax.named_scope("select"):
                    selection = indexer_selection(
                        *index, cfg.index_topk, jnp.ones((B, L), bool))
            out = sparse_expanded_attention(
                q_nope, q_rope, c, k_rope, w_kvb, scale, sinks, selection)
        else:
            out, selection = attend(q_nope, q_rope, c, k_rope, w_kvb, scale,
                                    sinks, index), None
        out = out.reshape(B, L, H * cfg.v_head_dim)
        if cfg.mla_gate:
            with jax.named_scope("gate"):
                gate = dense(H * cfg.v_head_dim, name="gate")(x)
                out = (out.astype(jnp.float32)
                       * jax.nn.sigmoid(gate.astype(jnp.float32))
                       ).astype(self.dtype)
        return dense(cfg.hidden_size, name="o")(out), selection


class HyperConnection(nn.Module):
    """Manifold-constrained hyper-connections (DeepSeek's mHC) around one
    sublayer ``F`` of a residual stream of ``n`` rows, ``X [B, L, n, H]``:
    with ``x = RMSNorm(vec X)`` (no gain: it folds into ``phi``),
    ``H_pre = sigmoid(a_pre x phi_pre + b_pre)``, ``H_post = magnitude *
    sigmoid(a_post x phi_post + b_post)``, ``H_res = Sinkhorn(exp(a_res
    mat(x phi_res) + b_res))``; the layer is ``X' = H_res X + H_post^T
    F(H_pre X)``.  Called, it returns ``(H_pre X, combine)``, ``combine(y)``
    being ``X'``.  Coefficients float32, ``x phi`` at ``highest``
    precision."""

    n: int
    magnitude: float = 2.0
    eps: float = 1e-6
    norm_eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, X):
        B, L, n, H = X.shape
        f32 = jnp.float32
        # the norm is a scalar a token, so it is applied after the product:
        # x phi = (vec X phi) / rms(vec X), and no float32 copy of the
        # stream is made (at a 24,576-token prompt it is 2.4 GB)
        rms = jnp.sqrt(jnp.mean(jnp.square(X.astype(f32)), axis=(2, 3))
                       + self.norm_eps)[..., None]
        width = 2 * n + n * n
        phi = self.param("phi", nn.initializers.lecun_normal(),
                         (n * H, width), f32)
        alpha = self.param("alpha", nn.initializers.ones, (3,), f32)
        bias = self.param("bias", nn.initializers.normal(1.0), (width,), f32)
        z = jnp.dot(X.reshape(B, L, n * H), phi,
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=f32) / rms
        pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + bias[:n])
        post = self.magnitude * jax.nn.sigmoid(
            alpha[1] * z[..., n:2 * n] + bias[n:2 * n])
        res = sinkhorn(
            (alpha[2] * z[..., 2 * n:] + bias[2 * n:]).reshape(B, L, n, n),
            SINKHORN_ITERS, self.eps)
        # the mixes as sums of n rows, each an elementwise pass over the
        # stream in its own dtype
        u = sum(pre[..., j, None] * X[:, :, j].astype(f32) for j in range(n))

        def combine(y):
            y = y.astype(f32)
            return jnp.stack([
                sum(res[..., i, j, None] * X[:, :, j].astype(f32)
                    for j in range(n)) + post[..., i, None] * y
                for i in range(n)], axis=2).astype(self.dtype)

        return u.astype(self.dtype), combine


class GroupedQueryAttention(nn.Module):
    """Softmax attention without positions, ``num_attention_heads`` query
    heads over ``num_key_value_heads`` key-value heads of ``head_dim``, the
    attended values gated elementwise by ``sigmoid(W_g x)`` before ``W_o``
    (``use_gqa_gate``).  ``attend(q, k, v)`` is a cache hook's
    (``layer_attention(i)``); without one the whole sequence is attended
    causally."""

    cfg: DecoderConfig
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    attention: str = "dense"

    @nn.compact
    def __call__(self, x, attend=None):
        cfg = self.cfg
        B, L, _ = x.shape
        H, G, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype)
        q = dense(H * D, name="q")(x).reshape(B, L, H, D)
        k = dense(G * D, name="k")(x).reshape(B, L, G, D)
        v = dense(G * D, name="v")(x).reshape(B, L, G, D)
        if attend is None:
            out = grouped_query_attention(
                q, k, v, jnp.ones((B, L), bool), self.attention)
        else:
            out = attend(q, k, v)
        out = out.reshape(B, L, H * D)
        if cfg.use_gqa_gate:
            gate = dense(H * D, name="g")(x)
            out = (out.astype(jnp.float32)
                   * jax.nn.sigmoid(gate.astype(jnp.float32))
                   ).astype(self.dtype)
        return dense(cfg.hidden_size, name="o")(out)


class DeltaRuleAttention(nn.Module):
    """Gated delta-rule linear attention with a decay a channel (Kimi Delta
    Attention): per head a ``[d_k, d_v]`` float32 state, zero at a
    sequence's start.

    ``q~, k~, v~ = W_q x, W_k x, W_v x``, each through a depthwise causal
    convolution over the current and the ``linear_conv_kernel - 1`` earlier
    positions (no bias) and a SiLU; ``q = l2norm(q') d_k^-0.5``, ``k =
    l2norm(k')``; the log decay ``g = -exp(A_log[h]) softplus(W_f2 (W_f1 x)
    + dt_bias)``, a value a key channel; ``beta = sigmoid(W_b x)``, a value
    a head, doubled under ``kda_allow_neg_eigval``; the recurrence is
    :func:`delta_rule_step`; ``o = RMSNorm_dv(o) sigmoid(W_g2 (W_g1 x))``;
    ``y = W_o o``.

    ``state`` is a cache hook's accessor (``layer_state(i)``): ``mode``
    ``"prefill"`` runs the whole-prompt form from zero state
    (:func:`delta_rule_chunked`), positions at and past ``state.lengths``
    masked out of the recurrence (``beta = 0``, ``g = 0``) and out of the
    saved convolution inputs, and writes the state at the prompt's end;
    ``"decode"`` hands every slot's state to one :func:`delta_rule_step`
    (``ops/delta_rule.py``: a Pallas kernel that reads each slot's and
    head's state once and writes it over itself) and the new state back:
    the serve program donates the state arrays, so the update is in place.
    Without one the whole sequence runs from zero state."""

    cfg: DecoderConfig
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, state=None):
        cfg = self.cfg
        B, L, _ = x.shape
        H, D, K = (cfg.linear_num_heads, cfg.linear_head_dim,
                   cfg.linear_conv_kernel)
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype)
        in_f32 = partial(jax.lax.dot_general,
                         preferred_element_type=jnp.float32)
        # the convolution's inputs, q, k and v side by side: [B, L, 3 H D]
        fresh = jnp.concatenate(
            [dense(H * D, name=n)(x) for n in ("q", "k", "v")], axis=-1)
        taps = jnp.concatenate([
            self.param(n + "_conv", nn.initializers.lecun_normal(),
                       (K, H * D), self.param_dtype)
            for n in ("q", "k", "v")], axis=-1).astype(jnp.float32)
        decode = state is not None and state.mode == "decode"
        if decode:
            S, before = state.read()  # [B, H, D, D], [B, K - 1, 3 H D]
        else:
            before = jnp.zeros((B, K - 1, fresh.shape[-1]), fresh.dtype)
        padded = jnp.concatenate([before.astype(fresh.dtype), fresh], axis=1)
        conv = sum(
            taps[j] * padded[:, j:j + L].astype(jnp.float32)
            for j in range(K))
        q, k, v = (t.reshape(B, L, H, D) for t in jnp.split(
            jax.nn.silu(conv), 3, axis=-1))
        q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + 1e-6) * (
            D ** -0.5)
        k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + 1e-6)

        a_log = self.param("A_log", nn.initializers.zeros, (H,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (H * D,),
                             jnp.float32)
        f = dense(H * D, name="f_b", dtype=jnp.float32, dot_general=in_f32)(
            dense(D, name="f_a")(x))
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            f + dt_bias).reshape(B, L, H, D)
        beta = jax.nn.sigmoid(dense(
            H, name="b", dtype=jnp.float32, dot_general=in_f32)(x))
        if cfg.kda_allow_neg_eigval:
            beta = 2.0 * beta

        if decode:
            o, S = delta_rule_step(
                S, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
            state.write(S, padded[:, 1:])
            o = o[:, None]
        elif state is None:
            o, _ = delta_rule_chunked(q, k, v, g, beta)
        else:
            valid = (jnp.arange(L, dtype=jnp.int32)[None, :]
                     < state.lengths[:, None].astype(jnp.int32))
            o, S = delta_rule_chunked(
                q, k, v, jnp.where(valid[..., None, None], g, 0.0),
                jnp.where(valid[..., None], beta, 0.0))
            # the K - 1 inputs before position ``lengths``: in ``padded``
            # position t sits at row t + K - 1
            tail = jax.vmap(
                lambda rows, at: jax.lax.dynamic_slice_in_dim(
                    rows, at, K - 1, axis=0)
            )(padded, state.lengths.astype(jnp.int32))
            state.write(S, tail)

        o = RMSNorm(cfg.rms_norm_eps, jnp.float32, self.param_dtype,
                    name="o_norm")(o)
        gate = dense(H * D, name="g_b")(dense(D, name="g_a")(x))
        o = o.reshape(B, L, H * D) * jax.nn.sigmoid(
            gate.astype(jnp.float32))
        return dense(cfg.hidden_size, name="o")(o.astype(self.dtype))


class DecoderLayer(nn.Module):
    cfg: DecoderConfig
    index: int
    held_experts: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    attention: str = "dense"

    @nn.compact
    def __call__(self, h, positions, attend=None):
        """``attend`` is what the cache hook gives this layer's kind: the
        attention of an ``"mla"`` or ``"gqa"`` layer, the state accessor
        of a ``"kda"`` one."""
        cfg = self.cfg
        norm = partial(RMSNorm, cfg.rms_norm_eps, self.dtype,
                       self.param_dtype)
        kind = cfg.layer_kind(self.index)
        with jax.named_scope(kind):
            x = norm(name="attn_norm")(h)
            if kind == "mla":
                h = h + LatentAttention(
                    cfg, self.dtype, self.param_dtype, self.attention,
                    name="attn",
                )(x, positions, attend)
            elif kind == "gqa":
                h = h + GroupedQueryAttention(
                    cfg, self.dtype, self.param_dtype, self.attention,
                    name="attn",
                )(x, attend)
            else:
                h = h + DeltaRuleAttention(
                    cfg, self.dtype, self.param_dtype, name="attn",
                )(x, attend)
        x = norm(name="ffn_norm")(h)
        if self.index < cfg.first_k_dense_replace:
            return h + SwiGLU(cfg.intermediate_size, self.dtype,
                              self.param_dtype, name="ffn")(x)
        return h + _expert_share(cfg, self.held_experts, self.dtype,
                                 self.param_dtype, "ffn")(x)


def _expert_share(cfg: DecoderConfig, held_experts, dtype, param_dtype,
                  name: str) -> ExpertShareFFN:
    """The expert layer ``cfg`` describes, holding ``held_experts``."""
    return ExpertShareFFN(
        cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_routed_experts,
        held_experts or (0, cfg.n_routed_experts), cfg.num_experts_per_tok,
        cfg.n_group, cfg.topk_group, cfg.routed_scaling_factor,
        cfg.norm_topk_prob, cfg.n_shared_experts, dtype, param_dtype,
        cfg.scoring_func, cfg.router_choice_bias, cfg.zero_expert_num,
        cfg.swiglu_limit, name=name,
    )


class StreamLayer(nn.Module):
    """A layer of the DSA family: gated sparse latent attention, then the
    dense or expert feed-forward, each pre-normed and inside its own
    :class:`HyperConnection` over the ``hc_mult`` rows of the residual
    stream.  ``attend`` is the cache hook's
    ``sparse_attention(i)``; ``selection`` the whole-sequence form's
    selection of the ``full`` layer before this one.  Returns ``(h,
    selection)``."""

    cfg: DecoderConfig
    index: int
    held_experts: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    attention: str = "dense"

    @nn.compact
    def __call__(self, h, positions, attend=None, selection=None):
        cfg = self.cfg
        norm = partial(RMSNorm, cfg.rms_norm_eps, self.dtype,
                       self.param_dtype)
        found = {}

        def attn(u):
            out, found["selection"] = LatentAttention(
                cfg, self.dtype, self.param_dtype, self.attention,
                cfg.indexes(self.index), name="attn",
            )(norm(name="attn_norm")(u), positions, attend, selection)
            return out

        def ffn(u):
            x = norm(name="ffn_norm")(u)
            if self.index < cfg.first_k_dense_replace:
                return SwiGLU(cfg.intermediate_size, self.dtype,
                              self.param_dtype, cfg.swiglu_limit,
                              name="ffn")(x)
            return _expert_share(cfg, self.held_experts, self.dtype,
                                 self.param_dtype, "ffn")(x)

        def residual(name, f, h):
            with jax.named_scope("hc"):
                u, combine = HyperConnection(
                    cfg.hc_mult, cfg.hc_magnitude, cfg.hc_eps,
                    cfg.rms_norm_eps, self.dtype, name=name + "_hc")(h)
            y = f(u)
            with jax.named_scope("hc"):
                return combine(y)

        with jax.named_scope("mla"):
            h = residual("attn", attn, h)
        return residual("ffn", ffn, h), found["selection"]


class ShortcutDoubleLayer(nn.Module):
    """A double layer with a shortcut-connected expert branch: two
    latent-attention sublayers (own weights each) and two dense SwiGLU
    feed-forwards in series, and one expert layer that reads the first
    sublayer's normed output and is added back only at the end::

        a1 = h  + MLA_0(N_in0(h))
        x1 = N_post0(a1)
        m  = MoE(x1)
        d1 = a1 + FFN_0(x1)
        a2 = d1 + MLA_1(N_in1(d1))
        x2 = N_post1(a2)
        h' = a2 + FFN_1(x2) + m

    ``attend`` is the cache hook's pair, sublayer ``j``'s at ``[j]``.
    Nothing between ``m``'s definition and its use depends on it: in a
    deployment its exchange runs behind the first dense feed-forward and
    the second attention."""

    cfg: DecoderConfig
    held_experts: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    attention: str = "dense"

    @nn.compact
    def __call__(self, h, positions, attend=(None, None)):
        cfg = self.cfg
        norm = partial(RMSNorm, cfg.rms_norm_eps, self.dtype,
                       self.param_dtype)

        def mla(j, h):
            with jax.named_scope("mla"):
                return h + LatentAttention(
                    cfg, self.dtype, self.param_dtype, self.attention,
                    name=f"attn_{j}",
                )(norm(name=f"attn_norm_{j}")(h), positions, attend[j])

        def dense(j, x):
            with jax.named_scope("dense"):
                return SwiGLU(cfg.intermediate_size, self.dtype,
                              self.param_dtype, name=f"ffn_{j}")(x)

        a1 = mla(0, h)
        x1 = norm(name="ffn_norm_0")(a1)
        m = _expert_share(cfg, self.held_experts, self.dtype,
                          self.param_dtype, "moe")(x1)
        a2 = mla(1, a1 + dense(0, x1))
        x2 = norm(name="ffn_norm_1")(a2)
        return a2 + dense(1, x2) + m


class Decoder(nn.Module):
    """A pre-norm decoder-only language model assembled from ``cfg``
    (:meth:`DecoderConfig.from_dict`): an attention kind a layer (latent;
    or grouped-query and delta-rule), SwiGLU, a dense or an expert
    feed-forward per layer, or double layers with a shortcut expert branch;
    an untied head.

    Args:
        held_experts: ``(first, count)``: the routed experts of every
            expert layer that live here (None: all of them).
        dtype / param_dtype: compute and storage dtype (bfloat16 to serve
            at the published widths; float32 in the tests).
        attention: the cacheless forward's kernel, ``"dense"`` or
            ``"flash"`` (a cache hook brings its own choice).
    """

    cfg: DecoderConfig
    held_experts: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    attention: str = "dense"

    @property
    def max_len(self) -> int:
        return self.cfg.max_position_embeddings

    @property
    def experts_held(self) -> int:
        """Routed experts each expert layer computes here (0: no expert
        layer).  A model with some sows their assignment counts, and
        ``ServingEngine`` hands them back from its decode program."""
        cfg = self.cfg
        if cfg.num_hidden_layers <= cfg.first_k_dense_replace:
            return 0
        return (self.held_experts or (0, cfg.n_routed_experts))[1]

    @property
    def zero_experts(self) -> int:
        """Zero-compute outputs of each expert layer's router (0: none).  A
        model with some also sows each token's count of picks among them,
        and ``ServingEngine`` hands those back beside the held experts'."""
        return self.cfg.zero_expert_num if self.experts_held else 0

    @property
    def experts_per_token(self) -> int:
        return self.cfg.num_experts_per_tok

    def cache_spec(self) -> CacheSpec:
        cfg = self.cfg
        if cfg.use_dsa:
            # the latent rows kept as 32-bit words, a row a whole-tile
            # slice (``ops/sparse_attention.py``), in every layer; the
            # indexer's keys in the ``full`` layers only
            return CacheSpec(
                layers=cfg.num_hidden_layers,
                planes=(("latent", cfg.latent_width, {"packed": True}),
                        ("index", cfg.index_head_dim,
                         {"layers": cfg.full_layers})),
                values=cfg.latent_width,
                kind="sparse_latent",
                heads=cfg.num_attention_heads,
                head_dim=cfg.qk_head_dim,
                max_len=cfg.max_position_embeddings,
                index_topk=cfg.index_topk,
            )
        if cfg.gqa_layers is None:
            return CacheSpec(
                layers=cfg.latent_sublayers,
                planes=(("latent", cfg.latent_row_width),),
                values=cfg.latent_width,
                kind="latent",
                heads=cfg.num_attention_heads,
                head_dim=cfg.qk_head_dim,
                max_len=cfg.max_position_embeddings,
            )
        H, D = cfg.linear_num_heads, cfg.linear_head_dim
        return CacheSpec(
            layers=cfg.num_hidden_layers,
            # a token's keys, then its values, one row: the paged kernel
            # scores a query against the key lanes of its key-value head
            # and keeps that head's value lanes of what it attends
            planes=(("kv", cfg.kv_row_width),),
            kind="hybrid",
            heads=cfg.num_attention_heads,
            head_dim=cfg.head_dim,
            max_len=cfg.max_position_embeddings,
            layer_kinds=tuple("rows" if kind == "gqa" else "state"
                              for kind in cfg.layer_kinds),
            state=(("state", (H, D, D), "float32"),
                   ("conv", (cfg.linear_conv_kernel - 1, cfg.conv_width),
                    "cache")),
        )

    @nn.compact
    def __call__(self, input_ids, train: bool = True, positions=None,
                 decode: bool = False, kv_cache=None):
        """Logits ``[B, L, vocab]`` (float32).  ``train`` changes nothing
        (no dropout); ``positions`` (``[L]`` or ``[B, L]``) default to
        ``arange``; ``decode=True`` is the single-token incremental forward
        and needs ``kv_cache`` and ``positions``, as in ``GPT``."""
        cfg = self.cfg
        B, L = input_ids.shape
        if decode and (kv_cache is None or positions is None or L != 1):
            raise ValueError(
                f"Decoder: decode=True is single-token incremental decode "
                f"through a kv_cache hook at explicit positions; got "
                f"sequence length {L}, kv_cache {kv_cache is not None}, "
                f"positions {positions is not None}"
            )
        if L > cfg.max_position_embeddings:
            raise ValueError(
                f"Decoder: sequence length {L} exceeds "
                f"max_position_embeddings={cfg.max_position_embeddings}"
            )
        if positions is None:
            positions = jnp.arange(L, dtype=jnp.int32)
        positions = jnp.broadcast_to(
            jnp.asarray(positions, jnp.int32).reshape(-1, L), (B, L))
        h = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=self.dtype,
            param_dtype=self.param_dtype, name="embed_tokens",
        )(input_ids)
        hook_method = {"mla": "latent_attention", "gqa": "layer_attention",
                       "kda": "layer_state"}
        if cfg.use_dsa:
            return self._streams(h, positions, kv_cache)
        for i in range(cfg.num_hidden_layers):
            if cfg.shortcut_double_layers:
                # sublayer j of double layer i is row 2 i + j of the plane
                attend = (None, None) if kv_cache is None else tuple(
                    kv_cache.latent_attention(2 * i + j) for j in (0, 1))
                h = ShortcutDoubleLayer(
                    cfg, self.held_experts, self.dtype, self.param_dtype,
                    self.attention, name=f"layer_{i}",
                )(h, positions, attend)
                continue
            attend = (None if kv_cache is None else getattr(
                kv_cache, hook_method[cfg.layer_kind(i)])(i))
            h = DecoderLayer(
                cfg, i, self.held_experts, self.dtype, self.param_dtype,
                self.attention, name=f"layer_{i}",
            )(h, positions, attend)
        with jax.named_scope("head"):
            h = RMSNorm(cfg.rms_norm_eps, self.dtype, self.param_dtype,
                        name="norm")(h)
            return nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype, name="lm_head",
                dot_general=partial(jax.lax.dot_general,
                                    preferred_element_type=jnp.float32),
            )(h)

    def _streams(self, h, positions, kv_cache):
        """The DSA family's layers and head: ``hc_mult`` copies of the
        embedding as the residual stream, summed before the final norm; the
        head in float32 under ``lm_head_fp32``."""
        cfg = self.cfg
        h = jnp.broadcast_to(h[:, :, None, :], h.shape[:2] + (
            cfg.hc_mult, h.shape[-1]))
        selection = None
        for i in range(cfg.num_hidden_layers):
            attend = (None if kv_cache is None
                      else kv_cache.sparse_attention(i))
            h, selection = StreamLayer(
                cfg, i, self.held_experts, self.dtype, self.param_dtype,
                self.attention, name=f"layer_{i}",
            )(h, positions, attend, selection)
        head_dtype = jnp.float32 if cfg.lm_head_fp32 else self.dtype
        with jax.named_scope("head"):
            h = RMSNorm(cfg.rms_norm_eps, head_dtype, self.param_dtype,
                        name="norm")(h.astype(jnp.float32).sum(axis=2))
            return nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=head_dtype,
                param_dtype=(jnp.float32 if cfg.lm_head_fp32
                             else self.param_dtype), name="lm_head",
                dot_general=partial(jax.lax.dot_general,
                                    preferred_element_type=jnp.float32),
            )(h)
