"""The ``axk1`` family: everything the benchmark knows about a configuration
keyed like SKT's A.X-K1 ``config.json`` (``model_type: "axk1"``, key for key
the DeepSeek-V3 family's: ``hidden_size``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``n_routed_experts``, ``rope_scaling`` ...), under the
names every family module has (``benchmark/lib/model.py`` lists them).

A configuration of this family is ONE CHIP'S SHARE of a layer-wise
deployment: ``n_routed_experts`` and ``vocab_size`` in the file are what
this chip holds, ``published`` gives the source's counts (the router keeps
its published width) and ``deployment`` which experts are here.  Program and
reference are given the same share: what the absent experts would add is
left out of both, and the vocabulary is the slice.

1. the builder (``build_model``, ``init_params``): the only part that
   imports the program, inside its functions.  Weights are made on the
   device leaf by leaf in the configuration's ``dtype``: a float32 tree of
   the published widths would not fit beside itself;
2. the plain float32 reference (``logits_at``, ``causal_lm_loss``, and the
   pieces the tests compare layer by layer): ``jax.numpy`` at ``highest``
   matmul precision, nothing of the program, computed in blocks (a request
   at a time, a group of heads at a time, an expert at a time, a slice of
   the dense feed-forward at a time) so that it fits beside the resident
   engine.  ``logits_at(params, ids, positions)`` has no argument for the
   configuration, and this family's shapes do not give it (heads, rotary
   constants, routing constants): it reads the one ``build_model`` was
   last called with;
3. the counts: ``train_flops_per_token``, ``serve_flops`` and
   ``decode_bytes_per_step``, from the configuration's shapes alone.

The equations, from the source's keys and the family's convention.  Per
layer ``h += Attn(RMSNorm(h))``, ``h += FFN(RMSNorm(h))``; final RMSNorm;
untied head; no biases.  MLA: ``q = W_qb RMSNorm(W_qa x)``, per head ``dn``
nope + ``dr`` rope values; ``[c, k_r] = W_kva x``, ``c = RMSNorm(c)``,
``k_r`` roped once for all heads; ``[k_nope, v] = W_kvb c`` per head; scores
``(q_nope . k_nope + q_r . k_r) * (dn + dr)^-0.5 * m^2`` with ``m = 0.1
mscale_all_dim ln(factor) + 1``; YaRN frequencies, cos/sin scaled by
``mscale(mscale) / mscale(mscale_all_dim)`` (1 here).  FFN: the first
``first_k_dense_replace`` layers ``W_d(silu(W_g x) * W_u x)``; after them the
shared expert plus the routed part: ``s = sigmoid(x W_r)``, groups of
``E / n_group``, a group's score the sum of its two highest ``s``,
``topk_group`` groups kept, the ``num_experts_per_tok`` highest ``s`` within
them, weights ``s`` renormalised over those and times
``routed_scaling_factor``.

Assumed (the keys do not say): ``topk_method: "none"`` read as no
score-correction bias; rotary pairs ``(2i, 2i+1)``; ties to the lower index.
Departures: weights random from the seed; the multi-token-prediction and
training-only keys (``seq_aux``, ``ep_size``) unused.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# the configuration ``build_model`` was last called with (see 2. above)
_BUILT = {}

# --------------------------------------------------------------------------- #
# 1. The builder
# --------------------------------------------------------------------------- #


def held_experts(config: dict) -> tuple:
    """``(first, count)`` of the routed experts this chip holds."""
    return (int(config["deployment"]["first_expert"]),
            int(config["n_routed_experts"]))


def program_config(config: dict) -> dict:
    """The configuration as the program's decoder reads it: the source's
    keys, with the router at its published width."""
    return {**config,
            "n_routed_experts": int(config["published"]["n_routed_experts"])}


def build_model(config: dict):
    """``Decoder`` at the configuration's widths, holding its share of the
    experts, parameters and products in the configuration's ``dtype``."""
    from stoke_tpu.models.decoder import Decoder, DecoderConfig

    _BUILT.clear()
    _BUILT.update(config)
    dtype = jnp.dtype(config["dtype"])
    return Decoder(
        DecoderConfig.from_dict(program_config(config)),
        held_experts=held_experts(config), dtype=dtype, param_dtype=dtype,
    )


def init_params(model, seed: int, seq_len: int):
    """The model's variables from the seed, each leaf made on the device in
    its own dtype by its own jitted call (no float32 copy of the tree ever
    exists; the largest leaf's float32 draw is 0.7 GB): norm scales 1, the
    embedding standard normal, every matrix normal with variance 1 / fan-in."""
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32), train=False)
    )["params"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    base = jax.random.PRNGKey(seed % (2**31))

    @partial(jax.jit, static_argnums=(1, 2, 3))
    def normal(key, shape, dtype, std):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    made = []
    for i, (path, leaf) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            made.append(jnp.ones(leaf.shape, leaf.dtype))
            continue
        std = 1.0 if "embedding" in name else leaf.shape[-2] ** -0.5
        made.append(normal(jax.random.fold_in(base, i), leaf.shape,
                           leaf.dtype, std))
    return {"params": jax.tree_util.tree_unflatten(treedef, made)}


# --------------------------------------------------------------------------- #
# 2. The reference
# --------------------------------------------------------------------------- #

_HEADS_A_BLOCK = 4
_FFN_BLOCK = 2048


def _f32(a):
    return a.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary(config: dict):
    """``(inv_freq float32[dr / 2], cos_sin_factor, softmax_scale)``."""
    dim, base = int(config["qk_rope_head_dim"]), float(config["rope_theta"])
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    dqk = int(config["qk_nope_head_dim"]) + dim
    y = config.get("rope_scaling")
    if not y:
        return plain.astype(np.float32), 1.0, dqk ** -0.5

    def correction_dim(rotations):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) / (
                                  2 * math.log(base))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / ((high - low) or 0.001),
                   0.0, 1.0)
    inv_freq = plain / y["factor"] * ramp + plain * (1.0 - ramp)
    m_all = _mscale(y["factor"], y["mscale_all_dim"])
    return (inv_freq.astype(np.float32),
            _mscale(y["factor"], y["mscale"]) / m_all,
            dqk ** -0.5 * m_all * m_all)


def _rope(x, positions, inv_freq, factor):
    """Pairs ``(2i, 2i+1)`` of ``x [..., dr]`` turned by ``positions *
    inv_freq[i]``."""
    angle = positions[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        x.shape)


def latent_attention(config: dict, p, x, positions):
    """One sequence's MLA, ``x [L, hidden]`` -> ``[L, hidden]``, causal, keys
    and values expanded per head, a block of heads at a time."""
    H = int(config["num_attention_heads"])
    dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    dv, C = int(config["v_head_dim"]), int(config["kv_lora_rank"])
    eps = float(config["rms_norm_eps"])
    inv_freq, factor, scale = rotary(config)
    L = x.shape[0]
    q = _rms_norm(x @ _f32(p["q_a"]["kernel"]), p["q_a_norm"]["scale"], eps)
    q = (q @ _f32(p["q_b"]["kernel"])).reshape(L, H, dn + dr)
    q_rope = _rope(q[..., dn:], positions[:, None], inv_freq, factor)
    kv = x @ _f32(p["kv_a"]["kernel"])
    c = _rms_norm(kv[:, :C], p["kv_a_norm"]["scale"], eps)
    k_rope = _rope(kv[:, C:], positions, inv_freq, factor)
    w_kvb = p["kv_b"].reshape(C, H, dn + dv)
    causal = jnp.tril(jnp.ones((L, L), bool))
    hb = math.gcd(H, _HEADS_A_BLOCK)

    def block(g):
        def heads(t):
            return jax.lax.dynamic_slice_in_dim(t, g * hb, hb, axis=1)

        kv_h = jnp.einsum("lc,chd->lhd", c, _f32(heads(w_kvb)))
        s = jnp.einsum("qhd,khd->hqk", heads(q)[..., :dn], kv_h[..., :dn])
        s = s + jnp.einsum("qhd,kd->hqk", heads(q_rope), k_rope)
        pr = jax.nn.softmax(jnp.where(causal, s * scale, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, kv_h[..., dn:])

    out = jax.lax.map(block, jnp.arange(H // hb))  # [H / hb, L, hb, dv]
    out = jnp.moveaxis(out, 0, 1).reshape(L, H * dv)
    return out @ _f32(p["o"]["kernel"])


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


def dense_ffn(p, x):
    """``W_d(silu(W_g x) * W_u x)``, a slice of the width at a time."""
    width = p["gate"]["kernel"].shape[1]
    bw = math.gcd(width, _FFN_BLOCK)

    def add(i, acc):
        cut = lambda w, axis: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            w, i * bw, bw, axis=axis)
        return acc + _swiglu(x, cut(p["gate"]["kernel"], 1),
                             cut(p["up"]["kernel"], 1),
                             cut(p["down"]["kernel"], 0))

    return jax.lax.fori_loop(0, width // bw, add, jnp.zeros_like(x))


def route(config: dict, scores):
    """``float32[L, E]``: each token's weight on each routed expert (0 where
    not chosen), from sigmoid ``scores [L, E]``.  Ties to the lower index."""
    L, E = scores.shape
    G, keep = int(config["n_group"]), int(config["topk_group"])
    k = int(config["num_experts_per_tok"])
    grouped = scores.reshape(L, G, E // G)
    group_score = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)
    kept = jnp.argsort(-group_score, axis=-1, stable=True)[:, :keep]
    in_kept = (kept[:, :, None] == jnp.arange(G)).any(1)
    masked = jnp.where(in_kept[:, :, None], grouped, -1.0).reshape(L, E)
    chosen = jnp.argsort(-masked, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if config.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * float(config["routed_scaling_factor"])
    return (jax.nn.one_hot(chosen, E, dtype=jnp.float32)
            * w[:, :, None]).sum(1)


def expert_ffn(config: dict, p, x, held):
    """Shared expert plus the routed part the experts ``held = (first,
    count)`` give, an expert at a time over every token (its weight 0 where
    the token did not choose it)."""
    first, count = held
    gate = route(config, jax.nn.sigmoid(x @ _f32(p["router"])))

    def add(e, acc):
        pick = lambda w: jax.lax.dynamic_index_in_dim(  # noqa: E731
            w, e, keepdims=False)
        weight = jax.lax.dynamic_index_in_dim(gate, first + e, axis=1)
        return acc + weight * _swiglu(
            x, pick(p["w_gate"]), pick(p["w_up"]), pick(p["w_down"]))

    shared = p["shared"]
    start = _swiglu(x, shared["gate"]["kernel"], shared["up"]["kernel"],
                    shared["down"]["kernel"])
    return jax.lax.fori_loop(0, count, add, start)


def hidden_states(config: dict, params, ids):
    """Final-norm hidden states ``[L, hidden]`` of ONE sequence ``ids
    [L]``."""
    eps = float(config["rms_norm_eps"])
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    h = _f32(params["embed_tokens"]["embedding"][ids])
    for i in range(int(config["num_hidden_layers"])):
        p = params[f"layer_{i}"]
        h = h + latent_attention(
            config, p["attn"],
            _rms_norm(h, p["attn_norm"]["scale"], eps), positions)
        x = _rms_norm(h, p["ffn_norm"]["scale"], eps)
        if i < int(config["first_k_dense_replace"]):
            h = h + dense_ffn(p["ffn"], x)
        else:
            h = h + expert_ffn(config, p["ffn"], x, held_experts(config))
    return _rms_norm(h, params["norm"]["scale"], eps)


def reference_logits_at(config: dict, params, ids, positions):
    """Logits ``[B, T, vocab]`` at ``positions [B, T]`` of ``ids [B, L]``,
    a request at a time."""
    head = params["lm_head"]["kernel"]

    def one(args):
        row, at = args
        return hidden_states(config, params, row)[at] @ _f32(head)

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, (ids, positions))


def reference_loss(config: dict, params, ids):
    """Mean next-token cross entropy over ``ids [B, L]``."""

    def one(row):
        lg = hidden_states(config, params, row)[:-1] @ _f32(
            params["lm_head"]["kernel"])
        logp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.take_along_axis(logp, row[1:, None], axis=-1).mean()

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, ids).mean()


def _built() -> dict:
    if not _BUILT:
        raise RuntimeError(
            "axk1 reference: no configuration yet; build_model(config) "
            "names the one logits_at and causal_lm_loss compute")
    return _BUILT


def logits_at(params, ids, positions):
    return reference_logits_at(_built(), params, ids, positions)


def causal_lm_loss(params, ids):
    return reference_loss(_built(), params, ids)


# --------------------------------------------------------------------------- #
# 3. The counts
#
# From the configuration's shapes alone; nothing here asks the program or
# the compiler.  All of them are of THIS CHIP'S SHARE: the experts held,
# the vocabulary slice.
# --------------------------------------------------------------------------- #


def param_counts(config: dict) -> dict:
    """Parameters by part, of the share: ``mla``, ``dense_ffn``, ``expert``
    (one routed or shared expert), ``router``, ``embedding`` (and the head,
    each), ``dense_layer``, ``expert_layer``, ``total``."""
    h, H = int(config["hidden_size"]), int(config["num_attention_heads"])
    C, dr = int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"])
    dn, dv = int(config["qk_nope_head_dim"]), int(config["v_head_dim"])
    ql = int(config["q_lora_rank"])
    mla = (h * ql + ql + ql * H * (dn + dr) + h * (C + dr) + C
           + C * H * (dn + dv) + H * dv * h)
    norms = 2 * h
    expert = 3 * h * int(config["moe_intermediate_size"])
    router = h * int(config["published"]["n_routed_experts"])
    dense_layer = mla + norms + 3 * h * int(config["intermediate_size"])
    expert_layer = (mla + norms + router + expert * (
        int(config["n_shared_experts"]) + int(config["n_routed_experts"])))
    embedding = int(config["vocab_size"]) * h
    n_dense = int(config["first_k_dense_replace"])
    n_expert = int(config["num_hidden_layers"]) - n_dense
    return {
        "mla": mla, "dense_ffn": 3 * h * int(config["intermediate_size"]),
        "expert": expert, "router": router, "embedding": embedding,
        "dense_layer": dense_layer, "expert_layer": expert_layer,
        "total": (n_dense * dense_layer + n_expert * expert_layer
                  + 2 * embedding + h),
    }


def _matmul_params_a_token(config: dict) -> float:
    """Matrix parameters one token meets on this chip, head left out: MLA
    and dense or shared feed-forward whole, the router, and of the routed
    experts the expected ``num_experts_per_tok * held / published`` (uniform
    routing).  ``W_kvb`` counts once a token in either form of the attention:
    expanded it is applied to the token's latent, absorbed to its query and
    output."""
    n = param_counts(config)
    n_dense = int(config["first_k_dense_replace"])
    n_expert = int(config["num_hidden_layers"]) - n_dense
    routed = (int(config["num_experts_per_tok"])
              * int(config["n_routed_experts"])
              / int(config["published"]["n_routed_experts"]))
    return (int(config["num_hidden_layers"]) * n["mla"]
            + n_dense * n["dense_ffn"]
            + n_expert * (n["router"] + n["expert"] * (
                int(config["n_shared_experts"]) + routed)))


def _attention_flops_a_pair(config: dict, absorbed: bool) -> float:
    """Forward operations one (query, key) pair costs in one layer, all
    heads: expanded ``2 H (dn + dr) + 2 H dv``; absorbed ``2 H (C + dr) +
    2 H C``."""
    H = int(config["num_attention_heads"])
    C, dr = int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"])
    dn, dv = int(config["qk_nope_head_dim"]), int(config["v_head_dim"])
    return 2.0 * H * ((C + dr) + C if absorbed else (dn + dr) + dv)


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward of one token in a sequence of ``seq_len`` on
    this share: 6 per matrix parameter met (head included) plus expanded
    causal attention over on average half the sequence, three times its
    forward."""
    head = int(config["vocab_size"]) * int(config["hidden_size"])
    attn = (3.0 * int(config["num_hidden_layers"]) * (seq_len / 2)
            * _attention_flops_a_pair(config, absorbed=False))
    return 6.0 * (_matmul_params_a_token(config) + head) + attn


def serve_flops(config: dict, prefill_tokens: float, decode_tokens: float,
                context_tokens: float,
                prefill_context_tokens: float = 0.0) -> float:
    """Operations the algorithm needs to serve ``prefill_tokens`` prompt
    tokens and ``decode_tokens`` decode tokens on this share: 2 per matrix
    parameter met by either kind; the head for decode tokens (a prefill
    needs one row of logits, left out); absorbed attention over
    ``context_tokens`` (the sum of the decode rows' context lengths) and
    expanded causal attention over ``prefill_context_tokens`` (the sum over
    prompts of ``P (P + 1) / 2``) (query, key) pairs a layer."""
    layers = int(config["num_hidden_layers"])
    head = int(config["vocab_size"]) * int(config["hidden_size"])
    return (2.0 * _matmul_params_a_token(config)
            * (prefill_tokens + decode_tokens)
            + 2.0 * head * decode_tokens
            + layers * context_tokens
            * _attention_flops_a_pair(config, absorbed=True)
            + layers * prefill_context_tokens
            * _attention_flops_a_pair(config, absorbed=False))


def decode_bytes_per_step(config: dict, batch: int,
                          context_tokens: float) -> float:
    """Bytes one decode step has to move on this share: every held weight
    once (the router's in float32, the rest in the configuration's
    ``dtype``; of the embedding only ``batch`` rows), the live latent rows
    once (``context_tokens`` rows of ``kv_lora_rank + qk_rope_head_dim``
    values a layer, the step's fresh rows among them, written once too)."""
    n = param_counts(config)
    size = jnp.dtype(config["dtype"]).itemsize
    layers = int(config["num_hidden_layers"])
    n_expert = layers - int(config["first_k_dense_replace"])
    h = int(config["hidden_size"])
    weights = ((n["total"] - n["embedding"] - n_expert * n["router"]) * size
               + n_expert * n["router"] * 4 + batch * h * size)
    row = (int(config["kv_lora_rank"]) + int(config["qk_rope_head_dim"])) * (
        jnp.dtype(config["cache_dtype"]).itemsize)
    return weights + layers * row * (context_tokens + batch)
