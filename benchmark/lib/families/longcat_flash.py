"""The ``longcat_flash`` family: everything the benchmark knows about a
configuration keyed like Meituan's LongCat-Flash ``config.json``
(``attention_method: "MLA"``, ``num_layers``, ``ffn_hidden_size``,
``expert_ffn_hidden_size``, ``moe_topk``, ``zero_expert_num``,
``zero_expert_type``, ``mla_scale_q_lora``, ``mla_scale_kv_lora`` ...; the
language model of LongCat-Flash-Chat, -Thinking and -Omni, arXiv:2509.01322),
under the names every family module has (``benchmark/lib/model.py`` lists
them).

A configuration of this family is ONE CHIP'S SHARE of a layer-wise
deployment, as ``axk1``'s is: ``n_routed_experts`` and ``vocab_size`` in the
file are what this chip holds, ``published`` gives the source's counts (the
router keeps its published width: the routed experts and, after them, the
``zero_expert_num`` zero-compute ones) and ``deployment`` which experts are
here.  Program and reference are given the same share: what the absent
experts would add is left out of both; the zero-compute experts' part needs
no weight and no exchange and is computed in full in both.

1. the builder (``build_model``, ``init_params``): the only part that
   imports the program, inside its functions;
2. the plain float32 reference (``logits_at``, ``causal_lm_loss`` and the
   pieces the tests compare): ``jax.numpy`` at ``highest`` matmul precision,
   nothing of the program; expanded latent attention (no absorbed form, no
   cache), a block of heads at a time; an expert at a time, upcast as it is
   used.  ``logits_at`` reads the configuration ``build_model`` was last
   called with, as ``axk1``'s does;
3. the counts: ``param_counts``, ``train_flops_per_token``, ``serve_flops``
   and ``decode_bytes_per_step``, from the configuration's shapes alone.

The equations.  All norms RMSNorm with ``rms_norm_eps``; no biases; final
RMSNorm; untied head.  ``num_layers`` double layers, each with input ``h``::

    a1 = h  + MLA_0(N_in0(h))
    x1 = N_post0(a1)
    m  = MoE(x1)                 # the shortcut branch: taken here, added last
    d1 = a1 + FFN_0(x1)          # dense SwiGLU of ffn_hidden_size
    a2 = d1 + MLA_1(N_in1(d1))
    x2 = N_post1(a2)
    h' = a2 + FFN_1(x2) + m

``MLA_j`` (own weights for ``j`` = 0, 1): ``q = s_q W_qb RMSNorm(W_qa x)``,
per head ``dn`` nope + ``dr`` rope values, ``s_q = (hidden_size /
q_lora_rank)^0.5`` under ``mla_scale_q_lora``; ``[c~, k_r] = W_kva x``; ``c =
s_kv RMSNorm(c~)``, ``s_kv = (hidden_size / kv_lora_rank)^0.5`` under
``mla_scale_kv_lora``, the rope key unscaled; ``[k_nope, v] = W_kvb c`` per
head; rotary on ``q``'s last ``dr`` and on ``k_r`` (one key for all heads),
``rope_theta``, no scaling; scores ``(q_nope . k_nope + q_rope . k_rope) (dn
+ dr)^-0.5``, causal softmax, ``y = W_o concat(P v)``.

``MoE(x)``: ``s = softmax(W_r x)`` in float32 over all ``n_routed_experts +
zero_expert_num`` outputs; the token's experts are the ``moe_topk`` largest
of ``s + b`` (``b`` the score-correction bias); weights ``w_e =
routed_scaling_factor s_e`` (the unbiased score, not renormalised); ``MoE(x)
= sum over chosen routed e of w_e W_d,e(silu(W_g,e x) * W_u,e x) + sum over
chosen zero-compute e of w_e x``.  Ties to the lower index.

Assumed (the keys do not say) and departures: the configuration file's
``assumed`` and ``departures``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmark.lib.families.axk1 import (  # noqa: F401
    _HEADS_A_BLOCK,
    _f32,
    _rms_norm,
    _rope,
    _swiglu,
    dense_ffn,
    held_experts,
    program_config,
)

# the configuration ``build_model`` was last called with (see 2. above)
_BUILT = {}

# --------------------------------------------------------------------------- #
# 1. The builder
# --------------------------------------------------------------------------- #


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
    its own dtype by its own jitted call: norm scales 1, the embedding
    standard normal, every matrix normal with variance 1 / fan-in, and the
    router's score-correction bias normal with standard deviation 1 over
    the router's outputs (the size of a mean score: a zero bias would leave
    the choice by ``s + b`` unexercised; the family trains it for balance).

    ``W_qb`` under ``mla_scale_q_lora`` and ``W_kvb`` under
    ``mla_scale_kv_lora`` are drawn with variance 1 / ``hidden_size``, not 1
    / their own fan-in.  The two scales exist to align a low-rank product's
    variance with a full-rank one's: ``s^2 = hidden_size / rank`` times a
    variance of 1 / ``hidden_size`` over ``rank`` inputs is 1.  Under 1 /
    fan-in draws the queries come out 2 and the latents 3.46 times a unit
    size, the attention scores have a deviation near 6 where a unit one is
    meant, the softmax is all but one-hot, and every rounding of a score
    flips a winner: the served tokens then missed the float32 reference's
    argmax 68% of the time on the chip with nothing wrong in the program
    (PERF.md section 6, PR 35).  No checkpoint is like that."""
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32), train=False)
    )["params"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    base = jax.random.PRNGKey(seed % (2**31))
    cfg = model.cfg

    @partial(jax.jit, static_argnums=(1, 2, 3))
    def normal(key, shape, dtype, std):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    made = []
    for i, (path, leaf) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            made.append(jnp.ones(leaf.shape, leaf.dtype))
            continue
        if "embedding" in name:
            std = 1.0
        elif "e_score_correction_bias" in name:
            std = 1.0 / leaf.shape[0]
        elif ("['q_b']" in name and cfg.mla_scale_q_lora) or (
                "['kv_b']" in name and cfg.mla_scale_kv_lora):
            std = cfg.hidden_size ** -0.5
        else:
            std = leaf.shape[-2] ** -0.5
        made.append(normal(jax.random.fold_in(base, i), leaf.shape,
                           leaf.dtype, std))
    return {"params": jax.tree_util.tree_unflatten(treedef, made)}


# --------------------------------------------------------------------------- #
# 2. The reference
# --------------------------------------------------------------------------- #


def latent_attention(config: dict, p, x, positions):
    """One sequence's MLA with the family's two scales, ``x [L, hidden]`` ->
    ``[L, hidden]``, causal, keys and values expanded per head, a block of
    heads at a time."""
    hidden, H = int(config["hidden_size"]), int(config["num_attention_heads"])
    dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    dv, C = int(config["v_head_dim"]), int(config["kv_lora_rank"])
    ql = int(config["q_lora_rank"])
    eps = float(config["rms_norm_eps"])
    s_q = (hidden / ql) ** 0.5 if config.get("mla_scale_q_lora") else 1.0
    s_kv = (hidden / C) ** 0.5 if config.get("mla_scale_kv_lora") else 1.0
    inv_freq = (1.0 / float(config["rope_theta"]) ** (
        jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))
    scale = (dn + dr) ** -0.5
    L = x.shape[0]
    q = _rms_norm(x @ _f32(p["q_a"]["kernel"]), p["q_a_norm"]["scale"], eps)
    q = s_q * (q @ _f32(p["q_b"]["kernel"])).reshape(L, H, dn + dr)
    q_rope = _rope(q[..., dn:], positions[:, None], inv_freq, 1.0)
    kv = x @ _f32(p["kv_a"]["kernel"])
    c = s_kv * _rms_norm(kv[:, :C], p["kv_a_norm"]["scale"], eps)
    k_rope = _rope(kv[:, C:], positions, inv_freq, 1.0)
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


def route(config: dict, logits, bias):
    """``float32[L, E + Z]``: each token's weight on each router output (0
    where not chosen), from the router's ``logits [L, E + Z]``: softmax over
    all of them, the ``moe_topk`` largest of score + ``bias`` chosen, the
    unbiased score times ``routed_scaling_factor`` the weight (renormalised
    over the chosen only under ``norm_topk_prob``).  Ties to the lower
    index."""
    k = int(config["moe_topk"])
    scores = jax.nn.softmax(logits, axis=-1)
    chosen = jnp.argsort(-(scores + bias), axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if config.get("norm_topk_prob", False):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * float(config["routed_scaling_factor"])
    return (jax.nn.one_hot(chosen, logits.shape[-1], dtype=jnp.float32)
            * w[:, :, None]).sum(1)


def expert_ffn(config: dict, p, x, held, zero_part: bool = True):
    """The routed part the experts ``held = (first, count)`` give, an expert
    at a time over every token (its weight 0 where the token did not choose
    it), plus, with ``zero_part``, the zero-compute experts': each token's
    summed weight on the outputs past the routed ones, times the token."""
    first, count = held
    gate = route(config, x @ _f32(p["router"]),
                 _f32(p["e_score_correction_bias"]))
    routed = gate.shape[1] - int(config["zero_expert_num"])

    def add(e, acc):
        pick = lambda w: jax.lax.dynamic_index_in_dim(  # noqa: E731
            w, e, keepdims=False)
        weight = jax.lax.dynamic_index_in_dim(gate, first + e, axis=1)
        return acc + weight * _swiglu(
            x, pick(p["w_gate"]), pick(p["w_up"]), pick(p["w_down"]))

    start = (gate[:, routed:].sum(-1, keepdims=True) * x if zero_part
             else jnp.zeros_like(x))
    return jax.lax.fori_loop(0, count, add, start)


def double_layer(config: dict, p, h, positions):
    """One double layer, ``h [L, hidden]`` -> ``[L, hidden]``, as the module
    docstring writes it."""
    eps = float(config["rms_norm_eps"])
    a1 = h + latent_attention(
        config, p["attn_0"], _rms_norm(h, p["attn_norm_0"]["scale"], eps),
        positions)
    x1 = _rms_norm(a1, p["ffn_norm_0"]["scale"], eps)
    m = expert_ffn(config, p["moe"], x1, held_experts(config))
    d1 = a1 + dense_ffn(p["ffn_0"], x1)
    a2 = d1 + latent_attention(
        config, p["attn_1"], _rms_norm(d1, p["attn_norm_1"]["scale"], eps),
        positions)
    x2 = _rms_norm(a2, p["ffn_norm_1"]["scale"], eps)
    return a2 + dense_ffn(p["ffn_1"], x2) + m


def hidden_states(config: dict, params, ids):
    """Final-norm hidden states ``[L, hidden]`` of ONE sequence ``ids
    [L]``."""
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    h = _f32(params["embed_tokens"]["embedding"][ids])
    for i in range(int(config["num_layers"])):
        h = double_layer(config, params[f"layer_{i}"], h, positions)
    return _rms_norm(h, params["norm"]["scale"],
                     float(config["rms_norm_eps"]))


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
            "longcat_flash reference: no configuration yet; "
            "build_model(config) names the one logits_at and "
            "causal_lm_loss compute")
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


def router_outputs(config: dict) -> int:
    """The router's width: the published routed experts, then the
    zero-compute ones."""
    return (int(config["published"]["n_routed_experts"])
            + int(config["zero_expert_num"]))


def param_counts(config: dict) -> dict:
    """Parameters by part, of the share: ``mla`` (one sublayer's),
    ``dense_ffn`` (one), ``expert`` (one routed expert), ``router`` (its
    matrix and its choice bias, both float32), ``embedding`` (and the head,
    each), ``double_layer``, ``total``."""
    h, H = int(config["hidden_size"]), int(config["num_attention_heads"])
    C, dr = int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"])
    dn, dv = int(config["qk_nope_head_dim"]), int(config["v_head_dim"])
    ql = int(config["q_lora_rank"])
    mla = (h * ql + ql + ql * H * (dn + dr) + h * (C + dr) + C
           + C * H * (dn + dv) + H * dv * h)
    dense = 3 * h * int(config["ffn_hidden_size"])
    expert = 3 * h * int(config["expert_ffn_hidden_size"])
    router = (h + 1) * router_outputs(config)
    double_layer = (2 * mla + 2 * dense + 4 * h + router
                    + expert * int(config["n_routed_experts"]))
    embedding = int(config["vocab_size"]) * h
    return {
        "mla": mla, "dense_ffn": dense, "expert": expert, "router": router,
        "embedding": embedding, "double_layer": double_layer,
        "total": int(config["num_layers"]) * double_layer + 2 * embedding + h,
    }


def _matmul_params_a_token(config: dict) -> float:
    """Matrix parameters one token meets on this chip in one forward, head
    left out: both MLAs and both dense feed-forwards whole, the router's
    matrix, and of the routed experts the expected ``moe_topk * held /
    router outputs`` (uniform routing over routed and zero-compute outputs
    alike).  An assignment to a zero-compute expert meets no parameter."""
    n = param_counts(config)
    drawn = (int(config["moe_topk"]) * int(config["n_routed_experts"])
             / router_outputs(config))
    return int(config["num_layers"]) * (
        2 * n["mla"] + 2 * n["dense_ffn"]
        + int(config["hidden_size"]) * router_outputs(config)
        + n["expert"] * drawn)


def _attention_flops_a_pair(config: dict, absorbed: bool) -> float:
    """Forward operations one (query, key) pair costs in one latent
    sublayer, all heads: expanded ``2 H (dn + dr) + 2 H dv``; absorbed ``2 H
    (C + dr) + 2 H C``."""
    H = int(config["num_attention_heads"])
    C, dr = int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"])
    dn, dv = int(config["qk_nope_head_dim"]), int(config["v_head_dim"])
    return 2.0 * H * ((C + dr) + C if absorbed else (dn + dr) + dv)


def latent_sublayers(config: dict) -> int:
    """Two a double layer: each reads and writes a row of the cache."""
    return 2 * int(config["num_layers"])


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward of one token in a sequence of ``seq_len`` on
    this share: 6 per matrix parameter met (head included) plus expanded
    causal attention over on average half the sequence in every latent
    sublayer, three times its forward."""
    head = int(config["vocab_size"]) * int(config["hidden_size"])
    attn = (3.0 * latent_sublayers(config) * (seq_len / 2)
            * _attention_flops_a_pair(config, absorbed=False))
    return 6.0 * (_matmul_params_a_token(config) + head) + attn


def serve_flops(config: dict, prefill_tokens: float, decode_tokens: float,
                context_tokens: float,
                prefill_context_tokens: float = 0.0) -> float:
    """Operations the algorithm needs to serve ``prefill_tokens`` prompt
    tokens and ``decode_tokens`` decode tokens on this share: 2 per matrix
    parameter met by either kind (nothing for a zero-compute assignment);
    the head for decode tokens (a prefill needs one row of logits, left
    out); absorbed attention over ``context_tokens`` (the sum of the decode
    rows' context lengths) and expanded causal attention over
    ``prefill_context_tokens`` (the sum over prompts of ``P (P + 1) / 2``)
    (query, key) pairs in each of the latent sublayers."""
    sublayers = latent_sublayers(config)
    head = int(config["vocab_size"]) * int(config["hidden_size"])
    return (2.0 * _matmul_params_a_token(config)
            * (prefill_tokens + decode_tokens)
            + 2.0 * head * decode_tokens
            + sublayers * context_tokens
            * _attention_flops_a_pair(config, absorbed=True)
            + sublayers * prefill_context_tokens
            * _attention_flops_a_pair(config, absorbed=False))


def decode_bytes_per_step(config: dict, batch: int,
                          context_tokens: float) -> float:
    """Bytes one decode step has to move on this share: every held weight
    once (the routers' in float32, the rest in the configuration's
    ``dtype``; of the embedding only ``batch`` rows), the live latent rows
    of every latent sublayer once (``context_tokens`` rows of ``kv_lora_rank
    + qk_rope_head_dim`` values each, the step's fresh rows among them,
    written once too)."""
    n = param_counts(config)
    size = jnp.dtype(config["dtype"]).itemsize
    layers = int(config["num_layers"])
    h = int(config["hidden_size"])
    weights = ((n["total"] - n["embedding"] - layers * n["router"]) * size
               + layers * n["router"] * 4 + batch * h * size)
    row = (int(config["kv_lora_rank"]) + int(config["qk_rope_head_dim"])) * (
        jnp.dtype(config["cache_dtype"]).itemsize)
    return weights + latent_sublayers(config) * row * (context_tokens + batch)
