"""The ``solar_open2`` family: everything the benchmark knows about a
configuration keyed like Upstage's Solar-Open2 ``config.json`` (``model_type:
"solar_open2"``: ``gqa_layers``, ``use_gqa_gate``, ``linear_attn_config``,
the ``kda_*`` keys, ``n_routed_experts`` ...), under the names every family
module has (``benchmark/lib/model.py`` lists them).

A configuration of this family is ONE CHIP'S SHARE of a layer-wise
deployment, as ``axk1``'s is: ``n_routed_experts`` and ``vocab_size`` in the
file are what this chip holds, ``published`` gives the source's counts (the
router keeps its published width) and ``deployment`` which experts are here.
Program and reference are given the same share.

1. the builder (``build_model``, ``init_params``): the only part that
   imports the program, inside its functions;
2. the plain float32 reference (``logits_at``, ``causal_lm_loss`` and the
   pieces the tests compare layer by layer): ``jax.numpy`` at ``highest``
   matmul precision, nothing of the program; the delta rule is the
   sequential recurrence, a ``lax.scan`` over positions, no chunks; the
   expert layer is ``axk1``'s reference (an expert at a time, upcast as it
   is used), the router's groups being one.  ``logits_at`` reads the
   configuration ``build_model`` was last called with, as ``axk1``'s does;
3. the counts: ``param_counts``, ``train_flops_per_token``, ``serve_flops``
   and ``decode_bytes_per_step``, from the configuration's shapes alone.

The equations.  Per layer ``h += Attn_i(RMSNorm(h))``, ``h +=
FFN(RMSNorm(h))``; final RMSNorm; untied head; no biases; no rotary.

Layer ``i`` in ``gqa_layers``: ``q = W_q x`` (``num_attention_heads`` heads of
``head_dim``), ``k = W_k x``, ``v = W_v x`` (``num_key_value_heads`` heads);
query head ``h`` attends with key-value head ``h // (H / G)``; ``o =
softmax(q k^T head_dim^-0.5 + causal) v``; ``o = o * sigmoid(W_g x)``
(elementwise, ``use_gqa_gate``); ``y = W_o o``.

Every other layer, per head, ``d_k = d_v = linear_attn_config.head_dim``,
state ``S [d_k, d_v]``, zero at a sequence's start: ``q~, k~, v~ = W_q x, W_k
x, W_v x``, each through a depthwise causal convolution over the current and
the ``short_conv_kernel_size - 1`` earlier positions (no bias), then SiLU;
``q_t = l2norm(q') d_k^-0.5``, ``k_t = l2norm(k')``, ``v_t = v'``; ``g_t =
-exp(A_log[h]) softplus(W_f2 (W_f1 x_t) + dt_bias)``, a value a channel,
``a_t = exp(g_t)``; ``b_t = sigmoid(W_b x_t)``, a value a head, doubled under
``kda_allow_neg_eigval``; ``S' = diag(a_t) S_{t-1}``; ``S_t = S' + b_t k_t (v_t
- S'^T k_t)^T``; ``o_t = S_t^T q_t``; ``o_t = RMSNorm_dv(o_t) sigmoid(W_g2
(W_g1 x_t))``; ``y = W_o concat_h(o_t)``.

Feed-forward, every layer: the shared expert plus the routed part, sigmoid
scores over the published experts, the ``num_experts_per_tok`` highest,
renormalised, times ``routed_scaling_factor``; experts SwiGLU.

Assumed (the keys do not say): the gate of a ``gqa`` layer is as wide as its
attended values; sigmoid scoring without a score-correction bias and SiLU
(the convention of the family whose keys ``norm_topk_prob``,
``routed_scaling_factor``, ``n_shared_experts`` are); ``l2norm(x) = x
rsqrt(sum x^2 + 1e-6)``; the output norm has a learned scale a channel and the
model's ``rms_norm_eps``; ties to the lower expert index.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmark.lib.families import axk1
from benchmark.lib.families.axk1 import (  # noqa: F401
    _f32,
    _rms_norm,
    held_experts,
    program_config,
)

# the configuration ``build_model`` was last called with (see 2. above)
_BUILT = {}

# What ``init_params`` draws a delta-rule layer's decay from: a channel
# forgets at ``rate x step`` a position, times ``e^z`` with ``z`` the
# token's decay projection, about standard normal.  Over a sequence's
# tokens a channel then remembers over 190 / 590 / 2,160 / 7,950 / 25,000
# positions (the 10th, 25th, 50th, 75th, 90th percentile of the channels):
# from less than a prompt to more than a 3,072-position slot, as a model
# served to a million positions needs them.  The library's INITIALISATION
# (``A_log = log U(1, 16)``, the same steps) gives 1.3 / 2.9 / 9.5 / 30 /
# 71: a state that short-lived never accumulates what a lower precision
# rounds off, and ``check`` could not tell bfloat16 state from float32
# (PERF.md section 6, PR 33).
DECAY_RATE = (1.0 / 256, 1.0 / 4)
DECAY_STEP = (0.001, 0.1)

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
    standard normal, every matrix (the convolutions' taps too) normal with
    variance 1 / fan-in; of a delta-rule layer ``A_log`` the log of a rate a
    head drawn log-uniformly from ``DECAY_RATE`` and ``dt_bias`` the inverse
    softplus of a step a channel drawn log-uniformly from ``DECAY_STEP``:
    with zeros there every channel would decay alike."""
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32), train=False)
    )["params"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    base = jax.random.PRNGKey(seed % (2**31))

    @partial(jax.jit, static_argnums=(1, 2, 3))
    def normal(key, shape, dtype, std):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    @partial(jax.jit, static_argnums=(1, 2))
    def decay(key, shape, name):
        low, high = DECAY_RATE if name == "A_log" else DECAY_STEP
        drawn = jax.random.uniform(key, shape, minval=math.log(low),
                                   maxval=math.log(high))
        if name == "A_log":
            return drawn
        step = jnp.exp(drawn)
        return step + jnp.log(-jnp.expm1(-step))

    made = []
    for i, (path, leaf) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        key = jax.random.fold_in(base, i)
        if "scale" in name:
            made.append(jnp.ones(leaf.shape, leaf.dtype))
        elif "A_log" in name or "dt_bias" in name:
            made.append(decay(key, leaf.shape,
                              "A_log" if "A_log" in name else "dt_bias"))
        else:
            std = 1.0 if "embedding" in name else leaf.shape[-2] ** -0.5
            made.append(normal(key, leaf.shape, leaf.dtype, std))
    return {"params": jax.tree_util.tree_unflatten(treedef, made)}


# --------------------------------------------------------------------------- #
# 2. The reference
# --------------------------------------------------------------------------- #


def layer_kind(config: dict, i: int) -> str:
    return "gqa" if i in config["gqa_layers"] else "kda"


def _linear(config: dict) -> tuple:
    """``(heads, head size, convolution taps)`` of the delta-rule layers."""
    linear = config["linear_attn_config"]
    return (int(linear["num_heads"]), int(linear["head_dim"]),
            int(linear["short_conv_kernel_size"]))


def gqa_attention(config: dict, p, x):
    """One sequence's gated grouped-query attention, ``x [L, hidden]`` ->
    ``[L, hidden]``, causal, a key-value head's query heads at a time."""
    H, G = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    D, L = int(config["head_dim"]), x.shape[0]
    q = (x @ _f32(p["q"]["kernel"])).reshape(L, G, H // G, D)
    k = (x @ _f32(p["k"]["kernel"])).reshape(L, G, D)
    v = (x @ _f32(p["v"]["kernel"])).reshape(L, G, D)
    causal = jnp.tril(jnp.ones((L, L), bool))

    def group(args):
        qg, kg, vg = args  # [L, H / G, D], [L, D], [L, D]
        s = jnp.einsum("qrd,kd->rqk", qg, kg) * D ** -0.5
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("rqk,kd->qrd", pr, vg)

    out = jax.lax.map(group, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
                              jnp.moveaxis(v, 1, 0)))  # [G, L, H / G, D]
    out = jnp.moveaxis(out, 0, 1).reshape(L, H * D)
    if config.get("use_gqa_gate", False):
        out = out * jax.nn.sigmoid(x @ _f32(p["g"]["kernel"]))
    return out @ _f32(p["o"]["kernel"])


def delta_rule(q, k, v, g, beta):
    """The recurrence, a position at a time: ``q``, ``k``, ``g [L, H, dk]``,
    ``v [L, H, dv]``, ``beta [L, H]`` -> ``o [L, H, dv]``, from zero state."""

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, :, None] * S
        u = jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + b_t[:, None, None] * k_t[:, :, None] * (v_t - u)[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    return o


def delta_rule_attention(config: dict, p, x):
    """One sequence's delta-rule layer, ``x [L, hidden]`` -> ``[L,
    hidden]``."""
    H, D, K = _linear(config)
    L = x.shape[0]

    def conv_silu(name):
        fresh = jnp.pad(x @ _f32(p[name]["kernel"]), ((K - 1, 0), (0, 0)))
        taps = _f32(p[name + "_conv"])
        return jax.nn.silu(sum(taps[j] * fresh[j:j + L] for j in range(K))
                           ).reshape(L, H, D)

    q, k, v = conv_silu("q"), conv_silu("k"), conv_silu("v")
    q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + 1e-6) * D ** -0.5
    k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    f = (x @ _f32(p["f_a"]["kernel"])) @ _f32(p["f_b"]["kernel"])
    g = -jnp.exp(_f32(p["A_log"]))[:, None] * jax.nn.softplus(
        f + _f32(p["dt_bias"])).reshape(L, H, D)
    beta = jax.nn.sigmoid(x @ _f32(p["b"]["kernel"]))
    if config.get("kda_allow_neg_eigval", False):
        beta = 2.0 * beta
    o = delta_rule(q, k, v, g, beta)
    o = _rms_norm(o, p["o_norm"]["scale"], float(config["rms_norm_eps"]))
    gate = (x @ _f32(p["g_a"]["kernel"])) @ _f32(p["g_b"]["kernel"])
    return (o.reshape(L, H * D) * jax.nn.sigmoid(gate)) @ _f32(
        p["o"]["kernel"])


def expert_ffn(config: dict, p, x, held):
    """``axk1``'s reference expert layer, the router's groups being one."""
    return axk1.expert_ffn({"n_group": 1, "topk_group": 1, **config},
                           p, x, held)


def hidden_states(config: dict, params, ids):
    """Final-norm hidden states ``[L, hidden]`` of ONE sequence ``ids
    [L]``."""
    eps = float(config["rms_norm_eps"])
    h = _f32(params["embed_tokens"]["embedding"][ids])
    for i in range(int(config["num_hidden_layers"])):
        p = params[f"layer_{i}"]
        attention = (gqa_attention if layer_kind(config, i) == "gqa"
                     else delta_rule_attention)
        h = h + attention(config, p["attn"],
                          _rms_norm(h, p["attn_norm"]["scale"], eps))
        h = h + expert_ffn(config, p["ffn"],
                           _rms_norm(h, p["ffn_norm"]["scale"], eps),
                           held_experts(config))
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
            "solar_open2 reference: no configuration yet; "
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
# From the configuration's shapes alone; all of THIS CHIP'S SHARE: the
# experts held, the vocabulary slice, the layers kept.
# --------------------------------------------------------------------------- #


def _layers(config: dict) -> tuple:
    """``(gqa layers, delta-rule layers)`` among the layers kept."""
    kinds = [layer_kind(config, i)
             for i in range(int(config["num_hidden_layers"]))]
    return kinds.count("gqa"), kinds.count("kda")


def param_counts(config: dict) -> dict:
    """Parameters by part, of the share: ``gqa`` and ``kda`` (a layer's
    attention), ``expert`` (one routed or shared expert), ``router``,
    ``ffn`` (a layer's feed-forward), ``embedding`` (and the head, each),
    ``total``."""
    h = int(config["hidden_size"])
    Hq, G = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    D = int(config["head_dim"])
    H, d, K = _linear(config)
    gqa = h * (Hq * D + 2 * G * D) + Hq * D * h
    if config.get("use_gqa_gate", False):
        gqa += h * Hq * D
    kda = (3 * h * H * d + 3 * K * H * d  # q, k, v and their taps
           + h * d + d * H * d + H + H * d  # decay: pair, A_log, dt_bias
           + h * H  # beta
           + h * d + d * H * d + d  # output gate pair, output norm
           + H * d * h)
    expert = 3 * h * int(config["moe_intermediate_size"])
    router = h * int(config["published"]["n_routed_experts"])
    ffn = router + expert * (int(config["n_shared_experts"])
                             + int(config["n_routed_experts"]))
    embedding = int(config["vocab_size"]) * h
    n_gqa, n_kda = _layers(config)
    return {
        "gqa": gqa, "kda": kda, "expert": expert, "router": router,
        "ffn": ffn, "embedding": embedding,
        "total": (n_gqa * gqa + n_kda * kda
                  + (n_gqa + n_kda) * (ffn + 2 * h) + 2 * embedding + h),
    }


def _matmul_params_a_token(config: dict) -> float:
    """Matrix parameters one token meets on this chip, head left out:
    attention and the shared expert whole, the router, and of the routed
    experts the expected ``num_experts_per_tok * held / published``."""
    n = param_counts(config)
    n_gqa, n_kda = _layers(config)
    routed = (int(config["num_experts_per_tok"])
              * int(config["n_routed_experts"])
              / int(config["published"]["n_routed_experts"]))
    return (n_gqa * n["gqa"] + n_kda * n["kda"] + (n_gqa + n_kda) * (
        n["router"] + n["expert"] * (int(config["n_shared_experts"])
                                     + routed)))


def _state_flops_a_token(config: dict) -> float:
    """Operations the recurrence costs a token in one delta-rule layer: the
    three passes over the ``[H, dk, dv]`` state (``S'^T k``, the rank-one
    update, ``S^T q``) at a multiply-add an element, and the decay's
    multiply."""
    H, d, _ = _linear(config)
    return (2.0 * 3 + 1) * H * d * d


def _attention_flops_a_pair(config: dict) -> float:
    """Forward operations one (query, key) pair costs in one ``gqa`` layer,
    all query heads: scores and values."""
    return 4.0 * int(config["num_attention_heads"]) * int(config["head_dim"])


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward of one token in a sequence of ``seq_len`` on
    this share: 6 per matrix parameter met (head included), causal
    attention over on average half the sequence in the ``gqa`` layers and
    the recurrence in the others, three times their forward."""
    n_gqa, n_kda = _layers(config)
    head = int(config["vocab_size"]) * int(config["hidden_size"])
    return (6.0 * (_matmul_params_a_token(config) + head)
            + 3.0 * n_gqa * (seq_len / 2) * _attention_flops_a_pair(config)
            + 3.0 * n_kda * _state_flops_a_token(config))


def serve_flops(config: dict, prefill_tokens: float, decode_tokens: float,
                context_tokens: float,
                prefill_context_tokens: float = 0.0) -> float:
    """Operations the algorithm needs to serve ``prefill_tokens`` prompt
    tokens and ``decode_tokens`` decode tokens on this share: 2 per matrix
    parameter met by either kind and the recurrence of the delta-rule
    layers; the head for decode tokens (a prefill needs one row of logits,
    left out); attention, in the ``gqa`` layers only, over
    ``context_tokens`` (the sum of the decode rows' context lengths) and
    ``prefill_context_tokens`` (the sum over prompts of ``P (P + 1) / 2``)
    (query, key) pairs a layer."""
    n_gqa, n_kda = _layers(config)
    head = int(config["vocab_size"]) * int(config["hidden_size"])
    tokens = prefill_tokens + decode_tokens
    return ((2.0 * _matmul_params_a_token(config)
             + n_kda * _state_flops_a_token(config)) * tokens
            + 2.0 * head * decode_tokens
            + n_gqa * (context_tokens + prefill_context_tokens)
            * _attention_flops_a_pair(config))


def decode_bytes_per_step(config: dict, batch: int,
                          context_tokens: float) -> float:
    """Bytes one decode step has to move on this share: every held weight
    once (the router's and the decay's two vectors in float32, the rest in
    the configuration's ``dtype``; of the embedding only ``batch`` rows);
    the live key and value rows of the ``gqa`` layers once
    (``context_tokens`` rows, the step's fresh rows among them, written
    once too); every live slot's recurrent state read and written once,
    float32, with the convolution's saved inputs."""
    n = param_counts(config)
    size = jnp.dtype(config["dtype"]).itemsize
    cache = jnp.dtype(config["cache_dtype"]).itemsize
    n_gqa, n_kda = _layers(config)
    h = int(config["hidden_size"])
    H, d, K = _linear(config)
    float32 = (n_gqa + n_kda) * n["router"] + n_kda * (H + H * d)
    weights = ((n["total"] - n["embedding"] - float32) * size + float32 * 4
               + batch * h * size)
    row = 2 * int(config["num_key_value_heads"]) * int(config["head_dim"])
    rows = n_gqa * row * cache * (context_tokens + batch)
    state = batch * n_kda * 2 * (H * d * d * 4 + (K - 1) * 3 * H * d * cache)
    return weights + rows + state
