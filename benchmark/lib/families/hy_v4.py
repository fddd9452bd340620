"""The ``hy_v4`` family: everything the benchmark knows about a configuration
keyed like Tencent's Hy4 ``config.json`` (``model_type: "hy_v4"``: the
DeepSeek-V3 family's MLA and expert keys, and ``use_dsa``, ``index_n_heads``,
``index_topk``, ``indexer_types``, ``gated_mla``, ``learnable_sink``,
``hc_mult``, ``swiglu_limit``, ``enable_lm_head_fp32``), under the names every
family module has (``benchmark/lib/model.py``).

A configuration of this family is ONE CHIP'S SHARE of a layer-wise
deployment, as in ``axk1.py``: ``n_routed_experts`` and ``vocab_size`` are
what this chip holds, ``published`` gives the source's counts (the router
keeps its published width), ``deployment`` which experts are here.

1. the model and its weights (``build_model``, ``init_params``), the only part that imports
   the program, inside its functions;
2. the plain float32 reference (``logits_at``, ``causal_lm_loss``):
   ``jax.numpy`` at ``highest`` matmul precision, nothing of the program, no
   kernel, cache or batching.  It runs a request at a time and a block of
   ``_QUERIES`` positions at a time through all the layers (every layer's
   latent rows and indexer keys of the positions before are kept, as the
   causal attention needs nothing after a query), and the attention a
   block of ``_ATTEND`` queries at a time over each query's own chosen
   rows, with ``W_kvb``'s key half folded into the query and its value half
   applied after (the same algebra; 2,048 keys expanded per head and query
   would not fit).  Its selection is its own float32 indexer's, its own
   ``top_k``.  ``logits_at`` reads the configuration ``build_model`` was
   last called with;
3. the counts: ``param_counts``, ``serve_flops``, ``decode_bytes_per_step``,
   and ``kernel_bytes_per_step`` for the two decode kernels.

The equations (from the keys and the published descriptions; see
``benchmark/configs/hy4-preview.json`` ``assumed`` for what the keys leave
open).  The residual stream is ``hc_mult`` rows ``X [n, hidden]``, at first
``n`` copies of the embedding; around each sublayer ``F`` (attention, then
feed-forward) ``x = RMSNorm(vec X)``, ``H_pre = sigmoid(a_0 x phi_pre +
b_pre)``, ``H_post = hc_magnitude sigmoid(a_1 x phi_post + b_post)``,
``H_res = Sinkhorn(exp(a_2 mat(x phi_res) + b_res))`` (20 iterations,
``hc_eps``), ``X' = H_res X + H_post^T F(H_pre X)``; the rows are summed
before the final RMSNorm; the head is float32.  ``F`` = RMSNorm then:

- gated MLA with a sink: ``q = W_qb RMSNorm(W_qa x)``, per head ``dn`` nope
  and ``dr`` rope values; ``[c, k_r] = W_kva x``, ``c = RMSNorm(c)``; ``[k_n,
  v] = W_kvb c``; scores ``(q_n . k_n + q_r . k_r) (dn + dr)^-0.5`` over the
  query's chosen keys ``S_t`` only; ``p = e^z / (e^{sink_h} + sum_{S_t}
  e^z)``; ``o = W_o (attn * sigmoid(W_gate x))``;
- the indexer of a ``full`` layer: ``q^I = W_qI RMSNorm(W_qa x)``
  (``index_n_heads`` x ``index_head_dim``), ``k^I = LayerNorm(W_kI x)``,
  ``w = W_w x index_n_heads^-0.5``, rope on the first ``dr`` values of both;
  ``I_ts = index_head_dim^-0.5 sum_j w_tj ReLU(q^I_tj . k^I_s)``, ``s <= t``;
  ``S_t`` its ``index_topk`` highest, ties to the lower position.  A
  ``shared`` layer uses the ``S_t`` of the ``full`` layer before it;
- feed-forward: layer 0 dense, the others the shared expert plus the routed
  part: ``s = sigmoid(x W_r)``, the ``num_experts_per_tok`` highest ``s + b``
  chosen, weights ``s`` renormalised over them times
  ``routed_scaling_factor``; every SwiGLU ``W_d(silu(min(W_g x, lim)) *
  clip(W_u x, -lim, lim))``.
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
# 1. The model and its weights
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
    experts, parameters and products in the configuration's ``dtype`` (the
    head in float32, as ``enable_lm_head_fp32`` states)."""
    from stoke_tpu.models.decoder import Decoder, DecoderConfig

    cfg = DecoderConfig.from_dict(program_config(config))
    if not getattr(cfg, "use_dsa", False):
        # a program that reads these keys without learned sparse attention
        # would serve another model under this one's name
        raise ValueError(
            f"{config['name']}: this program's decoder builds no learned "
            f"sparse attention (use_dsa), hyper-connections or sinks")
    _BUILT.clear()
    _BUILT.update(config)
    dtype = jnp.dtype(config["dtype"])
    return Decoder(cfg, held_experts=held_experts(config), dtype=dtype,
                   param_dtype=dtype)


def init_params(model, seed: int, seq_len: int):
    """The model's variables from the seed, each leaf made on the device in
    its own dtype by its own jitted call: norm scales 1 and the indexer key
    norm's bias 0; the embedding standard normal; every matrix normal with
    variance 1 / fan-in; the router's choice bias normal with standard
    deviation 1 over the router's outputs (a zero bias would leave the
    choice by ``s + b`` unexercised); the sinks 0 (``learnable_sink_init``);
    of each hyper-connection the three gains 1 and its biases standard
    normal, so that the rows of the stream mix differently token by token
    (gains near 0 would keep the ``n`` rows copies of one another)."""
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
        key = jax.random.fold_in(base, i)
        if "scale" in name or name.endswith("['alpha']"):
            made.append(jnp.ones(leaf.shape, leaf.dtype))
        elif "['k_norm']['bias']" in name or "sinks" in name:
            made.append(jnp.zeros(leaf.shape, leaf.dtype))
        elif name.endswith("['bias']"):  # a hyper-connection's
            made.append(normal(key, leaf.shape, leaf.dtype, 1.0))
        elif "e_score_correction_bias" in name:
            made.append(normal(key, leaf.shape, leaf.dtype,
                               1.0 / leaf.shape[0]))
        elif "embedding" in name:
            made.append(normal(key, leaf.shape, leaf.dtype, 1.0))
        else:
            made.append(normal(key, leaf.shape, leaf.dtype,
                               leaf.shape[-2] ** -0.5))
    return {"params": jax.tree_util.tree_unflatten(treedef, made)}


# --------------------------------------------------------------------------- #
# 2. The reference
# --------------------------------------------------------------------------- #

#: positions a step of the reference's loop takes through all the layers
_QUERIES = 512
#: queries whose chosen rows are gathered at once ([_ATTEND, topk, row])
_ATTEND = 64
_SINKHORN_ITERS = 20
_INDEX_NORM_EPS = 1e-6


def _f32(a):
    return a.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def _rope_pairs(x, positions, theta: float):
    """Pairs ``(2i, 2i+1)`` of ``x [..., d]`` turned by ``positions *
    theta^(-2i/d)``."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    angle = positions[..., None].astype(jnp.float32) * inv_freq.astype(
        np.float32)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        x.shape)


def _swiglu(x, w_gate, w_up, w_down, limit):
    g, u = x @ _f32(w_gate), x @ _f32(w_up)
    if limit:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return (jax.nn.silu(g) * u) @ _f32(w_down)


def sinkhorn(logits, iters: int, eps: float):
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def _hyper(config: dict, p, X):
    """``(H_pre X [Q, hidden], H_post [Q, n], H_res [Q, n, n])``."""
    Q, n, hidden = X.shape
    flat = X.reshape(Q, n * hidden)
    flat = flat / jnp.sqrt((flat * flat).mean(-1, keepdims=True)
                           + float(config["rms_norm_eps"]))
    z = flat @ p["phi"]
    a, b = p["alpha"], p["bias"]
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    post = float(config["hc_magnitude"]) * jax.nn.sigmoid(
        a[1] * z[:, n:2 * n] + b[n:2 * n])
    res = sinkhorn((a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(Q, n, n),
                   _SINKHORN_ITERS, float(config["hc_eps"]))
    return jnp.einsum("qn,qnh->qh", pre, X), post, res


def _index_scores(config: dict, q, w, keys, positions, n_keys):
    """``I [Q, L]`` of queries ``q [Q, Hi, D]`` at ``positions`` against
    ``keys [L, D]``, ``-inf`` after each query, a head at a time."""
    D = q.shape[-1]

    def head(j, acc):
        s = q[:, j] @ keys.T
        return acc + jnp.maximum(s, 0.0) * jax.lax.dynamic_index_in_dim(
            w, j, axis=1)

    s = jax.lax.fori_loop(0, q.shape[1], head,
                          jnp.zeros((q.shape[0], n_keys), jnp.float32))
    allow = jnp.arange(n_keys)[None, :] <= positions[:, None]
    return jnp.where(allow, s * D ** -0.5, -jnp.inf)


def _attend(config: dict, q_row, rows, chosen, count, sinks, w_uv, scale):
    """Each query over its chosen rows: ``q_row [Q, H, C + dr]`` (the key
    half of ``W_kvb`` folded in), ``rows [L, C + dr]``, ``chosen [Q, K]``
    positions of which the first ``count [Q]`` are real.  Returns ``[Q, H,
    dv]``, ``_ATTEND`` queries at a time."""
    C = int(config["kv_lora_rank"])

    def block(args):
        qb, cb, nb = args
        r = rows[cb]  # [A, K, C + dr]
        s = jnp.einsum("qhc,qkc->qhk", qb, r) * scale
        live = jnp.arange(cb.shape[1])[None, None, :] < nb[:, None, None]
        s = jnp.where(live, s, -jnp.inf)
        sink = sinks[None, :, None]
        m = jnp.maximum(s.max(-1, keepdims=True), sink)
        e = jnp.exp(s - m)
        pr = e / (e.sum(-1, keepdims=True) + jnp.exp(sink - m))
        lat = jnp.einsum("qhk,qkc->qhc", pr, r[..., :C])
        return jnp.einsum("qhc,chd->qhd", lat, w_uv)

    Q = q_row.shape[0]
    A = math.gcd(_ATTEND, Q)
    out = jax.lax.map(block, (q_row.reshape(Q // A, A, *q_row.shape[1:]),
                              chosen.reshape(Q // A, A, -1),
                              count.reshape(Q // A, A)))
    return out.reshape(Q, *out.shape[2:])


def _route(config: dict, p, x):
    """``float32[Q, E]``: each token's weight on each routed expert (0 where
    not chosen)."""
    s = jax.nn.sigmoid(x @ p["router"])
    k = int(config["num_experts_per_tok"])
    _, chosen = jax.lax.top_k(s + p["e_score_correction_bias"], k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if config.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * float(config["routed_scaling_factor"])
    return (jax.nn.one_hot(chosen, s.shape[-1], dtype=jnp.float32)
            * w[:, :, None]).sum(1)


def expert_ffn(config: dict, p, x, held=None):
    """Shared expert plus the routed part the experts ``held = (first,
    count)`` give (default: this chip's), an expert at a time over every
    token (its weight 0 where the token did not choose it)."""
    first, count = held or held_experts(config)
    limit = float(config.get("swiglu_limit") or 0.0)
    gate = _route(config, p, x)

    def add(e, acc):
        pick = lambda w: jax.lax.dynamic_index_in_dim(  # noqa: E731
            w, e, keepdims=False)
        weight = jax.lax.dynamic_index_in_dim(gate, first + e, axis=1)
        return acc + weight * _swiglu(x, pick(p["w_gate"]), pick(p["w_up"]),
                                      pick(p["w_down"]), limit)

    shared = p["shared"]
    start = _swiglu(x, shared["gate"]["kernel"], shared["up"]["kernel"],
                    shared["down"]["kernel"], limit)
    return jax.lax.fori_loop(0, count, add, start)


def _layout(config: dict):
    n = int(config["num_hidden_layers"])
    types = list(config["indexer_types"])[:n]
    full = [i for i, t in enumerate(types) if t == "full"]
    dense = next((i for i, t in enumerate(config["mlp_layer_types"])
                  if t != "dense"), n)
    return n, types, full, dense


def hidden_states(config: dict, params, ids, needed):
    """Final-norm hidden states ``[L, hidden]`` of ONE sequence ``ids
    [L]``, positions below ``needed`` (a traced count; the rows after are
    zeros): ``_QUERIES`` positions a step through all the layers."""
    n_layers, types, full, n_dense = _layout(config)
    L, hidden = ids.shape[0], int(config["hidden_size"])
    H = int(config["num_attention_heads"])
    dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    dv, C = int(config["v_head_dim"]), int(config["kv_lora_rank"])
    Hi, Di = int(config["index_n_heads"]), int(config["index_head_dim"])
    K = int(config["index_topk"])
    n = int(config["hc_mult"])
    eps = float(config["rms_norm_eps"])
    theta = float(config["rope_parameters"]["rope_theta"])
    limit = float(config.get("swiglu_limit") or 0.0)
    scale = (dn + dr) ** -0.5
    Q = _QUERIES if L % _QUERIES == 0 else L

    def step(b, carry):
        latent, index, out = carry
        q0 = b * Q
        pos = q0 + jnp.arange(Q, dtype=jnp.int32)
        x0 = _f32(params["embed_tokens"]["embedding"][
            jax.lax.dynamic_slice_in_dim(ids, q0, Q)])
        X = jnp.broadcast_to(x0[:, None, :], (Q, n, hidden))
        chosen = count = None
        for i in range(n_layers):
            p = params[f"layer_{i}"]
            a = p["attn"]
            u, post, res = _hyper(config, p["attn_hc"], X)
            x = _rms_norm(u, p["attn_norm"]["scale"], eps)
            q_lat = _rms_norm(x @ _f32(a["q_a"]["kernel"]),
                              a["q_a_norm"]["scale"], eps)
            q = (q_lat @ _f32(a["q_b"]["kernel"])).reshape(Q, H, dn + dr)
            q_rope = _rope_pairs(q[..., dn:], pos[:, None], theta)
            kv = x @ _f32(a["kv_a"]["kernel"])
            row = jnp.concatenate(
                [_rms_norm(kv[:, :C], a["kv_a_norm"]["scale"], eps),
                 _rope_pairs(kv[:, C:], pos, theta)], axis=-1)
            latent = latent.at[i].set(
                jax.lax.dynamic_update_slice_in_dim(latent[i], row, q0, 0))
            if types[i] == "full":
                ix = a["indexer"]
                qi = (q_lat @ _f32(ix["wq"]["kernel"])).reshape(Q, Hi, Di)
                qi = jnp.concatenate(
                    [_rope_pairs(qi[..., :dr], pos[:, None], theta),
                     qi[..., dr:]], axis=-1)
                ki = x @ _f32(ix["wk"]["kernel"])
                mu = ki.mean(-1, keepdims=True)
                var = ((ki - mu) ** 2).mean(-1, keepdims=True)
                ki = ((ki - mu) / jnp.sqrt(var + _INDEX_NORM_EPS)
                      * _f32(ix["k_norm"]["scale"]) + _f32(ix["k_norm"]["bias"]))
                ki = jnp.concatenate(
                    [_rope_pairs(ki[..., :dr], pos, theta), ki[..., dr:]],
                    axis=-1)
                w = (x @ _f32(ix["weights_proj"]["kernel"])) * Hi ** -0.5
                f = full.index(i)
                index = index.at[f].set(
                    jax.lax.dynamic_update_slice_in_dim(index[f], ki, q0, 0))
                scores = _index_scores(config, qi, w, index[f], pos, L)
                _, chosen = jax.lax.top_k(scores, min(K, L))
                count = jnp.minimum(pos + 1, K)
            w_kvb = _f32(a["kv_b"]).reshape(C, H, dn + dv)
            q_row = jnp.concatenate(
                [jnp.einsum("qhd,chd->qhc", q[..., :dn], w_kvb[..., :dn]),
                 q_rope], axis=-1)
            att = _attend(config, q_row, latent[i], chosen, count,
                          _f32(a["sinks"]), w_kvb[..., dn:], scale)
            att = att.reshape(Q, H * dv) * jax.nn.sigmoid(
                x @ _f32(a["gate"]["kernel"]))
            y = att @ _f32(a["o"]["kernel"])
            X = jnp.einsum("qij,qjh->qih", res, X) + post[..., None] * y[
                :, None, :]
            u, post, res = _hyper(config, p["ffn_hc"], X)
            x = _rms_norm(u, p["ffn_norm"]["scale"], eps)
            if i < n_dense:
                f_ = p["ffn"]
                y = _swiglu(x, f_["gate"]["kernel"], f_["up"]["kernel"],
                            f_["down"]["kernel"], limit)
            else:
                y = expert_ffn(config, p["ffn"], x)
            X = jnp.einsum("qij,qjh->qih", res, X) + post[..., None] * y[
                :, None, :]
        h = _rms_norm(X.sum(1), params["norm"]["scale"], eps)
        return latent, index, jax.lax.dynamic_update_slice_in_dim(
            out, h, q0, 0)

    carry = (jnp.zeros((n_layers, L, C + dr), jnp.float32),
             jnp.zeros((len(full), L, Di), jnp.float32),
             jnp.zeros((L, hidden), jnp.float32))
    return jax.lax.fori_loop(0, (needed + Q - 1) // Q, step, carry)[2]


def reference_logits_at(config: dict, params, ids, positions):
    """Logits ``[B, T, vocab]`` at ``positions [B, T]`` of ``ids [B, L]``,
    a request at a time."""
    head = params["lm_head"]["kernel"]

    def one(args):
        row, at = args
        h = hidden_states(config, params, row, at.max() + 1)
        return h[at] @ _f32(head)

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, (ids, positions))


def reference_loss(config: dict, params, ids):
    """Mean next-token cross entropy over ``ids [B, L]``."""

    def one(row):
        h = hidden_states(config, params, row, row.shape[0])
        logp = jax.nn.log_softmax(h[:-1] @ _f32(params["lm_head"]["kernel"]),
                                  axis=-1)
        return -jnp.take_along_axis(logp, row[1:, None], axis=-1).mean()

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, ids).mean()


def _built() -> dict:
    if not _BUILT:
        raise RuntimeError(
            "hy_v4 reference: no configuration yet; build_model(config) "
            "names the one logits_at and causal_lm_loss compute")
    return _BUILT


def logits_at(params, ids, positions):
    return reference_logits_at(_built(), params, ids, positions)


def causal_lm_loss(params, ids):
    return reference_loss(_built(), params, ids)


# --------------------------------------------------------------------------- #
# 3. The counts
#
# From the configuration's shapes alone, of THIS CHIP'S SHARE.
# --------------------------------------------------------------------------- #


def param_counts(config: dict) -> dict:
    """Parameters by part, of the share: ``attention`` (MLA with its gate
    and sinks), ``indexer``, ``hyper`` (one sublayer's hyper-connection),
    ``dense_ffn``, ``expert`` (one routed or shared expert), ``router``
    (with its choice bias), ``embedding`` (and the head, each),
    ``dense_layer``, ``expert_layer`` (without an indexer), ``total``."""
    h, H = int(config["hidden_size"]), int(config["num_attention_heads"])
    C, dr = int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"])
    dn, dv = int(config["qk_nope_head_dim"]), int(config["v_head_dim"])
    ql = int(config["q_lora_rank"])
    Hi, Di = int(config["index_n_heads"]), int(config["index_head_dim"])
    n = int(config["hc_mult"])
    attention = (h * ql + ql + ql * H * (dn + dr) + h * (C + dr) + C
                 + C * H * (dn + dv) + H * dv * h + h * H * dv + H)
    indexer = ql * Hi * Di + h * Di + 2 * Di + h * Hi
    hyper = n * h * (2 * n + n * n) + 3 + (2 * n + n * n)
    norms = 2 * h
    expert = 3 * h * int(config["moe_intermediate_size"])
    E = int(config["published"]["n_routed_experts"])
    router = h * E + E
    dense_ffn = 3 * h * int(config["intermediate_size"])
    common = attention + 2 * hyper + norms
    dense_layer = common + dense_ffn
    expert_layer = common + router + expert * (
        int(config["n_shared_experts"]) + int(config["n_routed_experts"]))
    embedding = int(config["vocab_size"]) * h
    n_layers, _, full, n_dense = _layout(config)
    return {
        "attention": attention, "indexer": indexer, "hyper": hyper,
        "dense_ffn": dense_ffn, "expert": expert, "router": router,
        "embedding": embedding, "dense_layer": dense_layer,
        "expert_layer": expert_layer,
        "total": (n_dense * dense_layer + (n_layers - n_dense) * expert_layer
                  + len(full) * indexer + 2 * embedding + h),
    }


def _matmul_params_a_token(config: dict) -> float:
    """Matrix parameters one token meets on this chip, head left out:
    attention, indexers, hyper-connections, dense or shared feed-forward,
    the router, and of the routed experts the expected ``top_k * held /
    published`` (uniform routing)."""
    c = param_counts(config)
    n_layers, _, full, n_dense = _layout(config)
    routed = (int(config["num_experts_per_tok"])
              * int(config["n_routed_experts"])
              / int(config["published"]["n_routed_experts"]))
    return (n_layers * (c["attention"] + 2 * c["hyper"])
            + len(full) * c["indexer"] + n_dense * c["dense_ffn"]
            + (n_layers - n_dense) * (c["router"] + c["expert"] * (
                int(config["n_shared_experts"]) + routed)))


def _pair_flops(config: dict, absorbed: bool) -> float:
    """Forward operations of one attended (query, key) pair in one layer,
    all heads: expanded ``2 H (dn + dr) + 2 H dv``; absorbed ``2 H (C + dr)
    + 2 H C``."""
    H = int(config["num_attention_heads"])
    C, dr = int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"])
    dn, dv = int(config["qk_nope_head_dim"]), int(config["v_head_dim"])
    return 2.0 * H * ((C + dr) + C if absorbed else (dn + dr) + dv)


def _index_pair_flops(config: dict) -> float:
    """An indexer's operations a (query, key) pair: the heads' products,
    ReLU and weighted sum."""
    Hi, Di = int(config["index_n_heads"]), int(config["index_head_dim"])
    return 2.0 * Hi * Di + 3.0 * Hi


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward of one token in a sequence of ``seq_len``: 6 per
    matrix parameter met (head included), the attended pairs (``min(t + 1,
    index_topk)`` a position) and the indexer's pairs three times their
    forward."""
    n_layers, _, full, _ = _layout(config)
    K = int(config["index_topk"])
    head = int(config["vocab_size"]) * int(config["hidden_size"])
    attended = min(seq_len / 2, K)
    return (6.0 * (_matmul_params_a_token(config) + head)
            + 3.0 * n_layers * attended * _pair_flops(config, False)
            + 3.0 * len(full) * (seq_len / 2) * _index_pair_flops(config))


def serve_flops(config: dict, prefill_tokens: float, decode_tokens: float,
                context_tokens: float,
                prefill_context_tokens: float = 0.0) -> float:
    """Operations the algorithm needs to serve ``prefill_tokens`` prompt
    tokens and ``decode_tokens`` decode tokens on this share: 2 per matrix
    parameter met; the head for decode tokens; the indexers over every
    (query, key) pair (``context_tokens`` in decode, ``prefill_context_tokens
    = sum P (P + 1) / 2`` in prefill); attention over the CHOSEN pairs only:
    absorbed over ``min(context_tokens, decode_tokens * index_topk)`` in
    decode (exact where every context is at least ``index_topk`` or none
    is, as in the cells), expanded over ``index_topk`` keys a prompt token
    in prefill (over by ``K (K - 1) / 2`` a prompt, the first positions'
    shorter choice: under 8% of the attention of a 6,144-token prompt)."""
    n_layers, _, full, _ = _layout(config)
    K = int(config["index_topk"])
    head = int(config["vocab_size"]) * int(config["hidden_size"])
    decode_pairs = min(context_tokens, decode_tokens * K)
    prefill_pairs = min(prefill_context_tokens, prefill_tokens * K)
    return (2.0 * _matmul_params_a_token(config)
            * (prefill_tokens + decode_tokens)
            + 2.0 * head * decode_tokens
            + n_layers * decode_pairs * _pair_flops(config, True)
            + n_layers * prefill_pairs * _pair_flops(config, False)
            + len(full) * (context_tokens + prefill_context_tokens)
            * _index_pair_flops(config))


def _latent_row_bytes(config: dict) -> int:
    return (int(config["kv_lora_rank"]) + int(config["qk_rope_head_dim"])) * (
        jnp.dtype(config["cache_dtype"]).itemsize)


def decode_bytes_per_step(config: dict, batch: float,
                          context_tokens: float) -> float:
    """Bytes one decode step has to move on this share: every held weight
    once (router and head in float32, the rest in ``dtype``; of the
    embedding ``batch`` rows), the indexer keys of the ``full`` layers to
    each slot's length (``context_tokens``), and ``min(context, index_topk)``
    latent rows a slot and layer at the mean context, the fresh rows
    written once."""
    c = param_counts(config)
    size = jnp.dtype(config["dtype"]).itemsize
    n_layers, _, full, n_dense = _layout(config)
    h = int(config["hidden_size"])
    n_expert = n_layers - n_dense
    weights = ((c["total"] - 2 * c["embedding"] - n_expert * c["router"])
               * size + (n_expert * c["router"] + c["embedding"]) * 4
               + batch * h * size)
    cache = jnp.dtype(config["cache_dtype"]).itemsize
    keys = len(full) * (context_tokens + batch) * int(
        config["index_head_dim"]) * cache
    mean_context = context_tokens / max(batch, 1)
    rows = n_layers * batch * (min(mean_context, int(config["index_topk"]))
                               + 1) * _latent_row_bytes(config)
    return weights + keys + rows


def kernel_bytes_per_step(config: dict, kernel: str, step: dict,
                          serve_config: dict):
    """Bytes a decode step's calls of ``kernel`` move, from the step's
    counters (``step``: the mean ``serve/commit`` attributes of the window,
    ``window_blocks``, ``selected_rows``, ``sparse_row_passes``) and the
    cell's ``serve_config``; None for a kernel this family does not have.

    - ``index_scores``: in each ``full`` layer every live slot's pages of
      indexer keys to its length (``window_blocks`` pages of ``block_size``
      keys), and the whole ``[max_seqs, max_blocks * block_size]`` float32
      scores written;
    - ``sparse_latent_attention``: in each layer the rows it fetched
      (``sparse_row_passes x selected_rows x`` the row's bytes: the kernel's
      own count of the words its row DMAs fetched), each slot's absorbed queries read and its
      rows written."""
    n_layers, _, full, _ = _layout(config)
    cache = jnp.dtype(config["cache_dtype"]).itemsize
    BS = int(serve_config["kv_block_size"])
    slots = int(serve_config["max_seqs"])
    if kernel == "index_scores":
        positions = -(-int(serve_config["max_seq_len"]) // BS) * BS
        return len(full) * (
            step["window_blocks"] * BS * int(config["index_head_dim"]) * cache
            + slots * positions * 4)
    if kernel == "sparse_latent_attention":
        width = 2 * -(-(int(config["kv_lora_rank"])
                        + int(config["qk_rope_head_dim"])) // 2 // 128) * 128
        q_and_out = 2 * slots * int(config["num_attention_heads"]) * width * (
            jnp.dtype(config["dtype"]).itemsize)
        return (step["sparse_row_passes"] * step["selected_rows"]
                * _latent_row_bytes(config) + n_layers * q_and_out)
    return None
