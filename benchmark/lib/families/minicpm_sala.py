"""The ``minicpm_sala`` family: everything the benchmark knows about a
configuration keyed like OpenBMB's MiniCPM-SALA ``config.json``
(``model_type: "minicpm_sala"``: ``mixer_types``, ``lightning_*``,
``qk_norm``, ``attn_use_rope``, the output gates and norm, ``scale_emb``,
``scale_depth``, ``dim_model_base``, ``mup_denominator``), with the block
selection's settings under ``sparse_config``, under the names every family
module has (``benchmark/lib/model.py``).

A configuration of this family is ONE PIPELINE STAGE of a dense model: the
layers it keeps are whole, each with its published index, and
``published`` gives the source's depth.

1. the model and its weights (``build_model``, ``init_params``), the only
   part that imports the program, inside its functions;
2. the plain float32 reference (``logits_at``, ``causal_lm_loss``):
   ``jax.numpy`` at ``highest`` matmul precision, nothing of the program, no
   kernel, cache or batching.  A request at a time, ``_QUERIES`` positions
   at a time through all the layers (the keys and values of the sparse
   layers and the lightning layers' states carried from block to block, as
   causality needs nothing later); the sparse layer's selection from its own
   compressed keys, ``_ATTEND`` queries at a time, its own ``top_k``; its
   attention softmax over all positions, masked to the chosen blocks and
   causality; the lightning recurrence a plain scan over positions.
   ``logits_at`` gives logits at the positions asked for only and reads the
   configuration ``build_model`` was last called with;
3. the counts: ``param_counts``, ``serve_flops``, ``decode_bytes_per_step``
   and ``kernel_bytes_per_step`` for the three decode kernels.

The equations (``benchmark/configs/minicpm-sala.json`` ``assumed`` has what
the keys leave open).  ``h = scale_emb E[x]``; per layer ``h += a
Mixer(RMSNorm(h))``, ``h += a SwiGLU(RMSNorm(h))`` with ``a = scale_depth /
sqrt(mup_denominator)``; logits ``W_head (RMSNorm(h) dim_model_base /
hidden_size)``.  Mixer:

- ``minicpm4``: ``q = W_q x`` (``num_attention_heads`` x ``head_dim``),
  ``k``, ``v`` (``num_key_value_heads``), q and k RMS-normed per head, no
  positions; compressed keys ``c_j = mean(k_{S j} .. k_{S j + K - 1})``
  existing once ``S j + K - 1 <= t``; ``s_tj = sum_{h in g}
  softmax_j(q_th . c_j / sqrt(D))``; block ``i``'s score the max over the
  windows overlapping it; the chosen blocks ``init_blocks`` first ones, the
  ones holding the last ``window_size`` positions, the rest the top scores up
  to ``topk``, ties to the lower block, all blocks where ``t + 1 <=
  dense_len``; softmax over the chosen positions ``<= t``; ``o = W_o(attn *
  sigmoid(W_g x))``;
- ``lightning-attn``: ``q, k, v`` (``lightning_nh`` x
  ``lightning_head_dim``), q and k RMS-normed per head then rotary (halves,
  ``rope_theta``); ``S_t = lam_h S_{t-1} + k_t v_t^T`` from zero, ``o_t =
  q_t S_t / sqrt(d)``, ``lam_h = exp(-2^(-8 (h + 1) / H) (1 - l / (L - 1) +
  1e-5))`` with ``l`` the layer's published index of ``L``; ``o = W_o(
  RMSNorm(o) * sigmoid(W_g x))``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# the configuration ``build_model`` was last called with (see 2. above)
_BUILT = {}

MIXERS = {"minicpm4": "sparse", "lightning-attn": "lightning"}

# --------------------------------------------------------------------------- #
# 1. The model and its weights
# --------------------------------------------------------------------------- #


def _layer_kinds(config: dict) -> list:
    n = int(config["num_hidden_layers"])
    return [MIXERS[m] for m in config["mixer_types"][:n]]


def residual_scale(config: dict) -> float:
    return float(config["scale_depth"]) / math.sqrt(
        float(config["mup_denominator"]))


def program_config(config: dict) -> dict:
    """The configuration as the program's decoder reads it: the source's
    keys, and the published depth the lightning decay is taken over."""
    return {**config, "lightning_decay_depth": int(
        config["published"]["num_hidden_layers"])}


def build_model(config: dict):
    """``Decoder`` at the configuration's widths, parameters and products in
    the configuration's ``dtype``."""
    from stoke_tpu.models.decoder import Decoder, DecoderConfig

    cfg = DecoderConfig.from_dict(program_config(config))
    if getattr(cfg, "mixer_types", None) is None:
        # a program that reads these keys without the mixer layers would
        # serve another model under this one's name
        raise ValueError(
            f"{config['name']}: this program's decoder builds no "
            f"block-sparse or lightning layers (mixer_types)")
    _BUILT.clear()
    _BUILT.update(config)
    dtype = jnp.dtype(config["dtype"])
    return Decoder(cfg, dtype=dtype, param_dtype=dtype)


def init_params(model, seed: int, seq_len: int):
    """The model's variables from the seed, each leaf made on the device in
    its own dtype by its own jitted call: norm scales 1; the embedding
    normal with standard deviation ``1 / scale_emb``, so that the scaled
    embedding has unit RMS; every matrix normal with variance ``1 /
    fan-in``, and the matrices that end a residual branch (``o`` and the
    feed-forward's ``down``) times ``1 / a``: each branch then adds about
    unit RMS to the stream, as much as the embedding holds (with the
    published muP factor on random weights the branches would add a
    quarter each and the logits would mostly read the token itself)."""
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32), train=False)
    )["params"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    base = jax.random.PRNGKey(seed % (2**31))
    config = _built()
    a = residual_scale(config)

    @partial(jax.jit, static_argnums=(1, 2, 3))
    def normal(key, shape, dtype, std):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    made = []
    for i, (path, leaf) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        key = jax.random.fold_in(base, i)
        if "scale" in name:
            made.append(jnp.ones(leaf.shape, leaf.dtype))
        elif "embedding" in name:
            made.append(normal(key, leaf.shape, leaf.dtype,
                               1.0 / float(config["scale_emb"])))
        else:
            std = leaf.shape[-2] ** -0.5
            if "['o']" in name or "['down']" in name:
                std /= a
            made.append(normal(key, leaf.shape, leaf.dtype, std))
    return {"params": jax.tree_util.tree_unflatten(treedef, made)}


# --------------------------------------------------------------------------- #
# 2. The reference
# --------------------------------------------------------------------------- #

#: positions a step of the reference's loop takes through all the layers
_QUERIES = 512
#: queries of the sparse layer's selection and attention at a time
_ATTEND = 32


def _f32(a):
    return a.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def _rope_halves(x, positions, theta: float):
    """``x [Q, H, D]`` turned by ``positions [Q]``: value ``i`` with ``i +
    D/2`` by ``positions * theta^(-2i/D)``."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float64) / D)
    angle = positions[:, None, None].astype(jnp.float32) * inv.astype(
        np.float32)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def lightning_decay(config: dict, layer: int):
    """``lam_h`` of the lightning heads of published layer ``layer``."""
    H = int(config["lightning_nh"])
    L = int(config["published"]["num_hidden_layers"])
    slopes = 2.0 ** (-8.0 * (np.arange(H) + 1) / H)
    return jnp.asarray(np.exp(-slopes * (1.0 - layer / (L - 1) + 1e-5)),
                       jnp.float32)


def lightning_scan(q, k, v, decay, state):
    """The recurrence a position at a time: ``q``, ``k [Q, H, dk]``, ``v
    [Q, H, dv]``, ``decay [H]``, ``state [H, dk, dv]`` -> ``(o [Q, H, dv],
    state)``."""

    def step(S, xs):
        q_t, k_t, v_t = xs
        S = decay[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    state, o = jax.lax.scan(step, state, (q, k, v))
    return o, state


def _window_blocks(config: dict, n_blocks: int, n_windows: int):
    """int32 ``[n_blocks, span]``: the windows overlapping each block, the
    index ``n_windows`` (a ``-inf`` column) where a block has fewer."""
    sparse = config["sparse_config"]
    B, S, K = (int(sparse["block_size"]), int(sparse["kernel_stride"]),
               int(sparse["kernel_size"]))
    overlap = [[j for j in range(n_windows)
                if S * j <= i * B + B - 1 and S * j + K - 1 >= i * B]
               for i in range(n_blocks)]
    span = max(len(o) for o in overlap)
    out = np.full((n_blocks, span), n_windows, np.int32)
    for i, o in enumerate(overlap):
        out[i, :len(o)] = o
    return jnp.asarray(out)


def _chosen_blocks(config: dict, s, pos, n_blocks: int, index):
    """bool ``[A, G, n_blocks]``: each query's chosen blocks from its window
    scores ``s [A, G, W]`` (``-inf`` where a window does not exist)."""
    sparse = config["sparse_config"]
    B, topk = int(sparse["block_size"]), int(sparse["topk"])
    ctx = pos[:, None, None] + 1
    padded = jnp.concatenate([s, jnp.full(s.shape[:-1] + (1,), -jnp.inf)], -1)
    score = padded[..., index].max(-1)  # [A, G, n_blocks]
    i = jnp.arange(n_blocks)[None, None, :]
    live = i * B < ctx
    forced = live & ((i < int(sparse["init_blocks"]))
                     | ((i + 1) * B > ctx - int(sparse["window_size"])))
    key = jnp.where(forced, jnp.inf, jnp.where(live, score, -jnp.inf))
    _, top = jax.lax.top_k(key, min(topk, n_blocks))
    picked = (jax.nn.one_hot(top, n_blocks, dtype=jnp.int32).sum(-2) > 0)
    return jnp.where(ctx <= int(sparse["dense_len"]), live, picked & live)


def _sparse_attention(config: dict, q, keys, vals, pos):
    """Each query ``q [Q, H, D]`` at ``pos [Q]`` over the blocks it chose of
    ``keys``, ``vals [L, G, D]`` (zeros past the positions written)."""
    sparse = config["sparse_config"]
    B, S, K = (int(sparse["block_size"]), int(sparse["kernel_stride"]),
               int(sparse["kernel_size"]))
    L, G, D = keys.shape
    H = q.shape[1]
    n_windows, n_blocks = L // S, -(-L // B)
    # c_j over the keys written so far: windows past them are never read
    part = keys.reshape(n_windows, S, G, D).sum(1)
    c = sum(jnp.pad(part[i:], ((0, i), (0, 0), (0, 0)))
            for i in range(K // S)) / K
    index = _window_blocks(config, n_blocks, n_windows)
    kpos = jnp.arange(L)

    def block(args):
        qb, pb = args  # [A, H, D], [A]
        qg = qb.reshape(-1, G, H // G, D)
        logits = jnp.einsum("aghd,wgd->aghw", qg, c) / math.sqrt(D)
        exists = (jnp.arange(n_windows)[None, :]
                  <= (pb[:, None] + 1 - K) // S)[:, None, None, :]
        p = jax.nn.softmax(jnp.where(exists, logits, -jnp.inf), axis=-1)
        s = jnp.where(exists[:, :, 0], jnp.where(exists, p, 0.0).sum(2),
                      -jnp.inf)
        chosen = _chosen_blocks(config, s, pb, n_blocks, index)
        mask = jnp.repeat(chosen, B, axis=-1)[..., :L] & (
            kpos[None, None, :] <= pb[:, None, None])  # [A, G, L]
        sc = jnp.einsum("aghd,lgd->aghl", qg, keys) / math.sqrt(D)
        pr = jax.nn.softmax(jnp.where(mask[:, :, None], sc, -jnp.inf), -1)
        return jnp.einsum("aghl,lgd->aghd", pr, vals).reshape(-1, H, D)

    Q = q.shape[0]
    A = math.gcd(_ATTEND, Q)
    out = jax.lax.map(block, (q.reshape(Q // A, A, H, D),
                              pos.reshape(Q // A, A)))
    return out.reshape(Q, H, D)


def _swiglu(p, x):
    w = {n: _f32(p[n]["kernel"]) for n in ("gate", "up", "down")}
    return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]


def hidden_states(config: dict, params, ids, needed):
    """The head's input ``[L, hidden]`` (the final norm times
    ``dim_model_base / hidden_size``) of ONE sequence ``ids [L]``, positions
    below ``needed`` (a traced count; rows after are zeros): ``_QUERIES``
    positions a step through all the layers."""
    kinds = _layer_kinds(config)
    L, hidden = ids.shape[0], int(config["hidden_size"])
    Hq, G, D = (int(config["num_attention_heads"]),
                int(config["num_key_value_heads"]), int(config["head_dim"]))
    Hl, Dl = int(config["lightning_nh"]), int(config["lightning_head_dim"])
    eps = float(config["rms_norm_eps"])
    theta = float(config["rope_theta"])
    a = residual_scale(config)
    unit = int(config["sparse_config"]["block_size"])
    Q = _QUERIES if L % _QUERIES == 0 else L
    if L % unit:
        raise ValueError(f"the reference takes sequences of whole blocks of "
                         f"{unit}, not {L}")
    sparse_layers = [i for i, k in enumerate(kinds) if k == "sparse"]
    linear_layers = [i for i, k in enumerate(kinds) if k == "lightning"]

    def step(b, carry):
        keys, vals, states, out = carry
        q0 = b * Q
        pos = q0 + jnp.arange(Q, dtype=jnp.int32)
        h = _f32(params["embed_tokens"]["embedding"][
            jax.lax.dynamic_slice_in_dim(ids, q0, Q)]) * float(
                config["scale_emb"])
        for i, kind in enumerate(kinds):
            p = params[f"layer_{i}"]
            at = p["attn"]
            x = _rms_norm(h, p["attn_norm"]["scale"], eps)
            if kind == "sparse":
                q = (x @ _f32(at["q"]["kernel"])).reshape(Q, Hq, D)
                k = (x @ _f32(at["k"]["kernel"])).reshape(Q, G, D)
                v = (x @ _f32(at["v"]["kernel"])).reshape(Q, G, D)
                q = _rms_norm(q, at["q_norm"]["scale"], eps)
                k = _rms_norm(k, at["k_norm"]["scale"], eps)
                s = sparse_layers.index(i)
                keys = keys.at[s].set(
                    jax.lax.dynamic_update_slice_in_dim(keys[s], k, q0, 0))
                vals = vals.at[s].set(
                    jax.lax.dynamic_update_slice_in_dim(vals[s], v, q0, 0))
                o = _sparse_attention(config, q, keys[s], vals[s], pos)
                o = o.reshape(Q, Hq * D) * jax.nn.sigmoid(
                    x @ _f32(at["g"]["kernel"]))
            else:
                q, k, v = ((x @ _f32(at[n]["kernel"])).reshape(Q, Hl, Dl)
                           for n in ("q", "k", "v"))
                q = _rope_halves(_rms_norm(q, at["q_norm"]["scale"], eps),
                                 pos, theta)
                k = _rope_halves(_rms_norm(k, at["k_norm"]["scale"], eps),
                                 pos, theta)
                s = linear_layers.index(i)
                o, new = lightning_scan(q / math.sqrt(Dl), k, v,
                                        lightning_decay(config, i), states[s])
                states = states.at[s].set(new)
                o = _rms_norm(o.reshape(Q, Hl * Dl), at["o_norm"]["scale"],
                              eps) * jax.nn.sigmoid(x @ _f32(at["g"]["kernel"]))
            h = h + a * (o @ _f32(at["o"]["kernel"]))
            h = h + a * _swiglu(p["ffn"],
                                _rms_norm(h, p["ffn_norm"]["scale"], eps))
        h = _rms_norm(h, params["norm"]["scale"], eps) * (
            float(config["dim_model_base"]) / hidden)
        return keys, vals, states, jax.lax.dynamic_update_slice_in_dim(
            out, h, q0, 0)

    carry = (jnp.zeros((len(sparse_layers), L, G, D), jnp.float32),
             jnp.zeros((len(sparse_layers), L, G, D), jnp.float32),
             jnp.zeros((len(linear_layers), Hl, Dl, Dl), jnp.float32),
             jnp.zeros((L, hidden), jnp.float32))
    return jax.lax.fori_loop(0, (needed + Q - 1) // Q, step, carry)[3]


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
            "minicpm_sala reference: no configuration yet; build_model("
            "config) names the one logits_at and causal_lm_loss compute")
    return _BUILT


def logits_at(params, ids, positions):
    return reference_logits_at(_built(), params, ids, positions)


def causal_lm_loss(params, ids):
    return reference_loss(_built(), params, ids)


# --------------------------------------------------------------------------- #
# 3. The counts
#
# From the configuration's shapes alone, of the layers kept here.
# --------------------------------------------------------------------------- #


def _counts_of_kinds(config: dict) -> tuple:
    kinds = _layer_kinds(config)
    return kinds.count("sparse"), kinds.count("lightning")


def param_counts(config: dict) -> dict:
    """Parameters by part: ``sparse_attention`` (q, k, v, o, gate, qk
    norms), ``lightning_attention`` (q, k, v, o, gate, qk norms, output
    norm), ``ffn`` (a SwiGLU), ``sparse_layer`` and ``lightning_layer``
    (with their two norms), ``embedding`` (and the head, each), ``total``."""
    h = int(config["hidden_size"])
    Hq, G, D = (int(config["num_attention_heads"]),
                int(config["num_key_value_heads"]), int(config["head_dim"]))
    Hl, Dl = int(config["lightning_nh"]), int(config["lightning_head_dim"])
    sparse = h * Hq * D + 2 * h * G * D + Hq * D * h + h * Hq * D + 2 * D
    lightning = 5 * h * Hl * Dl + 2 * Dl + Hl * Dl
    ffn = 3 * h * int(config["intermediate_size"])
    embedding = int(config["vocab_size"]) * h
    n_sparse, n_light = _counts_of_kinds(config)
    out = {
        "sparse_attention": sparse, "lightning_attention": lightning,
        "ffn": ffn, "sparse_layer": sparse + ffn + 2 * h,
        "lightning_layer": lightning + ffn + 2 * h, "embedding": embedding,
    }
    out["total"] = (n_sparse * out["sparse_layer"]
                    + n_light * out["lightning_layer"] + 2 * embedding + h)
    return out


def _matmul_params_a_token(config: dict) -> float:
    """Matrix parameters one token meets, head and embedding left out."""
    c = param_counts(config)
    n_sparse, n_light = _counts_of_kinds(config)
    return (n_sparse * c["sparse_attention"] + n_light
            * c["lightning_attention"] + (n_sparse + n_light) * c["ffn"])


def _attended_a_query(config: dict, context: float) -> float:
    """Positions a query with ``context`` keys attends in a sparse layer:
    all of them up to ``dense_len``, else at most ``topk`` blocks."""
    sparse = config["sparse_config"]
    if context <= int(sparse["dense_len"]):
        return context
    return min(context, int(sparse["topk"]) * int(sparse["block_size"]))


def _state_flops_a_token(config: dict) -> float:
    """The recurrence a token in one lightning layer: the decay and the
    rank-one update (3 an element) and ``q S`` (2 an element)."""
    return 5.0 * int(config["lightning_nh"]) * int(
        config["lightning_head_dim"]) ** 2


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward of one token in a sequence of ``seq_len``: 6 a
    matrix parameter met (head included), the sparse layers' attended pairs
    and compressed scores and the recurrence, three times their forward."""
    n_sparse, n_light = _counts_of_kinds(config)
    H, D = int(config["num_attention_heads"]), int(config["head_dim"])
    head = int(config["vocab_size"]) * int(config["hidden_size"])
    stride = int(config["sparse_config"]["kernel_stride"])
    pairs = _attended_a_query(config, seq_len / 2)
    return (6.0 * (_matmul_params_a_token(config) + head)
            + 3.0 * n_sparse * (pairs + seq_len / 2 / stride) * 4.0 * H * D
            + 3.0 * n_light * _state_flops_a_token(config))


def serve_flops(config: dict, prefill_tokens: float, decode_tokens: float,
                context_tokens: float,
                prefill_context_tokens: float = 0.0) -> float:
    """Operations the algorithm needs to serve ``prefill_tokens`` prompt
    tokens and ``decode_tokens`` decode tokens: 2 a matrix parameter met;
    the head for decode tokens (a prefill needs one row, left out); in each
    sparse layer the attended pairs (decode: ``min(context_tokens,
    decode_tokens x topk x block_size)``; prefill: ``min(sum P (P + 1) / 2,
    prefill_tokens x topk x block_size)``) at ``4 H D`` a pair and the
    compressed keys scored (a ``kernel_stride``-th of the pairs of full
    attention) at ``2 H D``; the recurrence of each lightning layer a
    token."""
    n_sparse, n_light = _counts_of_kinds(config)
    H, D = int(config["num_attention_heads"]), int(config["head_dim"])
    head = int(config["vocab_size"]) * int(config["hidden_size"])
    sparse = config["sparse_config"]
    reach = int(sparse["topk"]) * int(sparse["block_size"])
    stride = int(sparse["kernel_stride"])
    tokens = prefill_tokens + decode_tokens
    pairs = (min(context_tokens, decode_tokens * reach)
             + min(prefill_context_tokens, prefill_tokens * reach))
    scored = (context_tokens + prefill_context_tokens) / stride
    return (2.0 * _matmul_params_a_token(config) * tokens
            + 2.0 * head * decode_tokens
            + n_sparse * (4.0 * H * D * pairs + 2.0 * H * D * scored)
            + n_light * _state_flops_a_token(config) * tokens)


def _state_bytes(config: dict) -> int:
    """A slot's float32 state in one lightning layer."""
    return int(config["lightning_nh"]) * int(
        config["lightning_head_dim"]) ** 2 * 4


def _block_bytes(config: dict) -> int:
    """One key-value head's keys and values of one block, as cached."""
    return (int(config["sparse_config"]["block_size"]) * 2
            * int(config["head_dim"])
            * jnp.dtype(config["cache_dtype"]).itemsize)


def decode_bytes_per_step(config: dict, batch: float,
                          context_tokens: float) -> float:
    """Bytes one decode step has to move: every held weight once (of the
    embedding ``batch`` rows); in each sparse layer the compressed keys to
    each slot's length (``context_tokens / kernel_stride`` rows of a head
    each), one key-value head's lanes of the blocks each group chose (at the
    mean context) and the fresh rows written; each lightning layer's state
    of every live slot read and written once, float32."""
    c = param_counts(config)
    size = jnp.dtype(config["dtype"]).itemsize
    cache = jnp.dtype(config["cache_dtype"]).itemsize
    n_sparse, n_light = _counts_of_kinds(config)
    G, D = int(config["num_key_value_heads"]), int(config["head_dim"])
    h = int(config["hidden_size"])
    sparse = config["sparse_config"]
    weights = (c["total"] - c["embedding"]) * size + batch * h * size
    mean = context_tokens / max(batch, 1)
    blocks = -(-_attended_a_query(config, mean) // int(sparse["block_size"]))
    keys = context_tokens / int(sparse["kernel_stride"]) * G * D * cache
    chosen = batch * G * blocks * _block_bytes(config)
    fresh = batch * 2 * G * D * cache
    state = batch * n_light * 2 * _state_bytes(config)
    return weights + n_sparse * (keys + chosen + fresh) + state


def kernel_bytes_per_step(config: dict, kernel: str, step: dict,
                          serve_config: dict):
    """Bytes a decode step's calls of ``kernel`` move, from the step's
    counters (``step``: the mean ``serve/commit`` attributes of the window)
    and the cell's ``serve_config``; None for a kernel this family does not
    have.

    - ``compressed_scores``: in each sparse layer the compressed keys each
      live slot has (``compressed_keys`` rows of one key-value head), every
      slot's query read and its ``[G, windows]`` float32 scores written;
    - ``block_sparse_attention``: the pages it fetched (``sparse_block_passes
      x selected_blocks x`` one head's block bytes: the kernel's own count
      of the words its DMAs fetched), every slot's query read and output
      written, in each sparse layer;
    - ``lightning_step``: every slot's state in each lightning layer read
      and written (``state_bytes x state_passes``: the grid walks every
      slot), and every slot's q, k, v and output, float32."""
    n_sparse, n_light = _counts_of_kinds(config)
    slots = int(serve_config["max_seqs"])
    H, G, D = (int(config["num_attention_heads"]),
               int(config["num_key_value_heads"]), int(config["head_dim"]))
    size = jnp.dtype(config["dtype"]).itemsize
    cache = jnp.dtype(config["cache_dtype"]).itemsize
    if kernel == "compressed_scores":
        windows = -(-int(serve_config["max_seq_len"])
                    // int(config["sparse_config"]["kernel_stride"]))
        return (step["compressed_keys"] * D * cache
                + n_sparse * slots * (H * D * size + G * windows * 4))
    if kernel == "block_sparse_attention":
        return (step["sparse_block_passes"] * step["selected_blocks"]
                * _block_bytes(config) + n_sparse * slots * 2 * H * D * size)
    if kernel == "lightning_step":
        Hl, Dl = int(config["lightning_nh"]), int(config["lightning_head_dim"])
        return (step["state_bytes"] * step["state_passes"]
                + n_light * slots * 4 * Hl * Dl * 4)
    return None
