"""Plain float32 reference of the block the program runs.

Straight ``jax.numpy``: learned token and position embeddings, post-LN
blocks (attention -> add -> LayerNorm -> GELU FFN -> add -> LayerNorm), a
final LayerNorm, the head tied to the token embedding, causal softmax
attention.  No kernel, no cache, no batching tricks; it reads the same
parameter tree the program trains and serves, and derives layer and head
counts from that tree's shapes.  Callers wrap it in
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul
otherwise runs in bf16 passes.

Departures from the published GPT-2 (the program's, reproduced here so the
two can be compared): post-LN where GPT-2 is pre-LN, LayerNorm epsilon 1e-12
inside the blocks and 1e-5 at the end.  Same matmuls, shapes and FLOPs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK_LN_EPS = 1e-12
FINAL_LN_EPS = 1e-5


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


def hidden_states(params, ids):
    """Final-LayerNorm hidden states ``[B, L, hidden]`` of ``ids [B, L]``."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    n_layers = sum(k.startswith("layer_") for k in params)
    B, L = ids.shape
    h = params["tok_emb"]["embedding"][ids] + params["pos_emb"]["embedding"][:L]
    causal = jnp.tril(jnp.ones((L, L), bool))
    for i in range(n_layers):
        p = params[f"layer_{i}"]
        a = p["attention"]
        qkv = jnp.einsum("blh,hknd->kbnld", h, a["qkv"]["kernel"])
        qkv = qkv + a["qkv"]["bias"][:, None, :, None, :]
        q, k, v = qkv[0], qkv[1], qkv[2]  # [B, heads, L, D]
        scores = jnp.einsum("bnqd,bnkd->bnqk", q, k) / jnp.sqrt(q.shape[-1])
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out = jnp.einsum("bnqk,bnkd->bqnd", probs, v).reshape(B, L, -1)
        y = out @ a["out"]["kernel"] + a["out"]["bias"]
        h = _layer_norm(h + y, p["ln_attn"], BLOCK_LN_EPS)
        y = _gelu_tanh(h @ p["ff_in"]["kernel"] + p["ff_in"]["bias"])
        y = y @ p["ff_out"]["kernel"] + p["ff_out"]["bias"]
        h = _layer_norm(h + y, p["ln_ff"], BLOCK_LN_EPS)
    return _layer_norm(h, params["ln_final"], FINAL_LN_EPS)


def logits(params, ids):
    """``[B, L, vocab]`` logits of the tied head."""
    emb = params["tok_emb"]["embedding"].astype(jnp.float32)
    return hidden_states(params, ids) @ emb.T


def logits_at(params, ids, positions):
    """Logits ``[B, T, vocab]`` at ``positions [B, T]`` only: a full-length
    sequence's ``[L, vocab]`` is never materialised."""
    h = hidden_states(params, ids)
    h = jnp.take_along_axis(h, positions[:, :, None], axis=1)
    return h @ params["tok_emb"]["embedding"].astype(jnp.float32).T


def causal_lm_loss(params, ids):
    """Mean next-token cross entropy over ``ids [B, L]``."""
    lg = logits(params, ids)[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return -picked.mean()
