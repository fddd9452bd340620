"""The ``gpt2`` family: everything the benchmark knows about a configuration
keyed like OpenAI's GPT-2 (``n_embd``, ``n_layer``, ``n_head``,
``n_positions``, ``n_inner``), under the names every family module has
(``benchmark/lib/model.py`` lists them).  Three parts, each moved here whole
from the file that held it until PR 28:

1. the builder (``build_model``, ``init_params``): how this program makes the
   model, the only part that imports the program, inside its functions;
2. the plain float32 reference (``hidden_states``, ``logits``, ``logits_at``,
   ``causal_lm_loss``): ``jax.numpy`` alone, nothing of the program;
3. the counts (``ffn_width``, ``matmul_params``, ``train_flops_per_token``):
   operations the algorithm needs, from the configuration's shapes.  A
   kernel's operation and byte functions (for a ``<kernel>_roofline``
   metric) belong here too, beside ``train_flops_per_token``; none exists
   yet.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# --------------------------------------------------------------------------- #
# 1. The builder
#
# The program's decoder takes its widths from a table keyed by a size name
# (``stoke_tpu.models.bert.BERT_SIZES``, a public dict read at call time by
# ``GPT`` and ``ServingEngine``), so a configuration is registered there under
# its own name.
# --------------------------------------------------------------------------- #


def build_model(config: dict):
    """``GPT`` at the configuration's widths, causal flash attention on the
    training and prefill path, no dropout."""
    from stoke_tpu.models import GPT
    from stoke_tpu.models.bert import BERT_SIZES, BertSize
    from stoke_tpu.ops import make_flash_attention

    if config["n_embd"] % config["n_head"]:
        raise ValueError(f"{config['name']}: n_embd not divisible by n_head")
    BERT_SIZES[config["name"]] = BertSize(
        int(config["n_layer"]), int(config["n_embd"]),
        int(config["n_head"]), ffn_width(config),
    )
    return GPT(
        vocab_size=int(config["vocab_size"]),
        size_name=config["name"],
        max_len=int(config["n_positions"]),
        dropout_rate=0.0,
        attention_fn=make_flash_attention(causal=True),
        attention_is_causal=True,
    )


def init_params(model, seed: int, seq_len: int):
    """The model's variables, made on the device in one jitted call from the
    seed (no file is loaded)."""
    import numpy as np

    from stoke_tpu import init_module

    return init_module(
        model, jax.random.PRNGKey(seed % (2**31)),
        np.zeros((1, seq_len), np.int32), train=False,
    )


# --------------------------------------------------------------------------- #
# 2. The reference
#
# Plain float32 reference of the block the program runs.
#
# Straight ``jax.numpy``: learned token and position embeddings, post-LN
# blocks (attention -> add -> LayerNorm -> GELU FFN -> add -> LayerNorm), a
# final LayerNorm, the head tied to the token embedding, causal softmax
# attention.  No kernel, no cache, no batching tricks; it reads the same
# parameter tree the program trains and serves, and derives layer and head
# counts from that tree's shapes.  Callers wrap it in
# ``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul
# otherwise runs in bf16 passes.
#
# Departures from the published GPT-2 (the program's, reproduced here so the
# two can be compared): post-LN where GPT-2 is pre-LN, LayerNorm epsilon 1e-12
# inside the blocks and 1e-5 at the end.  Same matmuls, shapes and FLOPs.
# --------------------------------------------------------------------------- #

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


# --------------------------------------------------------------------------- #
# 3. The counts
#
# Operations the algorithm needs, counted from the configuration's shapes.
#
# Nothing here asks the program or the compiler: a change to the program cannot
# move these counts.  Recomputed operations (remat) do not count.
# --------------------------------------------------------------------------- #


def ffn_width(config: dict) -> int:
    return int(config.get("n_inner") or 4 * config["n_embd"])


def matmul_params(config: dict) -> int:
    """Parameters that take part in a matrix multiplication per token: the
    four attention projections and the two FFN matrices of every layer, and
    the vocabulary head (tied to the embedding, counted once; the embedding
    lookup itself is a gather, not a matmul)."""
    h, layers = int(config["n_embd"]), int(config["n_layer"])
    per_layer = 4 * h * h + 2 * h * ffn_width(config)
    return layers * per_layer + int(config["vocab_size"]) * h


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward of one token in a sequence of ``seq_len``:
    6 FLOPs per matmul parameter (2 forward, 4 backward) plus causal
    attention, ``QK^T`` and ``PV`` over on average half the sequence:
    ``2 * seq * hidden`` forward per layer, three times that with the
    backward pass."""
    attn = 6 * int(config["n_layer"]) * seq_len * int(config["n_embd"])
    return 6.0 * matmul_params(config) + attn
