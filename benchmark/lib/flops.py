"""Operations the algorithm needs, counted from the configuration's shapes.

Nothing here asks the program or the compiler: a change to the program cannot
move these counts.  Recomputed operations (remat) do not count.
"""

from __future__ import annotations


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
