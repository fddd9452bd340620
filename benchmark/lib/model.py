"""The one place that knows how this program builds a model from a
configuration file.

The program's decoder takes its widths from a table keyed by a size name
(``stoke_tpu.models.bert.BERT_SIZES``, a public dict read at call time by
``GPT`` and ``ServingEngine``), so a configuration is registered there under
its own name.  When the program grows a config-driven decoder (ROADMAP D5)
this function changes, in a benchmark PR, and nothing else here does.
"""

from __future__ import annotations

from .flops import ffn_width


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
    import jax
    import numpy as np

    from stoke_tpu import init_module

    return init_module(
        model, jax.random.PRNGKey(seed % (2**31)),
        np.zeros((1, seq_len), np.int32), train=False,
    )
