"""A second family for `test_a_second_family_is_data`: the program's one
decoder again (there is no other yet), but a configuration keyed like a
Hugging Face Llama-style `config.json` (`hidden_size`, `num_hidden_layers`,
`num_attention_heads`, `max_position_embeddings`, `intermediate_size`), none
of which the `gpt2` family or any file of `benchmark/` reads.  The test
registers it as `benchmark.lib.families.stub_hf`; a real family is a file
of that directory and needs no registering."""

from benchmark.lib.families import gpt2

# the configuration as its source would publish it, and its rehearsal sizes
CONFIG = {
    "name": "stub-model", "source": "none: a test's stand-in",
    "family": "stub_hf", "vocab_size": 32000, "hidden_size": 2048,
    "num_hidden_layers": 16, "num_attention_heads": 32,
    "max_position_embeddings": 2048, "intermediate_size": 5632,
}
TINY = {"vocab_size": 384, "hidden_size": 96, "num_hidden_layers": 3,
        "num_attention_heads": 3, "max_position_embeddings": 96,
        "intermediate_size": 160}

logits_at = gpt2.logits_at
causal_lm_loss = gpt2.causal_lm_loss
init_params = gpt2.init_params


def _as_gpt2(config: dict) -> dict:
    return {
        "name": config["name"],
        "vocab_size": config["vocab_size"],
        "n_embd": config["hidden_size"],
        "n_layer": config["num_hidden_layers"],
        "n_head": config["num_attention_heads"],
        "n_positions": config["max_position_embeddings"],
        "n_inner": config["intermediate_size"],
    }


def build_model(config: dict):
    return gpt2.build_model(_as_gpt2(config))


def train_flops_per_token(config: dict, seq_len: int) -> float:
    return gpt2.train_flops_per_token(_as_gpt2(config), seq_len)
