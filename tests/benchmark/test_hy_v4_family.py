"""The ``hy_v4`` family module's arithmetic (``benchmark/lib/families/
hy_v4.py``), the ``kernel_roofline`` reader and the ``longdoc24`` traffic
file, from the files alone: the parameter count against ``jax.eval_shape``
of the built tree, what ``serve_flops`` and ``decode_bytes_per_step`` count
for chosen keys, the reader on a synthetic trace, the configuration against
the source's keys, and the traffic's lengths and prefill buckets.  Shapes
only: nothing of the published size is made."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.jobs import serve_lm  # noqa: E402
from benchmark.lib.families import hy_v4  # noqa: E402
from benchmark.readers import kernel_roofline  # noqa: E402


def _read(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _read("benchmark", "configs", "hy4-preview.json")


def test_the_file_keeps_every_width_of_the_source(config):
    # the source's keys for Hy4-preview, all but the six reduced
    source = {
        "hidden_size": 6144, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "num_attention_heads": 64,
        "q_lora_rank": 2048, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "qk_head_dim": 256, "v_head_dim": 256,
        "index_n_heads": 32, "index_head_dim": 128, "index_topk": 2048,
        "hc_mult": 4, "hc_magnitude": 2, "hc_eps": 1e-06, "swiglu_limit": 10,
        "num_experts_per_tok": 8, "routed_scaling_factor": 2.827,
        "n_group": 1, "topk_group": 1, "n_shared_experts": 1,
        "rope_parameters": {"rope_theta": 10000000, "rope_type": "default"},
        "gated_mla": True, "gating_type": "elementwise",
        "learnable_sink": True, "enable_ihc": True, "use_dsa": True,
        "enable_lm_head_fp32": True, "rms_norm_eps": 1e-05}
    assert {k: config[k] for k in source} == source
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size", "indexer_types", "layer_types",
                                 "mlp_layer_types"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 15104)
    assert config["indexer_types"] == ["full", "full", "shared", "shared",
                                       "shared"]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    # the floors: a period and 4 layers after the dense one, 8 experts, an
    # eighth of the vocabulary
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["deployment"]["chips_sharing_each_layer"] * 8 == 256
    assert hy_v4.held_experts(config) == (0, 8)


def test_param_counts_from_the_keys_equal_the_built_tree(config):
    n = hy_v4.param_counts(config)
    # the counts from the keys
    assert n["attention"] == 265_685_568
    assert n["indexer"] == 9_371_904
    assert n["hyper"] == 589_851
    assert n["dense_ffn"] == 339_738_624
    assert n["expert"] == 37_748_736
    assert n["router"] == 6144 * 256 + 256
    assert n["embedding"] == 92_798_976
    assert n["total"] == 3_243_721_294
    model = hy_v4.build_model(config)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert n["total"] == sum(int(np.prod(l.shape)) for l in leaves)
    # bfloat16 but for the head, the routers with their biases, the sinks
    # and the hyper-connections, float32
    f32 = (n["embedding"] + 4 * n["router"] + 5 * 64
           + 10 * n["hyper"])
    assert sum(int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves) == (
        2 * n["total"] + 2 * f32)
    params = shapes["params"]
    assert "indexer" in params["layer_1"]["attn"]
    assert "indexer" not in params["layer_2"]["attn"]
    assert params["layer_0"]["attn"]["gate"]["kernel"].shape == (6144, 16384)
    assert params["layer_3"]["ffn"]["w_gate"].shape == (8, 6144, 2048)
    assert params["layer_3"]["attn_hc"]["phi"].shape == (4 * 6144, 24)
    assert params["lm_head"]["kernel"].dtype == jnp.float32


def test_serve_flops_count_the_chosen_keys_and_the_indexers_pairs(config):
    n = hy_v4.param_counts(config)
    met = (5 * (n["attention"] + 2 * n["hyper"]) + 2 * n["indexer"]
           + n["dense_ffn"] + 4 * (n["router"]
                                   + n["expert"] * (1 + 8 * 8 / 256)))
    assert hy_v4.serve_flops(config, 1, 0, 0) == pytest.approx(2.0 * met)
    head = 15104 * 6144
    assert hy_v4.serve_flops(config, 0, 1, 0) == pytest.approx(
        2.0 * (met + head))
    absorbed, index = 2 * 64 * (576 + 512), 2 * 32 * 128 + 3 * 32
    # a decode row of 10,000 keys attends 2,048 of them; its indexers score
    # all 10,000 in the 2 full layers
    assert hy_v4.serve_flops(config, 0, 1, 10_000) - hy_v4.serve_flops(
        config, 0, 1, 0) == 5 * 2048 * absorbed + 2 * 10_000 * index
    # a prompt of P tokens: P P+1 / 2 indexer pairs, 2,048 keys a token
    P = 8192
    pairs = P * (P + 1) / 2
    assert hy_v4.serve_flops(config, P, 0, 0, pairs) - hy_v4.serve_flops(
        config, P, 0, 0) == pytest.approx(
        5 * P * 2048 * 2 * 64 * 512 + 2 * pairs * index)


def test_decode_bytes_are_the_held_weights_once_the_keys_and_chosen_rows(
        config):
    empty = hy_v4.decode_bytes_per_step(config, 0, 0)
    n = hy_v4.param_counts(config)
    weights = 2 * (n["total"] - 2 * n["embedding"] - 4 * n["router"]) + 4 * (
        4 * n["router"] + n["embedding"])
    assert empty == weights
    one = hy_v4.decode_bytes_per_step(config, 1, 10_000) - empty
    assert one == (6144 * 2 + 2 * 10_001 * 128 * 2
                   + 5 * (2048 + 1) * 576 * 2)


def test_kernel_bytes_follow_the_steps_counters(config):
    serve = _read("benchmark", "traffic", "longdoc24.json")["serve_config"]
    step = {"window_blocks": 1000, "selected_rows": 5 * 24 * 2048,
            "sparse_row_passes": 4 / 3}
    assert hy_v4.kernel_bytes_per_step(config, "index_scores", step,
                                       serve) == 2 * (
        1000 * 16 * 128 * 2 + 24 * 26624 * 4)
    assert hy_v4.kernel_bytes_per_step(
        config, "sparse_latent_attention", step, serve) == pytest.approx(
        5 * 24 * 2048 * 1536 + 5 * 2 * 24 * 64 * 768 * 2)
    assert hy_v4.kernel_bytes_per_step(config, "flash_fwd", step,
                                       serve) is None


def test_kernel_roofline_sums_the_named_kernel_inside_the_program():
    modules = [("jit_serve_decode", 1000, 500), ("jit_serve_prefill", 2000,
                                                 900),
               ("jit_serve_decode", 4000, 500)]
    ops = [
        ("index_scores f32[24,1,26624]", 1100, 40),
        ("index_scores f32[24,1,26624]", 4100, 60),
        # the same kernel in a prefill: not this program's
        ("index_scores f32[24,1,26624]", 2100, 70),
        ("sparse_latent_attention bf16[24,64,768]", 1200, 80),
        ("fusion f32[24,6144]", 1300, 90),
    ]
    seconds, runs = kernel_roofline.kernel_seconds(
        ops, modules, "index_scores", "jit_serve_decode")
    assert runs == 2 and seconds == pytest.approx(100e-9)
    seconds, _ = kernel_roofline.kernel_seconds(
        ops, modules, "sparse_latent_attention", "jit_serve_decode")
    assert seconds == pytest.approx(80e-9)
    assert kernel_roofline.kernel_seconds(ops, modules, "flash_fwd",
                                          "jit_serve_decode") == (0.0, 2)


def test_the_traffic_gives_long_documents_in_five_prefill_buckets():
    traffic = _read("benchmark", "traffic", "longdoc24.json")
    cfg = traffic["serve_config"]
    assert traffic["clients"] == cfg["max_seqs"] == 24
    pool = serve_lm.make_pool(traffic, 15104, int(traffic["order_seed"]))
    prompts = sorted(len(p) for p, _ in pool)
    outputs = sorted(o for _, o in pool)
    assert len(pool) == 48 and prompts[0] >= 6144 and prompts[-1] <= 24576
    assert 11_000 < np.median(prompts) < 13_500
    assert outputs[0] >= 512 and outputs[-1] <= 2048
    assert max(len(p) + o for p, o in pool) <= cfg["max_seq_len"] == 26624
    pad = cfg["prefill_pad_multiple"]
    buckets = {-(-len(p) // pad) * pad for p, _ in pool}
    assert buckets == {8192, 12288, 16384, 20480, 24576}
    # every context is 3 to 13 times index_topk
    assert prompts[0] / 2048 >= 3 and (prompts[-1] + 2048) / 2048 <= 13
