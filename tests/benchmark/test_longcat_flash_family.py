"""The ``longcat_flash`` family module's arithmetic
(``benchmark/lib/families/longcat_flash.py``), from the published share's
configuration file alone: the parameter count against ``jax.eval_shape`` of
the built tree, the operations ``serve_flops`` counts (nothing for an
assignment to a zero-compute expert), the bytes ``decode_bytes_per_step``
counts (the rows of 8 latent sublayers), and the configuration file against
the catalog's keys.  Shapes only: nothing of the published size is made."""

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

from benchmark.lib.families import longcat_flash  # noqa: E402


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(
            ROOT, "benchmark", "configs", "longcat-flash-omni.json")) as f:
        return json.load(f)


def test_the_file_keeps_every_width_of_the_source(config):
    # the catalog's keys for LongCat-Flash-Omni, all but the three reduced
    source = {
        "attention_bias": False, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
        "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
        "routed_scaling_factor": 6, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    assert {k: config[k] for k in source} == source
    assert config["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_layers": 28, "n_routed_experts": 512,
                                   "vocab_size": 131072}
    assert (config["num_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (4, 8, 16384)
    # the floors: four layers, eight experts, an eighth of the vocabulary
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["deployment"]["chips_sharing_each_layer"] * 8 == 512
    assert longcat_flash.router_outputs(config) == 768
    assert longcat_flash.held_experts(config) == (0, 8)


def test_param_counts_from_the_keys_equal_the_built_tree(config):
    n = longcat_flash.param_counts(config)
    # ISSUE 35's table, from the keys
    assert n["mla"] == 90_572_800
    assert n["dense_ffn"] == 226_492_416
    assert n["router"] == 4_718_592 + 768
    assert n["expert"] == 37_748_736
    assert n["double_layer"] == 940_864_256
    assert n["embedding"] == 100_663_296
    assert n["total"] == 3_964_789_760
    model = longcat_flash.build_model(config)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert n["total"] == sum(int(np.prod(l.shape)) for l in leaves)
    # bfloat16 but for the routers and their choice biases, float32
    assert sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in leaves) == 7_967_334_400
    layer = shapes["params"]["layer_3"]
    assert layer["moe"]["router"].shape == (6144, 768)
    assert layer["moe"]["e_score_correction_bias"].shape == (768,)
    assert layer["moe"]["w_gate"].shape == (8, 6144, 2048)
    assert "shared" not in layer["moe"]
    assert layer["ffn_1"]["down"]["kernel"].shape == (12288, 6144)
    assert layer["attn_1"]["kv_b"].shape == (512, 64 * 256)


def test_serve_flops_count_nothing_for_a_zero_compute_assignment(config):
    n = longcat_flash.param_counts(config)
    one = longcat_flash.serve_flops(config, 0, 1, 0)
    # a token meets both MLAs, both dense feed-forwards, the router's matrix
    # and 12 x 8 / 768 = 0.125 of a held expert in each of 4 double layers,
    # and the head
    met = 4 * (2 * n["mla"] + 2 * n["dense_ffn"] + 6144 * 768
               + 0.125 * n["expert"])
    assert one == 2.0 * met + 2.0 * n["embedding"]
    assert longcat_flash.serve_flops(config, 1, 0, 0) == 2.0 * met
    # the zero-compute outputs widen the router and draw assignments away
    # from the held experts; they add no product of their own: with none,
    # the same 12 a token fall on 512 outputs and meet more of the held
    fewer = {**config, "zero_expert_num": 0}
    assert (longcat_flash.serve_flops(fewer, 1, 0, 0)
            - longcat_flash.serve_flops(config, 1, 0, 0)) == pytest.approx(
        2.0 * 4 * ((12 * 8 / 512 - 0.125) * n["expert"] - 6144 * 256))
    # attention pairs in 8 latent sublayers: absorbed over the decode rows'
    # context, expanded over the prompts' causal pairs
    assert longcat_flash.serve_flops(config, 0, 0, 1) == 8 * 2 * 64 * (
        576 + 512)
    assert longcat_flash.serve_flops(config, 0, 0, 0, 1) == 8 * 2 * 64 * (
        192 + 128)
    assert longcat_flash.train_flops_per_token(config, 1024) == (
        6.0 * (met + n["embedding"]) + 3.0 * 8 * 512 * 2 * 64 * 320)


def test_decode_bytes_are_the_held_weights_once_and_eight_planes_rows(config):
    empty = longcat_flash.decode_bytes_per_step(config, 0, 0)
    # every weight but the embedding: 7.97 GB less 0.2
    assert empty == 7_967_334_400 - 2 * 100_663_296
    per_row = longcat_flash.decode_bytes_per_step(config, 0, 1000) - empty
    assert per_row == 1000 * 8 * 576 * 2
    per_slot = longcat_flash.decode_bytes_per_step(config, 1, 0) - empty
    assert per_slot == 6144 * 2 + 8 * 576 * 2  # embedding row, fresh rows
