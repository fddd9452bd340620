"""The ``minicpm_sala`` family module's counts against the program and the
cell's files: parameters against ``jax.eval_shape`` of the built tree at the
published widths, each decode kernel's bytes against a count by hand, the
traffic file's lengths and prefill buckets, and the cache's bytes a slot
against the pool the engine builds (abstract shapes only: nothing is
allocated)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.jobs.serve_lm import _quantile_lengths  # noqa: E402
from benchmark.lib.families import minicpm_sala  # noqa: E402


def _read(relative):
    with open(os.path.join(ROOT, relative)) as f:
        return json.load(f)


CONFIG = _read("benchmark/configs/minicpm-sala.json")
TRAFFIC = _read("benchmark/traffic/longreason64.json")


@pytest.fixture(scope="module")
def shapes():
    model = minicpm_sala.build_model(CONFIG)
    return model, jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        train=False))["params"]


def _size(tree):
    return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))


def test_param_counts_are_the_built_trees(shapes):
    _, params = shapes
    counts = minicpm_sala.param_counts(CONFIG)
    assert _size(params) == counts["total"] == 1_711_129_600
    assert _size(params["layer_0"]) == counts["sparse_layer"] == 253_763_840
    for i in (1, 2, 3):
        assert _size(params[f"layer_{i}"]) == counts["lightning_layer"] == (
            285_225_216)
        assert _size(params[f"layer_{i}"]["attn"]) == (
            counts["lightning_attention"])
    assert _size(params["layer_0"]["attn"]) == counts["sparse_attention"]
    assert _size(params["embed_tokens"]) == counts["embedding"] == (
        300_843_008)


def test_kernel_bytes_are_the_hand_count():
    step = {"compressed_keys": 1000.0, "selected_blocks": 500.0,
            "sparse_block_passes": 2.0, "state_bytes": 3e7,
            "state_passes": 1.25}
    sc = TRAFFIC["serve_config"]
    slots = sc["max_seqs"]
    # a compressed key of one key-value head: 128 bfloat16; every slot's 32
    # x 128 query and its 2 x 3,840 float32 scores
    assert minicpm_sala.kernel_bytes_per_step(
        CONFIG, "compressed_scores", step, sc) == (
        1000 * 256 + slots * (32 * 128 * 2 + 2 * 3840 * 4))
    # a chosen block of one key-value head: 64 keys and values of 128
    assert minicpm_sala.kernel_bytes_per_step(
        CONFIG, "block_sparse_attention", step, sc) == (
        2.0 * 500 * 64 * 256 * 2 + slots * 2 * 32 * 128 * 2)
    # three layers' q, k, v and output, float32
    assert minicpm_sala.kernel_bytes_per_step(
        CONFIG, "lightning_step", step, sc) == (
        3e7 * 1.25 + 3 * slots * 4 * 32 * 128 * 4)
    assert minicpm_sala.kernel_bytes_per_step(CONFIG, "index_scores", step,
                                              sc) is None


def test_traffic_lengths_and_prefill_buckets():
    prompts = _quantile_lengths(TRAFFIC["prompt_len"], TRAFFIC["pool"])
    outputs = _quantile_lengths(TRAFFIC["output_len"], TRAFFIC["pool"])
    sc = TRAFFIC["serve_config"]
    assert prompts.min() >= 16384 and prompts.max() <= 49152
    assert abs(np.median(prompts) - 24576) < 300
    assert outputs.min() >= 4096 and outputs.max() <= 12288
    assert (prompts + outputs).max() <= sc["max_seq_len"]
    pad = sc["prefill_pad_multiple"]
    buckets = sorted({-(-int(p) // pad) * pad for p in prompts})
    assert buckets == [16384, 24576, 32768, 40960, 49152]
    # every context is longer than dense_len and than the positions a query
    # attends (topk blocks of block_size)
    sparse = CONFIG["sparse_config"]
    assert prompts.min() > sparse["dense_len"]
    assert prompts.min() >= 4 * sparse["topk"] * sparse["block_size"]


def test_cache_bytes_a_slot_are_the_pool_the_engine_builds(shapes):
    from stoke_tpu.serving.kv_cache import PagedKVCache

    model, _ = shapes
    spec = model.cache_spec()
    sc = TRAFFIC["serve_config"]
    blocks = sc["max_seqs"] * -(-sc["max_seq_len"] // sc["kv_block_size"]) + 1
    arrays = jax.eval_shape(lambda: PagedKVCache(
        len(spec.layers_of("rows")), blocks, sc["kv_block_size"], spec.planes,
        dtype=jnp.bfloat16, state=spec.state,
        state_layers=len(spec.layers_of("state")), max_seqs=sc["max_seqs"],
        slot_rows=spec.slot_rows, max_seq_len=sc["max_seq_len"],
        slot_state=spec.slot_state).arrays)
    nbytes = [int(np.prod(a.shape)) * a.dtype.itemsize for a in arrays]
    rows, compressed, sums, *state = nbytes
    # 1,024 B of rows a token, 512 B of compressed keys a 16 tokens, the
    # float32 key sums of a window's two strides a slot, 2 MiB of float32
    # state a slot in each of 3 lightning layers
    assert rows == blocks * 64 * 1024
    assert compressed == sc["max_seqs"] * 3840 * 512
    assert sums == sc["max_seqs"] * 2 * 256 * 4
    assert state == [sc["max_seqs"] * 2 * 1024 * 1024] * 3
    weights = minicpm_sala.param_counts(CONFIG)["total"] * 2
    resident = weights + sum(nbytes)
    # 7.98 GB resident before prefill's temporaries: 47% of a 16 GiB chip
    assert abs(resident / 1e9 - 7.98) < 0.01
    assert resident > 0.25 * 16 * 2 ** 30
