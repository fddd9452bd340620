"""MiniCPM-SALA's decoder (a block-sparse softmax layer, then three lightning
layers) and its cache hook, on the CPU in float32 at a small size, against
the plain reference the benchmark keeps (``benchmark/lib/families/
minicpm_sala.py``: ``jax.numpy`` at ``highest`` precision, nothing of the
program).  The selection is cut with the widths: blocks of 8, compressed
keys of 4 with a stride of 2, ``topk`` 4, a window of 16 and ``dense_len``
16, so that a prompt of 80 attends 4 of its 10 blocks and one of 12 attends
densely.

Tolerance: 2e-5 on logits of a range near 20 (program and reference are
both float32 and differ in the order of their sums: chunks of the lightning
recurrence, blocks of the attention; 1.6e-5 seen)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib.families import minicpm_sala  # noqa: E402
from stoke_tpu.models.decoder import DecoderConfig  # noqa: E402
from stoke_tpu.serving.kv_cache import HybridCacheHook, PagedKVCache  # noqa: E402


def _read(relative):
    with open(os.path.join(ROOT, relative)) as f:
        return json.load(f)


TINY = {**_read("benchmark/configs/minicpm-sala.json"),
        **_read("tests/benchmark/rehearsal/configs/minicpm-sala.json")}
BLOCK = 8


@pytest.fixture(scope="module")
def built():
    model = minicpm_sala.build_model(TINY)
    return model, minicpm_sala.init_params(model, 7, 16)["params"]


def _reference(params, seq):
    ids = jnp.asarray(seq, jnp.int32)[None]
    at = jnp.arange(ids.shape[1], dtype=jnp.int32)[None]
    return np.asarray(minicpm_sala.reference_logits_at(TINY, params, ids,
                                                       at))[0]


def test_forward_matches_reference(built):
    """200 positions: the lightning layers' last chunk is a part one."""
    model, params = built
    seq = np.random.default_rng(0).integers(0, 512, 200).astype(np.int32)
    got = jax.jit(lambda p, x: model.apply({"params": p}, x, train=False))(
        params, jnp.asarray(seq)[None])
    np.testing.assert_allclose(np.asarray(got[0]), _reference(params, seq),
                               atol=2e-5, rtol=0)


@pytest.mark.parametrize("prompt", [80, 79])
def test_prefill_then_decode_logits_match_reference(built, prompt):
    """A prompt of 80 (or 79: a stride begun at its end) in a bucket of 96,
    then six decode steps beside an idle slot, three of which complete a
    window of compressed keys: logits at the prompt's end and at every step
    against the reference's full forward; the prefill visits fewer key
    tiles than are causal, each step fetches every chosen page once,
    whole, and every window's compressed keys in the store are the mean of
    its keys in the plane."""
    model, params = built
    spec = model.cache_spec()
    new, bucket = 6, 96
    seq = np.random.default_rng(1).integers(0, 512, prompt + new)
    want = _reference(params, np.pad(seq, (0, -len(seq) % BLOCK)))
    cache = PagedKVCache(1, 26, BLOCK, spec.planes, state=spec.state,
                         state_layers=3, max_seqs=2, slot_rows=spec.slot_rows,
                         max_seq_len=128, slot_state=spec.slot_state)
    arrays = cache.pages + cache.state
    tables = np.zeros((2, 16), np.int32)
    tables[1, :12] = np.arange(13, 25)
    run = jax.jit(lambda p, a, t, pos, mode, lens, slot, ids: _apply(
        model, spec, p, a, t, pos, mode, lens, slot, ids),
        static_argnums=(4,))
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :prompt] = seq[:prompt]
    logits, tiles, _, arrays = run(
        params, arrays, tables[1:], np.arange(bucket)[None], "prefill",
        np.array([prompt]), np.array([1]), tokens)
    np.testing.assert_allclose(np.asarray(logits[0, 0]), want[prompt - 1],
                               atol=2e-5, rtol=0)
    visited, causal = np.asarray(tiles).tolist()
    assert 0 < visited < causal
    for t in range(prompt, prompt + new):
        lens = np.array([1, t + 1])
        ids = np.array([[0], [seq[t]]])
        step_tables = tables.copy()
        step_tables[0] = 0
        logits, _, words, arrays = run(
            params, arrays, step_tables, np.array([[0], [t]]), "decode", lens,
            np.array([0]), ids)
        np.testing.assert_allclose(np.asarray(logits[1, 0]), want[t],
                                   atol=2e-5, rtol=0)
        page_words = BLOCK * spec.planes[0][1]  # float32: a value a word
        assert int(words[1]) == 2 * spec.block_sparse.topk * page_words
    # the windows written in prefill and, from the running sums, in decode
    plane, store = np.asarray(arrays[0][0]), np.asarray(arrays[1][1])
    width = store.shape[-1]
    keys = plane[tables[1]].reshape(-1, 2 * width)[:prompt + new, :width]
    K, S = spec.block_sparse.kernel_size, spec.block_sparse.kernel_stride
    windows = (prompt + new - K) // S + 1
    want_c = np.stack([keys[j * S:j * S + K].mean(0) for j in range(windows)])
    np.testing.assert_allclose(store[:windows], want_c, atol=1e-6, rtol=0)


def _apply(model, spec, params, arrays, tables, positions, mode, lens, slot,
           ids):
    n = len(spec.planes) + len(spec.slot_rows) + len(spec.slot_state)
    hook = HybridCacheHook(
        arrays[:n], arrays[n:], tables, positions, mode=mode, lengths=lens,
        layer_kinds=spec.layer_kinds, slot=slot,
        block_sparse=spec.block_sparse)
    logits = model.apply({"params": params}, ids, train=False,
                         positions=positions, decode=mode == "decode",
                         kv_cache=hook)
    return logits, hook.key_tiles, hook.words_fetched, hook.pages + hook.state


def test_keys_the_decoder_cannot_build_are_errors():
    base = minicpm_sala.program_config(TINY)
    for bad, why in (({"attn_use_rope": True}, "positions"),
                     ({"lightning_nkv": 2}, "state a head"),
                     ({"tie_word_embeddings": True}, "untied"),
                     ({"mixer_types": ["minicpm4", "mamba"] * 2}, "mixer"),
                     ({"sparse_config": None}, "sparse_config"),
                     ({"qk_norm": False}, "qk_norm"),
                     ({"attn_use_output_gate": False}, "attn_use_output_gate"),
                     ({"use_output_gate": False}, "use_output_gate"),
                     ({"use_output_norm": False}, "use_output_norm"),
                     ({"lightning_use_rope": False}, "lightning_use_rope")):
        with pytest.raises(ValueError, match=why):
            DecoderConfig.from_dict({**base, **bad})
    made = DecoderConfig.from_dict(base)
    assert made.layer_kinds == ("sparse_gqa",) + ("lightning",) * 3
    assert not made.n_routed_experts and made.first_k_dense_replace == 4
    assert abs(made.residual_scale - 1.4 / 32 ** 0.5) < 1e-12


def test_a_long_prompt_goes_through_the_feed_forward_in_chunks():
    import flax.linen as nn

    from stoke_tpu.models.moe import TOKEN_CHUNK, SwiGLU, chunked_swiglu, token_chunks

    class Chunked(nn.Module):
        @nn.compact
        def __call__(self, x):
            return chunked_swiglu(SwiGLU(16, name="ffn"), x)

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2 * TOKEN_CHUNK, 8))
    assert token_chunks(2 * TOKEN_CHUNK) == 2
    assert token_chunks(TOKEN_CHUNK) == token_chunks(2 * TOKEN_CHUNK - 8) == 1
    params = Chunked().init(jax.random.PRNGKey(1), x[:, :4])
    whole = SwiGLU(16).apply({"params": params["params"]["ffn"]}, x)
    # the same parameters and products, a chunk at a time
    np.testing.assert_array_equal(np.asarray(Chunked().apply(params, x)),
                                  np.asarray(whole))
