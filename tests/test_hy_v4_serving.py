"""The DSA family's serving path (``ServingEngine`` over a model with
learned sparse attention: the sparse latent cache, its two decode kernels,
the sparse flash prefill) on the CPU at the small size of
``tests/test_hy_v4.py``, with ``index_topk`` 8 and prompts of up to 40
positions: the served streams against the plain reference's argmax, the
selection the shared layers are handed, the pool's planes and what
``serve/commit`` counts.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from stoke_tpu import ServeConfig  # noqa: E402
from stoke_tpu.ops import sparse_attention as sa  # noqa: E402
from stoke_tpu.serving import kv_cache  # noqa: E402
from stoke_tpu.serving.engine import ServingEngine  # noqa: E402
from test_hy_v4 import PROMPTS, TINY, _reference, build  # noqa: E402


@pytest.fixture(scope="module")
def built():
    return build()


@pytest.fixture(scope="module")
def served(built):
    """``(engine, prompts, streams, selections, handed)``: the three
    prompts served 4 tokens each, the ``(addresses, counts)`` every layer's
    decode kernel call was handed while the decode program was traced, and
    the ``(context, words fetched)`` each committed decode step handed
    ``_sparse_counts``."""
    model, params = built
    seen, handed = {}, []
    real = kv_cache.sparse_latent_attention
    real_counts = ServingEngine._sparse_counts

    def record(q_row, words, layer, addr, count, sinks, scale):
        seen[layer] = (addr, count)
        return real(q_row, words, layer, addr, count, sinks, scale)

    def record_counts(self, context, live, issued):
        handed.append((np.asarray(context).copy(),
                       np.asarray(issued).copy()))
        return real_counts(self, context, live, issued)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kv_cache, "sparse_latent_attention", record)
        mp.setattr(ServingEngine, "_sparse_counts", record_counts)
        eng = ServingEngine(model, params, _serve_config())
        rng = np.random.default_rng(2)
        prompts = [rng.integers(0, TINY["vocab_size"], n).astype(np.int32)
                   for n in PROMPTS]
        out = eng.generate(prompts, max_new_tokens=4)
    return eng, prompts, out, dict(seen), handed


def _serve_config(**kw):
    return ServeConfig(**{
        "max_seqs": 3, "kv_block_size": 16, "max_seq_len": 512,
        "prefill_pad_multiple": 256, "kv_dtype": "float32",
        "attention": "flash", **kw})


def test_shared_layers_have_no_indexer_and_reuse_the_full_layers_choice(
        built, served):
    model, params = built
    has = {i: "indexer" in params[f"layer_{i}"]["attn"] for i in range(5)}
    assert has == {0: True, 1: True, 2: False, 3: False, 4: False}
    # in the traced decode program each layer's kernel call is handed its
    # selection: the shared layers the very one the full layer before them
    # made, layer 1 its own
    seen = served[3]
    assert set(seen) == set(range(5))
    assert all(seen[i][0] is seen[1][0] and seen[i][1] is seen[1][1]
               for i in (2, 3, 4))
    assert seen[0][0] is not seen[1][0]


def test_engine_serves_the_reference_greedy_stream_and_counts_its_reads(
        built, served):
    _, params = built
    eng, prompts, out, _, _ = served
    for prompt, tokens in zip(prompts, out):
        seq = np.concatenate([prompt, tokens])
        logits = _reference(params, seq[None])[0]
        assert [int(np.argmax(logits[len(prompt) - 1 + i]))
                for i in range(len(tokens))] == list(tokens)
    # 32-bit words a latent row (latent values whole) in 5 layers, and the
    # indexer keys of the 2 layers that have an indexer
    words = sa.packed_width(40, jnp.float32)
    assert eng.cache.bytes_per_token == 5 * words * 4 + 2 * 16 * 4
    assert eng.cache.pages[1].shape[0] == 2
    # a slot's words of each plane: 5 layers of 8 rows, and 2 layers of its
    # 3, 1 and 1 indexer pages of [16, 16] float32
    issued = np.stack([np.full(3, 5 * 8 * words),
                       2 * np.asarray([3, 1, 1]) * 256], axis=-1)
    counts = eng._sparse_counts(np.asarray([41, 5, 1]), [0, 1],
                                issued.astype(np.int32))
    assert counts["indexer_keys"] == 2 * (41 + 5)
    assert counts["selected_rows"] == 5 * (8 + 5)
    fetched = 3 * 5 * 8 * words * 4
    assert counts["sparse_row_passes"] == fetched / (5 * 13 * 40 * 4)
    # every slot's fetched pages over the live slots' 3 + 1 a layer
    assert counts["index_page_passes"] == 2 * 5 * 1024 / (2 * 4 * 1024)


def test_the_words_a_step_reports_are_the_kernels_own(served):
    # the decode program hands back, a slot, the words its five layers'
    # row kernels fetched: a whole step of min(128, index_topk) = 8 rows of
    # packed words a layer for a slot with any context; and beside them the
    # words its two indexer kernels fetched: the slot's pages of [16, 16]
    # float32 a layer
    handed = served[4]
    words = sa.packed_width(40, jnp.float32)
    assert handed
    for context, issued in handed:
        assert issued.dtype == np.int32 and issued.shape == (3, 2)
        assert issued.tolist() == [[5 * 8 * words if c else 0,
                                    2 * -(-int(c) // 16) * 16 * 16]
                                   for c in context]


# sha256 (its first 16 digits) of each serve program's StableHLO as it
# lowered for the engine of three slots above (serve_prefill at commit
# a78fec6; serve_decode since it hands back the indexer kernels' fetched
# words), the programs other families' changes must leave alone.  A change
# to a program on purpose records the new digest here.
LOWERED = {"serve_decode": "b4e6840906a6ad86",
           "serve_prefill": "c54c17f7dba3b351"}


@pytest.mark.parametrize("program", list(LOWERED))
def test_serve_programs_lower_to_the_stablehlo_they_did(built, program):
    import hashlib

    model, params = built
    eng = ServingEngine(model, params, _serve_config())
    batch = eng.scheduler.decode_batch()
    if program == "serve_decode":
        jitted, args = eng._decode_jit, batch
    else:
        jitted = eng._prefill_jit
        args = (np.zeros((1, 256), np.int32), batch[2][:1],
                np.array([5], np.int32))
    text = jitted.lower(eng.qparams, *eng.cache.pages, *eng.cache.state,
                        *args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == LOWERED[program]
