"""MiniCPM-SALA's decoder (a block-sparse softmax layer, then three lightning
layers) served through ``ServingEngine``, on the CPU in float32 at a small
size, against the plain reference the benchmark keeps (``benchmark/lib/families/
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

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib.families import minicpm_sala  # noqa: E402
from stoke_tpu import ServeConfig  # noqa: E402
from stoke_tpu.serving.engine import ServingEngine  # noqa: E402


def _read(relative):
    with open(os.path.join(ROOT, relative)) as f:
        return json.load(f)


TINY = {**_read("benchmark/configs/minicpm-sala.json"),
        **_read("tests/benchmark/rehearsal/configs/minicpm-sala.json")}
BLOCK = 8


@pytest.fixture(scope="module")
def built():
    """The model, its weights and one engine of two slots for every test."""
    model = minicpm_sala.build_model(TINY)
    params = minicpm_sala.init_params(model, 7, 16)["params"]
    return params, ServingEngine(model, params, ServeConfig(
        max_seqs=2, kv_block_size=BLOCK, max_seq_len=128,
        prefill_pad_multiple=32, kv_dtype="float32", attention="flash"))


def _reference(params, seq):
    ids = jnp.asarray(seq, jnp.int32)[None]
    at = jnp.arange(ids.shape[1], dtype=jnp.int32)[None]
    return np.asarray(minicpm_sala.reference_logits_at(TINY, params, ids,
                                                       at))[0]


@pytest.mark.parametrize("prompt", [80, 12])
def test_engine_serves_the_reference_greedy_stream(built, prompt):
    """Through ``ServingEngine``'s ``serve_prefill`` and ``serve_decode``:
    the selection excludes blocks at 80 and attends densely at 12, under
    ``dense_len``; every served token is the reference's argmax, and the
    commit carries the step's block counts."""
    params, engine = built
    seq = np.random.default_rng(prompt).integers(0, 512, prompt).astype(
        np.int32)
    (served,) = engine.generate([seq], max_new_tokens=6)
    full = np.concatenate([seq, served])
    logits = _reference(params, np.pad(full, (0, -len(full) % BLOCK)))
    assert served == logits[prompt - 1:prompt + 5].argmax(-1).tolist()
    counts = engine._block_sparse_counts(np.array([prompt + 4]), [0],
                                         np.array([0]))
    blocks = -(-(prompt + 4) // BLOCK)
    assert counts["selected_blocks"] == 2 * (blocks if prompt + 4 <= 16
                                             else 4)
    assert counts["compressed_keys"] == 2 * ((prompt + 4 - 4) // 2 + 1)
