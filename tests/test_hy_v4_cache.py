"""The DSA family's cache hook (``SparseLatentHook``: the packed latent
plane, the indexer plane of the ``full`` layers, the decode kernels and the
sparse flash prefill) driving the decoder's own forward, against the plain
reference's logits, at the small size of ``tests/test_hy_v4.py`` (whose
docstring gives the tolerance).
"""

import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from stoke_tpu.serving.kv_cache import PagedKVCache, SparseLatentHook  # noqa
from test_hy_v4 import TINY, _reference, build  # noqa: E402


@pytest.fixture(scope="module")
def built():
    return build()


def test_prefill_then_decode_logits_match_reference(built):
    """The serve programs' own forward through the hook: a padded prompt,
    then two decode steps at its end, against the reference's logits of
    the same positions."""
    model, params = built
    spec = model.cache_spec()
    rng = np.random.default_rng(1)
    P, n = 256, 40
    seq = rng.integers(0, TINY["vocab_size"], n + 2).astype(np.int32)
    cache = PagedKVCache(spec.layers, 40, 16, spec.planes, jnp.float32)
    table = jnp.asarray(np.arange(1, 33)[None], jnp.int32)
    index_layers = spec.plane_layers("index")

    @partial(jax.jit, static_argnums=3)
    def run(pages, tokens, positions, mode, lengths):
        hook = SparseLatentHook(pages, table, positions, mode=mode,
                                lengths=lengths, index_topk=spec.index_topk,
                                index_layers=index_layers)
        logits = model.apply({"params": params}, tokens, train=False,
                             positions=positions, decode=mode == "decode",
                             kv_cache=hook)
        return logits, hook.pages

    tokens = np.zeros((1, P), np.int32)
    tokens[0, :n] = seq[:n]
    got, pages = run(cache.pages, jnp.asarray(tokens),
                     jnp.arange(P, dtype=jnp.int32)[None], "prefill",
                     jnp.asarray([n], jnp.int32))
    want = _reference(params, seq[None])[0]
    np.testing.assert_allclose(np.asarray(got)[0, :n], want[:n], atol=2e-4,
                               rtol=0)
    for t in range(n, n + 2):
        got, pages = run(pages, jnp.asarray([[seq[t]]]),
                         jnp.asarray([[t]], jnp.int32), "decode",
                         jnp.asarray([t + 1], jnp.int32))
        np.testing.assert_allclose(np.asarray(got)[0, 0], want[t], atol=2e-4,
                                   rtol=0)
