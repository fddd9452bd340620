"""The block-sparse prefill (``ops/block_sparse.py`` ``prefill_choices``,
``block_sparse_flash``) against its plain forms, interpreted on the CPU at
the small size of ``tests/test_block_sparse.py``: a prompt of 64 of which
all or 45 positions are real.

Tolerance: 1e-5 on attention outputs of values near 1 (the kernel sums a
block of keys at a time, the plain form all at once)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stoke_tpu.ops.block_sparse import (
    BlockSparse,
    block_scores,
    block_sparse_flash,
    block_sparse_flash_reference,
    choose_blocks,
    compress_keys,
    prefill_choices,
    window_scores,
)

CFG = BlockSparse(kernel_size=4, kernel_stride=2, block_size=8, topk=4,
                  init_blocks=1, window_size=16, dense_len=16)


def _normal(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


@pytest.mark.parametrize("length", [64, 45])
def test_prefill_flash_kernel_equals_its_plain_form(length):
    L, H, G, D = 64, 4, 2, 16
    q = _normal(6, (L, H, D))
    k = _normal(7, (L, G, D))
    v = _normal(8, (L, G, D))
    c = compress_keys(k, CFG)
    visited, words, count, tiles = prefill_choices(q, c, jnp.int32(length),
                                                   CFG)
    qg = q.reshape(L, G, H // G, D)
    kg, vg = jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)
    got = block_sparse_flash(qg, kg, vg, visited, words, count, 0.25, 8,
                             interpret=True)
    want = block_sparse_flash_reference(qg, kg, vg, visited, words, count,
                                        0.25, 8)
    np.testing.assert_allclose(np.asarray(got)[:length],
                               np.asarray(want)[:length], rtol=1e-5,
                               atol=1e-5)
    # the tiles of a whole prompt's queries skip blocks none chose
    visited_tiles, causal_tiles = np.asarray(tiles).tolist()
    assert 0 < visited_tiles <= causal_tiles
    if length == L:
        assert visited_tiles < causal_tiles


def test_prefill_choices_are_each_querys_own():
    """A query tile's words mark, for each query, the blocks its own
    selection chose: the plain selection a query at a time."""
    L, H, G, D = 64, 4, 2, 16
    q = _normal(9, (L, H, D))
    c = compress_keys(_normal(10, (L, G, D)), CFG)
    visited, words, count, _ = prefill_choices(q, c, jnp.int32(L), CFG)
    t = jnp.arange(L)
    want = choose_blocks(block_scores(window_scores(q, c, t + 1, CFG), 8, CFG),
                         jnp.broadcast_to((t + 1)[:, None], (L, G)), CFG)
    tile = L // visited.shape[1]
    got = np.zeros((L, G, 8), bool)
    for g in range(G):
        for ti in range(visited.shape[1]):
            for e in range(int(count[g, ti])):
                for r in range(tile):
                    if (int(words[g, ti, e]) >> r) & 1:
                        got[ti * tile + r, g, int(visited[g, ti, e])] = True
    assert (got == np.asarray(want)).all()
