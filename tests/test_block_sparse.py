"""The block-sparse attention kernels (``ops/block_sparse.py``) against their
plain ``jax.numpy`` forms, interpreted on the CPU at a small size: a block of
8 positions, compressed keys of 4 with a stride of 2, ``topk`` 4, a window of
16, ``dense_len`` 16, contexts that end inside a block, and tied scores.

Tolerances: 1e-5 on float32 scores and attention outputs of values near 1
(the kernel and its plain form differ in the order of their sums: the
kernels accumulate a block or a chunk at a time)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stoke_tpu.ops.block_sparse import (
    BlockSparse,
    block_sparse_attention,
    block_sparse_attention_reference,
    choice_lists,
    choose_blocks,
    compress_keys,
    compressed_scores,
    compressed_scores_reference,
)

CFG = BlockSparse(kernel_size=4, kernel_stride=2, block_size=8, topk=4,
                  init_blocks=1, window_size=16, dense_len=16)


def _normal(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


def test_compressed_keys_are_the_window_means():
    k = _normal(0, (24, 2, 8))
    c = compress_keys(k, CFG)
    for j in range(0, 24 // 2 - 1):
        np.testing.assert_allclose(c[j], k[2 * j:2 * j + 4].mean(0),
                                   rtol=1e-6, atol=1e-6)


def test_forced_blocks_are_always_chosen_and_count_toward_topk():
    nb = 12
    # the forced blocks score lowest; the others tie
    scores = jnp.broadcast_to(jnp.where(jnp.arange(nb) < 6, 1.0, 0.0),
                              (5, nb))
    context = jnp.array([17, 40, 50, 81, 96], jnp.int32)
    chosen = np.asarray(choose_blocks(scores, context, CFG))
    for ctx, row in zip(np.asarray(context), chosen):
        live = -(-ctx // 8)
        window = set(range(max(0, ctx - 16) // 8, live))
        assert row[0] and all(row[i] for i in window)
        assert row.sum() == min(live, CFG.topk)
        assert not row[live:].any()
        # the rest are the highest, ties to the lower block
        rest = [i for i in range(live) if row[i] and i and i not in window]
        free = [i for i in range(1, live) if i not in window]
        assert rest == sorted(free, key=lambda i: (-scores[0, i], i))[
            :len(rest)]


def test_a_context_up_to_dense_len_chooses_every_block():
    chosen = choose_blocks(jnp.zeros((3, 6)), jnp.array([1, 9, 16]), CFG)
    assert np.asarray(chosen).sum(-1).tolist() == [1, 2, 2]


@pytest.mark.parametrize("lengths", [[3, 21, 37, 45], [45, 44, 17, 2]])
def test_compressed_scores_kernel_equals_its_plain_form(lengths):
    B, H, G, D, W = 4, 4, 2, 16, 24
    q = _normal(1, (B, H, D))
    store = _normal(2, (B, W, G * D))
    # two equal compressed keys: tied scores
    store = store.at[:, 5].set(store[:, 4])
    lengths = jnp.array(lengths, jnp.int32)
    got = compressed_scores(q, store, lengths, CFG, interpret=True)
    want = compressed_scores_reference(q, store, lengths, CFG)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    short = int(np.argmin(lengths))  # 2 or 3 keys: no window yet
    assert np.isinf(np.asarray(got)[short]).all()


def _decode_case():
    B, H, G, D, BS, MB = 3, 4, 2, 16, 8, 6
    NB = B * MB + 1
    plane = _normal(3, (1, NB, BS, 2 * G * D))
    tables = jnp.arange(1, NB, dtype=jnp.int32).reshape(B, MB)
    lengths = jnp.array([13, 45, 30], jnp.int32)  # none a whole block
    q = _normal(4, (B, H, D))
    scores = _normal(5, (B, G, MB)).at[:, :, 2].set(0.5).at[:, :, 3].set(0.5)
    chosen = choose_blocks(scores, jnp.broadcast_to(lengths[:, None], (B, G)),
                           CFG)
    logical, count = choice_lists(chosen, CFG.list_len)
    blocks = jnp.take_along_axis(tables[:, None, :], logical, axis=2)
    return q, plane, blocks, logical, count, lengths


@pytest.fixture(scope="module")
def decoded():
    """The case and the kernel's output and word count on it."""
    case = _decode_case()
    return case, block_sparse_attention(*case[:2], 0, *case[2:], 0.25,
                                        interpret=True)


def test_block_sparse_attention_kernel_equals_its_plain_form(decoded):
    (q, plane, blocks, logical, count, lengths), (got, words) = decoded
    want = block_sparse_attention_reference(q, plane, 0, blocks, logical,
                                            count, lengths, 0.25)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # one DMA a chosen page, of the whole [BS, 2 G D] page
    page_words = plane.shape[2] * plane.shape[3]
    assert np.asarray(words).tolist() == (
        np.asarray(count).sum(-1) * page_words).tolist()


def test_block_sparse_attention_reads_the_tables_it_is_given(decoded):
    """The kernel attends the pages it is handed: another slot's table gives
    another answer (what the control of reading the next slot's blocks
    relies on)."""
    (q, plane, blocks, logical, count, lengths), (own, _) = decoded
    other, _ = block_sparse_attention(q, plane, 0, jnp.roll(blocks, 1, 0),
                                      logical, count, lengths, 0.25,
                                      interpret=True)
    assert float(jnp.abs(own - other).max()) > 0.1
