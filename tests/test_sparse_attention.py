"""The learned-sparse-attention kernels (``stoke_tpu/ops/sparse_attention.py``)
against their plain ``jax.numpy`` forms, through the Pallas interpreter on the
CPU, at small sizes: slot lengths that end inside a block, slots with fewer
keys than ``index_topk``, and scores that tie at the selection's edge.

Tolerances: 1e-5 on float32 outputs of size about 1 (the kernels sum in
another order than the plain forms); 2e-2 on a bfloat16 plane's attention,
whose probabilities the kernel rounds to bfloat16 block by block (online
softmax) where the plain form rounds them once.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stoke_tpu.ops import sparse_attention as sa

B, NB, BS, MB, LAYERS = 3, 20, 16, 5, 2
# the first ends inside a block; the third has fewer keys than index_topk
LENS = [37, 80, 5]


def _tables(rng):
    return jnp.asarray(
        rng.permutation(np.arange(1, NB))[:B * MB].reshape(B, MB), jnp.int32)


def test_index_scores_equal_the_gathered_window():
    rng = np.random.default_rng(0)
    heads, D = 4, 128
    plane = jnp.asarray(rng.normal(size=(LAYERS, NB, BS, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, heads, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(B, heads, 1)), jnp.float32)
    tables, lens = _tables(rng), jnp.asarray(LENS, jnp.int32)
    got = sa.index_scores(q, w, plane, 1, tables, lens, 0.1)
    want = sa.index_scores_reference(q, w, plane, 1, tables, lens, 0.1)
    assert got.shape == (B, 1, MB * BS)
    live = np.isfinite(np.asarray(want))
    assert (np.isfinite(np.asarray(got)) == live).all()
    assert [int(n) for n in live.sum(-1)[:, 0]] == LENS
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=1e-5, rtol=0)


# a step of 4 pages of float32 [BS, 128] over tables of 12 blocks: three
# steps for a slot whose table is full
INDEX_GROUP, INDEX_MB = 4, 12
INDEX_LENS = {
    "empty": 0, "one": 1, "a-page": BS,
    "a-page-short-of-a-group": (INDEX_GROUP - 1) * BS,
    "a-group": INDEX_GROUP * BS,
    "a-page-past-a-group": (INDEX_GROUP + 1) * BS,
    "the-table": INDEX_MB * BS,
}


@pytest.mark.parametrize("unroll", [None, 2], ids=["unroll-module",
                                                   "unroll-2"])
@pytest.mark.parametrize("n", list(INDEX_LENS.values()), ids=list(INDEX_LENS))
def test_index_scores_fetch_each_live_page_once(
        n, unroll, monkeypatch):
    """The first slot holds ``n`` keys, at the edges of a page, of a step of
    ``INDEX_GROUP`` pages and of the table; the second ends inside its third
    page, the third a few keys short of its table.  Entries of a table past
    its slot's live blocks are ids outside the plane, which no DMA fetches.
    ``unroll`` starts a trip of a whole step's issue loop (the module's: a
    whole step's)."""
    page = BS * 128 * 4
    monkeypatch.setattr(sa, "_INDEX_GROUP_BYTES", INDEX_GROUP * page)
    if unroll:
        monkeypatch.setattr(sa, "SPARSE_ISSUE_UNROLL", unroll)
    heads, D, nb = 4, 128, 2 * B * INDEX_MB
    assert sa.index_group(BS, D, jnp.float32, INDEX_MB) == INDEX_GROUP
    rng = np.random.default_rng(3)
    plane = jnp.asarray(rng.normal(size=(LAYERS, nb, BS, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, heads, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(B, heads, 1)), jnp.float32)
    lens = np.asarray([n, 37, INDEX_MB * BS - 5])
    live = -(-lens // BS)
    outside = np.asarray([nb, nb + 7, 2**31 - 1, -1, -nb])
    tables = np.where(
        np.arange(INDEX_MB)[None] < live[:, None],
        rng.permutation(np.arange(1, nb))[:B * INDEX_MB].reshape(B, -1),
        rng.choice(outside, (B, INDEX_MB)))
    tables, lens = jnp.asarray(tables, jnp.int32), jnp.asarray(lens, jnp.int32)
    got, words = sa.counted_index_scores(q, w, plane, 1, tables, lens, 0.1)
    want = sa.index_scores_reference(q, w, plane, 1, tables, lens, 0.1)
    assert got.shape == (B, 1, INDEX_MB * BS)
    finite = np.isfinite(np.asarray(want))
    assert (np.isfinite(np.asarray(got)) == finite).all()
    assert finite.sum(-1)[:, 0].tolist() == lens.tolist()
    np.testing.assert_allclose(np.asarray(got)[finite],
                               np.asarray(want)[finite], atol=1e-5, rtol=0)
    # the kernel's own count: a slot's live pages, each at its words, none
    # for an empty slot
    assert words.tolist() == (live * page // 4).tolist()
    assert (words[0] == 0) == (n == 0)


def test_selection_is_the_top_k_with_ties_to_the_lower_position():
    scores = jnp.asarray([[3.0, 1.0, 2.0, 2.0, 2.0, -jnp.inf],
                          [1.0, 1.0, 1.0, 1.0, -jnp.inf, -jnp.inf]])
    lens = jnp.asarray([5, 4], jnp.int32)
    chosen, count = sa.select_topk(scores, lens, 3)
    assert chosen.tolist() == [[0, 2, 3], [0, 1, 2]]
    assert count.tolist() == [3, 3]
    mask = sa.selection_mask(scores, 3)
    assert mask.tolist() == [[True, False, True, True, False, False],
                             [True, True, True, False, False, False]]
    # fewer candidates than k: every one of them
    _, few = sa.select_topk(scores[:, :2], jnp.asarray([2, 1]), 3)
    assert few.tolist() == [2, 1]
    assert sa.selection_mask(scores[:1, :2], 3).tolist() == [[True, True]]
    # the bisection against a stable sort, on scores with many ties, both
    # signs and rows shorter than k
    rng = np.random.default_rng(6)
    s = rng.integers(-4, 5, (16, 64)).astype(np.float32) * 0.5
    s[:, 40:] = np.where(rng.random((16, 24)) < 0.5, -np.inf, s[:, 40:])
    s[3, 5:] = -np.inf
    order = np.argsort(-s, axis=1, kind="stable")[:, :10]
    want = np.zeros(s.shape, bool)
    np.put_along_axis(want, order, True, axis=1)
    want &= np.isfinite(s)
    assert (np.asarray(sa.selection_mask(jnp.asarray(s), 10)) == want).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rows_pack_into_words_and_back_exactly(dtype):
    rng = np.random.default_rng(1)
    values = jnp.asarray(rng.normal(size=(7, 200)), dtype)
    width = sa.packed_width(200, dtype)
    assert width == (256 if dtype == jnp.float32 else 128)
    words = sa.pack_rows(values, width, dtype)
    assert words.dtype == jnp.uint32 and words.shape == (7, width)
    back = sa.unpack_rows(words, dtype)
    assert (back[:, :200] == values).all() and (back[:, 200:] == 0).all()


STEP = sa.SPARSE_ROWS_PER_STEP
# selections a slot: more than a step, and no multiple of the issue unroll
K_ROWS = STEP + 45
# an unroll below the module's, so that a step takes several trips
SMALL_UNROLL = 8
assert STEP % SMALL_UNROLL == 0 and K_ROWS % SMALL_UNROLL


def _edges(unroll):
    """Selection counts at the edges of the unroll and of a step."""
    return sorted({0, 1, unroll - 1, unroll, STEP - 1, STEP, STEP + 1,
                   K_ROWS})


ROW_CASES = [(dtype, atol, None, n)
             for dtype, atol in [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)]
             for n in _edges(sa.issue_unroll(STEP)) + [24]] + [
    (jnp.float32, 1e-5, SMALL_UNROLL, n) for n in _edges(SMALL_UNROLL)]


def _row_case_id(dtype, atol, unroll, n):
    if n == 24:  # the case this test had before it took counts: its old id
        return f"dtype{int(dtype == jnp.bfloat16)}-{atol}"
    return f"{jnp.dtype(dtype).name}-unroll{unroll or 'module'}-{n}"


@pytest.mark.parametrize("dtype,atol,unroll,n", ROW_CASES,
                         ids=[_row_case_id(*c) for c in ROW_CASES])
def test_sparse_latent_attention_equals_the_gathered_rows(dtype, atol, unroll,
                                                          n, monkeypatch):
    """The first slot chooses ``n`` rows, at the edges of the issue loop's
    unroll (the module's, or ``unroll`` a trip) and of a step; the second
    all ``K`` of a selection that is ``K`` = 24 when ``n`` is 24 (a step
    shorter than ``STEP``) and ``K_ROWS`` otherwise; the third 5."""
    if unroll:
        monkeypatch.setattr(sa, "SPARSE_ISSUE_UNROLL", unroll)
        assert sa.issue_unroll(STEP) == unroll
    rng = np.random.default_rng(2)
    C, H = 200, 4
    K = 24 if n == 24 else K_ROWS
    nb, mb = 40, 12  # 3 slots x 12 blocks of the 39 past the scratch block
    width = sa.packed_width(C, dtype)
    values = jnp.asarray(rng.normal(size=(LAYERS * nb * BS, C)), dtype)
    words = sa.pack_rows(values, width, dtype).reshape(
        LAYERS, nb, BS, 1, width)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, nb))[:B * mb].reshape(B, mb), jnp.int32)
    lens = jnp.asarray([n, mb * BS, 5], jnp.int32)
    scores = jnp.where(jnp.arange(mb * BS)[None] < lens[:, None],
                       jnp.asarray(rng.normal(size=(B, mb * BS))), -jnp.inf)
    chosen, count = sa.select_topk(scores, lens, K)
    assert count.tolist() == [min(n, K), K, 5]
    addr = sa.row_addresses(chosen, count, tables, BS)
    per_word = 4 // jnp.dtype(dtype).itemsize
    q = jnp.asarray(rng.normal(size=(B, H, per_word * width)), dtype)
    sinks = jnp.asarray([0.5, -1.0, -jnp.inf, 2.0], jnp.float32)
    got, issued = sa.sparse_latent_attention(q, words, 1, addr, count, sinks,
                                             0.07)
    want = sa.sparse_latent_reference(q, words, 1, addr, count, sinks, 0.07)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol, rtol=0)
    # the kernel's own count of the words its row DMAs fetched: whole steps
    # of min(STEP, K) rows a slot, the 5-row slot's too, and none for a
    # slot with none
    group = min(STEP, K)
    assert issued.tolist() == [-(-int(c) // group) * group * width
                               for c in count]
    assert (issued[0] == 0) == (n == 0)


@pytest.mark.parametrize("limit,group,want", [
    (128, 128, 128), (128, 24, 24), (16, 24, 12), (8, 128, 8), (5, 24, 4),
    (1, 128, 1)])
def test_issue_unroll_is_the_largest_divisor_of_the_step_not_above_the_limit(
        limit, group, want, monkeypatch):
    monkeypatch.setattr(sa, "SPARSE_ISSUE_UNROLL", limit)
    assert sa.issue_unroll(group) == want


def _rows_case(rng, block_size, K=24):
    """A plane of ``block_size``-row blocks, three slots' addresses of ``K``
    rows (24, 9 and none chosen), queries and sinks."""
    nb, C, H = 12, 200, 4
    width = sa.packed_width(C, jnp.bfloat16)
    values = jnp.asarray(rng.normal(size=(LAYERS * nb * block_size, C)),
                         jnp.bfloat16)
    words = sa.pack_rows(values, width, jnp.bfloat16).reshape(
        LAYERS, nb, block_size, 1, width)
    count = jnp.asarray([K, 9, 0], jnp.int32)
    addr = jnp.asarray(rng.integers(block_size, nb * block_size, (B, K)),
                       jnp.int32)
    addr = jnp.where(jnp.arange(K)[None] < count[:, None], addr, 0)
    q = jnp.asarray(rng.normal(size=(B, H, 2 * width)), jnp.bfloat16)
    sinks = jnp.asarray([0.5, -1.0, -jnp.inf, 2.0], jnp.float32)
    return words, addr, count, q, sinks


@pytest.mark.parametrize("block_size", [12, 24])
def test_row_kernels_take_a_block_size_that_is_no_power_of_two(block_size):
    """A row's place is its pool row in the plane viewed as ``[layers, NB *
    BS, 1, W]``: no block size is refused or mislaid."""
    rng = np.random.default_rng(5)
    words, addr, count, q, sinks = _rows_case(rng, block_size)
    got, issued = sa.sparse_latent_attention(q, words, 1, addr, count, sinks,
                                             0.07)
    want = sa.sparse_latent_reference(q, words, 1, addr, count, sinks, 0.07)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2, rtol=0)
    assert issued.tolist() == [24 * words.shape[-1]] * 2 + [0]
    L, nb, _, _, W = words.shape
    rows = jnp.asarray(rng.integers(0, 2**31, (3, W)), jnp.uint32)
    at = jnp.asarray([5, nb * block_size - 1, block_size + 7], jnp.int32)
    wrote = sa.write_rows(words, 1, at, rows)
    flat = words.reshape(L, nb * block_size, W)
    assert (wrote.reshape(flat.shape) == flat.at[1, at].set(rows)).all()


def test_sparse_latent_attention_reads_no_row_outside_the_plane():
    """The kernel runs without Mosaic's bounds checks, so it clamps each row
    address to the plane as an unsigned number: an address past the plane
    or below zero reads the plane's last row, as the reference does given
    that row."""
    rng = np.random.default_rng(8)
    words, addr, count, q, sinks = _rows_case(rng, BS)
    rows = words.shape[1] * BS
    addr = addr.at[0, :4].set(jnp.asarray([rows, rows + 5000, -1, -rows]))
    got, _ = sa.sparse_latent_attention(q, words, 1, addr, count, sinks, 0.07)
    inside = jnp.where((addr < 0) | (addr >= rows), rows - 1, addr)
    want = sa.sparse_latent_reference(q, words, 1, inside, count, sinks, 0.07)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2, rtol=0)


def test_write_rows_writes_each_row_in_place():
    rng = np.random.default_rng(3)
    words = jnp.asarray(rng.integers(0, 2**31, (LAYERS, NB, BS, 1, 128)),
                        jnp.uint32)
    rows = jnp.asarray(rng.integers(0, 2**31, (5, 128)), jnp.uint32)
    addr = jnp.asarray([17, 3, 200, 45, 199], jnp.int32)
    got = sa.write_rows(words, 1, addr, rows)
    want = words.reshape(LAYERS, NB * BS, 128).at[1, addr].set(rows)
    assert (got.reshape(LAYERS, NB * BS, 128) == want).all()


def test_prefill_selection_and_flash_attention_equal_their_plain_forms():
    rng = np.random.default_rng(4)
    L, heads, D, H, Dq, Dv, K = 512, 4, 128, 2, 64, 48, 40
    qi = jnp.asarray(rng.normal(size=(heads, L, D)), jnp.float32)
    wi = jnp.asarray(rng.normal(size=(heads, L, 1)), jnp.float32)
    ki = jnp.asarray(rng.normal(size=(L, D)), jnp.float32)
    # two keys with the same index vector: their scores tie for every query
    ki = ki.at[300].set(ki[301])
    bits = sa.prefill_selection(qi, wi, ki, K, 0.1)
    block = sa.query_block(L)
    mask = sa.bits_mask(bits, block)
    s = jnp.einsum("hqd,kd->qhk", qi, ki)
    s = (jnp.maximum(s, 0.0) * jnp.swapaxes(wi, 0, 1)).sum(1) * 0.1
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    want = sa.selection_mask(s, K)
    assert (mask == want).all()
    # fewer keys than K: all of them; then K exactly, never after the query
    assert (mask.sum(1)[:K] == jnp.arange(1, K + 1)).all()
    assert (mask.sum(1)[K:] == K).all() and not jnp.triu(mask, 1).any()
    # a tie at the edge goes to the lower position
    tied = want[:, 300] != want[:, 301]
    assert (want[tied, 300] & ~want[tied, 301]).all()
    q = jnp.asarray(rng.normal(size=(H, L, Dq)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(H, L, Dq)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(H, L, Dv)), jnp.float32)
    sinks = jnp.asarray([0.3, -1.0], jnp.float32)
    got = sa.sparse_flash_attention(q, k, v, bits, sinks, 0.125)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(sa.sparse_flash_reference(q, k, v, mask, sinks, 0.125)),
        atol=1e-5, rtol=0)


def test_selection_bits_round_trip():
    rng = np.random.default_rng(5)
    mask = jnp.asarray(rng.random((512, 96)) < 0.3)
    for block in (256, 512):
        bits = sa.selection_bits(mask, block)
        assert bits.shape == (16, 96) and bits.dtype == jnp.int32
        assert (sa.bits_mask(bits, block) == mask).all()
    with pytest.raises(ValueError, match="query block"):
        sa.query_block(768)
