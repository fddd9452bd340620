"""Real-TPU validation of the Pallas kernels.

The CPU suite exercises the same kernels through the pallas interpreter
(tests/test_attention.py, tests/test_serving.py); these tests compile the
real Mosaic kernels and therefore ONLY run when a TPU backend is present
(conftest.py forces the cpu platform for the rest of the suite, so this
module must be run explicitly, as the one chip-owning process:

    STOKE_TEST_TPU=1 python -m pytest tests/test_flash_tpu.py -q

The flash cases validate against `dense_reference` and the tolerances of
stoke_tpu/ops/flash_attention.py; the GPT-large-geometry cases at the
bottom are `chip_smoke.py`'s own kernel-leg checks.
"""

import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="requires a real TPU backend"
)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("masked", [False, True])
def test_flash_matches_dense_on_tpu(causal, masked):
    from stoke_tpu.ops.flash_attention import (
        BWD_RTOL_BF16,
        FWD_ATOL_BF16,
        dense_reference,
        flash_attention,
    )

    r = np.random.default_rng(0)
    B, H, L, D = 2, 4, 512, 64
    mk = lambda: jnp.asarray(r.normal(size=(B, H, L, D)).astype(np.float32), jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    mask = jnp.asarray((r.random(size=(B, L)) > 0.2).astype(np.int32)) if masked else None

    out = flash_attention(q, k, v, mask, causal=causal, interpret=False)
    ref = dense_reference(q, k, v, mask, causal=causal)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))) < FWD_ATOL_BF16

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, mask, causal=causal, interpret=False).astype(jnp.float32) ** 2
        )

    def loss_dense(q, k, v):
        return jnp.sum(dense_reference(q, k, v, mask, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    gscale = max(float(jnp.max(jnp.abs(b.astype(jnp.float32)))) for b in gd)
    gerr = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(gf, gd)
    )
    assert gerr < BWD_RTOL_BF16 * max(gscale, 1.0)


# ---- kernels added since round 2: first on-silicon validation ------------- #
# (CPU-interpret equivalence is necessary, not sufficient: block-spec/VMEM
# behavior differs on real Mosaic.)  On one chip the
# ring degenerates to a single hop; the composition under test is the
# per-hop flash call + lse merge wiring, which is exactly what changed.


def _mesh_1chip():
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "seq"))


def _qkv(r, B=2, H=4, L=512, D=64):
    mk = lambda: jnp.asarray(
        r.normal(size=(B, H, L, D)).astype(np.float32), jnp.bfloat16
    )
    return mk(), mk(), mk()


def _grad_close(loss_a, loss_b, args_, rtol):
    ga = jax.grad(loss_a, argnums=tuple(range(len(args_))))(*args_)
    gb = jax.grad(loss_b, argnums=tuple(range(len(args_))))(*args_)
    gscale = max(float(jnp.max(jnp.abs(b.astype(jnp.float32)))) for b in gb)
    gerr = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(ga, gb)
    )
    assert gerr < rtol * max(gscale, 1.0), (gerr, gscale)


def test_ring_flash_inner_matches_dense_on_tpu():
    from stoke_tpu.ops import ring_attention
    from stoke_tpu.ops.flash_attention import (
        BWD_RTOL_BF16,
        FWD_ATOL_BF16,
        dense_reference,
    )

    mesh = _mesh_1chip()
    q, k, v = _qkv(np.random.default_rng(1))

    def ring(q, k, v):
        return ring_attention(
            q, k, v, mesh=mesh, axis_name="seq", causal=True, inner="flash"
        )

    out = ring(q, k, v)
    ref = dense_reference(q, k, v, None, causal=True)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))) < FWD_ATOL_BF16

    _grad_close(
        lambda q, k, v: jnp.sum(ring(q, k, v).astype(jnp.float32) ** 2),
        lambda q, k, v: jnp.sum(dense_reference(q, k, v, None, causal=True) ** 2),
        (q, k, v),
        BWD_RTOL_BF16,
    )


def test_zigzag_ring_matches_dense_on_tpu():
    from stoke_tpu.ops import zigzag_ring_attention
    from stoke_tpu.ops.flash_attention import (
        BWD_RTOL_BF16,
        FWD_ATOL_BF16,
        dense_reference,
    )

    # one chip: the zigzag layout is the identity permutation (device 0
    # holds both blocks), so outputs compare directly against dense causal
    mesh = _mesh_1chip()
    q, k, v = _qkv(np.random.default_rng(2))

    def zz(q, k, v):
        return zigzag_ring_attention(q, k, v, mesh=mesh, axis_name="seq")

    out = zz(q, k, v)
    ref = dense_reference(q, k, v, None, causal=True)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))) < FWD_ATOL_BF16

    _grad_close(
        lambda q, k, v: jnp.sum(zz(q, k, v).astype(jnp.float32) ** 2),
        lambda q, k, v: jnp.sum(dense_reference(q, k, v, None, causal=True) ** 2),
        (q, k, v),
        BWD_RTOL_BF16,
    )


def test_chunked_ce_matches_full_logits_on_tpu():
    import optax

    from stoke_tpu.ops import chunked_softmax_cross_entropy

    r = np.random.default_rng(3)
    B, L, H, V = 2, 512, 64, 1024
    hidden = jnp.asarray(r.normal(size=(B, L, H)).astype(np.float32))
    emb = jnp.asarray(r.normal(size=(V, H)).astype(np.float32) * 0.05)
    targets = jnp.asarray(r.integers(0, V, size=(B, L)).astype(np.int32))
    mask = jnp.asarray((r.random(size=(B, L)) > 0.1).astype(np.int32))

    def full(hidden, emb):
        logits = jnp.einsum("blh,vh->blv", hidden, emb)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        m = mask.astype(jnp.float32)
        return jnp.sum(ce * m) / jnp.sum(m)

    def chunked(hidden, emb):
        return chunked_softmax_cross_entropy(
            hidden, emb, targets, chunk=128, mask=mask
        )

    a = jax.jit(chunked)(hidden, emb)
    b = jax.jit(full)(hidden, emb)
    np.testing.assert_allclose(float(a), float(b), rtol=1e-5)
    _grad_close(chunked, full, (hidden, emb), 1e-4)


# ---- chip_smoke.py's kernel leg, case by case (PR 21) --------------------- #
# GPT-large head geometry: 16 heads of 64, 16-token pages.

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
import chip_smoke  # noqa: E402


@pytest.mark.parametrize("seq_len", [1024, 96])
def test_flash_at_serve_and_train_lengths_on_tpu(seq_len):
    """The training length, and one short serve bucket that is not a
    multiple of 128 (a single whole-bucket block)."""
    chip_smoke.check_flash_parity(
        heads=16, head_dim=64, seq_len=seq_len, interpret=False
    )


def test_flash_key_masked_bucket_above_512_on_tpu():
    """A 640-token prefill bucket: blocked path, key mask on the lane axis
    (the case a 64-wide key block made illegal for Mosaic)."""
    from stoke_tpu.ops.flash_attention import (
        FWD_ATOL_BF16,
        dense_reference,
        flash_attention,
    )

    r = np.random.default_rng(4)
    q, k, v = _qkv(r, B=2, H=16, L=640, D=64)
    lengths = np.array([520, 640])
    mask = jnp.asarray(
        (np.arange(640)[None, :] < lengths[:, None]).astype(np.int32)
    )
    out = flash_attention(q, k, v, mask, causal=True, interpret=False)
    ref = dense_reference(q, k, v, mask, causal=True)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))) < FWD_ATOL_BF16


@pytest.mark.parametrize("pool_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_q", [1, 5], ids=["decode", "verify"])
def test_paged_kernels_compile_and_match_on_tpu(pool_dtype, n_q):
    chip_smoke.check_paged_parity(
        heads=16, head_dim=64, block_size=16,
        pool_dtype=jnp.dtype(pool_dtype), n_q=n_q, interpret=False,
    )


def test_sparse_latent_attention_at_the_long_document_decode_shape_on_tpu():
    """`sparse_latent_attention` compiled by Mosaic at the long-document
    serve cell's decode shape: 24 slots of 64 heads, up to 2,048 chosen
    384-word rows of one layer of a `[5, 4000, 16, 1, 384]` plane, with one
    slot of 700 rows, one of 1 and an empty one. Same tolerance as the
    interpreter's bfloat16 case (tests/test_sparse_attention.py)."""
    from stoke_tpu.ops import sparse_attention as sa

    B, H, L, NB, BS, W, K = 24, 64, 5, 4000, 16, 384, 2048
    counts = [K] * 21 + [700, 1, 0]
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    values = jax.random.normal(k[0], (L * NB * BS, 2 * W), jnp.bfloat16)
    words = sa.pack_rows(values, W, jnp.bfloat16).reshape(L, NB, BS, 1, W)
    count = jnp.asarray(counts, jnp.int32)
    addr = jax.random.randint(k[1], (B, K), BS, NB * BS, jnp.int32)
    addr = jnp.where(jnp.arange(K)[None] < count[:, None], addr, 0)
    q = jax.random.normal(k[2], (B, H, 2 * W), jnp.bfloat16)
    sinks = jax.random.normal(k[3], (H,), jnp.float32)
    out, issued = sa.sparse_latent_attention(q, words, 1, addr, count, sinks,
                                             0.07, interpret=False)
    want = sa.sparse_latent_reference(q, words, 1, addr, count, sinks, 0.07)
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - want.astype(jnp.float32)))
    assert float(err) < 2e-2
    # the kernel's own count: whole steps of rows a slot, none when empty
    step = sa.SPARSE_ROWS_PER_STEP
    assert issued.tolist() == [-(-c // step) * step * W for c in counts]
    # addresses outside the layer read its last row: the kernel runs
    # without Mosaic's bounds checks and clamps them itself (these lie in
    # the neighbouring layers, so a missing clamp fails here, not the chip)
    rows = NB * BS
    addr = addr.at[21, :3].set(jnp.asarray([rows, rows + 4000, -1]))
    out, _ = sa.sparse_latent_attention(q, words, 1, addr, count, sinks, 0.07,
                                        interpret=False)
    inside = jnp.where((addr < 0) | (addr >= rows), rows - 1, addr)
    want = sa.sparse_latent_reference(q, words, 1, inside, count, sinks, 0.07)
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - want.astype(jnp.float32)))
    assert float(err) < 2e-2


@pytest.mark.skipif(
    jax.device_count() < 2, reason="needs several chips"
)
def test_flash_partitions_itself_under_a_mesh_on_tpu():
    """A Mosaic kernel inside a multi-device jit must shard_map itself:
    batch-sharded inputs, no gather, same values as one device."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from stoke_tpu.ops.flash_attention import (
        flash_attention,
        partition_kernels_over,
    )

    n = jax.device_count()
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    sharded = NamedSharding(mesh, P("data"))
    q, k, v = _qkv(np.random.default_rng(5), B=2 * n, H=4, L=256, D=64)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    @jax.jit
    def under_mesh(q, k, v):
        with partition_kernels_over(mesh, ("data",)):
            return flash(q, k, v)

    out = under_mesh(*(jax.device_put(t, sharded) for t in (q, k, v)))
    assert out.sharding.spec == P("data")
    np.testing.assert_array_equal(
        np.asarray(out.astype(jnp.float32)),
        np.asarray(flash(q, k, v).astype(jnp.float32)),
    )

