"""The grouped product of ``stoke_tpu/ops/grouped_matmul.py`` alone, through
the Pallas interpreter on the CPU, against a plain per-group loop in float32
``jax.numpy``; and its pure counter ``expert_weight_passes`` against a count
over the kernel's own (row tile, group) visits."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from stoke_tpu.ops import grouped_matmul as gm  # noqa: E402

H, FF = 256, 128  # both orientations below: [H, ff] and [ff, H]


def _loop(lhs, rhs, sizes):
    """``lhs[rows of g] @ rhs[g]``, group by group, float32."""
    lhs, rhs = (jnp.asarray(a, jnp.float32) for a in (lhs, rhs))
    out, at = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32), 0
    with jax.default_matmul_precision("highest"):
        for g, n in enumerate(sizes):
            out[at:at + n] = lhs[at:at + n] @ rhs[g]
            at += n
    return out, at


# rows, K, N, group sizes (their sum may fall short of the rows: the rest
# belong to no group)
CASES = {
    "gate_even": (64, H, FF, [8] * 8),
    "down_even": (64, FF, H, [8] * 8),
    "rows_not_a_tile_multiple": (300, H, FF, [130, 41, 100, 29]),
    "empty_groups": (300, FF, H, [0, 130, 0, 0, 100, 0]),
    "one_group_owns_every_row": (264, H, FF, [0, 264, 0]),
    "no_row_held": (160, FF, H, [0, 0, 0, 0]),
    "groups_straddle_tiles": (512, H, FF, [100, 60, 200, 30, 90]),
    "one_row_groups_past_a_tile_edge": (200, FF, H, [127, 1, 1, 3]),
    "fewer_rows_than_a_tile": (24, H, FF, [5, 0, 9, 2]),
    "width_not_128s": (40, 48, 24, [3, 0, 10, 7]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_grouped_products_equal_the_per_group_loop(case, dtype):
    m, k, n, sizes = CASES[case]
    rng = np.random.default_rng(len(case))
    lhs = jnp.asarray(rng.standard_normal((m, k)), dtype)
    w_a, w_b = (
        jnp.asarray(rng.standard_normal((len(sizes), k, n)) / k ** 0.5, dtype)
        for _ in range(2)
    )
    held = sum(sizes)
    # what a row of no group holds must reach no group's result
    lhs = lhs.at[held:].set(jnp.nan)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    want_a, _ = _loop(lhs, w_a, sizes)
    want_b, _ = _loop(lhs, w_b, sizes)
    exact = dtype == jnp.float32

    got = gm.grouped_matmul(lhs, w_a, group_sizes)
    assert got.shape == (m, n) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got)[:held], want_a[:held],
        atol=2e-5 if exact else 1e-5, rtol=0 if exact else 1e-5,
    )  # bfloat16 inputs multiply exactly into float32 sums

    got = gm.grouped_swiglu(lhs, w_a, w_b, group_sizes)
    assert got.shape == (m, n) and got.dtype == dtype
    want = np.asarray(jax.nn.silu(want_a) * want_b)
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[:held], want[:held],
        atol=2e-5 if exact else 1e-5, rtol=0 if exact else 2.0 ** -8,
    )  # written once in the compute dtype: one rounding of the result


def test_weights_are_cast_a_tile_at_a_time_not_ahead():
    """float32 weights under bfloat16 rows: the kernel takes the parameter
    as stored and gives what casting it first would."""
    m, k, n, sizes = CASES["groups_straddle_tiles"]
    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((len(sizes), k, n)) / k ** 0.5,
                    jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    held = sum(sizes)
    got = gm.grouped_matmul(lhs, w, group_sizes)
    want = gm.grouped_matmul(lhs, w.astype(jnp.bfloat16), group_sizes)
    np.testing.assert_array_equal(np.asarray(got)[:held],
                                  np.asarray(want)[:held])


def test_mismatched_shapes_are_refused():
    lhs = jnp.zeros((16, 32))
    with pytest.raises(ValueError, match="weights must be"):
        gm.grouped_matmul(lhs, jnp.zeros((2, 16, 8)), jnp.zeros(2, jnp.int32))
    with pytest.raises(ValueError, match="group_sizes must be"):
        gm.grouped_matmul(lhs, jnp.zeros((2, 32, 8)), jnp.zeros(3, jnp.int32))


@pytest.mark.parametrize("m,k,n,itemsize,weights,want", [
    # the cell's three products: K whole, the widest 128s that fit 8 MiB
    (1536, 7168, 2048, 2, 2, (128, 128)),
    (1536, 2048, 7168, 2, 1, (128, 1024)),
    (16384, 7168, 2048, 2, 2, (128, 128)),
    # fewer rows than the MXU's height: all of them; no 128s: N whole
    (24, 128, 64, 4, 1, (24, 64)),
    # a K too long for any 128s to fit still takes 128 (and says so to the
    # compiler's limit), never a cut of K
    (256, 1 << 16, 512, 4, 2, (128, 128)),
])
def test_tiles_follow_from_the_shapes(m, k, n, itemsize, weights, want):
    assert gm.tiling(m, k, n, itemsize, weights) == want


def _fetched_over_held(sizes, m, k, n, itemsize, weights):
    """Walk the grid as the kernel is given it (column tiles outer, the
    visits of ``_visits`` inner) and count a fetch of a ``[k, tn]`` weight
    tile whenever the block index differs from the step before: bytes
    fetched over the bytes the weights hold."""
    tm, tn = gm.tiling(m, k, n, itemsize, weights)
    _, group_ids, tile_ids, n_visits = (
        np.asarray(a) for a in gm._visits(jnp.asarray(sizes, jnp.int32), m, tm)
    )
    # the visits are the (tile, group) pairs that share a row, each once
    starts = np.concatenate([[0], np.cumsum(sizes)])
    pairs = {(r // tm, g) for g in range(len(sizes))
             for r in range(starts[g], starts[g + 1])}
    assert pairs == set(zip(tile_ids[:n_visits].tolist(),
                            group_ids[:n_visits].tolist()))
    assert n_visits == len(pairs)
    fetched, last = 0, None
    for j in range(n // tn):
        for v in range(n_visits):
            if (group_ids[v], j) != last:
                fetched += k * tn * itemsize
                last = (group_ids[v], j)
    return fetched / (len(sizes) * k * n * itemsize)


@pytest.mark.parametrize("sizes,m", [
    ([8] * 12, 1536),                                  # the cell's decode step
    ([40, 1, 0, 20, 8, 3, 12, 2, 6, 0, 3, 1], 1536),   # two experts idle
    ([85, 90, 70, 101, 77, 88, 95, 60, 85, 92, 81, 100], 16384),  # straddling
    ([2048, 900, 10, 101, 0, 88, 300, 60, 85, 92, 81, 100], 16384),
    ([0] * 12, 1536),
])
def test_weight_passes_equal_a_count_over_the_visits(sizes, m):
    want = gm.expert_weight_passes(np.asarray(sizes))
    assert want == pytest.approx(np.count_nonzero(sizes) / len(sizes))
    for k, n, weights in ((256, 512, 2), (512, 256, 1)):
        assert _fetched_over_held(sizes, m, k, n, 2, weights) == (
            pytest.approx(want))
    # a step's layers together: the mean over the calls
    both = np.stack([np.asarray(sizes), np.full(len(sizes), 8)])
    assert gm.expert_weight_passes(both) == pytest.approx((want + 1.0) / 2)
