"""Shared by test_serving.py and test_speculative.py (ISSUE 27): the paged
attention formulation the flat page pool replaced, as an independent
reference, and one ragged flat-pool case."""

import jax
import jax.numpy as jnp
import numpy as np

from stoke_tpu.serving import SCRATCH_BLOCK


def old_paged_attention(q, k_pages, v_pages, tables, positions):
    """The ``[NB, BS, H, D]`` formulation the flat pool replaced, kept here
    as the independent reference: one layer's pages, ``jnp.take`` of the
    tables, the window reshaped to heads, two einsums and the fp32 masked
    softmax (decode is its one-row case at ``positions = context - 1``)."""
    B, H, S, D = q.shape
    k = jnp.take(k_pages, tables, axis=0).reshape(B, -1, H, D)
    v = jnp.take(v_pages, tables, axis=0).reshape(B, -1, H, D)
    s = jnp.einsum(
        "bhqd,bwhd->bhqw", q.astype(jnp.float32), k.astype(jnp.float32)
    ) / (D**0.5)
    w_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
    valid = w_pos[None, None, :] <= positions[:, :, None]
    s = jnp.where(valid[:, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqw,bwhd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def flat_pool_case(H, D, dtype, S, seed=0):
    """A 2-layer flat pool, ragged tables (slot 0: 3 blocks with a ragged
    tail, slot 1: all 4, slot 2: one token; unused entries point at the
    scratch block) and S query rows ending at each slot's last position."""
    r = np.random.default_rng(seed)
    n_layers, NB, BS, B, MB = 2, 9, 8, 3, 4
    k_pool, v_pool = (
        jnp.asarray(
            r.normal(size=(n_layers, NB, BS, H * D)).astype(np.float32)
        ).astype(dtype)
        for _ in range(2)
    )
    tables = np.full((B, MB), SCRATCH_BLOCK, np.int32)
    tables[0, :3] = [1, 2, 3]
    tables[1, :4] = [4, 5, 6, 7]
    tables[2, :1] = [8]
    ctx = np.array([19, 32, S], np.int32)
    positions = ctx[:, None] - S + np.arange(S, dtype=np.int32)[None, :]
    q = jnp.asarray(r.normal(size=(B, H, S, D)).astype(np.float32))
    return q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(positions)
