"""Grouped matrix product for TPU (Pallas): ``[M, K] x [G, K, N] -> [M, N]``
where consecutive runs of rows, ``group_sizes[g]`` long, each meet their own
``[K, N]`` weight: the held experts' products of
:class:`stoke_tpu.models.moe.ExpertShareFFN`.

The shape is megablox ``gmm``'s (``jax.experimental.pallas.ops.tpu``): the
row tiles and the groups that touch them are listed as (tile, group) visits
ahead of the kernel, the lists ride as scalar-prefetch arguments, the grid
runs over the live visits only (its bound is a traced value) and rows of no
group are never visited.  It differs where a serving step's cost lies, in
the weights: ``K`` is never tiled, so a group's ``[K, tn]`` weight tile
stays in VMEM while the grid walks that group's row tiles (Pallas fetches a
block again only when its index changes) and every weight streams from HBM
ONCE a call however the groups straddle the row tiles; the weights enter as
stored, cast a tile at a time; and the gate and up products of a SwiGLU can
share one walk over the rows (:func:`grouped_swiglu`).  Tiles follow from
the shapes (:func:`tiling`).  Off the TPU the kernel runs through the
Pallas interpreter, as the attention kernels do."""

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from stoke_tpu.ops.flash_attention import _current_partition, _partition_rows

#: bytes of weight tiles a call keeps in flight: each weight's ``[K, tn]``
#: tile twice (the pipeline's two buffers).  Swept on the v5e (PERF.md
#: section 6, PR 32).
_WEIGHT_TILES_BYTES = 8 * 1024 * 1024

#: rows a tile: the MXU's height.  Fewer rows cost a visit the same (the
#: weight tile is loaded into the array either way), more make a straddled
#: tile dearer.
_ROW_TILE = 128


def tiling(m: int, k: int, n: int, itemsize: int, weights: int = 1):
    """``(tm, tn)``, the row and column tile of a call: ``tm`` the MXU's
    128 rows (all of ``m`` where that is fewer), ``tn`` the widest multiple
    of 128 dividing ``n`` whose ``[k, tn]`` tiles, two a weight, fit the
    budget (``n`` whole where 128 does not divide it)."""
    tm = min(m, _ROW_TILE)
    if n % 128:
        return tm, n
    fit = _WEIGHT_TILES_BYTES // (2 * weights * k * itemsize)
    return tm, max(
        (d for d in range(128, n + 1, 128) if n % d == 0 and d <= fit),
        default=128,
    )


def _visits(group_sizes, m: int, tm: int):
    """The (row tile, group) pairs a call computes, in grid order: by group,
    a group's tiles ascending, so that a tile two groups share is visited
    twice in a row and its output block stays in VMEM between them.  Returns
    ``(offsets [G + 1], group_ids [V], tile_ids [V], n_visits)`` where ``V``
    is the static bound ``ceil(m / tm) + G - 1``; entries past ``n_visits``
    repeat the last group and are never run."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    first = offsets[:-1] // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(tiles)])
    v = jnp.arange(pl.cdiv(m, tm) + G - 1, dtype=jnp.int32)
    group_ids = jnp.minimum(
        (starts[1:, None] <= v[None, :]).sum(axis=0, dtype=jnp.int32), G - 1
    )
    tile_ids = first[group_ids] + v - starts[group_ids]
    return offsets, group_ids, tile_ids, starts[G]


def _kernel(offsets_ref, group_ids_ref, tile_ids_ref, lhs_ref, *refs, tm,
            swiglu, limit=None):
    """One visit: the row tile times the group's resident weight tile (two,
    gate and up, for a SwiGLU, the gate at most ``limit`` and the up
    product within it where there is one), stored over the group's own rows
    only; the tile's other rows keep what their groups' visits wrote."""
    *rhs_refs, out_ref = refs
    v = pl.program_id(1)
    g = group_ids_ref[v]
    x = lhs_ref[...]
    y = [
        jax.lax.dot_general(
            x, w[...].astype(x.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        for w in rhs_refs
    ]
    if swiglu and limit:
        y = jax.nn.silu(jnp.minimum(y[0], limit)) * jnp.clip(y[1], -limit,
                                                             limit)
    else:
        y = jax.nn.silu(y[0]) * y[1] if swiglu else y[0]
    row = tile_ids_ref[v] * tm + jax.lax.broadcasted_iota(
        jnp.int32, y.shape, 0)
    mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
    out_ref[...] = jnp.where(mine, y.astype(out_ref.dtype), out_ref[...])


@functools.partial(
    jax.jit, static_argnames=("out_dtype", "name", "interpret", "part",
                              "limit")
)
def _grouped(lhs, rhs: Sequence, group_sizes, out_dtype, name: str,
             interpret: bool, part, limit=None):
    m, k = lhs.shape
    G, _, n = rhs[0].shape
    for w in rhs:
        if w.shape != (G, k, n):
            raise ValueError(
                f"{name}: weights must be [G, K={k}, N], got "
                f"{[tuple(w.shape) for w in rhs]} for rows {lhs.shape}"
            )
    if group_sizes.shape != (G,):
        raise ValueError(
            f"{name}: group_sizes must be [G={G}], got {group_sizes.shape}"
        )
    itemsize = rhs[0].dtype.itemsize
    tm, tn = tiling(m, k, n, itemsize, len(rhs))
    offsets, group_ids, tile_ids, n_visits = _visits(
        group_sizes.astype(jnp.int32), m, tm
    )
    weight = pl.BlockSpec(
        (None, k, tn), lambda j, v, off, gid, tid: (gid[v], 0, j)
    )
    call = pl.pallas_call(
        functools.partial(_kernel, tm=tm, swiglu=len(rhs) == 2,
                          limit=limit),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # offsets, the visits' groups and tiles
            grid=(n // tn, n_visits),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, off, gid, tid: (tid[v], 0)),
                *[weight] * len(rhs),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, v, off, gid, tid: (tid[v], j)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # the weight tiles, the rows' and the output's two buffers each,
            # and room for the products before they are stored
            vmem_limit_bytes=int(
                2 * len(rhs) * k * tn * itemsize
                + 2 * tm * k * lhs.dtype.itemsize
                + (2 + 2 * len(rhs)) * tm * tn * 4
                + 8 * 1024 * 1024
            ),
        ),
        interpret=interpret,
        name=name,
    )
    return _partition_rows(call, part, m)(
        offsets, group_ids, tile_ids, lhs, *rhs
    )


def _where(interpret: Optional[bool]):
    """What of the trace around a call decides its program: the interpreter
    off the TPU, and under a mesh the ``shard_map`` in which every device
    multiplies all rows with its own replica of the weights (as the paged
    kernels walk the whole slot batch)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    part = _current_partition()
    return interpret, None if part is None else (part[0], ())


def grouped_matmul(lhs, rhs, group_sizes, *,
                   interpret: Optional[bool] = None):
    """``out[r] = lhs[r] @ rhs[g]`` for the rows ``r`` of group ``g``, the
    groups being consecutive runs of ``group_sizes[g]`` rows from row 0
    (what ``jax.lax.ragged_dot(lhs, rhs, group_sizes,
    preferred_element_type=float32)`` gives, to rounding).

    Args:
        lhs: ``[M, K]`` rows sorted by group, in the compute dtype.
        rhs: ``[G, K, N]`` weights as stored; a tile is cast to ``lhs``'s
            dtype inside the kernel, nothing is copied ahead of it.
        group_sizes: ``[G]`` integers, summing to at most ``M``.
        interpret: run through the pallas interpreter (``None`` = auto
            off-TPU, as :func:`stoke_tpu.ops.flash_attention`).

    Returns ``[M, N]`` float32 (accumulated in float32).  Rows past the
    last group belong to none: they are neither read nor written, and hold
    whatever the buffer held.
    """
    return _grouped(lhs, (rhs,), group_sizes, jnp.float32, "grouped_matmul",
                    *_where(interpret))


def grouped_swiglu(lhs, w_gate, w_up, group_sizes, *,
                   interpret: Optional[bool] = None,
                   limit: Optional[float] = None):
    """``silu(lhs @ w_gate[g]) * (lhs @ w_up[g])`` over the same groups as
    :func:`grouped_matmul`, both products in one walk over the rows:
    float32 products, the result written once, in ``lhs``'s dtype.  With a
    ``limit``: ``silu(min(gate, limit)) * clip(up, -limit, limit)``."""
    return _grouped(lhs, (w_gate, w_up), group_sizes, lhs.dtype,
                    "grouped_swiglu", *_where(interpret),
                    float(limit) if limit else None)


def expert_weight_passes(counts) -> float:
    """Bytes of weight the grouped products of a step read over the bytes
    the weights hold: 1.0 is every group's weight streamed once.

    ``counts`` is ``[..., G]`` host integers, the group sizes of each call
    site (an expert layer's products share one row).  A group's weight tile
    stays resident while the grid walks that group's row tiles, so a call
    fetches it once a column tile where the group has rows and never where
    it has none, however :func:`tiling` cuts rows and columns: with ``K``
    tiled this would count (row tile, group) visits instead."""
    counts = np.asarray(counts)
    return float((counts > 0).mean()) if counts.size else 0.0
