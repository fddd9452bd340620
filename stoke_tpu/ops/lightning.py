"""Lightning attention (arXiv:2401.04658, as MiniMax-01 serves it): linear
attention with one scalar decay a head, its ``[dk, dv]`` float32 state per
slot and head.  Per head, with ``lam`` the head's decay and ``q`` already
scaled::

    S_t = lam S_{t-1} + k_t v_t^T        o_t = q_t S_t

No normaliser over ``q . k``, no delta rule.  Two forms:

- :func:`lightning_step` (decode): a Pallas kernel that reads every slot's
  and head's state once, updates it in VMEM and writes it back over itself
  (aliased in to out, so a ``jit`` that donates the state updates it in
  place), as ``ops/delta_rule.py`` does for the delta rule.  ``k`` and ``q``
  index the state's rows, so they enter turned, ``[dk, 2 hb]`` a block of
  ``hb`` heads; ``v`` indexes its lanes.  :func:`lightning_reference` is
  its plain form;
- :func:`lightning_chunked` (prefill): the same recurrence over a whole
  prompt from zero state, ``LIGHTNING_CHUNK`` positions a step of a scan,
  in float32 at ``Precision.HIGHEST``; a decay a position (its log), so a
  caller masks padding with a log decay 0 and a zero key.
"""

from __future__ import annotations

import functools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from stoke_tpu.ops.delta_rule import head_block
from stoke_tpu.ops.flash_attention import _partition_rows
from stoke_tpu.ops.grouped_matmul import _where

#: positions a step of :func:`lightning_chunked` takes
LIGHTNING_CHUNK = 128

_HIGHEST = jax.lax.Precision.HIGHEST


def alibi_decay(heads: int, layer: int, layers: int):
    """``float32[heads]``: MiniMax-01's decay of head ``h`` in layer
    ``layer`` of ``layers``, ``exp(-m_h (1 - layer / (layers - 1) + 1e-5))``
    with ``m_h = 2^(-8 (h + 1) / heads)`` (ALiBi's slopes)."""
    h = jnp.arange(heads, dtype=jnp.float32)
    slope = 2.0 ** (-8.0 * (h + 1) / heads)
    return jnp.exp(-slope * (1.0 - layer / max(layers - 1, 1) + 1e-5))


def lightning_reference(state, q, k, v, decay):
    """:func:`lightning_step` as plain ``jax.numpy``: ``state [B, H, dk,
    dv]`` float32, ``q``, ``k [B, H, dk]``, ``v [B, H, dv]``, ``decay
    [H]``.  Returns ``(o [B, H, dv], new state)``, float32."""
    f32 = partial(jnp.asarray, dtype=jnp.float32)
    new = (state * f32(decay)[None, :, None, None]
           + f32(k)[..., :, None] * f32(v)[..., None, :])
    return (new * f32(q)[..., :, None]).sum(axis=2), new


def _kernel(cols_ref, v_ref, lam_ref, s_ref, o_ref, s_out_ref, *, hb):
    """A slot's block of ``hb`` heads: ``cols_ref [dk, 2 hb]`` holds head
    ``h``'s ``k`` and ``q`` as the columns ``h`` and ``hb + h``; ``v_ref [hb,
    dv]``; ``lam_ref [hb, dv]`` a head's decay along its row (a ``[1, 1]``
    value would be broadcast over sublanes and lanes at once, which Mosaic
    does not lower)."""
    for h in range(hb):
        k = cols_ref[:, h:h + 1]
        q = cols_ref[:, hb + h:hb + h + 1]
        new = s_ref[h] * lam_ref[h:h + 1, :] + k * v_ref[h:h + 1, :]
        o_ref[h:h + 1, :] = (new * q).sum(axis=0, keepdims=True)
        s_out_ref[h] = new


@functools.partial(jax.jit, static_argnames=("interpret", "part"))
def _step(state, q, k, v, decay, interpret: bool, part):
    B, H, dk, dv = state.shape
    for name, t, shape in (("q", q, (B, H, dk)), ("k", k, (B, H, dk)),
                           ("v", v, (B, H, dv)), ("decay", decay, (H,))):
        if t.shape != shape:
            raise ValueError(
                f"lightning_step: {name} must be {shape} for a state "
                f"{state.shape}, got {t.shape}")
    if state.dtype != jnp.float32:
        raise ValueError(
            f"lightning_step: the state is float32, got {state.dtype}")
    hb = head_block(H, dk, dv)
    nb = H // hb
    q, k, v = (jnp.asarray(t, jnp.float32) for t in (q, k, v))
    # [B, nb, dk, 2 hb]: what indexes the state's rows, rows in the sublanes
    cols = jnp.stack([k, q], axis=1).reshape(B, 2, nb, hb, dk)
    cols = cols.transpose(0, 2, 4, 1, 3).reshape(B, nb, dk, 2 * hb)
    lam = jnp.broadcast_to(jnp.asarray(decay, jnp.float32)[:, None],
                           (H, dv)).reshape(nb, hb, dv)

    def per_block(*tail):
        return pl.BlockSpec((None, None) + tail,
                            lambda b, j: (b, j) + (0,) * len(tail))

    call = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid=(B, nb),
        in_specs=[per_block(dk, 2 * hb), per_block(hb, dv),
                  pl.BlockSpec((None, hb, dv), lambda b, j: (j, 0, 0)),
                  per_block(hb, dk, dv)],
        out_specs=[per_block(hb, dv), per_block(hb, dk, dv)],
        out_shape=[jax.ShapeDtypeStruct((B, nb, hb, dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, nb, hb, dk, dv), jnp.float32)],
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # the state's four buffers, and room for the small operands'
            vmem_limit_bytes=4 * hb * dk * dv * 4 + 16 * 1024 * 1024,
        ),
        interpret=interpret,
        name="lightning_step",
    )

    def run(cols, v, state):
        return call(cols, v, lam, state)

    o, new = _partition_rows(run, part, B)(
        cols, v.reshape(B, nb, hb, dv), state.reshape(B, nb, hb, dk, dv))
    return o.reshape(B, H, dv), new.reshape(B, H, dk, dv)


def lightning_step(state, q, k, v, decay, *,
                   interpret: Optional[bool] = None):
    """One position of lightning attention, every slot and head at once, in
    float32 (the module's docstring has the equations).

    Args:
        state: ``[B, H, dk, dv]`` float32, aliased to the new state: donate
            it (or let it die) and the update is in place.
        q, k: ``[B, H, dk]`` (``q`` already scaled); v: ``[B, H, dv]``.
        decay: ``[H]``, each head's ``lam``.
        interpret: the Pallas interpreter (``None``: off the TPU).

    Returns ``(o [B, H, dv], S_new [B, H, dk, dv])``.  Every slot is walked,
    live or idle (``ops/delta_rule.py`` ``state_passes``)."""
    return _step(state, q, k, v, decay, *_where(interpret))


def lightning_chunked(q, k, v, decay, scale: float = 1.0, lengths=None,
                      rotate=None):
    """The recurrence over ``L`` positions of each sequence from zero state:
    ``q``, ``k [B, L, H, dk]``, ``v [B, L, H, dv]`` in any dtype, ``decay
    [H]`` each head's ``lam``, ``q`` times ``scale``; a position at or past
    ``lengths [B]`` (where given) leaves the state as it was; ``rotate(t,
    start)`` (where given) turns a chunk ``t [B, C, H, dk]`` of q or k, float32,
    whose first position is ``start``, before it is used.  Returns ``(o [B, L,
    H dv]`` float32, the heads side by side, ``state after the last
    position [B, H, dk, dv])``.

    A loop over chunks of ``LIGHTNING_CHUNK`` positions, each sliced out of
    the inputs, made float32 and turned inside the loop, its output written
    into place: no float32, turned or transposed copy of a whole prompt's q,
    k or v is made.  Within a chunk, with ``G_t`` the running sum of the log decay
    from the chunk's start and ``S_0`` the state before it: ``o_t = e^{G_t}
    q_t S_0 + sum_{j <= t} e^{G_t - G_j} (q_t . k_j) v_j`` and ``S_C =
    e^{G_C} S_0 + sum_j e^{G_C - G_j} k_j v_j^T``.  Every exponent taken is
    ``<= 0``."""
    B, L, H, dk = q.shape
    dv = v.shape[-1]
    C = min(LIGHTNING_CHUNK, L)
    pad = -L % C
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (q, k, v))
    n = (L + pad) // C
    if lengths is None:
        lengths = jnp.full((B,), L, jnp.int32)
    log_decay = jnp.log(jnp.asarray(decay, jnp.float32))  # [H]

    mm = partial(jnp.einsum, precision=_HIGHEST,
                 preferred_element_type=jnp.float32)
    lower = jnp.tril(jnp.ones((C, C), bool))

    def step(c, carry):
        S0, out = carry

        def chunk(t, turn=False):  # [B, C, H, d] -> float32 [B, H, C, d]
            t = jax.lax.dynamic_slice_in_dim(t, c * C, C, axis=1).astype(
                jnp.float32)
            if turn and rotate is not None:
                t = rotate(t, c * C)
            return jnp.swapaxes(t, 1, 2)

        live = (c * C + jnp.arange(C, dtype=jnp.int32))[None, :] < (
            lengths.astype(jnp.int32)[:, None])  # [B, C]
        qc = chunk(q, True) * scale
        kc = jnp.where(live[:, None, :, None], chunk(k, True), 0.0)
        vc = chunk(v)
        gc = jnp.where(live[:, None, :], log_decay[None, :, None], 0.0)
        G = jnp.cumsum(gc, axis=-1)  # [B, H, C]
        pair = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :],
                                 -jnp.inf))  # e^{G_t - G_j}, j <= t
        A = mm("bhtk,bhjk->bhtj", qc, kc) * pair
        o = (mm("bhtk,bhkv->bhtv", qc, S0) * jnp.exp(G)[..., None]
             + mm("bhtj,bhjv->bhtv", A, vc))
        to_end = jnp.exp(G[..., -1:] - G)  # e^{G_C - G_j}
        S1 = jnp.exp(G[..., -1])[..., None, None] * S0 + mm(
            "bhjk,bhjv->bhkv", kc * to_end[..., None], vc)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jnp.swapaxes(o, 1, 2).reshape(B, C, H * dv), c * C, axis=1)
        return S1, out

    state, out = jax.lax.fori_loop(
        0, n, step, (jnp.zeros((B, H, dk, dv), jnp.float32),
                     jnp.zeros((B, n * C, H * dv), jnp.float32)))
    return (out[:, :L] if pad else out), state
