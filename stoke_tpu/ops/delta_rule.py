"""One position of the gated delta rule for TPU (Pallas): every slot's and
head's ``[dk, dv]`` float32 state read from HBM once, updated while it is
in VMEM, and written back over itself: the decode step of
:class:`stoke_tpu.models.decoder.DeltaRuleAttention`.

Per slot and head, all in float32 (``g <= 0``, a value a key channel)::

    d      = S * exp(g)[:, None]          # decay a key channel
    u, p   = sum_k d * k[:, None],  sum_k d * q[:, None]
    w      = beta * (v - u)
    o      = p + (k . q) * w
    S_new  = d + k[:, None] * w[None, :]  # written over S

which is ``S' = diag(exp g) S``; ``S_new = S' + beta k (v - S'^T k)^T``;
``o = S_new^T q`` with ``S'^T q`` and ``S'^T k`` both taken of the decayed
state.  As plain ``jax.numpy`` the v5e's compiler makes three fusions of
it that read the state three times and write it once (PERF.md section 6,
PR 33); the kernel moves ``2 * state.nbytes`` a call and is bound by that
stream (it runs as long as a kernel that only copies the state: PERF.md
section 6, PR 34): the products and sums run on the vector unit (two rows
a head are no work for the matrix unit, and its passes no float32), the
reductions over the ``dk`` rows; the busiest unit is the one that
broadcasts the three columns along the lanes, at half the stream's time.

The grid walks the slots and, within a slot, blocks of ``hb`` heads
(:func:`head_block`).  ``exp(g)``, ``k`` and ``q`` index the state's rows:
they enter turned, ``[dk, 3 hb]`` a block with a head's three columns
``hb`` lanes apart, laid out ahead of the kernel in a few MB (a ``[..., dk,
1]`` operand would pad 128-fold in HBM); ``v``, ``beta`` and ``k . q``
index its lanes or a whole head.  The state is aliased in to out: under a
``jit`` that donates it the update is in place and the program holds no
second copy.  Off the TPU the kernel runs through the Pallas interpreter,
as the attention kernels do."""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from stoke_tpu.ops.flash_attention import _partition_rows
from stoke_tpu.ops.grouped_matmul import _where

#: bytes of state a grid step holds: ``hb`` heads of ``[dk, dv]`` float32,
#: four times in VMEM (the pipeline's two buffers, in and out).  On the v5e
#: 1, 2 and 4 MB run alike, at the rate of a kernel that only copies the
#: state, and 0.5 MB 12% slower (PERF.md section 6, PR 34).
_STATE_BLOCK_BYTES = 2 * 1024 * 1024


def head_block(heads: int, dk: int, dv: int) -> int:
    """``hb``, the heads of a slot a grid step takes: the most that divide
    ``heads`` and whose states fit :data:`_STATE_BLOCK_BYTES` (one where a
    single head's does not)."""
    fit = max(_STATE_BLOCK_BYTES // (dk * dv * 4), 1)
    return max(d for d in range(1, heads + 1) if heads % d == 0 and d <= fit)


def _kernel(cols_ref, v_ref, scal_ref, s_ref, o_ref, s_out_ref, *, hb):
    """A slot's block of ``hb`` heads: ``cols_ref [dk, 3 hb]`` holds head
    ``h``'s ``exp(g)``, ``k`` and ``q`` as the columns ``h``, ``hb + h``
    and ``2 hb + h``; ``v_ref [hb, dv]``; ``scal_ref [hb, 2]`` a head's
    ``beta`` and ``k . q``."""
    for h in range(hb):
        decay, k, q = (cols_ref[:, i * hb + h:i * hb + h + 1]
                       for i in range(3))  # [dk, 1] each
        d = s_ref[h] * decay
        u = (d * k).sum(axis=0, keepdims=True)  # [1, dv]
        p = (d * q).sum(axis=0, keepdims=True)
        w = scal_ref[h:h + 1, 0:1] * (v_ref[h:h + 1, :] - u)
        o_ref[h:h + 1, :] = p + scal_ref[h:h + 1, 1:2] * w
        s_out_ref[h] = d + k * w


@functools.partial(jax.jit, static_argnames=("interpret", "part"))
def _step(state, q, k, v, g, beta, interpret: bool, part):
    B, H, dk, dv = state.shape
    for name, t, shape in (("q", q, (B, H, dk)), ("k", k, (B, H, dk)),
                           ("g", g, (B, H, dk)), ("v", v, (B, H, dv)),
                           ("beta", beta, (B, H))):
        if t.shape != shape:
            raise ValueError(
                f"delta_rule_step: {name} must be {shape} for a state "
                f"{state.shape}, got {t.shape}"
            )
    if state.dtype != jnp.float32:
        raise ValueError(
            f"delta_rule_step: the state is float32, got {state.dtype}"
        )
    hb = head_block(H, dk, dv)
    nb = H // hb
    q, k, v, g, beta = (jnp.asarray(t, jnp.float32)
                        for t in (q, k, v, g, beta))
    # [B, nb, dk, 3 hb]: what indexes the state's rows, rows in the sublanes
    cols = jnp.stack([jnp.exp(g), k, q], axis=1).reshape(B, 3, nb, hb, dk)
    cols = cols.transpose(0, 2, 4, 1, 3).reshape(B, nb, dk, 3 * hb)
    scal = jnp.stack([beta, (k * q).sum(axis=-1)], axis=-1)

    def per_block(*tail):
        return pl.BlockSpec((None, None) + tail,
                            lambda b, j: (b, j) + (0,) * len(tail))

    call = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid=(B, nb),
        in_specs=[per_block(dk, 3 * hb), per_block(hb, dv), per_block(hb, 2),
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
        name="delta_rule_step",
    )
    o, new = _partition_rows(call, part, B)(
        cols, v.reshape(B, nb, hb, dv), scal.reshape(B, nb, hb, 2),
        state.reshape(B, nb, hb, dk, dv),
    )
    return o.reshape(B, H, dv), new.reshape(B, H, dk, dv)


def delta_rule_step(state, q, k, v, g, beta, *,
                    interpret: Optional[bool] = None):
    """One position of the gated delta rule, every slot and head at once,
    in float32 throughout (the module's docstring has the equations).

    Args:
        state: ``[B, H, dk, dv]`` float32, a slot's and head's state
            before the position.  Aliased to the new state: donate it (or
            let it die) and the update is in place.
        q, k, g: ``[B, H, dk]``; ``g`` the log of the decay, ``<= 0``.
        v: ``[B, H, dv]``.  beta: ``[B, H]``.
        interpret: run through the pallas interpreter (``None`` = auto
            off-TPU, as :func:`stoke_tpu.ops.flash_attention`).

    Returns ``(o [B, H, dv], S_new [B, H, dk, dv])``, float32.  Every slot
    is walked, live or idle (:func:`state_passes`); ``beta = 0`` and ``g =
    0`` leave a state bit for bit.  Under a mesh every device walks all
    slots over its own replica of the state, as the paged kernels do.
    """
    return _step(state, q, k, v, g, beta, *_where(interpret))


def delta_rule_reference(state, q, k, v, g, beta):
    """:func:`delta_rule_step` as plain ``jax.numpy``, line for line the
    module's equations: the definition the kernel is tested against.  No
    program calls it (XLA reads the state three times for it)."""
    decayed = state * jnp.exp(g)[..., None]
    u = (decayed * k[..., None]).sum(axis=2)
    p = (decayed * q[..., None]).sum(axis=2)
    w = beta[..., None] * (v - u)
    o = p + (k * q).sum(axis=-1, keepdims=True) * w
    return o, decayed + k[..., None] * w[..., None, :]


def state_passes(live_slots: int, slots: int) -> float:
    """Bytes of state a step's kernels move over the bytes the live slots'
    state holds once each way: 1.0 is every live slot's state read once and
    written once.

    The grid of :func:`delta_rule_step` fetches and stores every block of
    every slot once, idle slots too, whatever :func:`head_block` gives: a
    step with ``live_slots`` of ``slots`` in decode reads ``slots /
    live_slots`` (plain ``jax.numpy`` as XLA fused it read 2.0 at a full
    batch; a grid over the live slots only would read 1.0 at any)."""
    return slots / live_slots if live_slots else 0.0
