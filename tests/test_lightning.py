"""Lightning attention (``ops/lightning.py``): the decode kernel against its
plain form, and the whole-prompt chunked form against the recurrence a
position at a time, on the CPU in float32.

Tolerances: 1e-5 on the kernel, which does the plain form's products in
another order; 1e-4 on the chunked form over 200 positions, whose values
reach about 10 and whose chunks regroup the sums (2e-5 seen)."""

import jax
import jax.numpy as jnp
import numpy as np

from stoke_tpu.ops.lightning import (
    LIGHTNING_CHUNK,
    alibi_decay,
    lightning_chunked,
    lightning_reference,
    lightning_step,
)


def _normal(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def test_step_kernel_equals_its_plain_form():
    B, H, D = 3, 4, 16
    state = _normal(0, (B, H, D, D))
    q, k, v = _normal(1, (B, H, D)), _normal(2, (B, H, D)), _normal(3, (B, H, D))
    decay = alibi_decay(H, 1, 32)
    got_o, got_s = lightning_step(state, q, k, v, decay, interpret=True)
    want_o, want_s = lightning_reference(state, q, k, v, decay)
    np.testing.assert_allclose(got_s, want_s, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_o, want_o, rtol=1e-5, atol=1e-5)


def test_the_decay_is_minimax_slope_rate():
    lam = np.asarray(alibi_decay(32, 1, 32))
    m = 2.0 ** (-8.0 * (np.arange(32) + 1) / 32)
    np.testing.assert_allclose(lam, np.exp(-m * (1 - 1 / 31 + 1e-5)),
                               rtol=1e-6)
    assert (np.diff(lam) > 0).all() and lam.max() < 1


def test_chunked_form_equals_the_recurrence_stepped():
    """At a length that is no multiple of the chunk, with the second
    sequence's positions past its length masked out: outputs and the state
    at each length equal ``lightning_reference`` stepped a position at a
    time from zero state."""
    B, L, H, D = 2, 200, 4, 16
    assert L % LIGHTNING_CHUNK
    q, k, v = (_normal(i, (B, L, H, D)) for i in (4, 5, 6))
    decay = alibi_decay(H, 2, 32)
    lengths = jnp.array([L, 150], jnp.int32)
    o, state = lightning_chunked(q, k, v, decay, 0.25, lengths)
    S = jnp.zeros((B, H, D, D))
    want = []
    for t in range(L):
        out, new = lightning_reference(S, q[:, t] * 0.25, k[:, t], v[:, t],
                                       decay)
        keep = (t < lengths)[:, None, None, None]
        S = jnp.where(keep, new, S)
        want.append(out)
    want = jnp.stack(want, 1).reshape(B, L, H * D)
    np.testing.assert_allclose(o[0], want[0], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(o[1, :150], want[1, :150], rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(state, S, rtol=1e-4, atol=1e-4)
