"""The one-token delta rule of ``stoke_tpu/ops/delta_rule.py`` alone, through
the Pallas interpreter on the CPU, against the plain ``jax.numpy`` form of
its definition (``delta_rule_reference``); that it lowers for the TPU with the state aliased in to out;
and its pure counter ``state_passes``."""

import os
import re

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from stoke_tpu.ops import delta_rule as dr  # noqa: E402


def _inputs(rng, B, H, dk, dv):
    """As ``test_decoder_serving._delta_rule_inputs`` draws a position:
    unit keys, log decays from -0.02 to -7, ``beta`` in (0, 2); the state
    what a few hundred such positions leave, of order one."""
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    k = f(B, H, dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.exp(2.0 * f(B, H, dk) - 1.0)
    beta = 2.0 * jax.nn.sigmoid(f(B, H))
    return f(B, H, dk, dv), f(B, H, dk) * dk ** -0.5, k, f(B, H, dv), g, beta


# B, H, dk, dv, heads a block may hold (None: the module's budget)
CASES = {
    "tier1_shape": (2, 3, 16, 16, None),
    "one_128_wide_head": (1, 1, 128, 128, None),
    "two_128_wide_heads_a_block": (2, 4, 128, 128, 2),
    "dk_and_dv_differ": (2, 5, 8, 24, None),
    "block_does_not_divide_heads": (2, 6, 16, 16, 4),  # blocks of 3
    "prime_heads_past_the_block": (1, 7, 16, 16, 3),  # blocks of 1
    "one_head_past_the_budget": (3, 2, 16, 16, 0),
}


@pytest.mark.parametrize("case", CASES)
def test_kernel_equals_the_plain_form(case, monkeypatch):
    B, H, dk, dv, fit = CASES[case]
    if fit is not None:
        monkeypatch.setattr(dr, "_STATE_BLOCK_BYTES", fit * dk * dv * 4)
        assert dr.head_block(H, dk, dv) == max(
            d for d in range(1, max(fit, 1) + 1) if H % d == 0)
    args = _inputs(np.random.default_rng(len(case)), B, H, dk, dv)
    want_o, want_S = dr.delta_rule_reference(*args)
    got_o, got_S = dr.delta_rule_step(*args)
    assert got_o.shape == (B, H, dv) and got_o.dtype == jnp.float32
    assert got_S.shape == (B, H, dk, dv) and got_S.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(got_S), np.asarray(want_S),
                               atol=1e-5, rtol=0)


def test_stepping_the_kernel_follows_the_plain_form():
    """Forty positions from zero state, each step's state fed to the next:
    the error of a step does not grow along the recurrence."""
    rng = np.random.default_rng(40)
    B, H, d = 2, 3, 16
    want = got = jnp.zeros((B, H, d, d), jnp.float32)
    for _ in range(40):
        _, *xs = _inputs(rng, B, H, d, d)
        want_o, want = dr.delta_rule_reference(want, *xs)
        got_o, got = dr.delta_rule_step(got, *xs)
        np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                                   atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=0)


def test_no_decay_and_no_write_leave_the_state_bit_for_bit():
    """``beta = 0, g = 0``: how a caller holds a state still.  ``o`` is then
    ``S^T q``."""
    S, q, k, v, g, beta = _inputs(np.random.default_rng(3), 2, 3, 16, 16)
    o, new = dr.delta_rule_step(S, q, k, v, jnp.zeros_like(g),
                                jnp.zeros_like(beta))
    assert np.array_equal(np.asarray(new), np.asarray(S))
    np.testing.assert_allclose(
        np.asarray(o), np.einsum("bhkv,bhk->bhv", S, q), atol=1e-5, rtol=0)


@pytest.mark.parametrize("shape", [(2, 3, 16, 16), (4, 64, 128, 128)],
                         ids=["tier1_shape", "published_heads"])
def test_a_donated_state_lowers_aliased_into_the_kernel(shape):
    """As the serve program calls it: jitted, the state donated, lowered
    for the TPU with the kernel as a Mosaic call.  The call takes the state
    as its fourth operand and hands it back as its second result, aliased;
    the program's state argument is donated to the result that carries it,
    so nothing between the two copies it; and no operand of the kernel is
    a ``[..., dk, 1]`` column."""
    B, H, dk, dv = shape
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    step = jax.jit(
        lambda S, *xs: dr.delta_rule_step(S, *xs, interpret=False),
        donate_argnums=0)
    text = step.trace(
        f32(B, H, dk, dv), f32(B, H, dk), f32(B, H, dk), f32(B, H, dv),
        f32(B, H, dk), f32(B, H),
    ).lower(lowering_platforms=("tpu",)).as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1, text
    assert "delta_rule_step" in calls[0]
    alias = re.search(
        r"output_operand_aliases = \[#stablehlo\.output_operand_alias<"
        r"output_tuple_indices = \[(\d+)\],\s*operand_index = (\d+)", calls[0])
    assert alias and alias.groups() == ("1", "3"), calls[0][:400]
    hb = dr.head_block(H, dk, dv)
    state = f"tensor<{B}x{H // hb}x{hb}x{dk}x{dv}xf32>"
    operands = calls[0].rsplit(" : (", 1)[1].split(") -> ")[0]
    assert operands.split(", ")[3] == state, operands
    assert not re.search(r"x1xf32", operands), operands
    main = next(line for line in text.splitlines() if "func.func public" in line)
    donated = re.search(
        rf"%arg0: tensor<{B}x{H}x{dk}x{dv}xf32> "
        r"\{[^}]*tf\.aliasing_output = 1 : i32", main)
    assert donated, main[:400]


@pytest.mark.parametrize("bad,match", [
    (dict(q=(2, 3, 8)), "q must be"),
    (dict(v=(2, 3, 16, 1)), "v must be"),
    (dict(beta=(2, 3, 1)), "beta must be"),
    (dict(g=(3, 16)), "g must be"),
    (dict(state="bfloat16"), "float32"),
])
def test_wrong_operands_are_named(bad, match):
    names = ("state", "q", "k", "v", "g", "beta")
    args = dict(zip(names, _inputs(np.random.default_rng(0), 2, 3, 16, 16)))
    for name, change in bad.items():
        args[name] = (args[name].astype(change) if isinstance(change, str)
                      else jnp.zeros(change, jnp.float32))
    with pytest.raises(ValueError, match=match):
        dr.delta_rule_step(*args.values())


@pytest.mark.parametrize("live,slots,want", [
    (256, 256, 1.0), (128, 256, 2.0), (255, 256, 256 / 255), (1, 4, 4.0),
    (0, 4, 0.0),
])
def test_state_passes_counts_the_slots_the_grid_walks(live, slots, want):
    """The kernel's grid is ``(slots, heads / hb)`` and each step fetches
    and stores one block: every slot's state moves once each way, live or
    idle, so the bytes moved over the live slots' bytes is ``slots /
    live``: 1.0 a full batch, 2.0 a half-live one."""
    assert dr.state_passes(live, slots) == pytest.approx(want)
    if live:
        H, dk, dv = 64, 128, 128
        moved = slots * (H // dr.head_block(H, dk, dv)) * (
            dr.head_block(H, dk, dv) * dk * dv * 4) * 2
        assert moved / (live * H * dk * dv * 4 * 2) == pytest.approx(want)
