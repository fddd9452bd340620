"""The DSA family's decoder (Hy4: learned sparse attention with an indexer
whose selection later layers reuse, gated latent attention with sinks,
hyper-connections, limited SwiGLUs, a float32 head) and its serving path, on
the CPU at a small size in float32 with ``index_topk`` 8 and prompts of up
to 40 positions, so that the selection leaves keys out, against the plain
reference of ``benchmark/lib/families/hy_v4.py`` (``jax.numpy`` at
``highest`` precision, nothing of the program).

Tolerances: 2e-4 on logits of a range near 10 (program and reference are
both float32 here and differ in the order of their sums and in the
hyper-connections' Sinkhorn, iterated in another order; the largest
difference seen is 3e-5); the served stream must be the reference's argmax
token for token.
"""

import json
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib.families import hy_v4  # noqa: E402
from stoke_tpu.models.decoder import (  # noqa: E402
    DecoderConfig,
    sinkhorn,
)
from stoke_tpu.models.moe import ExpertShareFFN, SwiGLU  # noqa: E402
from stoke_tpu.ops.grouped_matmul import grouped_swiglu  # noqa: E402


def _read(relative):
    with open(os.path.join(ROOT, relative)) as f:
        return json.load(f)


# the cell's rehearsal sizes laid over its configuration: 5 layers (dense,
# then full, shared, shared, shared after the first full one), hidden 64, 4
# heads, an indexer of 4 heads of 16 choosing 8 keys, 8 experts of which 4
# are held, 4 rows of hyper-connections
TINY = {**_read("benchmark/configs/hy4-preview.json"),
        **_read("tests/benchmark/rehearsal/configs/hy4-preview.json")}
PROMPTS = (40, 23, 37)


def build():
    """The model and parameters drawn on the host (shapes from
    ``jax.eval_shape``; no initialiser is compiled): norm scales and gains
    1, every other leaf normal with variance 1 / fan-in (biases, sinks and
    the choice bias at their own scales, so that each matters here)."""
    model = hy_v4.build_model(TINY)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        train=False))["params"]
    rng = np.random.default_rng(7)

    def draw(path, leaf):
        name = jax.tree_util.keystr(path)
        if "scale" in name or name.endswith("['alpha']"):
            return jnp.ones(leaf.shape, leaf.dtype)
        std = (0.1 if "e_score_correction_bias" in name
               else 1.0 if leaf.ndim == 1 or "embedding" in name
               else leaf.shape[-2] ** -0.5)
        return jnp.asarray(rng.normal(0, std, leaf.shape), leaf.dtype)

    return model, jax.tree_util.tree_map_with_path(draw, shapes)


@pytest.fixture(scope="module")
def built():
    return build()


REFERENCE_LEN = 48  # every sequence here, padded: one compile


@jax.jit
def _reference_logits(params, ids):
    at = jnp.broadcast_to(jnp.arange(ids.shape[1], dtype=jnp.int32),
                          ids.shape)
    return hy_v4.reference_logits_at(TINY, params, ids, at)


def _reference(params, ids):
    """The reference's logits at every position of each row of ``ids``
    (padded after its end, which a causal forward does not see)."""
    ids = np.asarray(ids, np.int32)
    padded = np.zeros((ids.shape[0], REFERENCE_LEN), np.int32)
    padded[:, :ids.shape[1]] = ids
    return np.asarray(_reference_logits(params, jnp.asarray(padded)))[
        :, :ids.shape[1]]


def test_config_reads_the_source_keys_and_refuses_what_is_not_built():
    cfg = DecoderConfig.from_dict(hy_v4.program_config(TINY))
    assert cfg.use_dsa and cfg.mla_gate and cfg.learnable_sink
    assert cfg.indexer_types == ("full", "full", "shared", "shared", "shared")
    assert cfg.full_layers == (0, 1) and cfg.first_k_dense_replace == 1
    assert (cfg.hc_mult, cfg.hc_magnitude, cfg.swiglu_limit) == (4, 2, 10)
    assert cfg.rope_theta == 1e7 and cfg.lm_head_fp32
    assert cfg.router_choice_bias and cfg.scoring_func == "sigmoid"
    source = hy_v4.program_config(TINY)
    for key, value in (("gating_type", "headwise"),
                       ("indexer_types", ["shared"] * 5),
                       ("indexer_types", ["full", "sparse"] * 3),
                       ("layer_types", ["full_attention"] * 5),
                       ("rope_parameters", {"rope_type": "yarn"}),
                       ("qk_head_dim", 25), ("enable_ihc", False),
                       ("mlp_layer_types", ["sparse", "dense"] * 3)):
        with pytest.raises(ValueError):
            DecoderConfig.from_dict({**source, key: value})
    # every new field off by default: the other families build as before
    plain = DecoderConfig.from_dict(_read("benchmark/configs/axk1.json"))
    assert not (plain.use_dsa or plain.hc_mult or plain.mla_gate
                or plain.learnable_sink or plain.swiglu_limit
                or plain.lm_head_fp32)


def test_decoder_forward_matches_reference(built):
    model, params = built
    ids = np.random.default_rng(0).integers(0, TINY["vocab_size"], (2, 40))
    got = np.asarray(jax.jit(partial(model.apply, train=False))(
        {"params": params}, jnp.asarray(ids)))
    want = _reference(params, ids)
    assert got.dtype == np.float32 and np.ptp(want) > 4
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


def test_sinkhorn_output_is_doubly_stochastic_within_eps():
    """Rows and columns sum to 1 within ``hc_eps`` and float32's rounding of
    four terms (1e-5) after the 20 iterations, at the logits' spread the
    layers give (standard deviation 1).  At a spread of 3 the rows are
    still 3% off after 20: fewer iterations would not do."""
    logits = jnp.asarray(np.random.default_rng(3).normal(0, 1, (50, 4, 4)),
                         jnp.float32)
    m = sinkhorn(logits, 20, 1e-6)
    assert (m > 0).all()
    np.testing.assert_allclose(np.asarray(m.sum(-1)), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(m.sum(-2)), 1.0, atol=1e-5)
    wide = sinkhorn(3 * logits, 20, 1e-6)
    assert float(jnp.abs(wide.sum(-1) - 1).max()) > 1e-3


def test_the_swiglu_limit_caps_the_gate_and_clips_the_up_product():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(0, 4, (16, 8)), jnp.float32)
    layer = SwiGLU(32, limit=2.0)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    g, u = x @ params["gate"]["kernel"], x @ params["up"]["kernel"]
    assert (g > 2).any() and (jnp.abs(u) > 2).any()
    want = (jax.nn.silu(jnp.minimum(g, 2.0)) * jnp.clip(u, -2.0, 2.0)) @ (
        params["down"]["kernel"])
    np.testing.assert_allclose(np.asarray(layer.apply({"params": params}, x)),
                               np.asarray(want), atol=1e-5, rtol=0)
    w_gate = jnp.asarray(rng.normal(size=(2, 8, 128)), jnp.float32)
    w_up = jnp.asarray(rng.normal(size=(2, 8, 128)), jnp.float32)
    counts = jnp.asarray([10, 6], jnp.int32)
    got = grouped_swiglu(x, w_gate, w_up, counts, limit=2.0)
    group = np.repeat([0, 1], [10, 6])
    g = jnp.einsum("mk,mkn->mn", x, w_gate[group])
    u = jnp.einsum("mk,mkn->mn", x, w_up[group])
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(jax.nn.silu(jnp.minimum(g, 2.0)) * jnp.clip(u, -2, 2)),
        atol=1e-4, rtol=0)


def test_all_shares_and_the_common_part_once_add_up_to_the_uncut_layer():
    """The share test for this router (sigmoid, a choice bias, the
    limit): the held experts' parts of all 4 shares, with the shared expert
    counted once, add up to the reference's uncut layer."""
    shares, per, hidden, ff, top_k = 4, 2, 32, 16, 3
    E = shares * per
    config = {**TINY, "hidden_size": hidden, "moe_intermediate_size": ff,
              "n_routed_experts": E, "num_experts_per_tok": top_k,
              "published": {"n_routed_experts": E},
              "deployment": {"first_expert": 0}, "swiglu_limit": 1.5}
    kwargs = dict(hidden=hidden, ff=ff, num_experts=E, top_k=top_k,
                  routed_scaling_factor=2.827, choice_bias=True,
                  swiglu_limit=1.5)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(3, 7, hidden)),
                    jnp.float32)
    params = ExpertShareFFN(held=(0, E), **kwargs).init(
        jax.random.PRNGKey(0), x)["params"]
    params = {**params, "e_score_correction_bias": jnp.asarray(
        np.random.default_rng(6).normal(0, 0.2, E), jnp.float32)}
    flat = x.reshape(-1, hidden)
    with jax.default_matmul_precision("highest"):
        uncut = hy_v4.expert_ffn(config, params, flat, (0, E))
        common = hy_v4.expert_ffn(config, params, flat, (0, 0))
    routed, counted = 0.0, 0
    for s in range(shares):
        mine = {**params, **{n: params[n][s * per:(s + 1) * per]
                             for n in ("w_gate", "w_up", "w_down")}}
        out, sown = ExpertShareFFN(held=(s * per, per), **kwargs).apply(
            {"params": mine}, x, mutable=["intermediates"])
        routed = routed + out.reshape(-1, hidden) - common
        counted += int(sown["intermediates"]["expert_counts"][0].sum())
    np.testing.assert_allclose(np.asarray(routed + common), np.asarray(uncut),
                               atol=5e-5, rtol=0)
    assert counted == 3 * 7 * top_k
