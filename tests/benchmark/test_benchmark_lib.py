"""The yardstick's own arithmetic: trace reduction, percentile rule, FLOP
count, traffic generation, and the float32 reference against the program."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.jobs import serve_lm  # noqa: E402
from benchmark.lib import stats  # noqa: E402
from benchmark.lib.model import family  # noqa: E402
from benchmark.lib.trace_reduce import (  # noqa: E402
    host_owner,
    op_label,
    reduce_events,
)

# a hand-written device timeline, window 0..100:
#   gap 0-10 | a 10-30 | b 20-40 (overlaps a) | gap 40-50 |
#   loop 50-80 holding body 55-65 and body 70-75 | gap 80-100
EVENTS = [
    ("loop", 50, 30), ("a", 10, 20), ("body", 55, 10), ("b", 20, 20),
    ("body", 70, 5), ("zero", 45, 0),
]


def test_reduce_busy_is_the_union():
    r = reduce_events(EVENTS, window=(0, 100))
    assert (r["window_ns"], r["busy_ns"], r["idle_ns"]) == (100, 60, 40)


def test_reduce_gaps_at_both_ends_and_between():
    r = reduce_events(EVENTS, window=(0, 100))
    # (name, ns, start): longest first
    assert r["gaps"] == [("loop -> window-end", 20, 80),
                         ("window-start -> a", 10, 0), ("b -> loop", 10, 40)]


def test_reduce_self_time_of_nested_and_overlapping():
    ops = dict(reduce_events(EVENTS, window=(0, 100))["ops"])
    # the loop does not also claim its bodies; a and b keep their durations
    assert ops == {"a": 20, "b": 20, "loop": 15, "body": 15}


def test_reduce_default_window_and_top():
    r = reduce_events(EVENTS, top=2)
    assert (r["window_ns"], r["busy_ns"]) == (70, 60)
    assert len(r["ops"]) == 2 and len(r["gaps"]) == 1


def test_reduce_clips_to_the_window():
    r = reduce_events(EVENTS, window=(25, 60))
    assert (r["busy_ns"], r["idle_ns"]) == (25, 10)
    with pytest.raises(ValueError):
        reduce_events([])


@pytest.mark.parametrize("hlo,label", [
    ("%fusion.2701 = (bf16[4,1023]{1,0:T(4,128)(2,1)S(1)}, f32[4,1023,50257]"
     "{1,2,0:T(8,128)}) fusion(bf16[50257,1024]{1,0:T(8,128)(2,1)S(1)} "
     "%custom-call.7), kind=kOutput, calls=%fused_computation.3659",
     "fusion (bf16[4,1023], f32[4,1023,50257])"),
    ("%copy.221 = f32[24,769,16,16,64]{4,3,2,1,0:T(8,128)} copy(f32[24,769,"
     "16,16,64]{1,4,3,2,0:T(8,128)} %k_pages.1)", "copy f32[24,769,16,16,64]"),
    ("%add_convert_fusion = bf16[4,1023,50257]{1,2,0:T(8,128)(2,1)} fusion()",
     "add_convert_fusion bf16[4,1023,50257]"),
    ("not an instruction", "not an instruction"),
])
def test_op_label(hlo, label):
    assert op_label(hlo) == label


def test_host_owner():
    host = [("outer", 0, 100), ("inner", 40, 20), ("late", 90, 30)]
    assert host_owner(host, 45, 55) == "inner"   # shortest that covers half
    assert host_owner(host, 20, 30) == "outer"
    assert host_owner(host, 95, 125) == "late"
    assert host_owner(host, 200, 210) is None


def test_tracer_sums_recurring_gaps_under_their_host_owner(monkeypatch):
    from benchmark.lib import trace_reduce

    ops = [("fusion f32[4]", 10 + 100 * i, 60) for i in range(5)]
    host = [("PjitFunction(step)", 0, 1000)] + [
        ("np.asarray(jax.Array)", 70 + 100 * i, 35) for i in range(4)]
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace_reduce, "read_trace", lambda p: {
        "devices": {"/device:TPU:0": {
            "ops": ops,
            "modules": [("jit_step", 10 + 100 * i, 60) for i in range(5)]}},
        "host": host})
    r = trace_reduce.Tracer("x").reduce()
    assert (r["busy_ns"], r["window_ns"], r["idle_ns"]) == (300, 460, 160)
    assert r["ops"] == [("fusion f32[4]", 300)]
    assert r["modules"] == [("jit_step", 300)]
    assert r["gaps"] == [
        ("fusion -> fusion | host: np.asarray(jax.Array) x4", 160)]
    monkeypatch.setattr(trace_reduce, "read_trace",
                        lambda p: {"devices": {}, "host": host})
    assert trace_reduce.Tracer("x").reduce() is None


@pytest.mark.parametrize("n,p", [(5, 50.0), (19, 50.0), (40, 75.0),
                                 (100, 90.0), (199, 90.0), (200, 95.0),
                                 (1000, 99.0), (10000, 99.9)])
def test_supported_percentile(n, p):
    assert stats.supported_percentile(n) == p


def test_percentile_and_tail_note():
    v = list(range(1, 101))
    assert stats.percentile(v, 50.0) == 50.5
    assert stats.percentile(v, 95.0) == pytest.approx(95.05)
    assert stats.percentile([3.0], 95.0) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 50.0)
    note = stats.tail_note("ttft_ms", v, 95.0)
    assert "n=100" in note and "needs 200" in note and "p90=" in note
    assert "do not support" not in stats.tail_note("x", list(range(200)), 95.0)


def test_flops_per_token_gpt2_medium():
    config = {"family": "gpt2", "n_layer": 24, "n_embd": 1024, "n_head": 16,
              "n_inner": None, "vocab_size": 50257}
    counts = family(config)
    assert counts.matmul_params(config) == 353_453_056
    assert counts.train_flops_per_token(config, 1024) == (
        6 * 353_453_056 + 6 * 24 * 1024 * 1024)


def test_pool_is_the_same_multiset_for_every_seed():
    traffic = {"pool": 64,
               "prompt_len": {"dist": "lognormal", "median": 192,
                              "sigma": 0.8, "min": 16, "max": 896},
               "output_len": {"dist": "uniform", "min": 32, "max": 96}}
    a = serve_lm.make_pool(traffic, 1000, seed=2**31 + 7)
    b = serve_lm.make_pool(traffic, 1000, seed=3)
    again = serve_lm.make_pool(traffic, 1000, seed=3)
    lens = lambda pool: (sorted(len(p) for p, _ in pool),
                         sorted(o for _, o in pool))
    assert lens(a) == lens(b)
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]
    assert all((p == q).all() and o == r
               for (p, o), (q, r) in zip(b, again))
    plens = lens(a)[0]
    assert plens[0] >= 16 and plens[-1] <= 896
    assert 150 < plens[len(plens) // 2] < 240  # the median survives


def test_reference_matches_the_program_at_tiny_width():
    """The `gpt2` family's reference against `model.apply` with dense
    attention on the CPU, so the reference is known good before it judges a
    chip run.  Tolerance: both sides are float32 on the CPU; they differ in the order
    of sums (fused qkv einsum, LayerNorm's variance formula), a few ulps
    through two layers on logits of order 1."""
    import jax
    import jax.numpy as jnp

    from stoke_tpu.models import causal_lm_loss
    from stoke_tpu.models.bert import dense_attention

    config = {"name": "ref-tiny", "family": "gpt2", "n_layer": 2,
              "n_embd": 128, "n_head": 2, "n_inner": None,
              "vocab_size": 300, "n_positions": 64}
    reference = family(config)
    model = reference.build_model(config).clone(
        attention_fn=dense_attention, attention_is_causal=False)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, 300, (3, 48), dtype=np.int32))
    params = model.init(jax.random.PRNGKey(0), ids, train=False)["params"]
    # biases and LayerNorm offsets start at zero: make them count
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])
    want = model.apply({"params": params}, ids, train=False)
    got = reference.logits(params, ids)
    assert float(jnp.max(jnp.abs(want))) > 0.5
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    at = jnp.asarray([[0, 5, 47], [1, 2, 3], [10, 20, 30]])
    np.testing.assert_allclose(
        reference.logits_at(params, ids, at),
        jnp.take_along_axis(want, at[:, :, None], axis=1),
        atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(reference.causal_lm_loss(params, ids),
                               causal_lm_loss(want, ids), rtol=1e-5)
