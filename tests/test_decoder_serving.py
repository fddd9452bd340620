"""The config-driven decoder (MLA, expert share) and its serving path, on the
CPU at a small size in float32, against the plain reference the benchmark
keeps for the family (``benchmark/lib/families/axk1.py``: ``jax.numpy`` at
``highest`` precision, nothing of the program).

(a) the decoder's full forward against the reference; (b) prefill then decode
through the latent paged cache and ``ServingEngine`` against the reference's
full forward, and what ``serve/commit`` says the step read; (c) absorbed
decode equals expanded attention, and the paged kernel over the plane equals
absorbed decode over the gathered window; (d) the routed parts of all shares
plus the shared expert once add up to the uncut layer; (e) the router against
a ``numpy`` top-k with groups and a tie; (f) the pool's bytes a token and the
allocator's accounting; (g) the options without a latent program raise.  (h),
the cell's rehearsal, is
``tests/benchmark/test_benchmark_harness.py::test_cell_rehearsal``.
"""

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib.families import axk1  # noqa: E402
from stoke_tpu import ServeConfig  # noqa: E402
from stoke_tpu.models import GPT  # noqa: E402
from stoke_tpu.models.decoder import (  # noqa: E402
    Decoder,
    DecoderConfig,
    absorbed_attention,
    absorbed_paged_attention,
    expanded_attention,
)
from stoke_tpu.models.moe import ExpertShareFFN, group_limited_topk  # noqa: E402
from stoke_tpu.serving.engine import ServingEngine  # noqa: E402
from stoke_tpu.serving.kv_cache import (  # noqa: E402
    BlockAllocator,
    LatentAttentionHook,
    PagedKVCache,
)


def _read(relative):
    with open(os.path.join(ROOT, relative)) as f:
        return json.load(f)


# the cell's rehearsal sizes laid over its configuration, as the harness
# test lays them: 1 dense + 2 expert layers, hidden 128, 8 experts in 2
# groups of which 4 are held
TINY = {**_read("benchmark/configs/axk1.json"),
        **_read("tests/benchmark/rehearsal/configs/axk1.json")}


@pytest.fixture(scope="module")
def tiny():
    model = axk1.build_model(TINY)
    params = axk1.init_params(model, 7, 16)["params"]
    return model, params


def _reference_logits(params, ids):
    ids = jnp.asarray(ids, jnp.int32)
    at = jnp.broadcast_to(jnp.arange(ids.shape[1], dtype=jnp.int32), ids.shape)
    return np.asarray(axk1.reference_logits_at(TINY, params, ids, at))


# ------------------------------- (a) -------------------------------------- #


def test_decoder_forward_matches_reference(tiny):
    model, params = tiny
    ids = np.random.default_rng(0).integers(0, 512, (2, 40)).astype(np.int32)
    got = np.asarray(model.apply({"params": params}, ids, train=False))
    want = _reference_logits(params, ids)
    assert got.dtype == np.float32 and got.shape == (2, 40, 512)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_decoder_loss_reference_is_finite(tiny):
    _, params = tiny
    ids = np.random.default_rng(1).integers(0, 512, (2, 24)).astype(np.int32)
    axk1.build_model(TINY)
    loss = float(axk1.causal_lm_loss(params, jnp.asarray(ids)))
    assert 4.0 < loss < 9.0  # near ln 512 on random weights


def test_config_keys_the_decoder_cannot_build_are_errors():
    base = axk1.program_config(TINY)
    DecoderConfig.from_dict(base)
    for key, value in (("hidden_act", "gelu"), ("scoring_func", "softmax"),
                       ("moe_layer_freq", 2), ("tie_word_embeddings", True)):
        with pytest.raises(ValueError):
            DecoderConfig.from_dict({**base, key: value})
    with pytest.raises(ValueError, match="yarn"):
        DecoderConfig.from_dict({**base, "rope_scaling": {"type": "linear"}})


# ------------------------------- (b) -------------------------------------- #

BLOCK, BUCKET = 8, 16


@pytest.mark.parametrize("prompt_len", [5, 8, 16, 17, 23])
def test_prefill_then_decode_logits_match_reference(tiny, prompt_len):
    """Prompts that end inside a block, on a block boundary, on a bucket
    boundary and past both; logits of the prefill's last row and of every
    decode step against the reference's full forward."""
    model, params = tiny
    spec = model.cache_spec()
    rng = np.random.default_rng(prompt_len)
    new = 6
    seq = rng.integers(0, 512, prompt_len + new).astype(np.int32)
    want = _reference_logits(params, seq[None])[0]
    cache = PagedKVCache(spec.layers, 9, BLOCK, planes=spec.planes)
    table = np.array([[3, 1, 7, 5]], np.int32)  # 4 blocks of 8: 32 positions
    padded = -(-prompt_len // BUCKET) * BUCKET
    tokens = np.zeros((1, padded), np.int32)
    tokens[0, :prompt_len] = seq[:prompt_len]
    positions = jnp.arange(padded, dtype=jnp.int32)[None]
    hook = LatentAttentionHook(
        *cache.pages, jnp.asarray(table), positions, mode="prefill",
        lengths=jnp.array([prompt_len], jnp.int32), attention_impl="dense",
    )
    logits = model.apply({"params": params}, tokens, train=False,
                         positions=positions, kv_cache=hook)
    np.testing.assert_allclose(np.asarray(logits[0, prompt_len - 1]),
                               want[prompt_len - 1], atol=2e-5, rtol=0)
    (pages,) = hook.pages
    # padding rows went to the scratch block, the prompt's rows to the table
    assert not np.asarray(pages[:, [2, 4, 6, 8]]).any()
    for t in range(prompt_len, prompt_len + new):
        hook = LatentAttentionHook(
            pages, jnp.asarray(table), jnp.array([[t]], jnp.int32),
            mode="decode", lengths=jnp.array([t + 1], jnp.int32),
        )
        logits = model.apply(
            {"params": params}, seq[None, t:t + 1], train=False,
            positions=jnp.array([[t]], jnp.int32), decode=True, kv_cache=hook,
        )
        (pages,) = hook.pages
        np.testing.assert_allclose(np.asarray(logits[0, 0]), want[t],
                                   atol=2e-5, rtol=0)


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_engine_serves_the_reference_greedy_stream(tiny, attention):
    """Through ``ServingEngine`` (submit, step, generate; scheduler,
    allocator, ``_launch``): every served token is the argmax of the
    reference's full forward of the prompt and the tokens before it."""
    model, params = tiny
    eng = ServingEngine(model, params, ServeConfig(
        max_seqs=3, kv_block_size=BLOCK, max_seq_len=64,
        prefill_pad_multiple=BUCKET, attention=attention))
    assert eng.qparams is params  # served as given: on the device once
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, n).astype(np.int32)
               for n in (5, 16, 17, 23, 9)]
    outs = eng.generate(prompts, max_new_tokens=5)
    for prompt, tokens in zip(prompts, outs):
        seq = np.concatenate([prompt, tokens]).astype(np.int32)
        want = _reference_logits(params, seq[None])[0]
        at = len(prompt) - 1 + np.arange(len(tokens))
        assert tokens == list(want[at].argmax(-1))
    m = eng.metrics
    assert m.cache_bytes_per_token.value == eng.cache.bytes_per_token
    assert m.expert_assignments.value > 0
    assert m.expert_load_max_over_mean.value >= 1.0
    assert eng.allocator.used_blocks == 0


def test_decode_program_hands_back_the_held_experts_counts(tiny):
    model, params = tiny
    eng = ServingEngine(model, params, ServeConfig(
        max_seqs=2, kv_block_size=BLOCK, max_seq_len=32,
        prefill_pad_multiple=BUCKET))
    eng.submit(np.arange(6, dtype=np.int32), 3)
    eng.step()
    tokens, positions, tables, context = eng.scheduler.decode_batch()
    out = eng._decode_jit(params, *eng.cache.pages, tokens, positions,
                          tables, context)
    assert len(out) == 3  # tokens, counts, the one plane
    counts = np.asarray(out[1])
    assert counts.shape == (2, 4) and counts.dtype == np.int32  # a layer
    # 2 rows x 2 experts a token x 2 expert layers, the held ones only
    assert 0 <= counts.sum() <= 8


@pytest.mark.parametrize("family", ["latent", "mha"])
def test_commit_span_says_what_the_decode_step_read(tiny, monkeypatch, family):
    """``serve/commit`` carries the decode rows' ``context_tokens`` and the
    ``window_blocks`` one layer's attention read for them: a latent cache is
    read to each slot's own length, the MHA gather takes every slot's whole
    table."""
    import contextlib

    from stoke_tpu.telemetry import tracing

    seen = []

    def fake_xprof_span(name, **stats):
        seen.append((name, stats))
        return contextlib.nullcontext()

    monkeypatch.setattr(tracing, "xprof_span", fake_xprof_span)
    if family == "latent":
        model, params = tiny
    else:
        model = GPT(vocab_size=64, size_name="tiny", max_len=32)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ServingEngine(model, params, ServeConfig(
        max_seqs=3, kv_block_size=BLOCK, max_seq_len=32,
        prefill_pad_multiple=BUCKET))
    eng.submit(np.arange(9, dtype=np.int32), 4)
    eng.submit(np.arange(17, dtype=np.int32), 4)
    while not any(name == "serve/commit" and stats["context_tokens"] == 30
                  for name, stats in seen):
        assert eng.scheduler.has_work
        eng.step()
    commits = [stats for name, stats in seen if name == "serve/commit"]
    # both decoding, one token each behind them: 9 + 1 + 1 and 17 + 1 + 1
    # cached positions, in 2 and 3 blocks of 8; three slots of four blocks
    assert commits[-1]["context_tokens"] == 11 + 19
    assert commits[-1]["window_blocks"] == (2 + 3 if family == "latent"
                                            else 3 * 4)
    assert all(c["window_blocks"] > 0 for c in commits)
    # an expert model says how often its grouped products streamed the held
    # weights: once each, less the experts that drew no row in a layer
    if family == "latent":
        assert all(0.0 < c["expert_weight_passes"] <= 1.0 for c in commits)
        assert eng.metrics.expert_weight_passes.value == (
            commits[-1]["expert_weight_passes"])
    else:
        assert not any("expert_weight_passes" in c for c in commits)
        assert eng.metrics.expert_weight_passes is None


# ------------------------------- (c) -------------------------------------- #


def test_absorbed_decode_equals_expanded_attention():
    rng = np.random.default_rng(5)
    B, L, H, dn, dr, dv, C = 2, 11, 3, 16, 8, 12, 32
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    q_nope, q_rope = f(B, L, H, dn), f(B, L, H, dr)
    c, k_rope, w_kvb = f(B, L, C), f(B, L, dr), f(C, H, dn + dv) / 6
    scale = 0.2
    want = expanded_attention(q_nope, q_rope, c, k_rope, w_kvb, scale,
                              jnp.ones((B, L), bool), "dense")
    window = jnp.pad(jnp.concatenate([c, k_rope], -1),
                     ((0, 0), (0, 5), (0, 128 - C - dr)))
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    got = absorbed_attention(q_nope, q_rope, window, positions, w_kvb, scale)
    assert got.shape == (B, L, H, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=0)
    # and the flash form of the expanded attention, on the same layer
    flash = expanded_attention(q_nope, q_rope, c, k_rope, w_kvb, scale,
                               jnp.ones((B, L), bool), "flash")
    np.testing.assert_allclose(np.asarray(flash), np.asarray(want),
                               atol=2e-5, rtol=0)


# the cell's page: 16 rows of 640 lanes (512 latent + 64 key + 64 zeros).  A
# float32 page is 40 KB and a bfloat16 one 20 KB, so the kernel takes 16 and
# 32 pages a step and a table of 40 blocks is walked in 3 and 2 steps
PAGED = dict(H=4, dn=16, dr=64, dv=16, C=512, row=640, BS=16, MB=40, layers=3)
FULL = PAGED["MB"] * PAGED["BS"]


RAGGED = [5, FULL, 37, 264, 1, 530]


@pytest.mark.parametrize("lens,plane_dtype,q_dtype,layer,shuffled", [
    pytest.param([1], "float32", "float32", 0, True, id="length-1"),
    pytest.param([15], "float32", "float32", 0, True, id="length-BS-1"),
    pytest.param([16], "float32", "float32", 0, True, id="length-BS"),
    pytest.param([17], "float32", "float32", 0, True, id="length-BS+1"),
    pytest.param([FULL], "float32", "float32", 0, True, id="full-table"),
    pytest.param(RAGGED, "float32", "float32", 0, True, id="ragged"),
    pytest.param([0, 40, 0], "float32", "float32", 0, True, id="idle-slots"),
    pytest.param([33, 300, 7], "float32", "float32", 0, False,
                 id="pool-in-order"),
    pytest.param([33, 300, 7], "float32", "float32", 2, True, id="layer-2"),
    pytest.param(RAGGED, "bfloat16", "bfloat16", 1, True,
                 id="ragged-bfloat16"),
    pytest.param([16, 529, 0], "bfloat16", "bfloat16", 0, True,
                 id="idle-bfloat16"),
    pytest.param([600, 17], "bfloat16", "float32", 0, True,
                 id="bfloat16-plane-float32-queries"),
])
def test_paged_kernel_equals_absorbed_attention_over_the_window(
        lens, plane_dtype, q_dtype, layer, shuffled):
    """``latent_paged_attention`` (interpreted) under
    ``absorbed_paged_attention`` against ``absorbed_attention`` over each
    slot's gathered window.  A slot owns the blocks its budget needs (its
    length and 40 positions more, as the scheduler allocates ahead), holding
    rows a step must not read past the length; the table's other entries
    point at the scratch block."""
    P = PAGED
    rng = np.random.default_rng(len(lens) * 1000 + sum(lens))
    q_dtype = jnp.dtype(q_dtype)
    B = len(lens)
    NB = 1 + B * P["MB"]
    plane = jnp.asarray(
        rng.standard_normal((P["layers"], NB, P["BS"], P["row"])) * 0.5,
        plane_dtype).at[..., P["C"] + P["dr"]:].set(0)
    ids = rng.permutation(np.arange(1, NB)) if shuffled else np.arange(1, NB)
    tables = np.zeros((B, P["MB"]), np.int32)
    for b, n in enumerate(lens):
        owned = min(P["MB"], -(-(n + 40) // P["BS"])) if n else 0
        tables[b, :owned] = ids[b * P["MB"]:b * P["MB"] + owned]
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), q_dtype)  # noqa: E731
    q_nope, q_rope = f(B, 1, P["H"], P["dn"]), f(B, 1, P["H"], P["dr"])
    w_kvb = f(P["C"], P["H"], P["dn"] + P["dv"]) / 20
    scale = 0.13
    lens = jnp.asarray(lens, jnp.int32)
    got = absorbed_paged_attention(
        q_nope, q_rope, plane, layer, jnp.asarray(tables), lens, w_kvb, scale)
    window = plane[layer][tables].reshape(B, FULL, P["row"])
    want = absorbed_attention(
        q_nope, q_rope, window, lens[:, None] - 1, w_kvb, scale)
    assert got.shape == want.shape == (B, 1, P["H"], P["dv"])
    assert got.dtype == q_dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    idle = np.asarray(lens) == 0
    # an idle slot reads nothing and returns zeros (the reference's mean
    # over a window with every position masked is what callers discard)
    assert not got[idle].any()
    # bfloat16: the probabilities are rounded before and the outputs after
    # the product, once each as in the reference, at other partial sums
    atol = 2e-5 if q_dtype == jnp.float32 else 2.0 ** -7 * np.abs(want).max()
    np.testing.assert_allclose(got[~idle], want[~idle], atol=atol, rtol=0)


def test_latent_decode_program_holds_no_window(tiny):
    """The latent engine's decode program, as lowered: no array of the
    shape of the full-table window (every slot's whole table gathered out
    of the plane), merged or not."""
    model, params = tiny
    eng = ServingEngine(model, params, ServeConfig(
        max_seqs=3, kv_block_size=BLOCK, max_seq_len=40,
        prefill_pad_multiple=BUCKET))
    (plane,) = eng.cache.pages
    row = plane.shape[-1]
    B, MB = eng.scheduler.decode_batch()[2].shape
    assert (B, MB) == (3, 5)
    text = eng._decode_jit.lower(
        params, plane, *eng.scheduler.decode_batch()).as_text()
    for shape in ((B, MB * BLOCK, row), (B * MB, BLOCK, row),
                  (B, MB, BLOCK, row)):
        assert "tensor<" + "x".join(map(str, shape)) + "x" not in text, shape
    # the same text does hold the plane, so the pattern is the text's own
    assert "tensor<" + "x".join(map(str, plane.shape)) + "x" in text


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill"])
def test_expert_products_are_the_grouped_kernels_on_the_stored_weights(
        tiny, monkeypatch, program):
    """The engine's two programs as they lower for the TPU (the kernels as
    Mosaic calls, not interpreted): no ``ragged_dot`` is left, the held
    experts' products are the grouped kernels, and a held weight's shape
    appears only where the parameter is handed on, never as the result of
    a copy, convert, transpose or slice."""
    model, params = tiny
    eng = ServingEngine(model, params, ServeConfig(
        max_seqs=3, kv_block_size=BLOCK, max_seq_len=40,
        prefill_pad_multiple=BUCKET))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if program == "serve_decode":
        jitted, args = eng._decode_jit, eng.scheduler.decode_batch()
    else:
        jitted = eng._prefill_jit
        args = (np.zeros((1, BUCKET), np.int32),
                eng.scheduler.decode_batch()[2][:1], np.array([5], np.int32))
    text = jitted.trace(params, *eng.cache.pages, *args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "ragged_dot" not in text
    for kernel in ("grouped_swiglu", "grouped_matmul"):
        assert kernel in text, kernel
    ffn = params["layer_1"]["ffn"]
    for name in ("w_gate", "w_down"):
        shape = "tensor<" + "x".join(map(str, ffn[name].shape)) + "x"
        lines = [l.strip() for l in text.splitlines() if shape in l]
        assert lines, name
        for line in lines:
            assert re.match(
                r"(%\S+ = )?(func\.func |call @_grouped\w*\(|"
                r"stablehlo\.custom_call @tpu_custom_call\()", line,
            ), line[:200]


# ------------------------------- (d) -------------------------------------- #


def test_all_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    E, shares, hidden, ff = 8, 4, 32, 16
    config = {**TINY, "hidden_size": hidden, "moe_intermediate_size": ff,
              "n_routed_experts": E,
              "published": {"n_routed_experts": E},
              "deployment": {"first_expert": 0}}
    kwargs = dict(hidden=hidden, ff=ff, num_experts=E, top_k=2, n_group=2,
                  topk_group=1, routed_scaling_factor=2.5)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((3, 7, hidden)),
                    jnp.float32)
    whole = ExpertShareFFN(held=(0, E), **kwargs)
    params = whole.init(jax.random.PRNGKey(0), x)["params"]
    flat = x.reshape(-1, hidden)
    with jax.default_matmul_precision("highest"):
        uncut = axk1.expert_ffn(config, params, flat, (0, E))
        shared = axk1._swiglu(flat, *(params["shared"][n]["kernel"]
                                      for n in ("gate", "up", "down")))
    routed_sum, counted = 0.0, 0
    per = E // shares
    for s in range(shares):
        mine = {**params, **{n: params[n][s * per:(s + 1) * per]
                             for n in ("w_gate", "w_up", "w_down")}}
        out, sown = ExpertShareFFN(held=(s * per, per), **kwargs).apply(
            {"params": mine}, x, mutable=["intermediates"])
        with jax.default_matmul_precision("highest"):
            want = axk1.expert_ffn(config, mine, flat, (s * per, per))
        np.testing.assert_allclose(np.asarray(out.reshape(-1, hidden)),
                                   np.asarray(want), atol=2e-5, rtol=0)
        routed_sum = routed_sum + out.reshape(-1, hidden) - shared
        counted += int(sown["intermediates"]["expert_counts"][0].sum())
    np.testing.assert_allclose(np.asarray(routed_sum + shared),
                               np.asarray(uncut), atol=5e-5, rtol=0)
    assert counted == 3 * 7 * 2  # every assignment computed once, none dropped


# ------------------------------- (e) -------------------------------------- #


def _numpy_route(scores, n_group, topk_group, top_k, scale):
    N, E = scores.shape
    per = E // n_group
    experts = np.zeros((N, top_k), np.int64)
    weights = np.zeros((N, top_k), np.float64)
    for n in range(N):
        groups = scores[n].reshape(n_group, per)
        group_score = np.sort(groups, axis=1)[:, -2:].sum(1)
        kept = np.argsort(-group_score, kind="stable")[:topk_group]
        masked = np.full(E, -1.0)
        for g in kept:
            masked[g * per:(g + 1) * per] = scores[n, g * per:(g + 1) * per]
        experts[n] = np.argsort(-masked, kind="stable")[:top_k]
        w = scores[n, experts[n]].astype(np.float64)
        weights[n] = w / w.sum() * scale
    return experts, weights


def test_router_matches_numpy_topk_with_groups_and_a_tie():
    rng = np.random.default_rng(11)
    scores = rng.uniform(0.05, 0.95, (64, 24)).astype(np.float32)
    # ties: two experts of one group equal, in and across the cut; two
    # groups with equal scores
    scores[0, 3] = scores[0, 5] = 0.99
    scores[1, :] = 0.5
    scores[2, 0:6] = scores[2, 6:12]
    got_e, got_w = group_limited_topk(jnp.asarray(scores), 4, 2, 3, 2.5)
    want_e, want_w = _numpy_route(scores, 4, 2, 3, 2.5)
    np.testing.assert_array_equal(np.asarray(got_e), want_e)
    np.testing.assert_allclose(np.asarray(got_w), want_w, rtol=1e-6)
    # and the reference's router, as dense weights
    dense = np.asarray(axk1.route(
        {"n_group": 4, "topk_group": 2, "num_experts_per_tok": 3,
         "routed_scaling_factor": 2.5}, jnp.asarray(scores)))
    want_dense = np.zeros_like(dense)
    np.put_along_axis(want_dense, want_e, want_w.astype(np.float32), axis=1)
    np.testing.assert_allclose(dense, want_dense, rtol=1e-6)


# ------------------------------- (f) -------------------------------------- #


def test_latent_pool_bytes_a_token_and_block_accounting(tiny):
    published = DecoderConfig.from_dict(
        axk1.program_config(_read("benchmark/configs/axk1.json")))
    real = Decoder(published, held_experts=(0, 12)).cache_spec()
    assert real.kind == "latent" and real.layers == 6
    assert real.values_per_token == 512 + 64 == 576
    # stored as one plane, padded to whole 128-lane tiles
    assert real.planes == (("latent", 640),)
    assert (real.heads, real.head_dim) == (64, 192)
    assert Decoder(published, held_experts=(0, 12)).experts_held == 12
    pool = PagedKVCache(real.layers, 3, 16, dtype=jnp.bfloat16,
                        planes=real.planes)
    assert [p.shape for p in pool.pages] == [(6, 3, 16, 640)]
    assert pool.bytes_per_token == 6 * 640 * 2
    assert pool.nbytes == 6 * 3 * 16 * 640 * 2

    model, params = tiny
    eng = ServingEngine(model, params, ServeConfig(
        max_seqs=2, kv_block_size=8, max_seq_len=32, prefill_pad_multiple=16))
    alloc = eng.allocator
    assert isinstance(alloc, BlockAllocator)
    assert alloc.num_blocks == 2 * 4 + 1 and alloc.used_blocks == 0
    eng.submit(np.arange(9, dtype=np.int32), 4)
    eng.step()
    assert alloc.used_blocks == alloc.blocks_for(9 + 4) == 2
    eng.run()
    assert alloc.used_blocks == 0 and alloc.occupancy == 0.0


def test_gpt_describes_its_cache_as_two_planes():
    spec = GPT(vocab_size=64, size_name="tiny", max_len=32).cache_spec()
    assert spec.kind == "mha" and spec.planes == (("k", 128), ("v", 128))
    assert (spec.layers, spec.heads, spec.head_dim) == (2, 2, 64)
    assert spec.values_per_token == 256
    pool = PagedKVCache(2, 5, 8, spec.planes)
    assert pool.k_pages.shape == pool.v_pages.shape == (2, 5, 8, 128)
    assert pool.pages[0] is pool.k_pages and pool.pages[1] is pool.v_pages


# ------------------------------- (g) -------------------------------------- #


@pytest.mark.parametrize("option,program", [
    ({"sampling": True}, "sampling"),
    ({"prefill_chunk_tokens": 16}, "serve_prefill_chunk"),
    ({"sampling": True, "speculative_k": 2}, "sampling"),
    ({"quant": "int8"}, "quantized"),
    ({"quant": "bf16"}, "quantized"),
])
def test_options_without_a_latent_program_raise(tiny, option, program):
    model, params = tiny
    with pytest.raises(NotImplementedError, match=program):
        ServingEngine(model, params, ServeConfig(
            max_seqs=2, kv_block_size=8, max_seq_len=32,
            prefill_pad_multiple=16, **option))


def test_speculative_alone_names_the_verify_program(tiny):
    model, params = tiny
    cfg = ServeConfig(max_seqs=2, kv_block_size=8, max_seq_len=32,
                      prefill_pad_multiple=16, speculative_k=2)
    with pytest.raises(NotImplementedError, match="serve_verify"):
        ServingEngine(model, params, cfg)


def test_latent_hook_has_no_chunk_or_verify_mode():
    pages = jnp.zeros((1, 2, 8, 128))
    for mode in ("chunk", "verify"):
        with pytest.raises(NotImplementedError, match=mode):
            LatentAttentionHook(pages, None, None, mode=mode, lengths=None)


def test_a_model_without_the_contract_is_a_type_error(tiny):
    _, params = tiny
    with pytest.raises(TypeError, match="cache_spec"):
        ServingEngine(object(), params, ServeConfig())


# ------------------------- counts and the reader --------------------------- #


def test_counts_of_the_published_share():
    config = _read("benchmark/configs/axk1.json")
    n = axk1.param_counts(config)
    # ISSUE 29's table, from the keys
    assert round(n["mla"] / 1e6, 1) == 101.1
    assert round(n["dense_layer"] / 1e6, 1) == 497.5
    assert round(n["expert_layer"] / 1e6, 1) == 675.0
    assert round(2 * n["embedding"] / 1e6, 1) == 293.6
    assert round(n["total"] / 1e9, 3) == 4.166
    # a decode step moves every held weight once and the live rows
    empty = axk1.decode_bytes_per_step(config, 192, 0)
    assert 8.0e9 < empty < 8.4e9
    per_row = axk1.decode_bytes_per_step(config, 192, 1000) - empty
    assert per_row == 1000 * 6 * 576 * 2
    # 2 FLOPs a matrix parameter met a token, the head for decode tokens
    one = axk1.serve_flops(config, 0, 1, 0)
    assert 2.9e9 < one < 3.4e9
    assert axk1.serve_flops(config, 1, 0, 0) == one - 2 * n["embedding"]
    assert axk1.serve_flops(config, 0, 0, 1) == 6 * 2 * 64 * (576 + 512)
    assert axk1.serve_flops(config, 0, 0, 0, 1) == 6 * 2 * 64 * (192 + 128)
    assert axk1.train_flops_per_token(config, 1024) > 3 * one


def test_serve_roofline_reader_finds_nothing_without_a_trace(tmp_path,
                                                            monkeypatch):
    from benchmark.lib import spans as S
    from benchmark.readers import serve_roofline

    monkeypatch.setattr(S, "newest_xplane", lambda root=None: None)
    for what in ("mfu", "decode_hbm"):
        assert serve_roofline.read(
            {"device.peak_flops_per_s": 197e12, "serve.window_s": 15.0},
            {"what": what, "config": "benchmark/configs/axk1.json",
             "module": "jit_serve_decode"}) is None
