"""The config-driven decoder (MLA; grouped-query and delta-rule layers;
expert share) and its serving path, on the CPU at a small size in float32,
against the plain references the benchmark keeps for the three families
(``benchmark/lib/families/axk1.py``, ``solar_open2.py``,
``longcat_flash.py``: ``jax.numpy`` at ``highest`` precision, nothing of
the program).  A test that holds for several families is one test with the
family as its parameter.

(a) the decoder's full forward against the reference; (b) prefill then decode
through the paged cache (latent rows; rows beside per-slot state) and
``ServingEngine`` against the reference's full forward, and what
``serve/commit`` says the step read; (c) absorbed decode equals expanded
attention, the paged kernel over the plane equals absorbed decode over the
gathered window, and the delta rule's whole-prompt form equals its one-token
form stepped; (d) the routed parts of all shares plus the shared expert once
add up to the uncut layer; (e) the router against a ``numpy`` top-k with
groups and a tie; (f) the pool's bytes a token, the state arrays and the
allocator's accounting; (g) the options without a program for such a model
raise, naming the program.  (h), the cells' rehearsal, is
``tests/benchmark/test_benchmark_harness.py::test_cell_rehearsal``.

Tolerances.  2e-5 on logits of a range near 8: program and reference are
both float32 here and differ in the order of their sums (the program's
whole-prompt delta rule regroups the recurrence by chunks of 32 positions;
the largest difference seen is 6e-6).  1e-4 on the delta rule's outputs
alone, whose values reach 8 and whose chunked form multiplies through a
32 x 32 inverse (4e-5 seen).
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

from benchmark.lib.families import axk1, longcat_flash, solar_open2  # noqa: E402
from stoke_tpu import ServeConfig  # noqa: E402
from stoke_tpu.models import GPT, decoder  # noqa: E402
from stoke_tpu.models.decoder import (  # noqa: E402
    Decoder,
    DecoderConfig,
    absorbed_attention,
    absorbed_paged_attention,
    delta_rule_chunked,
    delta_rule_step,
    expanded_attention,
)
from stoke_tpu.models.moe import (  # noqa: E402
    ExpertShareFFN,
    group_limited_topk,
    softmax_topk,
)
from stoke_tpu.ops.delta_rule import delta_rule_reference  # noqa: E402
from stoke_tpu.serving.engine import ServingEngine  # noqa: E402
from stoke_tpu.serving.kv_cache import (  # noqa: E402
    BlockAllocator,
    HybridCacheHook,
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


# and the second family's: one period of the hybrid (a grouped-query layer,
# three delta-rule layers), hidden 128, 4 query heads over 2 key-value heads
# of 32, 4 delta-rule heads of 32 x 32 state, 8 experts of which 4 are held
TINY_SOLAR = {**_read("benchmark/configs/solar-open2.json"),
              **_read("tests/benchmark/rehearsal/configs/solar-open2.json")}
# and the third's: two double layers (four latent sublayers), hidden 128, a
# softmax router over 8 routed + 4 zero-compute outputs, 3 a token, 4 held
TINY_LONGCAT = {
    **_read("benchmark/configs/longcat-flash-omni.json"),
    **_read("tests/benchmark/rehearsal/configs/longcat-flash-omni.json")}
FAMILIES = {"axk1": (axk1, TINY), "solar_open2": (solar_open2, TINY_SOLAR),
            "longcat_flash": (longcat_flash, TINY_LONGCAT)}
# the families whose cache is the latent plane
LATENT = ["axk1", "longcat_flash"]


@pytest.fixture(scope="module")
def built():
    """``{family: (model, params)}``, each built once."""
    out = {}
    for name, (family, config) in FAMILIES.items():
        model = family.build_model(config)
        out[name] = (model, family.init_params(model, 7, 16)["params"])
    return out


@pytest.fixture(scope="module")
def tiny(built):
    return built["axk1"]


@pytest.fixture(scope="module")
def tiny_solar(built):
    return built["solar_open2"]


def _reference_logits(params, ids, family="axk1"):
    module, config = FAMILIES[family]
    ids = jnp.asarray(ids, jnp.int32)
    at = jnp.broadcast_to(jnp.arange(ids.shape[1], dtype=jnp.int32), ids.shape)
    return np.asarray(module.reference_logits_at(config, params, ids, at))


# ------------------------------- (a) -------------------------------------- #


@pytest.mark.parametrize("family", list(FAMILIES))
def test_decoder_forward_matches_reference(built, family):
    """Cacheless: 40 positions is a chunk of the delta rule and a part of
    one."""
    model, params = built[family]
    ids = np.random.default_rng(0).integers(0, 512, (2, 40)).astype(np.int32)
    got = np.asarray(model.apply({"params": params}, ids, train=False))
    want = _reference_logits(params, ids, family)
    assert got.dtype == np.float32 and got.shape == (2, 40, 512)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("family", list(FAMILIES))
def test_decoder_loss_reference_is_finite(built, family):
    _, params = built[family]
    module, config = FAMILIES[family]
    ids = np.random.default_rng(1).integers(0, 512, (2, 24)).astype(np.int32)
    module.build_model(config)
    loss = float(module.causal_lm_loss(params, jnp.asarray(ids)))
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
    with pytest.raises(ValueError, match="latent attention needs"):
        DecoderConfig.from_dict(
            {k: v for k, v in base.items() if k != "kv_lora_rank"})
    # the hybrid's keys
    base = solar_open2.program_config(TINY_SOLAR)
    cfg = DecoderConfig.from_dict(base)
    # the source's list of softmax layers outlives a cut in depth
    assert base["gqa_layers"][:2] == [0, 4] and cfg.gqa_layers == (0,)
    assert cfg.layer_kinds == ("gqa", "kda", "kda", "kda")
    linear = base["linear_attn_config"]
    for key, value, message in (
            ("use_rope", True, "use_rope"),
            ("kda_use_full_proj", True, "kda_use_full_proj"),
            ("linear_attn_config", None, "without linear_attn_config"),
            ("linear_attn_config", {**linear, "num_kv_heads": 2},
             "num_kv_heads"),
            ("num_key_value_heads", 3, "do not divide"),
            ("head_dim", 0, "needs head_dim")):
        with pytest.raises(ValueError, match=message):
            DecoderConfig.from_dict({**base, key: value})


def test_double_layer_keys_the_decoder_cannot_build_are_errors():
    base = longcat_flash.program_config(TINY_LONGCAT)
    cfg = DecoderConfig.from_dict(base)
    # the source's names, under the fields the decoder reads
    assert (cfg.num_hidden_layers, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.num_experts_per_tok) == (
                2, 256, 64, 3)
    assert cfg.shortcut_double_layers and cfg.latent_sublayers == 4
    assert (cfg.scoring_func, cfg.zero_expert_num, cfg.n_shared_experts,
            cfg.norm_topk_prob, cfg.router_choice_bias) == (
                "softmax", 4, 0, False, True)
    assert cfg.layer_kinds == ("mla", "mla")
    for key, value, message in (
            ("attention_method", "GQA", "attention_method"),
            ("zero_expert_type", "copy", "zero_expert_type"),
            ("router_bias", True, "router_bias"),
            ("rope_scaling", {"type": "linear"}, "yarn")):
        with pytest.raises(ValueError, match=message):
            DecoderConfig.from_dict({**base, key: value})
    with pytest.raises(ValueError, match="latent attention needs"):
        DecoderConfig.from_dict(
            {k: v for k, v in base.items() if k != "q_lora_rank"})


# ------------------------------- (b) -------------------------------------- #

BLOCK, BUCKET = 8, 16


class _SwappedSublayers:
    """A latent hook whose double layers' second sublayer reads and writes
    the first's row of the plane, and the first the second's: the planted
    addressing fault."""

    def __init__(self, hook):
        self._hook = hook

    def latent_attention(self, k):
        return self._hook.latent_attention(k ^ 1)


@pytest.mark.parametrize("prompt_len", [5, 8, 16, 17, 23])
@pytest.mark.parametrize("family", LATENT)
def test_prefill_then_decode_logits_match_reference(built, family,
                                                    prompt_len):
    """Prompts that end inside a block, on a block boundary, on a bucket
    boundary and past both (5 is shorter than its bucket of 16); logits of
    the prefill's last row and of every decode step against the
    reference's full forward.  A double layer's two sublayers keep two
    rows of the plane."""
    model, params = built[family]
    spec = model.cache_spec()
    assert spec.layers == {"axk1": 3, "longcat_flash": 4}[family]
    rng = np.random.default_rng(prompt_len)
    new = 6
    seq = rng.integers(0, 512, prompt_len + new).astype(np.int32)
    want = _reference_logits(params, seq[None], family)[0]
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
    if family != "longcat_flash":
        return
    # every sublayer wrote rows of its own, and the two of a double layer
    # are distinct: a decode step that reads them swapped moves the logits
    # a thousand tolerances (the rows are the same positions' latents of
    # other weights, so the result stays a plausible logit row)
    rows = np.asarray(pages)[:, 3, :5]
    assert all(rows[k].any() for k in range(4))
    assert not np.allclose(rows[0], rows[1], atol=1e-3)
    t = prompt_len + new - 1
    hook = LatentAttentionHook(
        pages, jnp.asarray(table), jnp.array([[t]], jnp.int32),
        mode="decode", lengths=jnp.array([t + 1], jnp.int32))
    swapped = model.apply(
        {"params": params}, seq[None, t:t + 1], train=False,
        positions=jnp.array([[t]], jnp.int32), decode=True,
        kv_cache=_SwappedSublayers(hook))
    assert np.abs(np.asarray(swapped[0, 0]) - want[t]).max() > 2e-2


@pytest.mark.parametrize("attention", ["dense", "flash"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_engine_serves_the_reference_greedy_stream(built, family, attention):
    """Through ``ServingEngine`` (submit, step, generate; scheduler,
    allocator, ``_launch``): every served token is the argmax of the
    reference's full forward of the prompt and the tokens before it.  Five
    requests on three slots: two slots serve a second request after their
    first, and a state layer's rows there are the second's alone."""
    model, params = built[family]
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
        want = _reference_logits(params, seq[None], family)[0]
        at = len(prompt) - 1 + np.arange(len(tokens))
        assert tokens == list(want[at].argmax(-1))
    m = eng.metrics
    assert m.cache_bytes_per_token.value == eng.cache.bytes_per_token
    assert m.expert_assignments.value > 0
    assert m.expert_load_max_over_mean.value >= 1.0
    assert eng.allocator.used_blocks == 0
    # a router with zero-compute outputs says what share of the live
    # tokens' assignments went to them; no other engine has the series
    if family == "longcat_flash":
        assert 0.0 < m.zero_expert_share.value < 1.0
    else:
        assert m.zero_expert_share is None


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_step_in_flight_serves_the_tokens_of_read_then_dispatch(built,
                                                                  family):
    """One case a cache kind (latent rows, per-slot state beside rows,
    zero-compute picks handed back beside the counts): with a decode step
    dispatched before the one before it is read (lag 1, what a greedy
    engine derives) every request gets the tokens of the read-then-dispatch
    order (lag 0, reached through the private attribute), ending by count
    and on an ``eos_id``; the slots turn over, so a state row and a freed
    block are met again."""
    model, params = built[family]
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 512, n).astype(np.int32)
               for n in (5, 17, 9, 12)]
    caps = [6, 3, 1, 5]

    def serve(eng, lag, eos):
        eng._lag = lag
        rids = [eng.submit(p, c, eos_id=eos) for p, c in zip(prompts, caps)]
        eng.run()
        assert eng.scheduler.in_flight == 0 and eng.allocator.used_blocks == 0
        return [list(eng.result(r).tokens) for r in rids]

    eng = ServingEngine(model, params, ServeConfig(
        max_seqs=2, kv_block_size=BLOCK, max_seq_len=64,
        prefill_pad_multiple=BUCKET))
    assert eng._lag == 1
    by_count = serve(eng, 1, None)
    assert [len(s) for s in by_count] == caps
    assert serve(eng, 0, None) == by_count
    assert eng.metrics.decode_steps_ahead.value > 0
    # a token inside the first request's stream ends it early
    eos = by_count[0][2]
    on_eos = serve(eng, 1, eos)
    assert len(on_eos[0]) <= 3 and on_eos[0][-1] == eos
    assert serve(eng, 0, eos) == on_eos


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


def test_decode_program_hands_back_the_zero_compute_picks_a_row(built):
    model, params = built["longcat_flash"]
    assert (model.experts_held, model.zero_experts,
            model.experts_per_token) == (4, 4, 3)
    eng = ServingEngine(model, params, ServeConfig(
        max_seqs=2, kv_block_size=BLOCK, max_seq_len=32,
        prefill_pad_multiple=BUCKET))
    eng.submit(np.arange(6, dtype=np.int32), 3)
    eng.step()
    tokens, positions, tables, context = eng.scheduler.decode_batch()
    out = eng._decode_jit(params, *eng.cache.pages, tokens, positions,
                          tables, context)
    assert len(out) == 4  # tokens, held counts, zero picks, the one plane
    held, zero = np.asarray(out[1]), np.asarray(out[2])
    # a row an expert layer: the held experts' counts; each slot's picks
    # among the zero-compute outputs, of its 3 a layer
    assert held.shape == (2, 4) and zero.shape == (2, 2)
    assert zero.dtype == np.int32 and (0 <= zero).all() and (zero <= 3).all()
    assert (held.sum(axis=1) + zero.sum(axis=1) <= 2 * 3).all()


def _engine_under_a_listening_profiler(built, monkeypatch, family, **config):
    """An engine of three slots of four blocks for ``family`` (``"mha"``: a
    tiny GPT; ``"latent"``, ``"hybrid"``, ``"double"``: the three decoder
    families) and the list every span it opens lands in with its
    attributes, in place of the profiler's annotation."""
    import contextlib

    from stoke_tpu.telemetry import tracing

    seen = []

    def fake_xprof_span(name, **stats):
        seen.append((name, stats))
        return contextlib.nullcontext()

    monkeypatch.setattr(tracing, "xprof_span", fake_xprof_span)
    if family != "mha":
        model, params = built[{"latent": "axk1", "hybrid": "solar_open2",
                               "double": "longcat_flash"}[family]]
    else:
        model = GPT(vocab_size=64, size_name="tiny", max_len=32)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ServingEngine(model, params, ServeConfig(
        max_seqs=3, kv_block_size=BLOCK, max_seq_len=32,
        prefill_pad_multiple=BUCKET, **config))
    return eng, seen


@pytest.mark.parametrize("family", ["latent", "mha", "hybrid", "double"])
def test_commit_span_says_what_the_decode_step_read(built, monkeypatch,
                                                    family):
    """``serve/commit`` carries the decode rows' ``context_tokens`` and the
    ``window_blocks`` one layer's attention read for them: the paged kernel
    of a latent or hybrid cache reads to each slot's own length, the MHA
    gather takes every slot's whole table.  A model with per-slot state
    also says how many bytes of it the live slots' layers read and wrote."""
    eng, seen = _engine_under_a_listening_profiler(built, monkeypatch, family)
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
    assert commits[-1]["window_blocks"] == (3 * 4 if family == "mha"
                                            else 2 + 3)
    assert all(c["window_blocks"] > 0 for c in commits)
    if family == "hybrid":
        # two live slots x three delta-rule layers, 4 heads of 32 x 32
        # float32 state read and written once
        assert commits[-1]["state_bytes"] == 2 * 3 * 2 * 4 * 32 * 32 * 4
        # and how often the state layers' kernel moved them: its grid walks
        # all three slots for the two that are live
        assert commits[-1]["state_passes"] == pytest.approx(3 / 2)
        assert eng.metrics.state_passes.value == commits[-1]["state_passes"]
    else:
        assert not any("state_bytes" in c or "state_passes" in c
                       for c in commits)
        assert eng.metrics.state_passes is None
    # an expert model says how often its grouped products streamed the held
    # weights: once each, less the experts that drew no row in a layer
    if family != "mha":
        assert all(0.0 < c["expert_weight_passes"] <= 1.0 for c in commits)
        assert eng.metrics.expert_weight_passes.value == (
            commits[-1]["expert_weight_passes"])
    else:
        assert not any("expert_weight_passes" in c for c in commits)
        assert eng.metrics.expert_weight_passes is None
    # ``window_blocks`` above is ONE latent sublayer's, 2 + 3, though a
    # double layer reads two rows of the plane; and a router with
    # zero-compute outputs says what share of the live rows' assignments
    # (2 rows x 3 a token x 2 expert layers) went to them
    if family == "double":
        assert all(0.0 <= c["zero_expert_share"] <= 1.0 for c in commits)
        assert round(commits[-1]["zero_expert_share"] * 12, 6) % 1 == 0
        assert any(c["zero_expert_share"] > 0 for c in commits)
        assert eng.metrics.zero_expert_share.value == (
            commits[-1]["zero_expert_share"])
    else:
        assert not any("zero_expert_share" in c for c in commits)


@pytest.mark.parametrize("family,sampling,fetches", [
    ("mha", False, 1), ("latent", False, 2), ("hybrid", False, 2),
    ("double", False, 3), ("mha", True, 2),
])
def test_commit_span_counts_the_steps_round_trip(built, monkeypatch, family,
                                                 sampling, fetches):
    """``serve/commit`` says what the step's round trip was made of: the
    fetches of the read (the tokens; the held experts' counts; the
    zero-compute picks; key data when sampling) with their bytes and what
    the fetches after the first cost, the arrays the upload put on the
    device with theirs, and what the step's own accounting cost between the
    read's return and the commit.  ``serve/prefill`` says at its opening
    what it will upload and fetch."""
    eng, seen = _engine_under_a_listening_profiler(
        built, monkeypatch, family, sampling=sampling)
    eng.submit(np.arange(9, dtype=np.int32), 4)
    eng.submit(np.arange(17, dtype=np.int32), 4)
    eng.run()
    commits = [stats for name, stats in seen if name == "serve/commit"]
    assert len(commits) >= 3
    B, MB, int32 = 3, 4, 4
    read = B * int32  # the tokens, one a slot
    layers = {"mha": 0, "latent": 2, "hybrid": 4, "double": 2}[family]
    read += layers * 4 * int32  # 4 held experts' counts an expert layer
    if family == "double":
        read += layers * B * int32  # each slot's zero-compute picks a layer
    # tokens, positions, block tables, context lengths
    uploads, upload = 4, 3 * B * int32 + B * MB * int32
    if sampling:
        key_data = eng._key_data
        read += key_data.nbytes
        # key data, temperatures, top-ks, top-ps
        uploads, upload = 8, upload + key_data.nbytes + 3 * B * 4
    for c in commits:
        assert c["read_fetches"] == fetches and c["read_bytes"] == read
        assert c["upload_arrays"] == uploads and c["upload_bytes"] == upload
        assert isinstance(c["read_extra_us"], float)
        assert isinstance(c["account_us"], float)
        # two clock readings apart at least, and no step of this size
        # spends a quarter second on either
        assert 0 < c["read_extra_us"] < 250e3 and 0 < c["account_us"] < 250e3
    prefills = [stats for name, stats in seen if name == "serve/prefill"]
    assert [p["prompt_len"] for p in prefills] == [9, 17]
    for p in prefills:
        # the padded prompt, the slot's table row, its length, a state
        # model's slot; when sampling the slot's key data and three knobs
        want = p["padded_len"] * int32 + MB * int32 + int32
        want += int32 * (family == "hybrid")
        want += (key_data[:1].nbytes + 3 * 4) if sampling else 0
        assert p["upload_bytes"] == want
        assert p["read_fetches"] == 1 + sampling
    # no new span: the decode step's tree is what it was, the commit bare
    names = {name for name, _ in seen}
    assert names == {
        "serve/step", "serve/admit", "serve/prefill", "serve/prefill/upload",
        "serve/prefill/dispatch", "serve/prefill/read", "serve/decode_step",
        "serve/decode_step/batch", "serve/decode_step/upload",
        "serve/decode_step/dispatch", "serve/decode_step/read",
        "serve/commit", "serve/gauges"}


# sha256 (its first 16 digits) of each program's StableHLO as it lowered at
# PR 36 (e5636b3) for the engine of three slots above: PR 37 put counters
# around the dispatch and the read and left the programs alone.  A PR that
# changes a program on purpose records the new digest here.
LOWERED = {
    ("latent", "serve_decode"): "c211a2049c4993ec",
    ("latent", "serve_prefill"): "87de03baaba77ccd",
    ("hybrid", "serve_decode"): "95109edbc7f339f8",
    ("hybrid", "serve_prefill"): "cc0829215b8ae2b1",
    ("double", "serve_decode"): "26701ad972a93506",
    ("double", "serve_prefill"): "f0167de255ba8a8b",
    ("mha", "serve_decode"): "b73d1d664954d802",
    ("mha", "serve_prefill"): "641004fed3c148bb",
}


@pytest.mark.parametrize("family,program", list(LOWERED))
def test_serve_programs_lower_to_the_stablehlo_they_did(built, monkeypatch,
                                                        family, program):
    import hashlib

    eng, _ = _engine_under_a_listening_profiler(built, monkeypatch, family)
    batch = eng.scheduler.decode_batch()
    if program == "serve_decode":
        jitted, args = eng._decode_jit, batch
    else:
        jitted = eng._prefill_jit
        args = (np.zeros((1, BUCKET), np.int32), batch[2][:1],
                np.array([5], np.int32))
        if eng.cache.state:
            args += (np.array([0], np.int32),)
    text = jitted.lower(eng.qparams, *eng.cache.pages, *eng.cache.state,
                        *args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == LOWERED[
        family, program]


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


def test_hybrid_decode_lowers_to_one_state_kernel_a_layer_in_place(
        tiny_solar, monkeypatch):
    """The hybrid's decode program as it lowers for the TPU: each of the
    three delta-rule layers is one ``delta_rule_step`` Mosaic call that
    takes the layer's state array and hands it back aliased, and the
    program's own state arguments are donated: nothing on the way holds a
    second copy of a layer's state."""
    model, params = tiny_solar
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = ServingEngine(model, params, ServeConfig(
        max_seqs=3, kv_block_size=BLOCK, max_seq_len=40,
        prefill_pad_multiple=BUCKET))
    text = eng._decode_jit.trace(
        params, *eng.cache.pages, *eng.cache.state,
        *eng.scheduler.decode_batch()).lower(
            lowering_platforms=("tpu",)).as_text()
    # the three layers call one function, which holds the one Mosaic call
    assert len(re.findall(r"call @_step\w*\(", text)) == 3
    (kernel,) = [line for line in text.splitlines()
                 if 'kernel_name = "delta_rule_step"' in line]
    assert "operand_index = 3" in kernel
    # [slots, blocks, heads, dk, dv]
    assert kernel.rsplit(" : (", 1)[1].split(") -> ")[0].split(
        ", ")[3] == "tensor<3x1x4x32x32xf32>"
    main = next(line for line in text.splitlines()
                if "func.func public @main" in line)
    donated = re.findall(
        r"tensor<3x4x32x32xf32> \{[^}]*tf\.aliasing_output", main)
    assert len(donated) == 3, main[:600]


def test_served_decode_steps_equal_the_plain_delta_rule_stepped(
        tiny_solar, monkeypatch):
    """Two engines over the same three requests on two slots, one decoding
    through the kernel and one through the plain form in its place: the
    same tokens, and after every engine step the same state arrays (the
    recurrent state and the convolution's inputs of each delta-rule layer,
    every slot's rows, the one idle at first too).  What stands between the
    kernel and the cache is under test: ``_LayerState.write``, the program's
    threading of the state arrays and, where the backend has it, their
    donation."""
    model, params = tiny_solar
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (7, 18, 12)]

    def engine():
        eng = ServingEngine(model, params, ServeConfig(
            max_seqs=2, kv_block_size=BLOCK, max_seq_len=48,
            prefill_pad_multiple=BUCKET))
        return eng, [eng.submit(prompt, 6) for prompt in prompts]

    got, got_ids = engine()
    stepped = []
    while got.scheduler.has_work:
        got.step()
        stepped.append([np.asarray(a) for a in got.cache.state])

    monkeypatch.setattr(decoder, "delta_rule_step", delta_rule_reference)
    want, want_ids = engine()
    for step, arrays in enumerate(stepped):
        want.step()
        assert len(arrays) == len(want.cache.state) == 6
        for layer, (a, b) in enumerate(zip(arrays, want.cache.state)):
            np.testing.assert_allclose(
                a, np.asarray(b), atol=1e-5, rtol=0,
                err_msg=f"engine step {step}, state array {layer}")
    assert not want.scheduler.has_work
    assert len(stepped) > 6  # the third request waited for a slot
    for a, b in zip(got_ids, want_ids):
        tokens = got.scheduler.finished[a].tokens
        assert len(tokens) == 6
        assert list(tokens) == list(want.scheduler.finished[b].tokens)


def _delta_rule_inputs(rng, B, L, H, d):
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    k = f(B, L, H, d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    # log decays from -0.02 to -7 a position: slow channels and ones that
    # forget within a chunk, whose running product underflows float32
    g = -jnp.exp(2.0 * f(B, L, H, d) - 1.0)
    beta = 2.0 * jax.nn.sigmoid(f(B, L, H))  # in (0, 2)
    return f(B, L, H, d) * d ** -0.5, k, f(B, L, H, d), g, beta


def _stepped(q, k, v, g, beta, state):
    outs = []
    for t in range(q.shape[1]):
        o, state = delta_rule_step(
            state, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        outs.append(o)
    return jnp.stack(outs, axis=1), state


@pytest.mark.parametrize("L,chunk,then_stepped", [
    (1, 32, 0), (31, 32, 0), (32, 32, 0), (33, 32, 7),
    (70, 32, 0), (70, 32, 19), (12, 5, 4), (64, 64, 0),
])
def test_whole_prompt_delta_rule_equals_the_one_token_form_stepped(
        monkeypatch, L, chunk, then_stepped):
    """``delta_rule_chunked`` (prefill) against ``delta_rule_step`` (decode)
    taken ``L`` times from zero state: outputs and the state after the last
    position, at lengths that are a part of a chunk, a chunk, and chunks and
    a part; ``then_stepped`` further positions taken a token at a time from
    the whole-prompt form's state, as decode follows prefill, land where
    stepping all the way does."""
    monkeypatch.setattr(decoder, "DELTA_RULE_CHUNK", chunk)
    rng = np.random.default_rng(L * 100 + chunk)
    B, H, d = 2, 3, 16
    q, k, v, g, beta = _delta_rule_inputs(rng, B, L + then_stepped, H, d)
    want_o, want_S = _stepped(q, k, v, g, beta,
                              jnp.zeros((B, H, d, d), jnp.float32))
    got_o, got_S = delta_rule_chunked(
        *(t[:, :L] for t in (q, k, v, g, beta)))
    assert got_o.shape == (B, L, H, d) and got_S.shape == (B, H, d, d)
    if then_stepped:
        more_o, got_S = _stepped(
            *(t[:, L:] for t in (q, k, v, g, beta)), got_S)
        got_o = jnp.concatenate([got_o, more_o], axis=1)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=1e-4, rtol=0)
    np.testing.assert_allclose(np.asarray(got_S), np.asarray(want_S),
                               atol=1e-4, rtol=0)


def test_masked_positions_leave_the_delta_rules_state_as_it_was():
    """How prefill masks a bucket's padding: ``beta = 0`` and ``g = 0`` at
    and past a length give the state at that length, whatever the padding
    holds."""
    rng = np.random.default_rng(9)
    B, L, H, d, n = 1, 48, 2, 16, 37
    q, k, v, g, beta = _delta_rule_inputs(rng, B, L, H, d)
    _, want = delta_rule_chunked(q[:, :n], k[:, :n], v[:, :n], g[:, :n],
                                 beta[:, :n])
    live = jnp.arange(L) < n
    _, got = delta_rule_chunked(
        q, k, v, jnp.where(live[None, :, None, None], g, 0.0),
        jnp.where(live[None, :, None], beta, 0.0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=0)


def test_hybrid_prefill_then_decode_logits_match_reference(tiny_solar):
    """Prefill and every decode step of two requests through the hybrid
    cache, logits against the reference's full forward: a prompt shorter
    than its bucket (11 of 16) and one a position past it (17 of 32); three
    slots decode together, one of them idle and carrying whatever an
    earlier request left; then the second request takes over the first's
    slot, whose state rows hold the first's last state."""
    model, params = tiny_solar
    spec = model.cache_spec()
    assert spec.layers_of("rows") == (0,) and spec.layers_of("state") == (
        1, 2, 3)
    rng = np.random.default_rng(4)
    SLOTS, new = 3, 5
    cache = PagedKVCache(
        1, 13, BLOCK, spec.planes, state=spec.state, state_layers=3,
        max_seqs=SLOTS)
    # whatever an earlier request left in every slot, finite
    arrays = cache.pages + tuple(
        jnp.asarray(rng.standard_normal(a.shape), a.dtype)
        for a in cache.state)
    tables = np.zeros((SLOTS, 4), np.int32)  # 4 blocks of 8: 32 positions

    def hook(arrays, tables, positions, mode, lengths, slot=None):
        return HybridCacheHook(
            arrays[:1], arrays[1:], jnp.asarray(tables),
            jnp.asarray(positions, jnp.int32), mode=mode,
            lengths=jnp.asarray(lengths, jnp.int32),
            layer_kinds=spec.layer_kinds,
            slot=None if slot is None else jnp.array([slot], jnp.int32))

    def serve(arrays, slot, blocks, prompt_len):
        seq = rng.integers(0, 512, prompt_len + new).astype(np.int32)
        want = _reference_logits(params, seq[None], "solar_open2")[0]
        tables[slot] = blocks
        padded = -(-prompt_len // BUCKET) * BUCKET
        tokens = np.zeros((1, padded), np.int32)
        # the bucket's padding holds other tokens, not zeros: masked all the same
        tokens[0] = rng.integers(0, 512, padded)
        tokens[0, :prompt_len] = seq[:prompt_len]
        h = hook(arrays, tables[slot:slot + 1], np.arange(padded)[None],
                 "prefill", [prompt_len], slot)
        logits = model.apply(
            {"params": params}, tokens, train=False,
            positions=jnp.arange(padded, dtype=jnp.int32)[None], kv_cache=h)
        np.testing.assert_allclose(np.asarray(logits[0, prompt_len - 1]),
                                   want[prompt_len - 1], atol=2e-5, rtol=0)
        arrays = h.pages + h.state
        for t in range(prompt_len, prompt_len + new):
            # every slot steps: the others feed token 0 at position 0
            # against an all-scratch table, as the scheduler has idle ones
            step_tables = np.zeros_like(tables)
            step_tables[slot] = tables[slot]
            ids = np.zeros((SLOTS, 1), np.int32)
            at = np.zeros((SLOTS, 1), np.int32)
            lens = np.ones(SLOTS, np.int32)
            ids[slot], at[slot], lens[slot] = seq[t], t, t + 1
            h = hook(arrays, step_tables, at, "decode", lens)
            logits = model.apply(
                {"params": params}, ids, train=False,
                positions=jnp.asarray(at), decode=True, kv_cache=h)
            arrays = h.pages + h.state
            np.testing.assert_allclose(np.asarray(logits[slot, 0]), want[t],
                                       atol=2e-5, rtol=0)
        assert all(np.isfinite(np.asarray(a)).all() for a in arrays)
        return arrays

    arrays = serve(arrays, 1, [3, 9, 7, 5], 11)
    before = [np.asarray(a) for a in arrays]
    arrays = serve(arrays, 1, [2, 4, 6, 8], 17)  # the same slot again
    # a prefill writes its own slot's state rows and no other's
    h = hook(arrays, tables[2:3], np.arange(BUCKET)[None], "prefill", [3], 2)
    model.apply({"params": params}, np.zeros((1, BUCKET), np.int32),
                train=False,
                positions=jnp.arange(BUCKET, dtype=jnp.int32)[None],
                kv_cache=h)
    assert len(h.state) == 3 * 2  # (state, conv) a delta-rule layer
    for old, now, fresh in zip(before[1:], arrays[1:], h.state):
        assert not np.array_equal(old[1], np.asarray(now)[1])
        np.testing.assert_array_equal(np.asarray(fresh)[:2],
                                      np.asarray(now)[:2])
        assert not np.array_equal(np.asarray(fresh)[2], np.asarray(now)[2])


# ------------------------------- (d) -------------------------------------- #


@pytest.mark.parametrize("family,shares,top_k,layer,keys", [
    ("axk1", 4, 2,
     dict(n_group=2, topk_group=1, routed_scaling_factor=2.5), {}),
    # the second family's layer: one group (a plain top-k), factor 1, and
    # the deployment's 8 shares
    ("solar_open2", 8, 2,
     dict(n_group=1, topk_group=1, routed_scaling_factor=1.0), {}),
    # the third's: 4 shares of 8 over 32 routed experts + 16 zero-compute
    # outputs, a softmax router with a choice bias, 6 a token not
    # renormalised, no shared expert
    ("longcat_flash", 4, 6,
     dict(routed_scaling_factor=6.0, norm_topk_prob=False,
          n_shared_experts=0, scoring="softmax", choice_bias=True,
          zero_experts=16),
     dict(routed_scaling_factor=6.0, zero_expert_num=16, moe_topk=6)),
])
def test_all_shares_and_the_common_part_once_add_up_to_the_uncut_layer(
        family, shares, top_k, layer, keys):
    """The guide's share test: the parts of the result all the shares of
    one layer give, with what every chip computes alike (the shared expert;
    the zero-compute experts' part) counted once, add up to what the
    family's reference gives for the uncut layer."""
    module, tiny_config = FAMILIES[family]
    per = 8 if family == "longcat_flash" else 2
    E, hidden, ff = per * shares, 32, 16
    config = {**tiny_config, "hidden_size": hidden,
              "moe_intermediate_size": ff, "n_routed_experts": E,
              "published": {"n_routed_experts": E},
              "deployment": {"first_expert": 0},
              **(keys or layer)}
    kwargs = dict(hidden=hidden, ff=ff, num_experts=E, top_k=top_k, **layer)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((3, 7, hidden)),
                    jnp.float32)
    whole = ExpertShareFFN(held=(0, E), **kwargs)
    params = whole.init(jax.random.PRNGKey(0), x)["params"]
    if "e_score_correction_bias" in params:
        # the size of a mean score, so that it moves the choice
        params = {**params, "e_score_correction_bias": jnp.asarray(
            np.random.default_rng(3).normal(0, 1 / 48, 48), jnp.float32)}
    flat = x.reshape(-1, hidden)
    with jax.default_matmul_precision("highest"):
        uncut = module.expert_ffn(config, params, flat, (0, E))
        # no expert held: what every chip adds alike
        common = module.expert_ffn(config, params, flat, (0, 0))
    assert float(jnp.abs(common).max()) > 0.01
    routed_sum, counted, zero_picks = 0.0, 0, []
    for s in range(shares):
        mine = {**params, **{n: params[n][s * per:(s + 1) * per]
                             for n in ("w_gate", "w_up", "w_down")}}
        out, sown = ExpertShareFFN(held=(s * per, per), **kwargs).apply(
            {"params": mine}, x, mutable=["intermediates"])
        with jax.default_matmul_precision("highest"):
            want = module.expert_ffn(config, mine, flat, (s * per, per))
        np.testing.assert_allclose(np.asarray(out.reshape(-1, hidden)),
                                   np.asarray(want), atol=2e-5, rtol=0)
        routed_sum = routed_sum + out.reshape(-1, hidden) - common
        counted += int(sown["intermediates"]["expert_counts"][0].sum())
        zero_picks.append(sown["intermediates"].get("zero_expert_count"))
    np.testing.assert_allclose(np.asarray(routed_sum + common),
                               np.asarray(uncut), atol=5e-5, rtol=0)
    if family == "longcat_flash":
        # every share counts the same zero-compute picks: once
        zero = np.asarray(zero_picks[0][0])
        assert all((np.asarray(z[0]) == zero).all() for z in zero_picks)
        assert zero.shape == (21,) and 0 < zero.sum() < 21 * top_k
        counted += int(zero.sum())
    else:
        assert zero_picks == [None] * shares
    # every assignment computed once, none dropped
    assert counted == 3 * 7 * top_k


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


def test_softmax_router_chooses_by_the_biased_score_and_weighs_by_the_plain():
    """The choice follows ``s + b``, the weights are ``6 s`` of the chosen,
    unbiased and not renormalised; ties go to the lower index; the
    reference's router gives the same as dense weights."""
    rng = np.random.default_rng(13)
    logits = rng.standard_normal((64, 24)).astype(np.float32)
    logits[1, :] = 0.25                      # every score equal
    logits[2, 5] = logits[2, 17] = 3.0       # a tie at the top
    bias = rng.normal(0, 1 / 24, 24).astype(np.float32)
    bias[7] = 1.0                            # always chosen, whatever s
    bias[5] = bias[17] = 0.5                 # the tie stays one with them
    got_e, got_w = softmax_topk(jnp.asarray(logits), jnp.asarray(bias), 4, 6.0)
    z = np.exp(logits.astype(np.float64)
               - logits.max(-1, keepdims=True).astype(np.float64))
    scores = z / z.sum(-1, keepdims=True)
    biased = (scores.astype(np.float32) + bias).astype(np.float64)
    want_e = np.argsort(-biased, axis=-1, kind="stable")[:, :4]
    np.testing.assert_array_equal(np.asarray(got_e), want_e)
    np.testing.assert_allclose(
        np.asarray(got_w), 6.0 * np.take_along_axis(scores, want_e, -1),
        rtol=1e-5)
    assert (np.asarray(got_e)[:, 0] == 7).all()
    assert np.asarray(got_w)[1].sum() == pytest.approx(6 * 4 / 24, rel=1e-5)
    # equal scores: the bias alone chooses; equal biased scores: the lower
    assert list(np.asarray(got_e)[1]) == list(
        np.argsort(-bias, kind="stable")[:4])
    assert list(np.asarray(got_e)[2][:3]) == [7, 5, 17]
    # without a bias the choice is by the score alone
    plain_e, _ = softmax_topk(jnp.asarray(logits), None, 4, 6.0)
    assert list(np.asarray(plain_e)[1]) == [0, 1, 2, 3]
    assert list(np.asarray(plain_e)[2][:2]) == [5, 17]
    dense = np.asarray(longcat_flash.route(
        {"moe_topk": 4, "routed_scaling_factor": 6.0},
        jnp.asarray(logits), jnp.asarray(bias)))
    want_dense = np.zeros_like(dense)
    np.put_along_axis(want_dense, want_e, np.asarray(got_w), axis=1)
    np.testing.assert_allclose(dense, want_dense, rtol=1e-6)


def test_a_token_with_only_zero_compute_picks_gets_its_weights_times_itself():
    """Twelve picks, all among the zero-compute outputs (the bias puts them
    first): the layer's result is ``sum w * x`` and nothing else, no held
    expert draws a row, and the picks are counted a token."""
    kwargs = dict(
        hidden=32, ff=16, num_experts=16, held=(4, 8), top_k=12,
        routed_scaling_factor=6.0, norm_topk_prob=False, n_shared_experts=0,
        scoring="softmax", choice_bias=True, zero_experts=12)
    layer = ExpertShareFFN(**kwargs)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 5, 32)),
                    jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    assert "shared" not in params and params["router"].shape == (32, 28)
    params = {**params, "e_score_correction_bias": jnp.asarray(
        np.r_[np.zeros(16), np.ones(12)], jnp.float32)}
    out, sown = layer.apply({"params": params}, x, mutable=["intermediates"])
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.softmax(x.reshape(10, 32) @ params["router"], -1)
    want = 6.0 * scores[:, 16:].sum(-1, keepdims=True) * x.reshape(10, 32)
    np.testing.assert_allclose(np.asarray(out.reshape(10, 32)),
                               np.asarray(want), atol=2e-6, rtol=0)
    assert not np.asarray(sown["intermediates"]["expert_counts"][0]).any()
    assert (np.asarray(sown["intermediates"]["zero_expert_count"][0])
            == 12).all()
    # what this layer is not built with raises
    for bad in (dict(scoring="sigmoid"), dict(n_group=2), dict(scoring="x")):
        with pytest.raises(ValueError, match="ExpertShareFFN"):
            ExpertShareFFN(**{**kwargs, **bad}).init(jax.random.PRNGKey(1), x)


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


def test_a_double_layer_model_keeps_a_row_of_the_plane_a_sublayer():
    published = _read("benchmark/configs/longcat-flash-omni.json")
    model = longcat_flash.build_model(published)
    spec = model.cache_spec()
    # one plane as axk1's, a row a latent sublayer: two a double layer
    assert spec.kind == "latent" and spec.layers == 2 * 4
    assert spec.planes == (("latent", 640),) and spec.values_per_token == 576
    assert (spec.heads, spec.head_dim) == (64, 192)
    assert spec.layer_kinds is None and spec.state == ()
    assert (model.experts_held, model.zero_experts,
            model.experts_per_token) == (8, 256, 12)
    pool = PagedKVCache(spec.layers, 3, 16, dtype=jnp.bfloat16,
                        planes=spec.planes)
    assert [p.shape for p in pool.pages] == [(8, 3, 16, 640)]
    assert pool.bytes_per_token == 8 * 640 * 2 and pool.state == ()
    # the cell's pool: 160 slots of 3072 positions and the scratch block
    assert 8 * (160 * 192 + 1) * 16 * 640 * 2 == 5_033_328_640


def test_gpt_describes_its_cache_as_two_planes():
    spec = GPT(vocab_size=64, size_name="tiny", max_len=32).cache_spec()
    assert spec.kind == "mha" and spec.planes == (("k", 128), ("v", 128))
    assert (spec.layers, spec.heads, spec.head_dim) == (2, 2, 64)
    assert spec.values_per_token == 256
    pool = PagedKVCache(2, 5, 8, spec.planes)
    assert pool.k_pages.shape == pool.v_pages.shape == (2, 5, 8, 128)
    assert pool.pages[0] is pool.k_pages and pool.pages[1] is pool.v_pages


def test_hybrid_cache_holds_planes_for_row_layers_and_state_for_the_rest():
    published = _read("benchmark/configs/solar-open2.json")
    model = solar_open2.build_model(published)
    spec = model.cache_spec()
    assert spec.kind == "hybrid" and spec.layers == 4
    assert spec.layer_kinds == ("rows", "state", "state", "state")
    # a token's keys then its values, 8 heads of 128 each, one row
    assert spec.planes == (("kv", 2 * 8 * 128),)
    assert spec.values_per_token == 2048
    assert spec.state == (("state", (64, 128, 128), "float32"),
                          ("conv", (3, 3 * 64 * 128), "cache"))
    assert (spec.heads, spec.head_dim) == (64, 128)
    assert model.experts_held == 40
    pool = PagedKVCache(1, 3, 16, spec.planes, dtype=jnp.bfloat16,
                        state=spec.state, state_layers=3, max_seqs=2)
    assert [p.shape for p in pool.pages] == [(1, 3, 16, 2048)]
    # an array an entry a state layer, layer by layer: a decode step
    # replaces each whole
    assert [(a.shape, str(a.dtype)) for a in pool.state] == 3 * [
        ((2, 64, 128, 128), "float32"), ((2, 3, 24576), "bfloat16")]
    assert pool.arrays == pool.pages + pool.state
    # one layer in four caches rows; the others 4 MB of state a slot each
    assert pool.bytes_per_token == 2048 * 2
    assert pool.nbytes == 3 * 16 * 2048 * 2
    assert pool.state_nbytes == 3 * 2 * (64 * 128 * 128 * 4 + 3 * 24576 * 2)
    # an mha or latent model sees the pool it saw: no state arrays
    plain = PagedKVCache(2, 5, 8, (("k", 128), ("v", 128)))
    assert plain.state == () and plain.arrays == plain.pages


# ------------------------------- (g) -------------------------------------- #


@pytest.mark.parametrize("option,program", [
    ({"sampling": True}, "sampling serve_prefill / serve_decode"),
    ({"prefill_chunk_tokens": 16}, "serve_prefill_chunk"),
    ({"sampling": True, "speculative_k": 2},
     "sampling serve_prefill / serve_decode"),
    ({"speculative_k": 2}, "serve_verify"),
    ({"quant": "int8"}, "quantized weight store"),
    ({"quant": "bf16"}, "quantized weight store"),
])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_options_without_a_program_raise_naming_it(built, family, option,
                                                   program):
    """A latent-cache model and a hybrid one (rows beside per-slot state)
    run through the greedy ``serve_prefill`` and ``serve_decode`` only:
    chunked prefill, verify with its rollback, sampling and the quantized
    store each raise, naming the cache kind and the program that is
    missing."""
    model, params = built[family]
    kind = model.cache_spec().kind
    assert kind == {"axk1": "latent", "solar_open2": "hybrid",
                    "longcat_flash": "latent"}[family]
    with pytest.raises(NotImplementedError,
                       match=f"a {kind}-cache model with experts has no "
                             f"{program}"):
        ServingEngine(model, params, ServeConfig(
            max_seqs=2, kv_block_size=8, max_seq_len=32,
            prefill_pad_multiple=16, **option))


@pytest.mark.parametrize("hook", [
    lambda mode: LatentAttentionHook(
        jnp.zeros((1, 2, 8, 128)), None, None, mode=mode, lengths=None),
    lambda mode: HybridCacheHook(
        (jnp.zeros((1, 2, 8, 128)),), (), None, None, mode=mode,
        lengths=None, layer_kinds=("rows", "state")),
], ids=["latent", "hybrid"])
def test_latent_and_hybrid_hooks_have_no_chunk_or_verify_mode(hook):
    for mode in ("chunk", "verify"):
        with pytest.raises(NotImplementedError, match=mode):
            hook(mode)


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


def test_counts_of_the_second_familys_published_share():
    config = _read("benchmark/configs/solar-open2.json")
    n = solar_open2.param_counts(config)
    # ISSUE 33's table, from the keys
    assert round(n["kda"] / 1e6, 1) == 137.7
    assert round(n["gqa"] / 1e6, 1) == 109.1
    assert round(n["ffn"] / 1e6, 1) == 646.2
    assert round(2 * n["embedding"] / 1e6, 1) == 201.3
    assert n["total"] == 3_308_352_064
    # and re-counted from the built tree
    model = solar_open2.build_model(config)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False))
    assert n["total"] == sum(
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))
    # a decode step moves every held weight once, the live K/V rows of the
    # one softmax layer, and every live slot's state there and back
    empty = solar_open2.decode_bytes_per_step(config, 0, 0)
    assert 6.4e9 < empty < 6.7e9
    per_row = solar_open2.decode_bytes_per_step(config, 0, 1000) - empty
    assert per_row == 1000 * 1 * 2 * 8 * 128 * 2
    per_slot = solar_open2.decode_bytes_per_step(config, 1, 0) - empty
    assert per_slot == (3 * 2 * (64 * 128 * 128 * 4 + 3 * 24576 * 2)
                        + 4096 * 2 + 4096)  # state, embedding row, K/V row
    # 2 FLOPs a matrix parameter met a token and the recurrence's three
    # passes and decay; attention pairs in the softmax layer only
    one = solar_open2.serve_flops(config, 0, 1, 0)
    assert 1.4e9 < one < 1.7e9
    assert solar_open2.serve_flops(config, 1, 0, 0) == one - 2 * n["embedding"]
    assert solar_open2.serve_flops(config, 0, 0, 1) == 4 * 64 * 128
    assert solar_open2.serve_flops(config, 0, 0, 0, 1) == 4 * 64 * 128
    assert solar_open2.train_flops_per_token(config, 1024) > 3 * (
        one - 2 * n["embedding"])


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
