"""Speculative decoding tests (ISSUE 17).

The contract under test: self-drafting speculative decode is a pure
dispatch-count optimization — the prompt-lookup drafter proposes k
tokens, ONE verify dispatch scores all k+1 positions, exact-match
acceptance emits the accepted run plus the correction token, and the
rollback steers every rejected draft's K/V restore so the cache is
bit-identical to a never-speculated engine.  Greedy speculative streams
must bit-match non-speculative streams; seeded sampling streams must
stay reproducible (one key split per EMITTED token); a
``speculative_k=None`` engine must not even construct the verify
programs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stoke_tpu.configs import ServeConfig
from stoke_tpu.models.gpt import GPT
from stoke_tpu.serving import ServingEngine, propose_draft
from stoke_tpu.serving.kv_cache import SCRATCH_BLOCK, PagedAttentionHook
from stoke_tpu.serving.sampling import (
    SamplingParams,
    accept_drafts,
    sample_tokens,
    select_key_data,
    speculative_sample_tokens,
    split_key_data,
)
from stoke_tpu.status import StokeStatus, StokeValidationError
from stoke_tpu.utils import init_module

pytestmark = pytest.mark.serving

VOCAB = 257

#: repetitive-text prompts — the workload prompt-lookup drafting exists
#: for (the tiled motifs repeat, so the drafter proposes the
#: continuation and the tiny GPT's cycling greedy stream accepts it)
REP_PROMPTS = [[5, 9, 3] * 4, [11, 2] * 6, [7] * 8, [1, 2, 3] * 4]


@pytest.fixture(scope="module")
def gpt():
    model = GPT(
        vocab_size=VOCAB, size_name="tiny", max_len=128, dropout_rate=0.0
    )
    variables = init_module(
        model, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32), train=False
    )
    return model, variables["params"]


def _cfg(**kw):
    base = dict(
        max_seqs=4, kv_block_size=8, max_seq_len=64, max_new_tokens=16,
        prefill_pad_multiple=16,
    )
    base.update(kw)
    return ServeConfig(**base)


def _gen(eng, prompts, n, sp=None):
    rids = [eng.submit(np.asarray(p, np.int32), n, sampling=sp)
            for p in prompts]
    eng.run()
    return [list(eng.scheduler.finished[r].tokens) for r in rids]


@pytest.fixture(scope="module")
def spec_run(gpt):
    """ONE greedy generation through a speculative engine and its
    non-speculative reference — the tests below assert different facets
    of the same run (engines compile once per module)."""
    model, params = gpt
    spec_eng = ServingEngine(
        model, params, _cfg(sampling=True, speculative_k=3)
    )
    ref_eng = ServingEngine(model, params, _cfg())
    return {
        "spec_eng": spec_eng,
        "ref_eng": ref_eng,
        "spec_out": _gen(spec_eng, REP_PROMPTS, 16),
        "ref_out": _gen(ref_eng, REP_PROMPTS, 16),
    }


# --------------------------------------------------------------------------- #
# drafter (host-side, jax-free)
# --------------------------------------------------------------------------- #


def test_propose_draft_continues_repeated_ngram():
    # tail bigram [8, 9] seen at the start, followed by [10, 11] there
    h = np.array([8, 9, 10, 11, 3, 8, 9], np.int32)
    assert propose_draft(h, 2) == [10, 11]
    # k caps the proposal; the continuation may run into the tail window
    assert propose_draft(h, 1) == [10]
    assert propose_draft(h, 5) == [10, 11, 3, 8, 9]


def test_propose_draft_prefers_longest_then_most_recent_match():
    # trigram [1,2,3] matches at position 0; the bigram [2,3] also
    # matches later — the longer (more specific) n-gram wins
    h = np.array([1, 2, 3, 7, 5, 2, 3, 9, 1, 2, 3], np.int32)
    assert propose_draft(h, 1) == [7]
    # with ngram_max=2 only the bigram is tried: most recent match wins
    assert propose_draft(h, 1, ngram_max=2) == [9]


def test_propose_draft_no_match_or_budget_is_empty():
    h = np.array([1, 2, 3, 4, 5], np.int32)
    assert propose_draft(h, 3) == []  # nothing repeats
    assert propose_draft(h, 0) == []  # no budget
    assert propose_draft(np.array([4], np.int32), 3) == []  # too short
    # periodic text matches its own overlapping window
    rep = np.array([5, 9, 5, 9, 5, 9], np.int32)
    assert propose_draft(rep, 2) != []
    assert propose_draft(rep, 2, ngram_min=3, ngram_max=4) == [5, 9]


# --------------------------------------------------------------------------- #
# accept/reject sampling layer
# --------------------------------------------------------------------------- #


def test_accept_drafts_counts_matched_prefix():
    targets = jnp.asarray([[4, 5, 6, 7], [4, 9, 6, 7], [1, 2, 3, 4]])
    drafts = jnp.asarray([[4, 5, 6], [4, 5, 6], [1, 2, 3]])
    lens = jnp.asarray([3, 3, 1])
    n_emit = accept_drafts(drafts, lens, targets)
    # row 0: all 3 accepted (+1 bonus) = 4; row 1: mismatch at i=1 -> 2;
    # row 2: draft_len caps acceptance at 1 (+1) = 2
    assert n_emit.tolist() == [4, 2, 2]


def test_speculative_sample_one_split_per_emitted_token():
    """The key stack produced by the scan must equal sequential
    split-and-draw, and select_key_data(stack, n) must be the key state
    after exactly n splits — the one-split-per-emitted-token discipline
    that keeps speculative and non-speculative draw streams in sync."""
    B, S, V = 2, 3, 11
    r = np.random.default_rng(0)
    logits = jnp.asarray(r.normal(size=(B, S, V)).astype(np.float32))
    kd0 = jnp.asarray(
        np.stack([np.asarray(jax.random.PRNGKey(i)) for i in range(B)])
    )
    temps = jnp.full((B,), 0.7, jnp.float32)
    ks = jnp.zeros((B,), jnp.int32)
    ps = jnp.ones((B,), jnp.float32)
    targets, stack = speculative_sample_tokens(logits, kd0, temps, ks, ps)
    kd = kd0
    for i in range(S):
        kd, sub = split_key_data(kd)
        tok = sample_tokens(logits[:, i], sub, temps, ks, ps)
        assert np.array_equal(np.asarray(targets[:, i]), np.asarray(tok))
        assert np.array_equal(np.asarray(stack[i]), np.asarray(kd))
        # select_key_data rewinds to the state after i+1 splits
        picked = select_key_data(stack, jnp.full((B,), i + 1, jnp.int32))
        assert np.array_equal(np.asarray(picked), np.asarray(kd))


# --------------------------------------------------------------------------- #
# verify attention + rollback
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize(
    "S,MB,steps",
    [(3, 4, (None, 2))]
    # the kernel's own step at table widths it must find a divisor for,
    # k = 1 and 3 drafts (S = k + 1 query rows)
    + [(k + 1, mb, (None,)) for mb in (3, 8) for k in (1, 3)],
)
def test_verify_attention_pallas_matches_reference(S, MB, steps):
    from _paged_reference import old_paged_attention
    from stoke_tpu.ops.flash_attention import (
        paged_verify_attention,
        paged_verify_attention_pallas,
    )

    B, H, D, BS = 3, 4, 16, 8
    NB = B * MB + 1
    r = np.random.default_rng(0)
    k_pages = jnp.asarray(r.normal(size=(NB, BS, H, D)).astype(np.float32))
    v_pages = jnp.asarray(r.normal(size=(NB, BS, H, D)).astype(np.float32))
    tables = jnp.asarray(
        np.arange(1, B * MB + 1, dtype=np.int32).reshape(B, MB)
    )
    # the last slot's final query row sits on the window's last position
    ctx = np.array([5, MB * BS // 2, MB * BS - S], np.int32)
    positions = jnp.asarray(
        np.stack([np.arange(c, c + S, dtype=np.int32) for c in ctx])
    )
    q = jnp.asarray(r.normal(size=(B, H, S, D)).astype(np.float32))
    ref = paged_verify_attention(q, k_pages, v_pages, tables, positions)
    np.testing.assert_allclose(
        np.asarray(old_paged_attention(q, k_pages, v_pages, tables,
                                       positions)),
        np.asarray(ref), atol=2e-5,
    )
    for ppb in steps:
        out = paged_verify_attention_pallas(
            q, k_pages, v_pages, tables, positions,
            pages_per_block=ppb, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5
        )


def test_verify_rollback_never_dirties_cache():
    """The never-dirty-cache guarantee: after rollback(n_keep), every
    draft position PAST the accepted window holds its pre-dispatch
    bytes, and accepted positions hold the fresh write — fixed-shape
    scratch steering, no branching."""
    L_, NB, BS, H, D = 1, 5, 4, 2, 3
    B, S = 2, 3
    r = np.random.default_rng(0)
    # the pool as PagedKVCache stores it: a token is one H*D-wide row
    k0 = jnp.asarray(r.normal(size=(L_, NB, BS, H * D)).astype(np.float32))
    v0 = jnp.asarray(r.normal(size=(L_, NB, BS, H * D)).astype(np.float32))
    tables = jnp.asarray([[1, 2], [3, 4]], np.int32)
    # slot 0 verifies positions 2..4 (crossing its block boundary at 4),
    # slot 1 positions 0..2
    positions = jnp.asarray([[2, 3, 4], [0, 1, 2]], np.int32)
    lengths = jnp.asarray([5, 3], np.int32)  # ctx + draft + 1 write budget
    hook = PagedAttentionHook(
        k0, v0, tables, positions, mode="verify", lengths=lengths
    )
    kw = jnp.asarray(r.normal(size=(B, H, S, D)).astype(np.float32))
    vw = jnp.asarray(r.normal(size=(B, H, S, D)).astype(np.float32))
    hook._write_layer(0, kw, vw)
    written_k = np.asarray(hook.k_pages)
    # slot 0 keeps 2 of its 3 rows, slot 1 keeps 1
    hook.rollback(jnp.asarray([2, 1], np.int32))
    k_after, v_after = np.asarray(hook.k_pages), np.asarray(hook.v_pages)

    def addr(slot, pos):
        return (0, int(tables[slot, pos // BS]), pos % BS)

    kept = [(0, 2), (0, 3), (1, 0)]
    rejected = [(0, 4), (1, 1), (1, 2)]
    for slot, pos in kept:
        assert np.array_equal(k_after[addr(slot, pos)],
                              written_k[addr(slot, pos)])
    for slot, pos in rejected:
        assert np.array_equal(k_after[addr(slot, pos)],
                              np.asarray(k0)[addr(slot, pos)])
        assert np.array_equal(v_after[addr(slot, pos)],
                              np.asarray(v0)[addr(slot, pos)])
    # everything the rollback touched is a rejected destination or the
    # scratch block (where kept rows' restores are steered) — no other
    # pool bytes moved
    diff = np.argwhere(written_k != k_after)
    assert set(diff[:, 1]) <= {SCRATCH_BLOCK} | {
        int(tables[s, p // BS]) for s, p in rejected
    }


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("H,D", [(2, 64), (12, 64), (16, 64)])
def test_flat_pool_verify_matches_old_pages_formulation(H, D, dtype):
    """Verify's S = k+1 query rows over the flat pool (whole pool + layer
    index) against the per-layer ``[NB, BS, H, D]`` formulation, ragged
    contexts and scratch-pointing table entries included; the Pallas
    kernel on one layer's reshaped pages agrees too."""
    from _paged_reference import flat_pool_case, old_paged_attention

    from stoke_tpu.ops.flash_attention import (
        paged_pool_attention,
        paged_verify_attention_pallas,
    )

    S = 4
    q, k_pool, v_pool, tables, positions = flat_pool_case(H, D, dtype, S)
    NB, BS = k_pool.shape[1:3]
    layer = 1
    out = paged_pool_attention(q, k_pool, v_pool, layer, tables, positions)
    pages = (
        k_pool[layer].reshape(NB, BS, H, D),
        v_pool[layer].reshape(NB, BS, H, D),
    )
    ref = old_paged_attention(q, *pages, tables, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    if (H, D) == (2, 64):  # the interpreter is slow at the wide shapes
        pal = paged_verify_attention_pallas(
            q, *pages, tables, positions, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(pal), np.asarray(ref), atol=2e-5
        )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_verify_rollback_restores_flat_pool_exactly(dtype):
    """Every layer of a flat pool: rows written by verify and rolled back
    in full are bit-identical to the pool before the dispatch, everywhere
    but the scratch block; rows kept hold what was written."""
    n_layers, NB, BS, H, D = 3, 5, 4, 2, 64
    B, S = 2, 3
    r = np.random.default_rng(1)
    k0, v0 = (
        jnp.asarray(
            r.normal(size=(n_layers, NB, BS, H * D)).astype(np.float32)
        ).astype(dtype)
        for _ in range(2)
    )
    tables = jnp.asarray([[1, 2], [3, 4]], np.int32)
    positions = jnp.asarray([[2, 3, 4], [0, 1, 2]], np.int32)
    lengths = jnp.asarray([5, 3], np.int32)
    written = []
    hook = PagedAttentionHook(
        k0, v0, tables, positions, mode="verify", lengths=lengths
    )
    for layer in range(n_layers):
        kw, vw = (
            jnp.asarray(r.normal(size=(B, H, S, D)).astype(np.float32))
            for _ in range(2)
        )
        hook._write_layer(layer, kw, vw)
        written.append(kw)
    assert hook.k_pages.shape == k0.shape and hook.k_pages.dtype == dtype
    # slot 0's row 0 (position 2 -> block 1, offset 2) holds head-major
    # [H*D] of what was written, in the pool's dtype
    for layer in range(n_layers):
        np.testing.assert_array_equal(
            np.asarray(hook.k_pages[layer, 1, 2], np.float32),
            np.asarray(
                written[layer][0, :, 0, :].reshape(H * D).astype(dtype),
                np.float32,
            ),
        )
    hook.rollback(jnp.asarray([0, 0], np.int32))
    for before, after in ((k0, hook.k_pages), (v0, hook.v_pages)):
        np.testing.assert_array_equal(
            np.asarray(after[:, 1:], np.float32),
            np.asarray(before[:, 1:], np.float32),
        )


# --------------------------------------------------------------------------- #
# engine end-to-end: greedy bit-match + dispatch accounting
# --------------------------------------------------------------------------- #


def test_greedy_speculative_streams_bit_match_reference(spec_run):
    """The counterfactual parity claim: exact-match verification makes
    greedy speculative streams BIT-IDENTICAL to the non-speculative
    engine's — speculation changes dispatch counts, never tokens."""
    assert spec_run["spec_out"] == spec_run["ref_out"]


def test_speculative_fewer_dispatches_at_equal_tokens(spec_run):
    """The perf claim on the repetitive trace: equal emitted tokens,
    strictly fewer decode dispatches, > 1.5 accepted tokens per verify
    dispatch."""
    spec_m = spec_run["spec_eng"].metrics
    ref_m = spec_run["ref_eng"].metrics
    assert spec_m.tokens_out.value == ref_m.tokens_out.value
    assert spec_m.decode_steps.value < ref_m.decode_steps.value
    per_dispatch = spec_m.tokens_out.value / spec_m.decode_steps.value
    assert per_dispatch > 1.5
    assert spec_m.spec_draft_tokens.value > 0
    assert 0 < spec_m.spec_accepted_tokens.value <= (
        spec_m.spec_draft_tokens.value
    )


def test_seeded_sampling_reproducible_and_matches_nonspeculative(
    gpt, spec_run
):
    """Seeded top-p streams through the verify program must equal the
    non-speculative sampling engine's (same per-request key sequence —
    one split per emitted token) and replay identically."""
    model, params = gpt
    sp = SamplingParams(temperature=0.9, top_p=0.95, seed=123)
    prompts = [[5, 9, 3] * 4, [7] * 8]
    samp_eng = ServingEngine(model, params, _cfg(sampling=True))
    out_ref = _gen(samp_eng, prompts, 12, sp)
    spec_eng = spec_run["spec_eng"]  # warm: programs already compiled
    out_a = _gen(spec_eng, prompts, 12, sp)
    out_b = _gen(spec_eng, prompts, 12, sp)
    assert out_a == out_ref
    assert out_a == out_b


def test_sampled_token_accounting_matches_nonspeculative(gpt):
    """serve/sampled_tokens counts tokens drawn through the sampling
    path — a speculative engine must count the same emitted tokens as a
    non-speculative one (greedy requests still excluded)."""
    model, params = gpt
    sp = SamplingParams(temperature=0.9, top_p=0.95, seed=7)
    a = ServingEngine(model, params, _cfg(sampling=True))
    b = ServingEngine(model, params, _cfg(sampling=True, speculative_k=2))
    for eng in (a, b):  # one sampled + one greedy request each
        eng.submit(np.asarray([5, 9, 3] * 3, np.int32), 8, sampling=sp)
        eng.submit(np.asarray([1, 2, 3, 4], np.int32), 8)
        eng.run()
    assert a.metrics.sampled_tokens.value == b.metrics.sampled_tokens.value
    assert b.metrics.sampled_tokens.value == 8.0


# --------------------------------------------------------------------------- #
# chunk packing
# --------------------------------------------------------------------------- #


def test_packed_chunks_match_unpacked_and_reduce_dispatches(gpt):
    """Chunk packing services EVERY prefilling slot per dispatch: same
    streams as the one-slot-per-iteration chunk path, fewer chunk
    dispatches when several long prompts prefill concurrently."""
    model, params = gpt
    long_a = list(range(1, 21)) + [5, 9, 3] * 4   # 32 tokens -> 2 chunks
    long_b = list(range(30, 50)) + [11, 2] * 6    # 32 tokens -> 2 chunks
    prompts = [long_a, long_b]
    ref = ServingEngine(model, params, _cfg(prefill_chunk_tokens=16))
    ref_out = _gen(ref, prompts, 8)
    packed = ServingEngine(
        model, params,
        _cfg(prefill_chunk_tokens=16, sampling=True, speculative_k=3),
    )
    packed_out = _gen(packed, prompts, 8)
    assert packed_out == ref_out
    # prefill_chunks counts DISPATCHES: unpacked services one slot's
    # chunk per iteration (2 prompts x 2 chunks = 4); packed rides both
    # slots on each of 2 dispatches
    assert ref.metrics.prefill_chunks.value == 4.0
    assert packed.metrics.prefill_chunks.value == 2.0


# --------------------------------------------------------------------------- #
# default-OFF + validation + audit
# --------------------------------------------------------------------------- #


def test_default_engine_constructs_no_speculative_programs(gpt, spec_run):
    """speculative_k=None keeps the PR-13 programs verbatim: no verify
    or packed-chunk program exists, the speculative counters stay
    disabled, and the shared sampling-prefill program lowers
    bit-identically with and without speculation (the feature touches
    decode dispatch, never the other programs)."""
    ref_eng = spec_run["ref_eng"]
    spec_eng = spec_run["spec_eng"]
    assert ref_eng._verify_jit is None
    assert ref_eng._packed_chunk_jit is None
    assert ref_eng.metrics.spec_draft_tokens is None
    assert spec_eng._verify_jit is not None
    assert spec_eng.metrics.spec_draft_tokens is not None
    # sampling alone does not opt in — speculative_k is the switch
    model, params = gpt
    samp = ServingEngine(model, params, _cfg(sampling=True))
    assert samp._verify_jit is None
    assert samp._packed_chunk_jit is None

    # fresh speculative engine: the run engine's cache arrays carry
    # post-dispatch sharding annotations that would differ textually
    spec_fresh = ServingEngine(
        model, params, _cfg(sampling=True, speculative_k=3)
    )
    MB = samp.scheduler.max_blocks_per_seq

    def prefill_hlo(eng):
        args = (
            eng.qparams, eng.cache.k_pages, eng.cache.v_pages,
            jnp.zeros((1, 16), jnp.int32),
            jnp.zeros((1, MB), jnp.int32),
            jnp.ones((1,), jnp.int32),
            jnp.zeros((1, 2), jnp.uint32),
            jnp.zeros((1,), jnp.float32),
            jnp.zeros((1,), jnp.int32),
            jnp.ones((1,), jnp.float32),
        )
        return jax.jit(eng._prefill_sampling_fn).lower(*args).as_text()

    assert prefill_hlo(samp) == prefill_hlo(spec_fresh)


def _reject(match, **kw):
    base = dict(max_seqs=2, kv_block_size=8, max_seq_len=64)
    base.update(kw)
    with pytest.raises(StokeValidationError, match=match):
        StokeStatus(batch_size_per_device=1, configs=[ServeConfig(**base)])


def test_status_rejects_bad_speculative_configs(gpt):
    _reject("speculative_k must be >= 1", sampling=True, speculative_k=0)
    _reject("needs sampling=True", speculative_k=3)
    _reject("chunk budget", sampling=True, speculative_k=8,
            prefill_chunk_tokens=8, prefill_pad_multiple=8)
    _reject("speculative_ngram_min must be >= 1", sampling=True,
            speculative_k=3, speculative_ngram_min=0)
    _reject("range is empty", sampling=True, speculative_k=3,
            speculative_ngram_min=3, speculative_ngram_max=2)
    # knobs a disabled feature would silently ignore are rejected
    _reject("drafter knobs set", speculative_ngram_max=5)
    # engine construction enforces the sampling rule too
    model, params = gpt
    with pytest.raises(ValueError, match="sampling"):
        ServingEngine(model, params, _cfg(speculative_k=3))


def test_speculative_programs_audit_clean(spec_run):
    """The verify program passes the PR-15 auditor with zero findings
    (donation honored, no hidden host round-trips)."""
    from stoke_tpu.analysis.program import audit_program_specs

    specs = spec_run["spec_eng"].audit_specs()
    assert "serve_verify" in {s.program for s in specs}
    rep = audit_program_specs(specs)
    assert rep.findings == []
