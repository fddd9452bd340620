"""Serving job: ``serve_lm``'s closed loop over ONE schedule of lengths, and
a check that holds two numbers.  Set-up, stamping, window and end-to-end
metrics are ``serve_lm``'s own functions, unedited.

**The schedule.**  ``serve_lm`` pairs and orders the pool's lengths by
permutations from ``--seed``.  Which request runs in which step follows from
the token counts alone, so the seed decides which prompts fall into the
window and which of them finish in one step and prefill together.  Where a
window holds few steps (a 30 s window of ``serve-axk1-closed192`` holds about
100 requests and 320 steps), a median over 100 requests and a 95th percentile
over 320 steps then move with the seed by more than a check admits, however
large the pool (PERF.md section 2 has the model of the loop and its numbers).
Here the traffic file's ``order_seed`` fixes pairing and order; ``--seed``
gives the token ids and the weights, so every run replays the same lengths on
other data.

**The check.**  ``serve_lm.check`` holds the worst served token's distance
from the reference's argmax against a limit.  With routed experts that
number does not tell a lower precision from the stated one: a near-tie in a
router assigns a token to another expert, its logits move by a step, and the
worst of some thousand tokens is such a token at any precision.  How OFTEN
that happens follows the precision.  So two numbers, over
``checked_requests`` requests finished in the window, spread over the prompt
lengths, each served token against the float32 reference forward of
``prompt + tokens so far``:

- ``worst_logit_gap_frac``: as in ``serve_lm``, against
  ``logit_tolerance_frac``.  A token read through a wrong block, position or
  mask lands anywhere in the logit range.
- ``argmax_miss_share``: the share of served tokens that are not the
  reference's argmax, against ``argmax_miss_share_limit``.

The reference runs a request at a time and only ``[tokens]`` numbers a
request leave the device, so the comparison's memory does not grow with
``checked_requests``.
"""

from __future__ import annotations

from unittest import mock

import numpy as np

from benchmark.jobs import serve_lm
from benchmark.jobs.serve_lm import end_to_end, measure  # noqa: F401

_pool_ordered_by = serve_lm.make_pool  # setup() puts this job's in its place


def make_pool(traffic: dict, vocab_size: int, seed: int) -> list:
    """``serve_lm.make_pool``'s lengths under ``order_seed``, with token ids
    from ``seed``."""
    lengths = _pool_ordered_by(traffic, vocab_size, int(traffic["order_seed"]))
    rng = np.random.default_rng(seed)
    return [
        (rng.integers(0, vocab_size, size=len(p), dtype=np.int32), o)
        for p, o in lengths
    ]


def setup(config: dict, traffic: dict, seed: int, chips: int):
    # serve_lm.setup draws its pool through its module's make_pool
    with mock.patch.object(serve_lm, "make_pool", make_pool):
        state = serve_lm.setup(config, traffic, seed, chips)
    state.miss_limit = float(traffic["argmax_miss_share_limit"])
    return state


def check(state):
    """Returns ``(ok, compared)``, as ``serve_lm.check`` does."""
    import jax
    import jax.numpy as jnp

    done = sorted(state.loop.finished, key=lambda r: len(r[0]))
    n = state.checked_requests
    if len(done) < n:
        print(f"bench: check serve_lm_replay only {len(done)} finished "
              f"requests", flush=True)
        return False, {"finished_requests_short_of":
                       {"value": n - len(done), "limit": 0}}
    picked = [done[round(i * (len(done) - 1) / (n - 1))] for i in range(n)]
    L, T = state.cfg.max_seq_len, state.max_out

    @jax.jit
    def gaps(params, ids, at, served):
        rows = state.logits_at(params, ids[None], at[None])[0]  # [T, vocab]
        top, low = rows.max(-1), rows.min(-1)
        got = jnp.take_along_axis(rows, served[:, None], axis=1)[:, 0]
        return (top - got) / (top - low), jnp.isfinite(rows).all()

    gap, finite = [], True
    with jax.default_matmul_precision("highest"):
        for prompt, tokens, _ in picked:
            seq = np.concatenate([prompt, tokens])
            k = len(tokens)
            ids = np.zeros(L, np.int32)
            ids[: len(seq)] = seq
            at = np.zeros(T, np.int32)
            # served token t came from position len(prompt) - 1 + t
            at[:k] = len(prompt) - 1 + np.arange(k)
            served = np.zeros(T, np.int32)
            served[:k] = tokens
            g, f = gaps(state.params, jnp.asarray(ids), jnp.asarray(at),
                        jnp.asarray(served))
            gap.append(np.asarray(g)[:k])
            finite = finite and bool(f)
    gap = np.concatenate(gap)
    worst = float(gap.max())
    miss = float((gap > 0).mean())
    ok = finite and worst <= state.tolerance and miss <= state.miss_limit
    print(
        f"bench: check serve_lm_replay {n} requests (prompts "
        f"{[len(p) for p, _, _ in picked]}), {gap.size} tokens, "
        f"{int((gap == 0).sum())} equal the reference argmax (missed "
        f"{miss:.5f}, limit {state.miss_limit}), worst gap {worst:.5f} of "
        f"the logit range (tolerance {state.tolerance}) -> "
        f"{'ok' if ok else 'FAILED'}",
        flush=True,
    )
    return ok, {
        "worst_logit_gap_frac": {"value": worst, "limit": state.tolerance},
        "argmax_miss_share": {"value": miss, "limit": state.miss_limit},
    }
