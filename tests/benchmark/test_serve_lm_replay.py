"""`benchmark/jobs/serve_lm_replay.py`: the schedule of lengths is the traffic
file's, the tokens are the seed's, and `check` holds two numbers."""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.jobs import serve_lm, serve_lm_replay  # noqa: E402

TRAFFIC = {
    "pool": 16, "order_seed": 5,
    "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.8,
                   "min": 4, "max": 40},
    "output_len": {"dist": "uniform", "min": 2, "max": 9},
}


def _lengths(pool):
    return [(len(p), o) for p, o in pool]


def test_lengths_follow_order_seed_and_tokens_follow_seed():
    a = serve_lm_replay.make_pool(TRAFFIC, 100, 1)
    b = serve_lm_replay.make_pool(TRAFFIC, 100, 2**31 + 7)
    assert _lengths(a) == _lengths(b) == _lengths(
        serve_lm.make_pool(TRAFFIC, 100, TRAFFIC["order_seed"]))
    # serve_lm alone: another seed, another order
    assert _lengths(a) != _lengths(serve_lm.make_pool(TRAFFIC, 100, 1))
    assert any((p != q).any() for (p, _), (q, _) in zip(a, b))
    again = serve_lm_replay.make_pool(TRAFFIC, 100, 1)
    assert all((p == q).all() for (p, _), (q, _) in zip(a, again))
    other = serve_lm_replay.make_pool({**TRAFFIC, "order_seed": 6}, 100, 1)
    assert _lengths(other) != _lengths(a)
    assert sorted(l for l, _ in _lengths(other)) == sorted(
        l for l, _ in _lengths(a))


def _copying_reference(params, ids, at):
    """A model that repeats the token it reads: logits peak at the token at
    each asked position, falling off with the squared distance."""
    import jax.numpy as jnp

    read = jnp.take_along_axis(ids, at, axis=1)  # [B, T]
    return -jnp.square(jnp.arange(8.0) - read[..., None].astype(jnp.float32))


def _state(finished, tolerance, miss_limit):
    return SimpleNamespace(
        loop=SimpleNamespace(finished=finished), checked_requests=2,
        cfg=SimpleNamespace(max_seq_len=16), max_out=4, params={},
        logits_at=_copying_reference, tolerance=tolerance,
        miss_limit=miss_limit)


FAITHFUL = (np.array([3], np.int32), [3, 3, 3, 3], 4)
# its third token is no copy: read 3, served 5, (0 - -4) / (0 - -16) below
ONE_MISS = (np.array([1, 3], np.int32), [3, 3, 5, 5], 4)


@pytest.mark.parametrize("tolerance,miss_limit,ok", [
    (0.3, 0.2, True),
    (0.3, 0.1, False),   # 1 of 8 tokens missed
    (0.2, 0.2, False),   # the miss lies 0.25 of the range below
])
def test_check_holds_the_worst_gap_and_the_share_of_misses(
        tolerance, miss_limit, ok, capsys):
    got, compared = serve_lm_replay.check(
        _state([FAITHFUL, ONE_MISS], tolerance, miss_limit))
    assert got is ok
    assert compared == {
        "worst_logit_gap_frac": {"value": 0.25, "limit": tolerance},
        "argmax_miss_share": {"value": 0.125, "limit": miss_limit},
    }
    assert "7 equal the reference argmax" in capsys.readouterr().out


def test_check_needs_its_requests():
    ok, compared = serve_lm_replay.check(_state([FAITHFUL], 0.3, 0.2))
    assert ok is False
    assert compared == {"finished_requests_short_of": {"value": 1, "limit": 0}}


def test_setup_leaves_serve_lm_as_it_found_it(monkeypatch):
    seen = {}

    def fake_setup(config, traffic, seed, chips):
        seen["pool"] = serve_lm.make_pool(traffic, 100, seed)
        return SimpleNamespace()

    original = serve_lm.make_pool
    monkeypatch.setattr(serve_lm, "setup", fake_setup)
    state = serve_lm_replay.setup(
        {}, {**TRAFFIC, "argmax_miss_share_limit": 0.06}, 3, 1)
    assert state.miss_limit == 0.06
    assert serve_lm.make_pool is original
    assert _lengths(seen["pool"]) == _lengths(
        serve_lm.make_pool(TRAFFIC, 100, TRAFFIC["order_seed"]))
