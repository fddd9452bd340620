"""Serving job: a closed loop of callers against the continuous-batching
``ServingEngine``.

``clients`` callers each submit their next request the moment their last one
finishes, so the slots stay full and no queue forms.  Requests come from a
pool whose prompt and output lengths are the evenly spaced quantiles of the
traffic file's distributions: every seed sends the same multiset of lengths,
paired and ordered by permutations from the seed, with other tokens and
weights.  Greedy decoding, every request runs to its ``max_new_tokens``.

Tokens are stamped by the harness's own clock after every ``engine.step()``
(that is when a caller of this API can see them).  Time to first token runs
from the harness's ``submit`` to the stamp of the first token.  A gap is the
time between two stamps of one request, divided over the tokens the later
stamp brought; a second token that arrives with the first (the decode step
of the iteration that prefilled) has no earlier stamp and gives no gap.
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from types import SimpleNamespace

import numpy as np

from benchmark.lib import compiles, stats
from benchmark.lib.model import family


def _quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of the length distribution ``spec``,
    rounded and clipped to its ``min`` and ``max``."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def make_pool(traffic: dict, vocab_size: int, seed: int) -> list:
    """``[(prompt tokens, output length), ...]`` from the seed."""
    rng = np.random.default_rng(seed)
    n = int(traffic["pool"])
    prompts = rng.permutation(_quantile_lengths(traffic["prompt_len"], n))
    outputs = rng.permutation(_quantile_lengths(traffic["output_len"], n))
    return [
        (rng.integers(0, vocab_size, size=int(p), dtype=np.int32), int(o))
        for p, o in zip(prompts, outputs)
    ]


class ClosedLoop:
    """The callers, the stamping and the counts of one phase at a time."""

    def __init__(self, engine, pool, clients: int):
        self.engine, self.pool = engine, pool
        self.clients = [{"req": None} for _ in range(clients)]
        self.cursor = 0
        self.turnovers = [0] * clients
        self.reset()

    def reset(self) -> None:
        self.recording = False
        self.ttft_s, self.gaps_s, self.fills = [], [], []
        self.tokens = self.steps = self.submitted = 0
        # (prompt, tokens, wanted) of the counted requests that finished
        # with the tokens they asked for
        self.finished = []

    def submit_all(self) -> None:
        for c in self.clients:
            if c["req"] is None:
                self._submit(c)

    def _submit(self, c) -> None:
        prompt, want = self.pool[self.cursor % len(self.pool)]
        self.cursor += 1
        submit_t = time.perf_counter()
        rid = self.engine.submit(prompt, want)
        req = self.engine.scheduler.queue[-1]
        if req.rid != rid:
            raise RuntimeError("the queue's tail is not the request submitted")
        c.update(req=req, prompt=prompt, want=want, seen=0, last=None,
                 submit_t=submit_t, counted=self.recording)
        self.submitted += self.recording

    def step(self, resubmit: bool) -> float:
        """One ``engine.step()``, then the stamps.  Returns the stamp."""
        self.engine.step()
        t = time.perf_counter()
        if self.recording:
            self.steps += 1
            self.fills.append(self.engine.scheduler.batch_fill)
        for i, c in enumerate(self.clients):
            req = c["req"]
            if req is None:
                continue
            new = len(req.tokens) - c["seen"]
            if new > 0:
                if self.recording:
                    self.tokens += new
                if c["seen"] == 0:
                    if c["counted"]:
                        self.ttft_s.append(t - c["submit_t"])
                    new -= 1
                if new and c["last"] is not None and self.recording:
                    self.gaps_s.extend([(t - c["last"]) / new] * new)
                c["seen"], c["last"] = len(req.tokens), t
            if req.finished:
                self.turnovers[i] += 1
                if c["counted"] and len(req.tokens) == c["want"]:
                    self.finished.append(
                        (c["prompt"], list(req.tokens), c["want"])
                    )
                c["req"] = None
                if resubmit:
                    self._submit(c)
        return t

    def drain(self) -> None:
        while any(c["req"] is not None for c in self.clients):
            self.step(resubmit=False)


def setup(config: dict, traffic: dict, seed: int, chips: int):
    from stoke_tpu import ServeConfig
    from stoke_tpu.serving.engine import ServingEngine

    t0 = time.perf_counter()
    cfg = ServeConfig(**traffic["serve_config"])
    pool = make_pool(traffic, int(config["vocab_size"]), seed)
    longest = max(len(p) + o for p, o in pool)
    if longest > cfg.max_seq_len:
        raise ValueError(f"a request needs {longest} positions")
    fam = family(config)
    model = fam.build_model(config)
    pad = cfg.prefill_pad_multiple
    buckets = sorted({-(-len(p) // pad) * pad for p, _ in pool})
    params = fam.init_params(model, seed, pad)["params"]
    engine = ServingEngine(model, params, cfg)
    t_built = time.perf_counter()
    print(f"bench: serve_lm ServeConfig in effect {dataclasses.asdict(cfg)}",
          flush=True)
    # every prefill bucket and the decode program, once each
    rng = np.random.default_rng(seed + 1)
    engine.generate(
        [rng.integers(0, int(config["vocab_size"]), size=b, dtype=np.int32)
         for b in buckets],
        max_new_tokens=2,
    )
    t_warm = time.perf_counter()
    loop = ClosedLoop(engine, pool, int(traffic["clients"]))
    # steady state before the window: every slot turns over once
    loop.submit_all()
    while min(loop.turnovers) < 1:
        loop.step(resubmit=True)
    print(
        f"bench: serve_lm set-up: weights and engine {t_built - t0:.1f} s, "
        f"prefill buckets {buckets} and decode warmed "
        f"{t_warm - t_built:.1f} s, every slot turned over once "
        f"{time.perf_counter() - t_warm:.1f} s", flush=True)
    return SimpleNamespace(
        engine=engine, loop=loop, params=params, cfg=cfg,
        logits_at=fam.logits_at,
        trace_seconds=float(traffic["trace_seconds"]),
        checked_requests=int(traffic["checked_requests"]),
        tolerance=float(traffic["logit_tolerance_frac"]),
        max_out=int(traffic["output_len"]["max"]),
    )


def measure(state, seconds: float, tracer) -> dict:
    engine, loop, m = state.engine, state.loop, state.engine.metrics
    if tracer is not None:
        seconds = min(seconds, state.trace_seconds)
        tracer.start()
    loop.reset()
    loop.recording = True
    before = {
        k: getattr(m, k).value
        for k in ("prefill_s", "prefills", "decode_s", "decode_steps")
    }
    compiles0 = compiles.count()
    t0 = t = time.perf_counter()
    while t - t0 < seconds:
        t = loop.step(resubmit=True)
    window_s = t - t0
    delta = {k: getattr(m, k).value - v for k, v in before.items()}
    n_compiles = compiles.count() - compiles0
    loop.recording = False
    if tracer is not None:
        tracer.stop()
    loop.drain()  # outside the window; counted requests still get stamped
    ttft_ms = [1e3 * x for x in loop.ttft_s]
    gaps_ms = [1e3 * x for x in loop.gaps_s]
    for name, values in (("ttft_ms", ttft_ms), ("itl_ms", gaps_ms)):
        print("bench: " + stats.tail_note(name, values, 95.0), flush=True)
    return {
        "attempted": loop.submitted,
        # submitted in the window and not finished, after the drain, with
        # the tokens it asked for
        "failed": loop.submitted - len(loop.finished),
        "compiles_in_window": n_compiles,
        "serve.window_s": window_s,
        "serve.tokens": loop.tokens,
        "serve.tokens_per_s": loop.tokens / window_s,
        "serve.ttft_ms_p50": stats.percentile(ttft_ms, 50.0),
        "serve.ttft_ms_p95": stats.percentile(ttft_ms, 95.0),
        "serve.itl_ms_p50": stats.percentile(gaps_ms, 50.0),
        "serve.itl_ms_p95": stats.percentile(gaps_ms, 95.0),
        "serve.engine_steps": loop.steps,
        "serve.batch_fill_mean": float(np.mean(loop.fills)),
        "serve.prefill_s": delta["prefill_s"],
        "serve.prefills": delta["prefills"],
        "serve.decode_s": delta["decode_s"],
        "serve.decode_steps": delta["decode_steps"],
        "serve.host_s": window_s - delta["prefill_s"] - delta["decode_s"],
    }


def check(state):
    """Returns ``(ok, compared)``: ``compared`` names each number held
    against a limit, ``{name: {"value": .., "limit": ..}}``.

    Requests finished in the window, spread over the prompt lengths:
    every served token's logit in the float32 reference forward of
    ``prompt + tokens so far`` against that forward's maximum, as a share of
    the forward's logit range.

    Tolerance (``logit_tolerance_frac`` in the traffic file): the engine's
    float32 matmuls run at the TPU's default precision (bf16 passes) and the
    reference at the highest, so on random weights, where the top logits lie
    close together, the served token need not be the reference's argmax; it
    has to be a near-tie: on the v5e the worst gap over twelve runs was
    0.0053 of the range, and 75 to 100% of the tokens were the reference's
    argmax (PR 24).  A token read through a wrong cache block, a wrong
    position or a wrong mask lands anywhere in the range, on average half
    of it below the maximum."""
    import jax
    import jax.numpy as jnp

    done = sorted(state.loop.finished, key=lambda r: len(r[0]))
    n = state.checked_requests
    if len(done) < n:
        print(f"bench: check serve_lm only {len(done)} finished requests",
              flush=True)
        return False, {"finished_requests_short_of":
                       {"value": n - len(done), "limit": 0}}
    picked = [done[round(i * (len(done) - 1) / (n - 1))] for i in range(n)]
    L, T = state.cfg.max_seq_len, state.max_out
    ids = np.zeros((n, L), np.int32)
    at = np.zeros((n, T), np.int32)
    served = np.zeros((n, T), np.int64)
    valid = np.zeros((n, T), bool)
    for i, (prompt, tokens, _) in enumerate(picked):
        seq = np.concatenate([prompt, tokens])
        ids[i, : len(seq)] = seq
        k = len(tokens)
        # served token t came from position len(prompt) - 1 + t
        at[i, :k] = len(prompt) - 1 + np.arange(k)
        served[i, :k], valid[i, :k] = tokens, True
    with jax.default_matmul_precision("highest"):
        rows = np.asarray(
            jax.jit(state.logits_at)(state.params, jnp.asarray(ids),
                                     jnp.asarray(at))
        )
    top, low = rows.max(-1), rows.min(-1)
    got = np.take_along_axis(rows, served[:, :, None], axis=2)[..., 0]
    gap = np.where(valid, (top - got) / (top - low), 0.0)
    ok = bool(np.isfinite(rows).all()) and float(gap.max()) <= state.tolerance
    print(
        f"bench: check serve_lm {n} requests (prompts "
        f"{[len(p) for p, _, _ in picked]}), {int(valid.sum())} tokens, "
        f"{int((gap[valid] == 0).sum())} equal the reference argmax, worst "
        f"gap {gap.max():.5f} of the logit range (tolerance "
        f"{state.tolerance}) -> {'ok' if ok else 'FAILED'}",
        flush=True,
    )
    return ok, {"worst_logit_gap_frac":
                {"value": float(gap.max()), "limit": state.tolerance}}


def end_to_end(observations: dict) -> dict:
    return {
        "serve_tokens_per_s": observations["serve.tokens_per_s"],
        "ttft_ms_p50": observations["serve.ttft_ms_p50"],
        "itl_ms_p95": observations["serve.itl_ms_p95"],
    }
