"""Training job: a causal language model trained through ``Stoke``.

The traffic file says which API drives the step (``"4call"``: model, loss,
backward, step; ``"train_step"``: the fused call), the sequence length, rows
per device, accumulation, precision, optimizer and sharding.  Tokens are
uniform over the vocabulary, from the seed.

The window: optimizer steps are dispatched until ``seconds`` have passed.
After dispatching step n the host fetches the losses of step n-1, so it
never runs more than one step ahead and never stalls the device; the window
ends with ``block_until_ready`` on the last step's outputs, and the rate is
the tokens of all those (completed) steps over the wall time to that point.
"""

from __future__ import annotations

import time
from collections import deque
from types import SimpleNamespace

import numpy as np

from benchmark.lib import compiles
from benchmark.lib.model import family


def _batches(loader):
    while True:
        yield from loader


def _optimizer_step(state, clock) -> list:
    """One optimizer step through the traffic's API.  Returns the device
    losses of its micro-steps (each divided by ``grad_accum``, as the
    program reports them); adds host seconds to ``clock``."""
    stoke, losses = state.stoke, []
    for _ in range(state.grad_accum):
        t = time.perf_counter()
        micro = next(state.batches)
        clock["loader"] += time.perf_counter() - t
        t = time.perf_counter()
        if state.api == "4call":
            out = stoke.model(micro)
            loss = stoke.loss(out, micro)
            stoke.backward(loss)
            stoke.step()
        else:
            loss = stoke.train_step(micro, micro)
        clock["dispatch"] += time.perf_counter() - t
        losses.append(loss)
    return losses


def _fetch(losses) -> list:
    return [float(np.asarray(l)) for l in losses]


def setup(config: dict, traffic: dict, seed: int, chips: int):
    import jax
    import optax

    from stoke_tpu import (
        ArrayDataset,
        ClipGradNormConfig,
        Stoke,
        StokeOptimizer,
    )
    from stoke_tpu.models import causal_lm_loss

    if traffic["api"] not in ("4call", "train_step"):
        raise ValueError(f"unknown api {traffic['api']!r}")
    seq_len = int(traffic["seq_len"])
    world = chips if traffic.get("distributed") else 1
    rows_micro = int(traffic["rows_per_device"]) * world
    grad_accum = int(traffic["grad_accum"])
    fam = family(config)
    model = fam.build_model(config)
    variables = fam.init_params(model, seed, seq_len)
    n_params = sum(
        int(leaf.size) for leaf in jax.tree_util.tree_leaves(variables)
    )
    tokens = np.random.default_rng(seed).integers(
        0, int(config["vocab_size"]),
        size=(int(traffic["dataset_rows"]), seq_len), dtype=np.int32,
    )
    # the reference's loss on the first micro-batch, on the weights as
    # made, before the trainer takes (and donates) them
    with jax.default_matmul_precision("highest"):
        ref_loss = float(
            jax.jit(fam.causal_lm_loss)(
                variables["params"], tokens[:rows_micro]
            )
        )
    opt = traffic["optimizer"]
    stoke = Stoke(
        model=model,
        optimizer=StokeOptimizer(
            optimizer=getattr(optax, opt["name"]),
            optimizer_kwargs={"learning_rate": opt["learning_rate"]},
        ),
        loss=causal_lm_loss,
        params=variables,
        batch_size_per_device=int(traffic["rows_per_device"]),
        grad_accum=grad_accum,
        grad_clip=ClipGradNormConfig(max_norm=float(traffic["grad_clip"])),
        device=jax.default_backend(),
        distributed=traffic.get("distributed"),
        precision=traffic["precision"],
        fsdp=bool(traffic.get("fsdp", False)),
        model_train_kwargs={"train": True},
        model_eval_kwargs={"train": False},
        verbose=False,
    )
    del variables  # the facade owns these buffers now
    if stoke.world_size != world:
        raise RuntimeError(f"world size {stoke.world_size}, wanted {world}")
    loader = stoke.DataLoader(
        ArrayDataset(tokens), shuffle=False, drop_last=True
    )
    state = SimpleNamespace(
        stoke=stoke, batches=_batches(loader), api=traffic["api"],
        grad_accum=grad_accum, tokens_per_step=rows_micro * seq_len * grad_accum,
        flops_per_token=fam.train_flops_per_token(config, seq_len),
        ref_loss=ref_loss, loss_tolerance=float(traffic["loss_tolerance"]),
        trace_seconds=float(traffic["trace_seconds"]),
    )
    # warm-up through the API the window uses: the step programs compile
    # (or come from the cache) here.  The very first micro-step ran on the
    # weights as made: its loss is what `check` compares.
    warm = [
        _fetch(_optimizer_step(state, {"loader": 0.0, "dispatch": 0.0}))
        for _ in range(int(traffic["warm_steps"]))
    ]
    state.first_loss = warm[0][0] * grad_accum
    print(
        f"bench: train_lm params={n_params} tokens/step="
        f"{state.tokens_per_step} flops/token={state.flops_per_token:.4g} "
        f"first_loss={state.first_loss:.5f} reference_loss={ref_loss:.5f}",
        flush=True,
    )
    return state


def check(state):
    """Returns ``(ok, compared)``: ``compared`` names each number held
    against a limit, ``{name: {"value": .., "limit": ..}}``.

    The program's loss on the first micro-batch, before any update,
    against the float32 reference's on the same rows and weights.

    Tolerance (``loss_tolerance`` in the traffic file, absolute, on a loss
    near ln(vocab) = 10.8): the program computes in bf16 (8 bits of
    mantissa, relative rounding 4e-3 per operation) where the reference is
    float32 at the highest matmul precision; over thousands of target
    positions the roundings largely cancel in the mean: on the v5e the gap
    was 0.00008 to 0.00037 over nine seeds and both widths (PR 24), and the
    tolerance is 0.003, eight times the worst.  A wrong mask, a dropped
    layer or a shifted target moves the loss by far more; so would fp8."""
    gap = abs(state.first_loss - state.ref_loss)
    ok = bool(np.isfinite(state.first_loss)) and gap <= state.loss_tolerance
    print(
        f"bench: check train_lm |program - reference| loss = {gap:.5f} "
        f"(tolerance {state.loss_tolerance}) -> {'ok' if ok else 'FAILED'}",
        flush=True,
    )
    return ok, {"first_loss_gap":
                {"value": float(gap), "limit": state.loss_tolerance}}


def measure(state, seconds: float, tracer) -> dict:
    import jax

    if tracer is not None:
        seconds = min(seconds, state.trace_seconds)
        tracer.start()
    clock = {"loader": 0.0, "dispatch": 0.0}
    pending, fetched = deque(), []
    compiles0 = compiles.count()
    t0 = time.perf_counter()
    done_at = [t0]  # the host's clock each time a step's losses arrived
    while True:
        pending.append(_optimizer_step(state, clock))
        if len(pending) > 1:
            fetched.append(_fetch(pending.popleft()))
            done_at.append(time.perf_counter())
        if time.perf_counter() - t0 >= seconds:
            break
    last = pending.popleft()
    jax.block_until_ready((last, state.stoke.params))
    window_s = time.perf_counter() - t0
    done_at.append(t0 + window_s)
    step_s = np.diff(done_at)
    if tracer is not None:
        tracer.stop()
    fetched.append(_fetch(last))
    steps = len(fetched)
    tokens = steps * state.tokens_per_step
    return {
        "attempted": steps,
        "failed": sum(not np.all(np.isfinite(l)) for l in fetched),
        "compiles_in_window": compiles.count() - compiles0,
        "train.steps": steps,
        "train.tokens": tokens,
        "train.window_s": window_s,
        "train.tokens_per_s": tokens / window_s,
        "train.flops_per_s": tokens * state.flops_per_token / window_s,
        "train.host_dispatch_s": clock["dispatch"],
        "train.loader_wait_s": clock["loader"],
        "train.last_loss": fetched[-1][-1] * state.grad_accum,
        # where a slow run lost its time: one long step is a stall, every
        # step longer is a slow device (read from the observations line)
        "train.step_ms_p50": 1e3 * float(np.median(step_s)),
        "train.step_ms_max": 1e3 * float(step_s.max()),
        "train.stall_s": float(
            np.clip(step_s - 1.5 * np.median(step_s), 0.0, None).sum()
        ),
    }


def end_to_end(observations: dict) -> dict:
    return {"train_tokens_per_s": observations["train.tokens_per_s"]}
