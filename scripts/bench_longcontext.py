"""Model-level long-context benchmark: GPT through the Stoke facade,
flash vs dense attention, on one chip.

The kernel-level sweep (scripts/flash_tpu_check.py) showed the pallas
flash kernel 3.5x faster than dense at L=4096 and alone above the dense
OOM cliff at L=8192.  This script shows the same advantage END TO END:
full training steps (fwd+bwd+optimizer, bf16, fused train_step) of a GPT
LM through the facade, sweeping sequence length, for both attention_fn
choices.  Prints one JSON line per (L, attention) point.

Run on the chip (supervised: the worker is the one chip-owning process):
    python scripts/bench_longcontext.py [--size mini] [--batch 4]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _supervise import supervise  # noqa: E402


def build(size, L, batch, attention, vocab=2048, chunked_ce=False):
    import jax
    import optax

    from stoke_tpu import Stoke, StokeOptimizer
    from stoke_tpu.models.gpt import GPT
    from stoke_tpu.utils import init_module

    kwargs = {}
    if attention == "flash":
        from stoke_tpu.ops import make_flash_attention

        kwargs.update(attention_fn=make_flash_attention(causal=True),
                      attention_is_causal=True)
    if chunked_ce:
        # chunked LM-head CE: the [B, L, V] logits tensor is never
        # materialized (ops/chunked_ce.py) — the second long-context
        # memory cliff, composable with the flash kernel
        from stoke_tpu.ops import chunked_causal_lm_loss

        kwargs.update(chunked_head=True)
        loss = lambda out, labels: chunked_causal_lm_loss(out, labels)
    else:
        from stoke_tpu.models.gpt import causal_lm_loss

        loss = causal_lm_loss
    model = GPT(vocab_size=vocab, size_name=size, max_len=L,
                dropout_rate=0.0, **kwargs)
    ids = np.zeros((2, L), np.int32)
    variables = init_module(model, jax.random.PRNGKey(0), ids, train=False)
    on_accel = jax.default_backend() not in ("cpu",)
    return Stoke(
        model=model,
        optimizer=StokeOptimizer(
            optimizer=optax.adamw, optimizer_kwargs={"learning_rate": 3e-4}),
        loss=loss,
        params=variables,
        batch_size_per_device=batch,
        device="tpu" if on_accel else "cpu",
        precision="bf16" if on_accel else None,
        model_train_kwargs={"train": True},
        model_eval_kwargs={"train": False},
        verbose=False,
    )


def bench_ring_inner(lengths, batch, heads, head_dim):
    """Op-level arm: ring attention with dense vs flash inner math, fwd+bwd,
    over a ("data", "seq") mesh spanning every visible device.  On a single
    chip the ring degenerates to one hop — which is precisely the comparison
    that matters there: the dense inner materializes the [L, L] score block
    and falls off the OOM cliff at L≥8k while the flash inner keeps running.
    On the simulated 8-device CPU mesh the same code exercises the full
    multi-hop composition (per-hop flash + lse merge)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from stoke_tpu.ops import ring_attention

    from _timing import delta_time

    devs = np.asarray(jax.devices()).reshape(1, -1)
    mesh = Mesh(devs, ("data", "seq"))
    n = devs.size
    r = np.random.default_rng(0)
    results = []
    for L in lengths:
        mk = lambda: jnp.asarray(
            r.normal(size=(batch, heads, L, head_dim)).astype(np.float32),
            jnp.bfloat16,
        )
        q, k, v = mk(), mk(), mk()
        for inner in ("dense", "flash"):
            try:
                def loss(q, k, v):
                    out = ring_attention(
                        q, k, v, mesh=mesh, axis_name="seq", causal=True,
                        inner=inner,
                    )
                    return jnp.sum(out.astype(jnp.float32) ** 2)

                step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                step(q, k, v)  # compile
                t = delta_time(lambda: step(q, k, v), 5)
                rec = {"bench": "ring_inner", "L": L, "batch": batch,
                       "heads": heads, "head_dim": head_dim, "devices": n,
                       "inner": inner, "fwdbwd_ms": round(t * 1e3, 2)}
            except Exception as e:
                rec = {"bench": "ring_inner", "L": L, "batch": batch,
                       "heads": heads, "head_dim": head_dim, "devices": n,
                       "inner": inner, "error": type(e).__name__}
            print(json.dumps(rec), flush=True)
            results.append(rec)
    ok = [p for p in results if "error" not in p]
    for L in sorted({p["L"] for p in ok}):
        d = next((p for p in ok if p["L"] == L and p["inner"] == "dense"), None)
        f = next((p for p in ok if p["L"] == L and p["inner"] == "flash"), None)
        if d and f:
            print(json.dumps({"bench": "ring_inner", "L": L,
                              "flash_inner_speedup": round(
                                  d["fwdbwd_ms"] / f["fwdbwd_ms"], 2)}),
                  flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--_worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--size", default="mini")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--lengths", default="1024,4096,8192")
    ap.add_argument("--op-ring", action="store_true",
                    help="op-level ring-inner arm (dense vs flash hop math) "
                    "instead of the model-level GPT sweep")
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--chunked-ce", action="store_true",
                    help="add a third arm: flash attention + chunked LM-head "
                    "CE (no [B, L, V] logits tensor)")
    args = ap.parse_args()
    if not args._worker:
        sys.exit(supervise(__file__, sys.argv[1:], watchdog_seconds=3000))

    import jax

    from _timing import delta_time

    if args.op_ring:
        bench_ring_inner(
            [int(x) for x in args.lengths.split(",")],
            args.batch, args.heads, args.head_dim,
        )
        return

    r = np.random.default_rng(0)
    results = []
    arms = [("dense", False), ("flash", False)]
    if args.chunked_ce:
        arms.append(("flash", True))
    for L in (int(x) for x in args.lengths.split(",")):
        ids = jax.device_put(
            r.integers(0, args.vocab, size=(args.batch, L)).astype(np.int32))
        for attention, chunked in arms:
            label = attention + ("+chunked_ce" if chunked else "")
            stoke = None
            try:
                stoke = build(args.size, L, args.batch, attention,
                              vocab=args.vocab, chunked_ce=chunked)
                t = delta_time(lambda: stoke.train_step(ids, (ids,)), 5)
                tok_s = args.batch * L / t
                rec = {"bench": "gpt_longcontext", "size": args.size,
                       "L": L, "batch": args.batch, "attention": label,
                       "vocab": args.vocab,
                       "step_ms": round(t * 1e3, 2),
                       "tok_per_sec": round(tok_s, 1)}
            except Exception as e:
                rec = {"bench": "gpt_longcontext", "size": args.size, "L": L,
                       "batch": args.batch, "attention": label,
                       "vocab": args.vocab,
                       "error": type(e).__name__}
            finally:
                # drop device state even when the step OOMs, or the dead
                # model's params/executables squat in HBM for the next arm
                del stoke
            print(json.dumps(rec), flush=True)
            results.append(rec)
    ok = [p for p in results if "error" not in p]
    for L in sorted({p["L"] for p in ok}):
        d = next((p for p in ok if p["L"] == L and p["attention"] == "dense"), None)
        f = next((p for p in ok if p["L"] == L and p["attention"] == "flash"), None)
        if d and f:
            print(json.dumps({"L": L, "flash_speedup": round(
                d["step_ms"] / f["step_ms"], 2)}), flush=True)


if __name__ == "__main__":
    main()
