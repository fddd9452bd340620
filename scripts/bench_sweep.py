"""Batch-size / step-API sweep for the CIFAR-10 ResNet-50 TPU benchmark.

Runs serially in ONE worker process (the chip belongs to one process) and
prints one JSON line per configuration.  Delta timing as in bench.py.

A supervisor process (never imports jax) runs the measurement in a
watchdogged subprocess, so a hang yields an error line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _supervise import supervise  # noqa: E402


def build(batch):
    import jax
    import optax

    from stoke_tpu import Stoke, StokeOptimizer
    from stoke_tpu.models import ResNet50
    from stoke_tpu.utils import init_module

    model = ResNet50(num_classes=10, cifar_stem=True)
    variables = init_module(
        model, jax.random.PRNGKey(0), np.zeros((2, 32, 32, 3), np.float32), train=False
    )
    on_accel = jax.default_backend() not in ("cpu",)
    return Stoke(
        model=model,
        optimizer=StokeOptimizer(
            optimizer=optax.sgd, optimizer_kwargs={"learning_rate": 0.05, "momentum": 0.9}
        ),
        loss=lambda logits, labels: optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean(),
        params=variables,
        batch_size_per_device=batch,
        device="tpu" if on_accel else "cpu",
        precision="bf16",
        model_train_kwargs={"train": True},
        model_eval_kwargs={"train": False},
        verbose=False,
    )


def measure(stoke, batch, api, steps=30, warmup=5):
    import jax

    r = np.random.default_rng(0)
    if api == "train_steps":
        # multi-step scan: SEG optimizer steps per dispatch, stacked inputs
        SEG = 10
        xs = jax.device_put(
            r.normal(size=(SEG, batch, 32, 32, 3)).astype(np.float32)
        )
        ys = jax.device_put(r.integers(0, 10, size=(SEG, batch)))

        def one_step(i):
            return stoke.train_steps(xs, (ys,))

        per_call = SEG
    else:
        pool = [
            (
                jax.device_put(r.normal(size=(batch, 32, 32, 3)).astype(np.float32)),
                jax.device_put(r.integers(0, 10, size=(batch,))),
            )
            for _ in range(4)
        ]

        def one_step(i):
            x, y = pool[i % len(pool)]
            if api == "train_step":
                return stoke.train_step(x, (y,))
            out = stoke.model(x)
            loss = stoke.loss(out, y)
            stoke.backward(loss)
            stoke.step()
            return loss

        per_call = 1

    def timed(n):
        t0 = time.perf_counter()
        last = None
        for i in range(n):
            last = one_step(i)
        np.asarray(jax.tree_util.tree_leaves(last)[0])
        return time.perf_counter() - t0

    for i in range(warmup):
        one_step(i)
    timed(1)
    t1 = timed(steps)
    t2 = timed(2 * steps)
    dt = max(t2 - t1, 1e-9)
    return batch * steps * per_call / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--_worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--batches", default="256,512,1024")
    ap.add_argument("--apis", default="4call,train_step,train_steps")
    ap.add_argument("--steps", type=int, default=None)
    args = ap.parse_args()
    if not args._worker:
        sys.exit(supervise(__file__, sys.argv[1:]))
    results = []
    for batch in (int(b) for b in args.batches.split(",")):
        for api in args.apis.split(","):
            stoke = build(batch)
            kw = {"steps": args.steps} if args.steps else {}
            if api == "train_steps":
                # each call is already 10 steps; fewer outer reps needed
                kw = {"steps": max(3, (args.steps or 30) // 10), "warmup": 1}
            ips = measure(stoke, batch, api, **kw)
            rec = {"batch": batch, "api": api, "imgs_per_sec": round(ips, 1)}
            print(json.dumps(rec), flush=True)
            results.append(rec)
            del stoke
    best = max(results, key=lambda r: r["imgs_per_sec"])
    print(json.dumps({"best": best}))


if __name__ == "__main__":
    main()
