"""Framework-overhead isolation: Stoke facade vs hand-written JAX train step.

Times CIFAR-10 ResNet-50 bf16 training two ways on the same chip with the
same delta-timing rig as bench.py:
  1. `stoke.train_steps` (the framework's fastest path)
  2. a minimal hand-rolled jitted train step (flax apply + optax sgd, bf16
     casts inline, donated state) — the "no framework" ceiling
Prints one JSON line per variant; the ratio is the facade overhead.  Run
on the chip (supervised: the worker is the one chip-owning process).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _supervise import supervise  # noqa: E402


def main():
    if "--_worker" not in sys.argv:
        sys.exit(supervise(__file__, sys.argv[1:]))

    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--_worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--seg", type=int, default=10)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import optax

    from stoke_tpu import Stoke, StokeOptimizer
    from stoke_tpu.models import ResNet50
    from stoke_tpu.utils import init_module

    batch, SEG = args.batch, args.seg
    r = np.random.default_rng(0)
    model = ResNet50(num_classes=10, cifar_stem=True)
    variables = init_module(
        model, jax.random.PRNGKey(0), np.zeros((2, 32, 32, 3), np.float32),
        train=False,
    )

    def timed(fn, state, xs, ys, reps):
        t0 = time.perf_counter()
        out = None
        for _ in range(reps):
            state, out = fn(state, xs, ys)
        np.asarray(jax.tree_util.tree_leaves(out)[0])
        return time.perf_counter() - t0, state

    xs = jax.device_put(r.normal(size=(SEG, batch, 32, 32, 3)).astype(np.float32))
    ys = jax.device_put(r.integers(0, 10, size=(SEG, batch)))

    # ---- variant 1: facade train_steps ---------------------------------- #
    stoke = Stoke(
        model=model,
        optimizer=StokeOptimizer(
            optimizer=optax.sgd,
            optimizer_kwargs={"learning_rate": 0.05, "momentum": 0.9},
        ),
        loss=lambda lo, la: optax.softmax_cross_entropy_with_integer_labels(
            lo, la).mean(),
        params=variables,
        batch_size_per_device=batch,
        device="tpu" if jax.default_backend() != "cpu" else "cpu",
        precision="bf16",
        model_train_kwargs={"train": True},
        model_eval_kwargs={"train": False},
        verbose=False,
    )

    def facade_step(state, xs, ys):
        return state, stoke.train_steps(xs, (ys,))

    timed(facade_step, None, xs, ys, 1)  # compile
    t1, _ = timed(facade_step, None, xs, ys, 3)
    t2, _ = timed(facade_step, None, xs, ys, 6)
    ips = batch * 3 * SEG / max(t2 - t1, 1e-9)
    print(json.dumps({"variant": "facade_train_steps",
                      "imgs_per_sec": round(ips, 1)}), flush=True)
    del stoke

    # ---- variant 2: minimal hand-rolled JAX ----------------------------- #
    tx = optax.sgd(0.05, momentum=0.9)
    params = variables["params"]
    bstats = variables.get("batch_stats", {})
    opt = tx.init(params)

    def loss_fn(p, bs, x, y):
        out, upd = model.apply(
            {"params": p, "batch_stats": bs},
            x.astype(jnp.bfloat16), train=True, mutable=["batch_stats"],
        )
        return optax.softmax_cross_entropy_with_integer_labels(
            out.astype(jnp.float32), y).mean(), upd["batch_stats"]

    def one(state, xy):
        p, bs, opt = state
        x, y = xy
        (l, bs), g = jax.value_and_grad(loss_fn, has_aux=True)(p, bs, x, y)
        up, opt = tx.update(g, opt, p)
        return (optax.apply_updates(p, up), bs, opt), l

    @jax.jit
    def raw_steps(state, xs, ys):
        state, ls = jax.lax.scan(lambda s, xy: one(s, xy), state, (xs, ys))
        return state, ls[-1]

    state = (params, bstats, opt)
    _, state = timed(raw_steps, state, xs, ys, 1)  # compile
    t1, state = timed(raw_steps, state, xs, ys, 3)
    t2, state = timed(raw_steps, state, xs, ys, 6)
    ips_raw = batch * 3 * SEG / max(t2 - t1, 1e-9)
    print(json.dumps({"variant": "raw_jax_scan",
                      "imgs_per_sec": round(ips_raw, 1)}), flush=True)
    print(json.dumps({"facade_fraction_of_raw":
                      round(ips / max(ips_raw, 1e-9), 3)}), flush=True)


if __name__ == "__main__":
    main()
