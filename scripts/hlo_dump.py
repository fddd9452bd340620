"""Offline HLO capture of the fused ResNet-50 optimizer step (CPU lowering).

VERDICT r5 #4: the falls-with-batch anomaly (9,257 imgs/sec @ batch 256 →
7,786 @ 1024 on v5e) has an evidence kit (scripts/profile_capture.py) but
the one artifact it produced was never analyzed and artifacts/ is not
committed.  This script regenerates the evidence with no chip: it lowers
and compiles the exact fused step the bench runs (bf16 policy, SGD momentum)
on the CPU backend at several batch sizes, writes the optimized HLO to
``artifacts/hlo_resnet50_cpu_bs<N>.txt.gz``, and prints the op-category
histogram per batch.

CPU-optimized HLO is NOT TPU-optimized HLO (different fusion/layout passes);
the op mix and op-count scaling with batch are still mechanical evidence for
the gap decomposition (docs/performance.md) — convolution/reduce/fusion counts
are batch-invariant (the graph is the same program, only shapes change), so
what changes with batch is per-op shape efficiency, not schedule length.

Run:  JAX_PLATFORMS=cpu python scripts/hlo_dump.py --batches 16,256,1024
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

from profile_capture import _hlo_histogram  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", default="16,256,1024")
    args = ap.parse_args()

    import jax
    import optax

    from stoke_tpu import Stoke, StokeOptimizer
    from stoke_tpu.engine import DeferredOutput
    from stoke_tpu.facade import is_deferred
    from stoke_tpu.models import ResNet50
    from stoke_tpu.utils import init_module

    artifacts = os.path.join(REPO, "artifacts")
    os.makedirs(artifacts, exist_ok=True)
    r = np.random.default_rng(0)
    model = ResNet50(num_classes=10, cifar_stem=True)
    variables = init_module(
        model, jax.random.PRNGKey(0), np.zeros((2, 32, 32, 3), np.float32),
        train=False,
    )

    for batch in (int(b) for b in args.batches.split(",")):
        stoke = Stoke(
            model=model,
            optimizer=StokeOptimizer(
                optimizer=optax.sgd,
                optimizer_kwargs={"learning_rate": 0.05, "momentum": 0.9},
            ),
            loss=lambda lo, la: (
                optax.softmax_cross_entropy_with_integer_labels(lo, la).mean()
            ),
            params=jax.tree_util.tree_map(lambda a: a.copy(), variables),
            batch_size_per_device=batch,
            device="cpu" if jax.default_backend() == "cpu" else "tpu",
            precision="bf16",
            model_train_kwargs={"train": True},
            model_eval_kwargs={"train": False},
            verbose=False,
        )
        x1 = jax.device_put(r.normal(size=(batch, 32, 32, 3)).astype(np.float32))
        y1 = jax.device_put(r.integers(0, 10, size=(batch,)))
        sentinel = DeferredOutput(None, -1)
        flat, treedef = jax.tree_util.tree_flatten(
            ((sentinel, y1), {}), is_leaf=is_deferred)
        arrays = stoke._place_batch([l for l in flat if not is_deferred(l)])
        dinfo = tuple((i, l._path) for i, l in enumerate(flat)
                      if is_deferred(l))
        fn = stoke._engine._build_fused(treedef, dinfo, True)
        # comm_state threads through the fused step on engines with the
        # gradient-transport layer; older snapshots lower without it
        extra = (
            (stoke._comm_state,) if hasattr(stoke, "_comm_state") else ()
        )
        compiled = fn.lower(
            stoke._variables, stoke._opt_state, stoke._grad_buf,
            stoke._scaler_state, *extra, stoke._rng,
            stoke._place_batch((x1,)), {}, arrays,
        ).compile()
        text = compiled.as_text()
        try:
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            flops = cost.get("flops")
        except Exception:
            flops = None
        hist = _hlo_histogram(text)
        path = os.path.join(artifacts, f"hlo_resnet50_cpu_bs{batch}.txt.gz")
        with gzip.open(path, "wt") as f:
            f.write(text)
        print(json.dumps({
            "probe": "hlo_dump_cpu", "batch": batch,
            "path": os.path.relpath(path, REPO),
            "gflops_per_step": None if flops is None else round(flops / 1e9, 2),
            **hist,
        }), flush=True)
        del stoke, compiled, text


if __name__ == "__main__":
    main()
