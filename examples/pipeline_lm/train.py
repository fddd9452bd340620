"""Pipeline-parallel causal LM trained through ``Stoke.train_steps``.

Runnable demonstration of the dp×pp composition at framework level: a
decoder-only LM whose transformer blocks are split over 4 pipeline stages
(``PipelinedLM``), with the remaining mesh axis data-parallel, driven by the
multi-step scanned ``train_steps`` fast path (N optimizer steps per
compiled dispatch — the dispatch-amortization that matters on real TPU
links).

Hermetic by default — simulated 8-device CPU mesh, tiny shapes:

    env PYTHONPATH=/root/repo JAX_PLATFORMS=cpu \
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/pipeline_lm/train.py

On a TPU slice, drop the env overrides and scale --batch/--seq-len/--size.
Schedule characterization (bubble fraction vs microbatches/rounds):
docs/sharding.md.
"""

from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=1,
                    help="virtual stages per device (circular schedule)")
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--size", default="tiny")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--segment", type=int, default=5,
                    help="optimizer steps per train_steps dispatch")
    args = ap.parse_args()

    import jax
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from stoke_tpu import (
        MeshConfig,
        PartitionRulesConfig,
        Stoke,
        StokeOptimizer,
    )
    from stoke_tpu.models import (
        PipelinedLM,
        causal_lm_loss,
        pipeline_parallel_rules,
    )

    n = len(jax.devices())
    S = args.stages
    assert n % S == 0, f"{n} devices not divisible by {S} stages"
    dp = n // S
    mesh = Mesh(np.asarray(jax.devices()).reshape(dp, S), ("data", "stage"))
    print(f"mesh: dp{dp}×pp{S} over {n} {jax.devices()[0].platform} devices, "
          f"rounds={args.rounds}")

    if args.batch % (args.microbatches * max(dp, 1)) != 0:
        raise SystemExit(
            f"--batch {args.batch} must be divisible by microbatches×dp = "
            f"{args.microbatches}×{dp} (each microbatch's rows shard over "
            f"the data axis)"
        )
    adapter = PipelinedLM(
        mesh,
        vocab_size=256,
        size_name=args.size,
        max_len=args.seq_len,
        num_microbatches=args.microbatches,
        rounds=args.rounds,
        data_axis="data" if dp > 1 else None,
    )
    variables = adapter.init(jax.random.PRNGKey(0))
    stoke = Stoke(
        model=adapter,
        optimizer=StokeOptimizer(
            optimizer=optax.adam, optimizer_kwargs={"learning_rate": 3e-3}
        ),
        loss=causal_lm_loss,
        params=variables,
        batch_size_per_device=max(1, args.batch // n),
        distributed="dp",
        configs=[
            MeshConfig(axes=("data", "stage"), shape=(dp, S)),
            PartitionRulesConfig(rules=pipeline_parallel_rules()),
        ],
        verbose=False,
    )
    w = stoke.params["stages"]
    lead = jax.tree_util.tree_leaves(w)[0]
    print(f"stage-stacked params: lead dim {lead.shape[0]} "
          f"(= rounds×stages), sharding {lead.sharding.spec}")

    # learnable data: a small pool of FIXED sequences (the model memorizes
    # their next-token structure; fresh random tokens would sit at the
    # ln(vocab) entropy floor forever)
    r = np.random.default_rng(0)
    seg = args.segment
    pool = r.integers(1, 256, size=(16, args.seq_len)).astype(np.int32)

    def make_segment():
        idx = r.integers(0, len(pool), size=(seg, args.batch))
        return pool[idx]

    t0 = time.perf_counter()
    first = last = None
    done = 0
    while done < args.steps:
        seqs = make_segment()
        reports = stoke.train_steps(seqs, (seqs,))
        losses = np.asarray(jax.device_get(reports)).reshape(seg, -1)
        if first is None:
            first = float(losses[0].mean())
        last = float(losses[-1].mean())
        done += seg
        print(f"step {stoke.optimizer_steps:4d}  loss {last:.4f}  "
              f"({seg} steps/dispatch)")
    dt = time.perf_counter() - t0
    toks = args.steps * args.batch * args.seq_len
    print(f"trained {args.steps} steps in {dt:.2f}s "
          f"({toks / dt:,.0f} tok/s incl. compile) — "
          f"loss {first:.4f} → {last:.4f}")
    assert last < first, "loss must decrease on the copy task"
    print("OK")


if __name__ == "__main__":
    main()
