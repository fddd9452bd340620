"""Reachability bound for the 20,000 imgs/sec north-star constant.

VERDICT r5 #1: the perf story ("0.46x and attacking") is unfalsifiable
until someone bounds what a v5e chip can physically do on cifar-stem
ResNet-50.  This script needs no chip: ``Stoke.estimate_step_flops``
(XLA cost analysis) works on the CPU backend, and the arithmetic from
FLOPs/img to implied TFLOP/s at a target imgs/sec is exact.

For each batch it prints one JSON line and finally a markdown table ready
for docs/performance.md:

  - flops/step (XLA cost analysis of the FULL fused optimizer step:
    forward + backward + SGD-momentum update, bf16 policy)
  - flops/img
  - implied TFLOP/s at the round-2 measured throughput (where one exists)
  - implied TFLOP/s and MFU at the 20,000 imgs/sec baseline constant
  - MFU against v5e bf16 peak (197 TFLOP/s, the public v5e spec)

Run:  JAX_PLATFORMS=cpu python scripts/reachability_table.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: public TPU v5e peak (dense bf16); the MFU denominator for the table
V5E_BF16_PEAK_TFLOPS = 197.0

#: imgs/sec measured on one v5e chip on 2026-07-29 (batch/API sweep,
#: train_steps; an older JAX — not re-measured since)
MEASURED_IMGS_PER_SEC = {256: 9257.0, 512: 8411.4, 1024: 7786.1}

#: the baseline constant encoded in bench.py
BASELINE_IMGS_PER_SEC = 20000.0


def build_stoke(batch, *, cifar=True):
    import jax
    import optax

    from stoke_tpu import Stoke, StokeOptimizer
    from stoke_tpu.models import ResNet50
    from stoke_tpu.utils import init_module

    side = 32 if cifar else 224
    classes = 10 if cifar else 1000
    model = ResNet50(num_classes=classes, cifar_stem=cifar)
    variables = init_module(
        model, jax.random.PRNGKey(0),
        np.zeros((2, side, side, 3), np.float32), train=False,
    )
    return Stoke(
        model=model,
        optimizer=StokeOptimizer(
            optimizer=optax.sgd,
            optimizer_kwargs={"learning_rate": 0.05, "momentum": 0.9},
        ),
        loss=lambda lo, la: __import__("optax")
        .softmax_cross_entropy_with_integer_labels(lo, la).mean(),
        params=variables,
        batch_size_per_device=batch,
        device="cpu" if jax.default_backend() == "cpu" else "tpu",
        precision="bf16",
        model_train_kwargs={"train": True},
        model_eval_kwargs={"train": False},
        verbose=False,
    ), side, classes


def probe(batch, *, cifar=True):
    import jax

    stoke, side, classes = build_stoke(batch, cifar=cifar)
    r = np.random.default_rng(0)
    x = jax.device_put(r.normal(size=(batch, side, side, 3)).astype(np.float32))
    y = jax.device_put(r.integers(0, classes, size=(batch,)))
    flops = stoke.estimate_step_flops(x, (y,))
    del stoke
    if flops is None:
        return None
    per_img = flops / batch
    rec = {
        "probe": "reachability",
        "config": "cifar32" if cifar else "imagenet224",
        "batch": batch,
        "gflops_per_step": round(flops / 1e9, 2),
        "mflops_per_img": round(per_img / 1e6, 2),
        "tflops_at_baseline_20k": round(per_img * BASELINE_IMGS_PER_SEC / 1e12, 3),
        "mfu_at_baseline_20k": round(
            per_img * BASELINE_IMGS_PER_SEC / 1e12 / V5E_BF16_PEAK_TFLOPS, 4
        ),
    }
    measured = MEASURED_IMGS_PER_SEC.get(batch) if cifar else None
    if measured:
        rec["measured_imgs_per_sec_r2"] = measured
        rec["tflops_at_measured"] = round(per_img * measured / 1e12, 3)
        rec["mfu_at_measured"] = round(
            per_img * measured / 1e12 / V5E_BF16_PEAK_TFLOPS, 4
        )
    print(json.dumps(rec), flush=True)
    return rec


def probe_serving(max_seqs=8):
    """Serving reachability row (ISSUE 18): the serve cost cards bound
    what one v5e chip could do on the bench arm's GPT-small decode loop.
    The decode program's roofline time at the v5e peaks is the attainable
    TPOT, so ``max_seqs / attainable_tpot_s`` is the attainable steady-
    state tokens/s/chip — exact arithmetic from the XLA cost analysis,
    no chip needed (the CPU backend lowers the same programs).  The
    measured leg cites the bench ledger's persisted on-chip serve
    capture when one exists."""
    import jax

    from stoke_tpu.configs import AttributionConfig, ServeConfig
    from stoke_tpu.models.gpt import GPT
    from stoke_tpu.serving import ServingEngine
    from stoke_tpu.utils import init_module

    # the bench --serve non-tiny arm's geometry (bench.py build_engine)
    model = GPT(
        vocab_size=8192, size_name="small", max_len=512, dropout_rate=0.0
    )
    variables = init_module(
        model, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32),
        train=False,
    )
    cfg = ServeConfig(
        max_seqs=max_seqs, kv_block_size=16, max_seq_len=256,
        max_new_tokens=8, prefill_pad_multiple=32, cost_cards=True,
    )
    eng = ServingEngine(
        model, variables["params"], cfg,
        attribution=AttributionConfig(
            peak_tflops=V5E_BF16_PEAK_TFLOPS, peak_hbm_gbps=819.0
        ),
    )
    r = np.random.default_rng(0)
    for _ in range(2):  # one prefill bucket + the decode program
        eng.submit(r.integers(1, 8192, size=24).astype(np.int32))
    eng.run()
    cost = eng.summary()["cost"]
    att = cost["attainable_tpot_s"]
    if att is None:
        return None
    rec = {
        "probe": "reachability",
        "config": f"gpt_small_serve (max_seqs={max_seqs})",
        "flops_per_token": round(cost["flops_per_token"] or 0.0, 1),
        "decode_bound": cost["decode_bound"],
        "attainable_tpot_s": round(att, 9),
        "attainable_tokens_per_sec_chip": round(max_seqs / att, 1),
    }
    # measured leg: the persisted on-chip bench capture, when one exists
    try:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        import bench

        ledger_rec = bench._load_results().get("gpt_small_serve_throughput")
        if ledger_rec and ledger_rec.get("platform") == "tpu":
            rec["measured_tokens_per_sec"] = ledger_rec["value"]
            rec["roofline_fraction"] = round(
                ledger_rec["value"] / (max_seqs / att), 4
            )
    except Exception:
        pass
    print(json.dumps(rec), flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", default="128,256,512,1024")
    ap.add_argument("--skip-224", action="store_true")
    ap.add_argument(
        "--skip-serve", action="store_true",
        help="skip the serving reachability row (ISSUE 18)",
    )
    args = ap.parse_args()

    rows = []
    for b in (int(x) for x in args.batches.split(",")):
        rec = probe(b, cifar=True)
        if rec:
            rows.append(rec)
    if not args.skip_224:
        rec = probe(64, cifar=False)
        if rec:
            rows.append(rec)
    serve_row = None if args.skip_serve else probe_serving()

    # markdown for docs/performance.md
    print("\n| config | batch | MFLOPs/img | TFLOP/s @ measured (MFU) | "
          "TFLOP/s @ 20k (MFU) |")
    print("|---|---|---|---|---|")
    for r in rows:
        meas = (
            f"{r['tflops_at_measured']} ({r['mfu_at_measured']:.1%} "
            f"@ {r['measured_imgs_per_sec_r2']:.0f} img/s)"
            if "tflops_at_measured" in r else "—"
        )
        print(
            f"| {r['config']} | {r['batch']} | {r['mflops_per_img']} | "
            f"{meas} | {r['tflops_at_baseline_20k']} "
            f"({r['mfu_at_baseline_20k']:.1%}) |"
        )
    if serve_row:
        # serving reachability (ISSUE 18): attainable tokens/s/chip at
        # the v5e peaks from the decode-family cost card, beside the
        # ledger's measured on-chip capture when one exists
        meas = (
            f"{serve_row['measured_tokens_per_sec']:.0f} tok/s "
            f"({serve_row['roofline_fraction']:.1%} of roofline)"
            if "measured_tokens_per_sec" in serve_row else "—"
        )
        print(
            f"| {serve_row['config']} | — | "
            f"{serve_row['flops_per_token'] / 1e6:.1f} MFLOPs/tok | "
            f"{meas} | attainable "
            f"{serve_row['attainable_tokens_per_sec_chip']:.0f} tok/s/chip "
            f"({serve_row['decode_bound']}-bound) |"
        )


if __name__ == "__main__":
    main()
