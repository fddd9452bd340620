"""Utilization anatomy for the CIFAR-10 ResNet-50 bench (gap analysis).

Measures, on one chip:
  1. bf16 matmul peak (8k^3) — the realistic MXU ceiling on this part
  2. ResNet-50 fwd-only (eval) step time
  3. full train_steps segment time (the bench path)
  4. XLA cost-model FLOPs of one fused optimizer step (facade
     estimate_step_flops)
and prints achieved TFLOP/s + fraction of measured peak per phase.

The point: if (3) tracks (4)/(1) closely and the 4call/train_step/
train_steps spread is small, the gap to the A100 constant is conv-shape
utilization (32x32 images, narrow channels), not framework overhead.

Run on the chip (supervised: the worker is the one chip-owning process).
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _supervise import supervise  # noqa: E402


_SMOKE_RUN = False  # set from --smoke: smoke results must NEVER persist


def _mfu_fields(step_flops, step_seconds, peak_tflops):
    """Achieved TFLOP/s + fraction-of-peak via the shared CostCard
    arithmetic (stoke_tpu.telemetry.attribution.roofline_summary) — the
    same math the live attribution gauges use, instead of this script
    re-deriving ``flops / t / 1e12`` per arm (ISSUE 4 satellite).
    Returns None when the backend reported no FLOPs."""
    from stoke_tpu.telemetry.attribution import roofline_summary

    rl = roofline_summary(step_flops, step_seconds, peak_tflops)
    if rl["achieved_tflops"] is None:
        return None
    return {
        "achieved_tflops": round(rl["achieved_tflops"], 2),
        "fraction": round(rl["mfu"], 4),
    }


def _persist_mfu(metric: str, mfu, rec: dict, peak_tflops: float) -> None:
    """Record an on-chip MFU measurement in the shared BENCH_RESULTS.json
    ledger.  Keep-best, accelerator-backed records only; never fails the
    probe run."""
    try:
        import time as _time

        import jax as _jax

        if _SMOKE_RUN or _jax.default_backend() == "cpu" or not mfu:
            return
        import bench

        bench.persist_result(
            metric,
            {
                "value": float(mfu),
                "unit": "mfu_vs_measured_matmul_peak",
                "vs_baseline": float(mfu),
                "date": _time.strftime("%Y-%m-%d"),
                "api": rec.get("probe"),
                "batch": rec.get("batch"),
                "backend": _jax.default_backend(),
                "peak_tflops": round(float(peak_tflops), 1),
                "achieved_tflops": rec.get("achieved_tflops"),
                "step_ms": rec.get("step_ms"),
                "source": "scripts/flops_probe.py fresh on-chip capture",
            },
            keep_best=True,
        )
    except Exception as e:  # ledger write must never fail the probe
        print(json.dumps({"ledger_error": str(e)[:120]}), flush=True)


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--_worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--gpt-size", default="base",
                    choices=["none", "tiny", "mini", "small", "medium",
                             "base", "large"],
                    help="compute-dense GPT phase size ('none' skips it)")
    ap.add_argument("--gpt-len", type=int, default=1024)
    ap.add_argument("--gpt-batch", type=int, default=8)
    ap.add_argument("--flash-len", type=int, default=4096,
                    help="sequence length of the flash+chunked-CE arm")
    ap.add_argument("--peak-n", type=int, default=8192,
                    help="matmul-peak probe size (shrink for CPU smokes)")
    ap.add_argument("--smoke", action="store_true",
                    help="CPU-safe flow validation: tiny shapes everywhere "
                    "(results are meaningless; nothing persists off-chip)")
    args = ap.parse_args()
    if args.smoke:
        global _SMOKE_RUN
        _SMOKE_RUN = True
        args.peak_n = min(args.peak_n, 512)
        args.gpt_size = "tiny"
        args.gpt_len = 128
        args.gpt_batch = 2
        args.flash_len = 256
    if not args._worker:
        sys.exit(supervise(__file__, sys.argv[1:]))

    import jax
    import jax.numpy as jnp
    import optax

    from stoke_tpu import Stoke, StokeOptimizer
    from stoke_tpu.models import ResNet50
    from stoke_tpu.utils import init_module

    from _timing import delta_time

    r = np.random.default_rng(0)

    # 1. matmul peak
    N = args.peak_n
    a = jax.device_put(jnp.asarray(r.normal(size=(N, N)).astype(np.float32),
                                   jnp.bfloat16))
    b = jax.device_put(jnp.asarray(r.normal(size=(N, N)).astype(np.float32),
                                   jnp.bfloat16))
    mm = jax.jit(lambda: (a @ b))
    t_mm = delta_time(mm, 10)
    peak_tflops = 2 * N**3 / t_mm / 1e12
    print(json.dumps({"probe": "matmul_peak", "n": N,
                      "ms": round(t_mm * 1e3, 3),
                      "tflops": round(peak_tflops, 1)}), flush=True)

    # 2-4. ResNet-50 through the facade (smoke: a narrow ResNet-18 — the
    # 50-layer compile alone takes minutes on one CPU core)
    batch, SEG = (16, 2) if args.smoke else (256, 10)
    if args.smoke:
        from stoke_tpu.models import ResNet18

        model = ResNet18(num_classes=10, num_filters=8, cifar_stem=True)
    else:
        model = ResNet50(num_classes=10, cifar_stem=True)
    variables = init_module(
        model, jax.random.PRNGKey(0), np.zeros((2, 32, 32, 3), np.float32),
        train=False,
    )
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
    x1 = jax.device_put(r.normal(size=(batch, 32, 32, 3)).astype(np.float32))
    y1 = jax.device_put(r.integers(0, 10, size=(batch,)))

    step_flops = stoke.estimate_step_flops(x1, (y1,))
    print(json.dumps({"probe": "cost_analysis",
                      "gflops_per_step": None if step_flops is None
                      else round(step_flops / 1e9, 1)}), flush=True)

    stoke.eval()
    t_fwd = delta_time(lambda: stoke.model(x1), 20)
    stoke.train()
    print(json.dumps({"probe": "fwd_only", "ms": round(t_fwd * 1e3, 3),
                      "imgs_per_sec": round(batch / t_fwd, 1)}), flush=True)

    xs = jax.device_put(r.normal(size=(SEG, batch, 32, 32, 3)).astype(np.float32))
    ys = jax.device_put(r.integers(0, 10, size=(SEG, batch)))
    t_seg = delta_time(lambda: stoke.train_steps(xs, (ys,)), 3)
    step_ms = t_seg / SEG * 1e3
    ips = batch * SEG / t_seg
    rec = {"probe": "train_steps", "step_ms": round(step_ms, 3),
           "batch": batch, "imgs_per_sec": round(ips, 1)}
    mf = _mfu_fields(step_flops, t_seg / SEG, peak_tflops)
    if mf:
        rec["achieved_tflops"] = mf["achieved_tflops"]
        rec["fraction_of_matmul_peak"] = mf["fraction"]
        _persist_mfu("cifar10_resnet50_bf16_train_mfu", rec
                     ["fraction_of_matmul_peak"], rec, peak_tflops)
    print(json.dumps(rec), flush=True)
    del stoke, xs, ys

    # 4b. ImageNet-shape ResNet-50 (224x224): the conv-utilization control.
    # Same model family as the headline bench but with spatial extents that
    # CAN tile the MXU — if ITS fraction-of-peak is healthy while the 32x32
    # run's is not, the CIFAR gap is conv shape, not the conv path itself.
    if not args.smoke:
        b224 = 64
        model224 = ResNet50(num_classes=1000, cifar_stem=False)
        v224 = init_module(
            model224, jax.random.PRNGKey(0),
            np.zeros((2, 224, 224, 3), np.float32), train=False,
        )
        s224 = Stoke(
            model=model224,
            optimizer=StokeOptimizer(
                optimizer=optax.sgd,
                optimizer_kwargs={"learning_rate": 0.05, "momentum": 0.9},
            ),
            loss=lambda lo, la: (
                optax.softmax_cross_entropy_with_integer_labels(lo, la).mean()
            ),
            params=v224,
            batch_size_per_device=b224,
            device="tpu" if jax.default_backend() != "cpu" else "cpu",
            precision="bf16",
            model_train_kwargs={"train": True},
            model_eval_kwargs={"train": False},
            verbose=False,
        )
        x224 = jax.device_put(
            r.normal(size=(b224, 224, 224, 3)).astype(np.float32))
        y224 = jax.device_put(r.integers(0, 1000, size=(b224,)))
        f224 = s224.estimate_step_flops(x224, (y224,))
        xs224 = jax.device_put(
            r.normal(size=(2, b224, 224, 224, 3)).astype(np.float32))
        ys224 = jax.device_put(r.integers(0, 1000, size=(2, b224)))
        t224 = delta_time(lambda: s224.train_steps(xs224, (ys224,)), 3)
        rec224 = {"probe": "resnet224", "batch": b224,
                  "step_ms": round(t224 / 2 * 1e3, 2),
                  "imgs_per_sec": round(b224 * 2 / t224, 1)}
        mf224 = _mfu_fields(f224, t224 / 2, peak_tflops)
        if mf224:
            rec224["achieved_tflops"] = mf224["achieved_tflops"]
            rec224["fraction_of_matmul_peak"] = mf224["fraction"]
            _persist_mfu("imagenet_resnet50_224_bf16_train_mfu",
                         rec224["fraction_of_matmul_peak"], rec224,
                         peak_tflops)
        print(json.dumps(rec224), flush=True)
        del s224, xs224, ys224, x224, y224, v224, model224

    # 5. compute-dense ceiling: GPT with MXU-shaped matmuls (hidden-width
    # GEMMs at seq 1k).  If THIS hits a healthy fraction of the measured
    # matmul peak while the 32x32 ResNet does not, the ResNet gap is
    # conv-shape utilization, not framework overhead — the round-2 gap
    # analysis keystone, now measured instead of argued.
    if args.gpt_size != "none":
        from stoke_tpu.models import causal_lm_loss
        from stoke_tpu.models.gpt import GPT

        L, gb, GSEG = args.gpt_len, args.gpt_batch, 4
        gpt = GPT(vocab_size=32768, size_name=args.gpt_size, max_len=L,
                  dropout_rate=0.0)
        gvars = init_module(
            gpt, jax.random.PRNGKey(0), np.zeros((2, L), np.int32),
            train=False,
        )
        gstoke = Stoke(
            model=gpt,
            optimizer=StokeOptimizer(
                optimizer=optax.adamw,
                optimizer_kwargs={"learning_rate": 3e-4},
            ),
            loss=causal_lm_loss,
            params=gvars,
            batch_size_per_device=gb,
            device="tpu" if jax.default_backend() != "cpu" else "cpu",
            precision="bf16",
            model_train_kwargs={"train": True},
            model_eval_kwargs={"train": False},
            verbose=False,
        )
        ids1 = jax.device_put(
            r.integers(0, 32768, size=(gb, L)).astype(np.int32))
        g_flops = gstoke.estimate_step_flops(ids1, (ids1,))
        gids = jax.device_put(
            r.integers(0, 32768, size=(GSEG, gb, L)).astype(np.int32))
        t_g = delta_time(lambda: gstoke.train_steps(gids, (gids,)), 3)
        grec = {"probe": "gpt_dense", "size": args.gpt_size, "L": L,
                "batch": gb,
                "step_ms": round(t_g / GSEG * 1e3, 2),
                "tok_per_sec": round(gb * L * GSEG / t_g, 1)}
        gmf = _mfu_fields(g_flops, t_g / GSEG, peak_tflops)
        if gmf:
            grec["achieved_tflops"] = gmf["achieved_tflops"]
            grec["mfu_vs_matmul_peak"] = gmf["fraction"]
            _persist_mfu(f"gpt_{args.gpt_size}_bf16_train_mfu",
                         grec["mfu_vs_matmul_peak"], grec, peak_tflops)
        print(json.dumps(grec), flush=True)
        del gstoke, gids

        # 6. long-context composition: flash attention + chunked LM-head CE
        # at L=4k, vocab 32k (VERDICT r3 item 3's "flash + chunked-CE" GPT
        # arm) — the realistic long-context train configuration whose MFU
        # belongs in the ledger next to the dense arm
        from stoke_tpu.ops import chunked_causal_lm_loss, make_flash_attention

        Lf = args.flash_len
        fb = max(1, args.gpt_batch // 4)
        gptf = GPT(vocab_size=32768, size_name=args.gpt_size, max_len=Lf,
                   dropout_rate=0.0, chunked_head=True,
                   attention_fn=make_flash_attention(causal=True),
                   attention_is_causal=True)
        fvars = init_module(
            gptf, jax.random.PRNGKey(0), np.zeros((2, Lf), np.int32),
            train=False,
        )
        fstoke = Stoke(
            model=gptf,
            optimizer=StokeOptimizer(
                optimizer=optax.adamw,
                optimizer_kwargs={"learning_rate": 3e-4},
            ),
            loss=lambda out, ids: chunked_causal_lm_loss(out, ids, chunk=512),
            params=fvars,
            batch_size_per_device=fb,
            device="tpu" if jax.default_backend() != "cpu" else "cpu",
            precision="bf16",
            model_train_kwargs={"train": True},
            model_eval_kwargs={"train": False},
            verbose=False,
        )
        fids1 = jax.device_put(
            r.integers(0, 32768, size=(fb, Lf)).astype(np.int32))
        f_flops = fstoke.estimate_step_flops(fids1, (fids1,))
        fids = jax.device_put(
            r.integers(0, 32768, size=(2, fb, Lf)).astype(np.int32))
        t_f = delta_time(lambda: fstoke.train_steps(fids, (fids,)), 3)
        frec = {"probe": "gpt_flash_chunked", "size": args.gpt_size,
                "L": Lf, "batch": fb,
                "step_ms": round(t_f / 2 * 1e3, 2),
                "tok_per_sec": round(fb * Lf * 2 / t_f, 1)}
        fmf = _mfu_fields(f_flops, t_f / 2, peak_tflops)
        if fmf:
            frec["achieved_tflops"] = fmf["achieved_tflops"]
            frec["mfu_vs_matmul_peak"] = fmf["fraction"]
            _persist_mfu(
                f"gpt_{args.gpt_size}_flash4k_chunkedce_train_mfu",
                frec["mfu_vs_matmul_peak"], frec, peak_tflops)
        print(json.dumps(frec), flush=True)


if __name__ == "__main__":
    main()
