"""Evidence capture for the ResNet throughput gap (VERDICT r3 item 2).

Round-2 measured 9,257 imgs/sec/chip at batch 256 *falling* to 7,786 at
1024, with no profiler/HLO evidence explaining why.  This script captures,
in one session on the chip:

  1. batch sweep — train_steps imgs/sec at several batch sizes (the
     falls-with-batch reproduction), persisted per batch;
  2. wall-clock breakdown — the facade's phase timers after the sweep;
  3. optimized-HLO dump of the fused optimizer step (batch 256 and the
     sweep's worst batch): op-category histogram (convolution / fusion /
     reduce / collectives / copies) printed, full text gzipped into
     artifacts/ for offline reading;
  4. optional jax.profiler trace (--trace-dir) around 3 steps.

Flags A/B: pass extra XLA flags via --xla-flags; they are applied to
XLA_FLAGS BEFORE jax import in the worker, so autotune experiments
(e.g. --xla_tpu_enable_experimental_fusion_cost_model=true) are one
flag away and land in the printed records.

Run on the chip (supervised: the worker is the one chip-owning process):
    python scripts/profile_capture.py --batches 128,256,512,1024
"""

from __future__ import annotations

import gzip
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

from _supervise import supervise  # noqa: E402


def _hlo_histogram(text: str) -> dict:
    cats = {
        "convolution": 0, "fusion": 0, "all-reduce": 0, "all-gather": 0,
        "reduce-scatter": 0, "copy": 0, "transpose": 0, "reduce": 0,
        "custom-call": 0,
    }
    for line in text.splitlines():
        ls = line.lstrip()
        for cat in cats:
            if ls.startswith(f"%{cat}") or f" = {cat}(" in ls or (
                cat + "." in ls.split("=")[-1][:40] if "=" in ls else False
            ):
                cats[cat] += 1
                break
    cats["total_lines"] = len(text.splitlines())
    return cats


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--_worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--batches", default="128,256,512,1024")
    ap.add_argument("--xla-flags", default="",
                    help="extra XLA_FLAGS for the worker (A/B autotune runs)")
    ap.add_argument("--trace-dir", default="",
                    help="capture a jax.profiler trace into this dir")
    ap.add_argument("--seg", type=int, default=10)
    ap.add_argument("--smoke", action="store_true",
                    help="CPU flow validation: narrow ResNet-18, tiny "
                    "batches (results meaningless)")
    args = ap.parse_args()
    if args.smoke:
        args.batches = "8,16"
        args.seg = 2
    if not args._worker:
        if args.xla_flags:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " " + args.xla_flags
            ).strip()
        sys.exit(supervise(__file__, sys.argv[1:]))

    import jax
    import optax

    from stoke_tpu import ProfilerConfig, Stoke, StokeOptimizer
    from stoke_tpu.models import ResNet50
    from stoke_tpu.utils import init_module

    from _timing import delta_time

    r = np.random.default_rng(0)
    batches = [int(b) for b in args.batches.split(",")]
    SEG = args.seg
    on_accel = jax.default_backend() != "cpu"
    if args.smoke:
        from stoke_tpu.models import ResNet18

        model = ResNet18(num_classes=10, num_filters=8, cifar_stem=True)
    else:
        model = ResNet50(num_classes=10, cifar_stem=True)
    variables = init_module(
        model, jax.random.PRNGKey(0), np.zeros((2, 32, 32, 3), np.float32),
        train=False,
    )
    artifacts = os.path.join(REPO, "artifacts")
    os.makedirs(artifacts, exist_ok=True)

    def make_stoke(batch):
        return Stoke(
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
            device="tpu" if on_accel else "cpu",
            precision="bf16",
            model_train_kwargs={"train": True},
            model_eval_kwargs={"train": False},
            # without this the facade's phase timers are nullcontexts and
            # the wall_clock probe would print empty
            configs=[ProfilerConfig(wall_clock_breakdown=True)],
            verbose=False,
        )

    results = []
    for batch in batches:
        stoke = make_stoke(batch)
        xs = jax.device_put(
            r.normal(size=(SEG, batch, 32, 32, 3)).astype(np.float32))
        ys = jax.device_put(r.integers(0, 10, size=(SEG, batch)))
        t_seg = delta_time(lambda: stoke.train_steps(xs, (ys,)), 3)
        rec = {
            "probe": "batch_sweep",
            "batch": batch,
            "step_ms": round(t_seg / SEG * 1e3, 3),
            "imgs_per_sec": round(batch * SEG / t_seg, 1),
            "xla_flags": args.xla_flags or None,
        }
        results.append(rec)
        print(json.dumps(rec), flush=True)

        if batch == 256 or batch == batches[-1]:
            # (smoke included: the HLO lower/compile path is the point)
            # optimized HLO of the fused step at this batch
            x1 = jax.device_put(
                r.normal(size=(batch, 32, 32, 3)).astype(np.float32))
            y1 = jax.device_put(r.integers(0, 10, size=(batch,)))
            try:
                from stoke_tpu.engine import DeferredOutput as _D
                from stoke_tpu.facade import is_deferred

                sentinel = _D(None, -1)
                flat, treedef = jax.tree_util.tree_flatten(
                    ((sentinel, y1), {}), is_leaf=is_deferred)
                arrays = stoke._place_batch(
                    [l for l in flat if not is_deferred(l)])
                dinfo = tuple((i, l._path) for i, l in enumerate(flat)
                              if is_deferred(l))
                fn = stoke._engine._build_fused(treedef, dinfo, True)
                compiled = fn.lower(
                    stoke._variables, stoke._opt_state, stoke._grad_buf,
                    stoke._scaler_state, stoke._comm_state, stoke._rng,
                    stoke._place_batch((x1,)), {}, arrays,
                ).compile()
                text = compiled.as_text()
                hist = _hlo_histogram(text)
                path = os.path.join(
                    artifacts, f"hlo_resnet50_bs{batch}.txt.gz")
                with gzip.open(path, "wt") as f:
                    f.write(text)
                print(json.dumps({"probe": "hlo_dump", "batch": batch,
                                  "path": os.path.relpath(path, REPO),
                                  **hist}), flush=True)
            except Exception as e:
                print(json.dumps({"probe": "hlo_dump", "batch": batch,
                                  "error": str(e)[:200]}), flush=True)

        if args.trace_dir and batch == 256:
            with jax.profiler.trace(args.trace_dir):
                for _ in range(3):
                    stoke.train_steps(xs, (ys,))
                stoke.block_until_ready()
            print(json.dumps({"probe": "trace", "dir": args.trace_dir}),
                  flush=True)
        print(json.dumps({"probe": "wall_clock", "batch": batch,
                          **{k: round(v, 3) for k, v in
                             stoke.wall_clock_breakdown.items()}}),
              flush=True)
        del stoke, xs, ys

    if len(results) > 1:
        best = max(results, key=lambda r: r["imgs_per_sec"])
        worst = min(results, key=lambda r: r["imgs_per_sec"])
        print(json.dumps({
            "probe": "sweep_summary",
            "best": {k: best[k] for k in ("batch", "imgs_per_sec")},
            "worst": {k: worst[k] for k in ("batch", "imgs_per_sec")},
            "falls_with_batch": results[-1]["imgs_per_sec"]
            < results[0]["imgs_per_sec"],
        }), flush=True)

    # segment-length sweep at the headline batch: each train_steps dispatch
    # is one host->device round trip whose per-step share is
    # latency/SEG, so if throughput rises
    # with SEG the gap is dispatch latency (recoverable by config), not
    # compute.  delta_time cancels FIXED overhead but not per-dispatch
    # cost.  Runs AFTER the summary, each arm fenced, so a seg-arm failure
    # (OOM on the 50-step stack) cannot lose the evidence the batch sweep
    # already paid chip time for.
    seg_batch = 256 if 256 in batches else batches[0]
    for seg in (10, 25, 50):
        if args.smoke and seg > 10:
            break
        if seg == SEG:
            # the batch sweep already measured this exact configuration —
            # reuse it instead of paying chip time for a duplicate point
            prior = next(r for r in results if r["batch"] == seg_batch)
            print(json.dumps({
                "probe": "seg_sweep", "batch": seg_batch, "seg": seg,
                "step_ms": prior["step_ms"],
                "imgs_per_sec": prior["imgs_per_sec"],
                "reused_from_batch_sweep": True,
            }), flush=True)
            continue
        stoke = xs = ys = None
        try:
            stoke = make_stoke(seg_batch)
            xs = jax.device_put(
                r.normal(size=(seg, seg_batch, 32, 32, 3)).astype(np.float32))
            ys = jax.device_put(r.integers(0, 10, size=(seg, seg_batch)))
            t = delta_time(lambda: stoke.train_steps(xs, (ys,)), 3)
            print(json.dumps({
                "probe": "seg_sweep", "batch": seg_batch, "seg": seg,
                "step_ms": round(t / seg * 1e3, 3),
                "imgs_per_sec": round(seg_batch * seg / t, 1),
            }), flush=True)
        except Exception as e:
            print(json.dumps({"probe": "seg_sweep", "seg": seg,
                              "error": str(e)[:200]}), flush=True)
        finally:
            # release THIS arm's HBM before the next (larger) arm allocates
            # — a failed seg-25 stack left referenced would cascade the
            # anticipated OOM into the seg-50 point
            del stoke, xs, ys


if __name__ == "__main__":
    main()
