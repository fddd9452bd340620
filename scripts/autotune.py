"""Telemetry-driven autotune sweep (ISSUE 6): search the exposed knobs,
score on MFU + goodput, persist the winner in the BENCH ledger.

The parent process NEVER imports jax (``XLA_FLAGS`` are fixed at backend
init, so every trial must be its own process — the discipline
``scripts/profile_capture.py`` established).  Each trial is a subprocess
whose environment carries the trial's flags; the worker builds a Stoke
run with the telemetry + attribution vertical enabled, measures
throughput via delta timing, and reports ``value`` / ``mfu`` /
``goodput_fraction`` / ``bound`` as one JSON line.  The search loop
(``stoke_tpu.autotune.greedy_search``) prunes the knob space with the
baseline's bound classification — a memory-bound workload does not burn
trial budget on compute flags.

Winners land in ``BENCH_RESULTS.json`` under ``autotune/<metric>`` with
full provenance (config key, flags, measured MFU, trial count); replay
with ``python bench.py --tuned``.

Usage:
    python scripts/autotune.py --smoke          # CPU flow validation
    python scripts/autotune.py --trials 12      # real sweep (on the chip;
                                                # TPU flags)
    python scripts/autotune.py --workload flash --seq-len 4096
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)


def _load_autotune_module():
    """Load ``stoke_tpu/autotune.py`` by FILE, not through the package:
    ``import stoke_tpu.autotune`` executes the package ``__init__``,
    which imports the facade and therefore jax — exactly the import the
    jax-free parent must never pay (beyond cost, parent-side jax would
    freeze a backend whose XLA_FLAGS no trial chose)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_stoke_autotune_standalone",
        os.path.join(REPO, "stoke_tpu", "autotune.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    # dataclass field-type resolution looks the class's module up in
    # sys.modules — register before exec
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


_autotune = _load_autotune_module()
TPU_XLA_FLAG_CANDIDATES = _autotune.TPU_XLA_FLAG_CANDIDATES
SearchOutcome = _autotune.SearchOutcome
TrialResult = _autotune.TrialResult
TrialSpec = _autotune.TrialSpec
greedy_search = _autotune.greedy_search
persist_winner = _autotune.persist_winner

LEDGER_DEFAULT = os.path.join(REPO, "BENCH_RESULTS.json")
RESNET_METRIC = "cifar10_resnet50_bf16_train_throughput"
SMOKE_METRIC = "cifar10_basicnn_train_throughput"
FLASH_METRIC = "flash_attention_fwdbwd_tokens_per_s"
SERVE_DECODE_METRIC = "serve_paged_decode_tokens_per_s"


def _parse_int_list(text: str) -> list:
    return [int(v) for v in text.split(",") if v.strip()]


# --------------------------------------------------------------------------- #
# trial worker (its own process: XLA_FLAGS are already in its environment)
# --------------------------------------------------------------------------- #


def _run_trial(payload: dict) -> dict:
    """Measure ONE trial.  Runs inside the subprocess the driver spawned;
    prints nothing itself — returns the result record the caller emits."""
    import numpy as np

    import jax

    spec = TrialSpec.from_dict(payload["spec"])
    steps = int(payload["steps"])
    warmup = int(payload["warmup"])
    on_accel = jax.default_backend() not in ("cpu",)
    out = {
        "trial": True,
        "config_key": spec.config_key(),
        "on_accelerator": on_accel,
        "ok": True,
    }

    if payload["workload"] == "flash":
        return {**out, **_measure_flash(spec, payload, steps, warmup)}
    if payload["workload"] == "serve_decode":
        return {**out, **_measure_serve_decode(spec, payload, steps, warmup)}

    import optax

    from stoke_tpu import (
        AttributionConfig,
        CommConfig,
        Stoke,
        StokeOptimizer,
        TelemetryConfig,
    )
    from stoke_tpu.models import BasicNN, ResNet50
    from stoke_tpu.telemetry import read_step_events
    from stoke_tpu.utils import init_module

    smoke = payload["workload"] == "smoke"
    # dp is a SWEEP-level decision, not a per-trial one: when any trial
    # sweeps comm_dtype, every trial (baseline included) runs under
    # distributed="dp" so the score compares wire formats, never the
    # dp/no-dp switch itself.  The sharding tier (ISSUE 8) follows the
    # same rule: every trial of a --comm-shard-tier sweep runs under the
    # tier, so a comm_dtype winner is measured against a same-tier
    # baseline (the sddp/fsdp trials take the sharded weight-update path
    # automatically — CommConfig.shard_updates auto-resolution)
    use_dp = bool(payload.get("dp") or spec.comm_dtype)
    shard_tier = payload.get("comm_shard_tier")
    batch = spec.batch or (8 if smoke else 256)
    seg = spec.steps_per_dispatch or (2 if smoke else 10)
    model = BasicNN() if smoke else ResNet50(num_classes=10, cifar_stem=True)
    variables = init_module(
        model, jax.random.PRNGKey(0),
        np.zeros((2, 32, 32, 3), np.float32), train=False,
    )
    obs_dir = tempfile.mkdtemp(prefix="stoke-autotune-obs-")
    configs = [
        TelemetryConfig(
            output_dir=obs_dir, log_every_n_steps=1,
            prometheus=False, tensorboard=False, sample_device_time=False,
        ),
        AttributionConfig(peak_tflops=float(payload["peak_tflops"])),
    ]
    if spec.comm_dtype:
        # oss tier: shard_updates' auto default resolves replicated, so
        # the tier sweep must opt in explicitly — otherwise every trial
        # measures the replicated exchange while the winner persists
        # under the `_shard_oss` metric (sddp/fsdp auto-engage)
        configs.append(CommConfig(
            dtype=spec.comm_dtype,
            shard_updates=True if shard_tier == "oss" else None,
        ))
    stoke = Stoke(
        model=model,
        optimizer=StokeOptimizer(
            optimizer=optax.sgd,
            optimizer_kwargs={"learning_rate": 0.05, "momentum": 0.9},
        ),
        loss=lambda lo, la: optax.softmax_cross_entropy_with_integer_labels(
            lo, la
        ).mean(),
        params=variables,
        batch_size_per_device=batch,
        device="tpu" if on_accel else "cpu",
        distributed="dp" if use_dp else None,
        oss=shard_tier in ("oss", "sddp"),
        sddp=shard_tier == "sddp",
        fsdp=shard_tier == "fsdp",
        precision=None if smoke else "bf16",
        configs=configs,
        model_train_kwargs={"train": True},
        model_eval_kwargs={"train": False},
        verbose=False,
    )
    r = np.random.default_rng(0)
    xs = jax.device_put(
        r.normal(size=(seg, batch, 32, 32, 3)).astype(np.float32)
    )
    ys = jax.device_put(r.integers(0, 10, size=(seg, batch)))

    def timed(n):
        t0 = time.perf_counter()
        last = None
        for _ in range(n):
            last = stoke.train_steps(xs, (ys,))
        np.asarray(jax.tree_util.tree_leaves(last)[0])  # force a fetch
        return time.perf_counter() - t0

    for _ in range(warmup):
        stoke.train_steps(xs, (ys,))
    timed(1)
    t1 = timed(steps)
    t2 = timed(2 * steps)
    dt = max(t2 - t1, 1e-9)
    value = batch * seg * steps / dt
    goodput = stoke.goodput or {}
    stoke.close_telemetry()
    bound = None
    try:
        records = read_step_events(os.path.join(obs_dir, "steps.jsonl"))
        for rec in reversed(records):
            if rec.get("bound") is not None:
                bound = rec["bound"]
                break
    except Exception:
        pass
    return {
        **out,
        "value": round(value, 1),
        "unit": "imgs/sec/chip",
        "mfu": goodput.get("mfu"),
        "goodput_fraction": goodput.get("goodput_fraction"),
        "bound": bound,
        "wall_s": round(dt, 4),
        "batch": batch,
        "steps_per_dispatch": seg,
    }


def _measure_flash(spec: TrialSpec, payload: dict, steps: int,
                   warmup: int) -> dict:
    """Flash-attention block-size trial: fwd+bwd latency of the Pallas
    kernel at the spec's blocking (interpret mode on CPU — tiny sizes
    only; real sweeps run on the chip)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from stoke_tpu.ops.flash_attention import flash_attention

    on_cpu = jax.default_backend() == "cpu"
    L = int(payload["seq_len"])
    B, H, D = (1, 2, 64) if on_cpu else (4, 8, 64)
    r = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(r.normal(size=(B, H, L, D)).astype(np.float32))
        for _ in range(3)
    )

    def loss(q, k, v):
        o = flash_attention(
            q, k, v, causal=True,
            block_q=spec.flash_block_q, block_k=spec.flash_block_k,
            interpret=on_cpu,
        )
        return (o * o).sum()

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    for _ in range(max(warmup, 1)):
        jax.block_until_ready(g(q, k, v))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = g(q, k, v)
    jax.block_until_ready(out)
    dt = max(time.perf_counter() - t0, 1e-9)
    return {
        "value": round(B * L * steps / dt, 1),
        "unit": "tokens/sec",
        "mfu": None,
        "goodput_fraction": None,
        "bound": None,
        "wall_s": round(dt, 4),
    }


def _measure_serve_decode(spec: TrialSpec, payload: dict, steps: int,
                          warmup: int) -> dict:
    """Paged-decode kernel trial (ISSUE 13): steady-state latency of
    ``paged_decode_attention_pallas`` at the spec's block knob over a
    synthetic full block pool — the decode-attention dispatch isolated
    from the rest of the serve loop, so the sweep scores exactly what the
    knob moves (the HBM→VMEM streaming schedule).  With ``spec_k`` in the
    payload (``--spec-k``, ISSUE 17) the trial measures the k-token
    verify kernel instead — ``paged_verify_attention_pallas`` at the
    spec's ``verify_pages_per_block`` over S=k+1 query rows per sequence,
    scored as candidate tokens per second (each dispatch scores S
    positions per slot).  CPU trials run the interpreter on tiny shapes
    (flow validation only); real sweeps run on the chip."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from stoke_tpu.ops.flash_attention import (
        paged_decode_attention_pallas,
        paged_verify_attention_pallas,
    )

    on_cpu = jax.default_backend() == "cpu"
    spec_k = payload.get("spec_k")
    # geometry: a full decode batch over a GPT-small-class cache on chip;
    # a toy pool under the interpreter
    B, H, D, BS = (2, 2, 16, 8) if on_cpu else (8, 8, 64, 16)
    L = int(payload["seq_len"]) if not on_cpu else 64
    MB = -(-L // BS)
    NB = B * MB + 1
    r = np.random.default_rng(0)
    k_pages = jnp.asarray(r.normal(size=(NB, BS, H, D)).astype(np.float32))
    v_pages = jnp.asarray(r.normal(size=(NB, BS, H, D)).astype(np.float32))
    tables = jnp.asarray(
        np.arange(1, B * MB + 1, dtype=np.int32).reshape(B, MB)
    )
    # ragged contexts keep the masked tail honest (the serve batch is
    # never uniformly full)
    ctx = np.linspace(L // 2, L, B, dtype=np.int32)

    if spec_k is not None:
        S = int(spec_k) + 1
        # verify-shaped batch: S consecutive query positions per slot
        # ending at the slot's context frontier (the draft window)
        positions = jnp.asarray(
            np.stack([np.arange(c - S, c, dtype=np.int32) for c in ctx])
        )
        q = jnp.asarray(r.normal(size=(B, H, S, D)).astype(np.float32))
        fn = jax.jit(
            lambda q_, k_, v_, t_, p_: paged_verify_attention_pallas(
                q_, k_, v_, t_, p_,
                pages_per_block=spec.verify_pages_per_block,
                interpret=on_cpu,
            )
        )
        args5 = (q, k_pages, v_pages, tables, positions)
        per_dispatch = B * S  # candidate positions scored per dispatch
    else:
        q = jnp.asarray(r.normal(size=(B, H, 1, D)).astype(np.float32))
        fn = jax.jit(
            lambda q_, k_, v_, t_, c_: paged_decode_attention_pallas(
                q_, k_, v_, t_, c_,
                pages_per_block=spec.decode_pages_per_block,
                interpret=on_cpu,
            )
        )
        args5 = (q, k_pages, v_pages, tables, jnp.asarray(ctx))
        per_dispatch = B  # one decode dispatch = one fresh token per slot

    for _ in range(max(warmup, 1)):
        jax.block_until_ready(fn(*args5))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args5)
    jax.block_until_ready(out)
    dt = max(time.perf_counter() - t0, 1e-9)
    return {
        "value": round(per_dispatch * steps / dt, 1),
        "unit": "tokens/sec",
        "mfu": None,
        "goodput_fraction": None,
        "bound": None,
        "wall_s": round(dt, 4),
    }


# --------------------------------------------------------------------------- #
# driver (jax-free)
# --------------------------------------------------------------------------- #


def _subprocess_measure(payload_base: dict, timeout: int, verbose: bool,
                        require_accel: bool = False):
    """Build the measure() callable the search loop drives: one fresh
    subprocess per trial so the trial's XLA_FLAGS land before jax import
    (flags are fixed at backend init — the bench.py:500 bug this PR
    fixes was exactly an in-process mutation after import)."""

    def measure(spec: TrialSpec) -> TrialResult:
        payload = {**payload_base, "spec": spec.to_dict()}
        env = dict(os.environ)
        if spec.xla_flags:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "") + " " + spec.xla_flags
            ).strip()
        try:
            proc = subprocess.run(
                [
                    sys.executable, os.path.abspath(__file__),
                    "--_trial", json.dumps(payload),
                ],
                capture_output=True, text=True, timeout=timeout, env=env,
            )
        except subprocess.TimeoutExpired:
            return TrialResult(
                spec, ok=False, error=f"trial timed out after {timeout}s"
            )
        line = next(
            (
                ln for ln in reversed(proc.stdout.strip().splitlines())
                if ln.startswith("{")
            ),
            None,
        )
        if proc.returncode != 0 or line is None:
            err = (proc.stderr or "no output").strip().splitlines()
            return TrialResult(
                spec, ok=False,
                error=(err[-1][:200] if err else "trial produced no JSON"),
            )
        rec = json.loads(line)
        if verbose:
            print(json.dumps(rec), flush=True)
        if not rec.get("ok", False):
            return TrialResult(
                spec, ok=False, error=rec.get("error", "trial failed")
            )
        if require_accel and rec.get("on_accelerator") is False:
            # no chip visible to the trial: the measurement is real but
            # its knobs are meaningless for the chip — a failed trial,
            # never a ledgered on-chip winner
            return TrialResult(
                spec, ok=False,
                error="trial ran on the CPU backend; refusing to score a "
                "CPU fallback in an on-chip sweep",
            )
        return TrialResult(
            spec,
            value=float(rec.get("value", 0.0)),
            unit=rec.get("unit", "imgs/sec/chip"),
            mfu=rec.get("mfu"),
            goodput_fraction=rec.get("goodput_fraction"),
            bound=rec.get("bound"),
            wall_s=rec.get("wall_s"),
        )

    return measure


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--_trial", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--smoke", action="store_true",
                    help="CPU flow validation: BasicNN, tiny knob space, "
                    ">= 4 trials, winner persisted under the smoke metric")
    ap.add_argument("--workload", choices=["resnet", "flash", "serve_decode"],
                    default="resnet")
    ap.add_argument("--trials", type=int, default=12,
                    help="total trial budget (baseline included)")
    ap.add_argument("--steps", type=int, default=None,
                    help="timed train_steps dispatches per trial")
    ap.add_argument("--warmup", type=int, default=None)
    ap.add_argument("--batches", default=None,
                    help="comma-separated batch candidates")
    ap.add_argument("--segs", default=None,
                    help="comma-separated steps_per_dispatch candidates")
    ap.add_argument("--xla-flag-candidates", default=None,
                    help="';'-separated XLA_FLAGS fragment candidates "
                    "(default: the curated TPU set; empty string = none)")
    ap.add_argument("--comm-dtypes", default=None,
                    help="comma-separated wire dtypes to sweep (e.g. "
                    "bf16,int8); default: not swept")
    ap.add_argument("--comm-shard-tier", default=None,
                    choices=["none", "oss", "sddp", "fsdp"],
                    help="run EVERY trial of the sweep under this sharding "
                    "tier (ISSUE 8 weight-update sharding) — a sweep-level "
                    "decision like dp, so a comm_dtype sweep measures the "
                    "sharded wire formats against a same-tier baseline "
                    "instead of confounding them with the tier switch.  "
                    "The winner persists under a tier-suffixed metric")
    ap.add_argument("--flash-blocks", default=None,
                    help="flash block-size candidates (workload=flash; "
                    "default 128,256,512, smoke 64,128)")
    ap.add_argument("--decode-pages", default=None,
                    help="decode_pages_per_block candidates "
                    "(workload=serve_decode; default 1,2,4,8, smoke 1,2)")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="speculative draft length k (workload="
                    "serve_decode; ISSUE 17): sweep the k-token VERIFY "
                    "kernel's verify_pages_per_block instead of the "
                    "single-token decode knob — S=k+1 "
                    "query rows per sequence, scored as candidate "
                    "positions per second.  The winner persists under a "
                    "_spec_k<k>-suffixed metric (a verify-kernel winner "
                    "is never the decode-kernel winner)")
    ap.add_argument("--seq-len", type=int, default=None,
                    help="sequence length for workload=flash / cached "
                    "context length for workload=serve_decode")
    ap.add_argument("--peak-tflops", type=float, default=None,
                    help="MFU denominator for trial attribution "
                    "(default: 197 = v5e bf16 dense; smoke: 1e-3)")
    ap.add_argument("--ledger", default=LEDGER_DEFAULT,
                    help="BENCH ledger path the winner persists into")
    ap.add_argument("--trial-timeout", type=int, default=900)
    ap.add_argument("--no-persist", action="store_true",
                    help="run the sweep but skip the ledger write")
    args = ap.parse_args()
    if args.comm_shard_tier and not args.comm_dtypes:
        ap.error("--comm-shard-tier requires --comm-dtypes (a tier sweep "
                 "without the wire-format knob never engages the sharded "
                 "transport, yet would persist its winner under the "
                 "tier-suffixed metric bench.py --tuned replays)")

    if args._trial is not None:
        # worker mode: measure one spec, emit one JSON line, exit
        payload = json.loads(args._trial)
        try:
            rec = _run_trial(payload)
        except Exception as e:  # the driver scores failures, not tracebacks
            rec = {
                "trial": True, "ok": False,
                "config_key": TrialSpec.from_dict(
                    payload.get("spec", {})
                ).config_key(),
                "error": f"{type(e).__name__}: {e}"[:300],
            }
        print(json.dumps(rec), flush=True)
        return 0 if rec.get("ok") else 1

    smoke = args.smoke
    flash = args.workload == "flash"
    serve_decode = args.workload == "serve_decode"
    if flash:
        # smoke runs persist under their own metric: a CPU interpret-mode
        # winner must never masquerade as a real on-chip flash record
        metric = FLASH_METRIC + ("_smoke" if smoke else "")
        blocks = _parse_int_list(
            args.flash_blocks or ("64,128" if smoke else "128,256,512")
        )
        space = {"flash_block_q": blocks, "flash_block_k": blocks}
        base = TrialSpec(flash_block_q=blocks[0], flash_block_k=blocks[0])
    elif serve_decode:
        # ISSUE 13 satellite: the serve side's ledgered on-chip number —
        # sweep the streaming decode kernel's block knob, same
        # CPU-fallback refusal as the other real sweeps; smoke winners
        # carry the _smoke suffix so interpreter tokens/s never
        # masquerade as a chip capture
        metric = SERVE_DECODE_METRIC + ("_smoke" if smoke else "")
        pages = _parse_int_list(
            args.decode_pages or ("1,2" if smoke else "1,2,4,8")
        )
        if args.spec_k is not None:
            # ISSUE 17: the speculative variant sweeps the verify
            # kernel's knobs under its own metric suffix
            metric = (
                SERVE_DECODE_METRIC + f"_spec_k{args.spec_k}"
                + ("_smoke" if smoke else "")
            )
            space = {"verify_pages_per_block": pages}
            base = TrialSpec(verify_pages_per_block=pages[0])
        else:
            space = {"decode_pages_per_block": pages}
            base = TrialSpec(decode_pages_per_block=pages[0])
    else:
        # baselines carry the workload defaults EXPLICITLY (batch 8/256,
        # seg 2/10 — what the worker would fall back to anyway) so the
        # config-key dedup skips candidates that merely restate them: a
        # real on-chip trial is minutes of chip time, and re-measuring
        # the baseline under a different key wastes budget
        metric = SMOKE_METRIC if smoke else RESNET_METRIC
        if smoke:
            space = {
                "batch": args.batches and _parse_int_list(args.batches)
                or [16, 32],
                "steps_per_dispatch": args.segs and _parse_int_list(args.segs)
                or [4, 8],
            }
            base = TrialSpec(batch=8, steps_per_dispatch=2)
        else:
            space = {
                "xla_flags": (
                    args.xla_flag_candidates.split(";")
                    if args.xla_flag_candidates is not None
                    else list(TPU_XLA_FLAG_CANDIDATES)
                ),
                "batch": _parse_int_list(args.batches or "128,256,512"),
                "steps_per_dispatch": _parse_int_list(args.segs or "10,25,50"),
            }
            if args.comm_dtypes:
                space["comm_dtype"] = [
                    d for d in args.comm_dtypes.split(",") if d.strip()
                ]
            base = TrialSpec(batch=256, steps_per_dispatch=10)

    payload_base = {
        "workload": (
            "smoke" if (smoke and not flash and not serve_decode)
            else args.workload
        ),
        "steps": args.steps or (2 if smoke else 10),
        "warmup": args.warmup if args.warmup is not None else (1 if smoke else 2),
        "peak_tflops": (
            args.peak_tflops
            if args.peak_tflops is not None
            else (1e-3 if smoke else 197.0)
        ),
        "seq_len": args.seq_len
        or (128 if smoke else (2048 if serve_decode else 4096)),
        # speculative verify-kernel variant (ISSUE 17): k drafts -> the
        # trial measures S=k+1 query rows through the verify kernel
        "spec_k": args.spec_k if serve_decode else None,
        # dp for EVERY trial of a comm sweep (baseline included), so the
        # comm_dtype knob is measured against a dp baseline instead of
        # confounding the wire format with the dp/no-dp switch
        "dp": "comm_dtype" in space or bool(args.comm_shard_tier),
        # sharding tier for EVERY trial (ISSUE 8): same sweep-level rule —
        # the comm_dtype knob under a sharded tier is measured against a
        # same-tier baseline
        "comm_shard_tier": args.comm_shard_tier,
    }
    if args.comm_shard_tier:
        # the tier is part of the measured configuration: its winner must
        # never shadow (nor be replayed as) the unsharded metric's
        metric += f"_shard_{args.comm_shard_tier}"

    measure = _subprocess_measure(
        payload_base, args.trial_timeout, verbose=True,
        # a real sweep's winner is an on-chip record: a trial that finds
        # no chip must fail rather than ledger CPU knobs under
        # backend="tpu"
        require_accel=not smoke,
    )
    outcome = greedy_search(
        measure, base, space, max_trials=args.trials,
        log=lambda m: print(f"autotune: {m}", flush=True),
    )

    best = outcome.best
    summary = {
        "autotune": "ok" if best.ok else "FAILED",
        "metric": metric,
        "trials": outcome.trials,
        "pruned_knobs": outcome.pruned_knobs,
        "winner": best.to_dict(),
    }
    if best.ok and not args.no_persist:
        backend = "cpu" if smoke else "tpu"
        record = persist_winner(
            args.ledger, metric, outcome, backend=backend,
            extra={
                "workload": payload_base["workload"],
                **(
                    {"comm_shard_tier": args.comm_shard_tier}
                    if args.comm_shard_tier
                    else {}
                ),
            },
        )
        summary["persisted"] = {
            "ledger": args.ledger,
            "key": f"autotune/{metric}",
            "config_key": record["config_key"],
        }
    print(json.dumps(summary), flush=True)
    return 0 if best.ok else 1


if __name__ == "__main__":
    sys.exit(main())
