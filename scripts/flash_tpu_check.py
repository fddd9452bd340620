"""On-TPU validation + microbenchmark for the Pallas flash-attention kernel.

Compiles NON-interpret on the real chip, checks forward and gradient numerics
against `stoke_tpu.ops.flash_attention.dense_reference` (the same reference
and tolerances the pytest gate `tests/test_flash_tpu.py` uses), then
benchmarks flash vs dense at L in {1024, 4096, 8192} (fwd and fwd+bwd),
printing one JSON line per point.

Run on the chip, in one process:
    python scripts/flash_tpu_check.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def check_numerics():
    import jax
    import jax.numpy as jnp

    from stoke_tpu.ops.flash_attention import (
        BWD_RTOL_BF16,
        FWD_ATOL_BF16,
        dense_reference,
        flash_attention,
    )

    r = np.random.default_rng(0)
    B, H, L, D = 2, 4, 512, 64
    mk = lambda: jnp.asarray(
        r.normal(size=(B, H, L, D)).astype(np.float32), jnp.bfloat16
    )
    q, k, v = mk(), mk(), mk()
    mask = jnp.asarray((r.random(size=(B, L)) > 0.2).astype(np.int32))

    failures = []
    for causal in (False, True):
        for m in (None, mask):
            out = flash_attention(q, k, v, m, causal=causal, interpret=False)
            ref = dense_reference(q, k, v, m, causal=causal)
            err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
            ok = err < FWD_ATOL_BF16
            if not ok:
                failures.append((causal, m is not None, "fwd", err))
            print(json.dumps({"check": "fwd", "causal": causal,
                              "masked": m is not None,
                              "max_abs_err": round(err, 5), "ok": ok}),
                  flush=True)

            def loss_flash(q, k, v):
                return jnp.sum(
                    flash_attention(q, k, v, m, causal=causal,
                                    interpret=False).astype(jnp.float32) ** 2
                )

            def loss_dense(q, k, v):
                return jnp.sum(dense_reference(q, k, v, m, causal=causal) ** 2)

            gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
            gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
            gerr = max(
                float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                      b.astype(jnp.float32))))
                for a, b in zip(gf, gd)
            )
            # grads of sum-of-squares scale with L; tolerance is relative
            gscale = max(
                float(jnp.max(jnp.abs(b.astype(jnp.float32)))) for b in gd
            )
            gok = gerr < BWD_RTOL_BF16 * max(gscale, 1.0)
            if not gok:
                failures.append((causal, m is not None, "bwd", gerr))
            print(json.dumps({"check": "bwd", "causal": causal,
                              "masked": m is not None,
                              "max_abs_err": round(gerr, 5),
                              "grad_scale": round(gscale, 3), "ok": gok}),
                  flush=True)
    return failures


def bench():
    import jax
    import jax.numpy as jnp

    from stoke_tpu.ops.flash_attention import dense_reference, flash_attention

    r = np.random.default_rng(0)

    def timeit(f, *args, iters=20):
        f(*args)  # compile
        jax.block_until_ready(f(*args))
        t0 = time.perf_counter()
        for _ in range(iters):
            o = f(*args)
        jax.block_until_ready(o)
        t1 = time.perf_counter()
        for _ in range(2 * iters):
            o = f(*args)
        jax.block_until_ready(o)
        # delta timing can go sub-noise-floor negative for sub-ms kernels
        return max((time.perf_counter() - t1 - (t1 - t0)) / iters, 1e-6)

    best_blocks = {}
    for L in (1024, 4096, 8192):
        B, H, D = 4, 8, 64
        mk = lambda: jnp.asarray(
            r.normal(size=(B, H, L, D)).astype(np.float32), jnp.bfloat16
        )
        q, k, v = mk(), mk(), mk()

        # dense reference: materializes the [L, L] scores — expected to OOM
        # at large L (that memory cliff is the kernel's reason to exist)
        td = tgd = None
        try:
            dense_f = jax.jit(
                lambda q, k, v: dense_reference(q, k, v, causal=True)
                .astype(jnp.bfloat16))
            td = timeit(dense_f, q, k, v)
        except Exception as e:
            print(json.dumps({"bench": "dense_fwd_oom", "L": L,
                              "error": type(e).__name__}), flush=True)
        try:
            gdense = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
                dense_reference(q, k, v, causal=True)), argnums=(0, 1, 2)))
            tgd = timeit(gdense, q, k, v, iters=10)
        except Exception as e:
            print(json.dumps({"bench": "dense_bwd_oom", "L": L,
                              "error": type(e).__name__}), flush=True)

        # block-size sweep: larger q blocks cut the K/V HBM refetch factor
        # (traffic ~ L^2 D / block_q), larger k blocks amortize the k sweep
        for bq, bk in ((128, 128), (256, 256), (256, 512), (512, 512)):
            if bq > L or bk > L:
                continue
            flash_f = jax.jit(lambda q, k, v, bq=bq, bk=bk: flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk, interpret=False))
            tf = timeit(flash_f, q, k, v)
            gflash = jax.jit(jax.grad(lambda q, k, v, bq=bq, bk=bk: jnp.sum(
                flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                                interpret=False)
                .astype(jnp.float32)), argnums=(0, 1, 2)))
            tgf = timeit(gflash, q, k, v, iters=10)
            rec = {
                "bench": "flash_vs_dense", "L": L, "B": B, "H": H, "D": D,
                "block_q": bq, "block_k": bk,
                "flash_fwd_ms": round(tf * 1e3, 3),
                "dense_fwd_ms": None if td is None else round(td * 1e3, 3),
                "fwd_speedup": None if td is None else round(td / tf, 2),
                "flash_fwdbwd_ms": round(tgf * 1e3, 3),
                "dense_fwdbwd_ms": None if tgd is None else round(tgd * 1e3, 3),
                "fwdbwd_speedup": None if tgd is None else round(tgd / tgf, 2),
            }
            print(json.dumps(rec), flush=True)
            cur = best_blocks.get(L)
            if cur is None or tgf < cur[1]:
                best_blocks[L] = ((bq, bk), tgf)
    print(json.dumps({"best_blocks": {
        str(L): {"blocks": list(bb), "fwdbwd_ms": round(t * 1e3, 3)}
        for L, (bb, t) in best_blocks.items()}}), flush=True)


if __name__ == "__main__":
    import jax

    if jax.default_backend() != "tpu":
        print(json.dumps({"error": "not on TPU", "backend": jax.default_backend()}))
        sys.exit(1)
    fails = check_numerics()
    bench()
    print(json.dumps({"numerics_failures": len(fails)}))
    sys.exit(1 if fails else 0)
