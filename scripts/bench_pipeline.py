"""Pipeline-schedule characterization: measured bubble fraction and
steady-state utilization for the GPipe (rounds=1) and circular (rounds=V)
schedules, as a host-time proxy on the simulated CPU mesh.

The analytic model (parallel/pipeline.py docstring): a schedule with S
stages, V rounds, and M microbatches runs T = V*M + S - 1 ticks, of which
V*M do useful work per device — bubble = (S-1)/(V*M+S-1).  This script
checks the IMPLEMENTATION against that model: per-step wall time is
measured across an M sweep and regressed as t(M) = a*(V*M + S - 1) + c;
the fit recovering the analytic tick count (R^2 ~ 1, c small) means the
schedule executes with no hidden serialization, and measured utilization
V*M*a/t(M) tracks the analytic V*M/(V*M+S-1).

CPU-mesh caveat: all "devices" share host cores, so absolute times mean
nothing; the VALID signal is how time scales with M and V — i.e. the tick
count, which is schedule-determined, not hardware-determined.

Usage (hermetic, CPU only):
    env JAX_PLATFORMS=cpu \
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python scripts/bench_pipeline.py
"""

from __future__ import annotations

import json
import time

import numpy as np


def measure(rounds: int, Ms, S=4, B=16, D=256, reps=7):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from stoke_tpu.parallel.pipeline import pipeline, stack_stage_params

    devices = jax.devices("cpu")[:S]
    mesh = Mesh(np.asarray(devices), ("stage",))
    r = np.random.default_rng(0)
    L = rounds * S
    stacked = stack_stage_params([
        {"w": jnp.asarray(r.normal(size=(D, D)).astype(np.float32) * 0.1)}
        for _ in range(L)
    ])

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    piped = pipeline(stage_fn, mesh, "stage", rounds=rounds)
    step = jax.jit(lambda p, xs: piped(p, xs))

    rows = []
    for M in Ms:
        xs = jnp.asarray(r.normal(size=(M, B, D)).astype(np.float32))
        step(stacked, xs).block_until_ready()  # compile
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            step(stacked, xs).block_until_ready()
            ts.append(time.perf_counter() - t0)
        t = float(np.median(ts))
        ticks = rounds * M + S - 1
        rows.append({"M": M, "ticks": ticks, "t_ms": round(t * 1e3, 2)})
    # regress t = a*ticks + c
    ticks = np.array([row["ticks"] for row in rows], float)
    ts = np.array([row["t_ms"] for row in rows], float)
    A = np.vstack([ticks, np.ones_like(ticks)]).T
    (a, c), res, *_ = np.linalg.lstsq(A, ts, rcond=None)
    pred = A @ np.array([a, c])
    ss_tot = float(((ts - ts.mean()) ** 2).sum())
    r2 = 1.0 - float(((ts - pred) ** 2).sum()) / max(ss_tot, 1e-12)
    for row in rows:
        useful = rounds * row["M"]
        row["bubble_analytic"] = round((S - 1) / row["ticks"], 4)
        row["util_analytic"] = round(useful / row["ticks"], 4)
        row["util_measured"] = round(useful * a / row["t_ms"], 4)
    return {
        "rounds": rounds,
        "stages": S,
        "tick_ms_fit": round(float(a), 3),
        "overhead_ms_fit": round(float(c), 3),
        "r2": round(r2, 4),
        "rows": rows,
    }


def main():
    Ms = [4, 8, 16, 32, 64]
    out = {"schedules": []}
    for rounds in (1, 2, 4):
        res = measure(rounds, Ms)
        out["schedules"].append(res)
        print(json.dumps(res))
    # headline: does time scale with the analytic tick count?
    ok = all(s["r2"] > 0.98 for s in out["schedules"])
    print(json.dumps({
        "metric": "pipeline_schedule_tick_model_fit",
        "value": min(s["r2"] for s in out["schedules"]),
        "unit": "r2",
        "ok": ok,
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
