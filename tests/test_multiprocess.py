"""Two-process CPU harness: the rank-coordination paths single-process tests
cannot reach (reference io_ops.py:551-703 — barrier → gather/consolidate →
rank-0 write → barrier; stoke.py:822-826 sampler enforcement).

Each test launches ``tests/_mp_worker.py`` twice with
``jax.distributed.initialize(coordinator_address=..., num_processes=2)``
over 4 local CPU devices per process (8 global).  The workers run real
collectives over gRPC — this is the CPU-scale equivalent of a 2-host TPU
pod.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

WORKER = os.path.join(os.path.dirname(__file__), "_mp_worker.py")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NPROC = 2
TIMEOUT = 240


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def run_workers(scenario: str, tmpdir: str):
    """Launch NPROC workers, wait, assert both succeeded."""
    env = {
        **os.environ,
        "PYTHONPATH": REPO,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "TF_CPP_MIN_LOG_LEVEL": "3",
    }
    port = _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, scenario, str(pid), str(NPROC), str(port), tmpdir],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        for pid in range(NPROC)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=TIMEOUT)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (rc, out, err) in enumerate(outs):
        if rc != 0 and "Multiprocess computations aren't implemented" in err:
            # environment capability, not a code failure: this jaxlib's CPU
            # client has no cross-process collectives implementation (gloo
            # not compiled in), so NO multiprocess scenario can run here
            pytest.skip(
                "jaxlib CPU backend lacks multiprocess collectives in this "
                "environment"
            )
        assert rc == 0, (
            f"worker {pid} failed (rc={rc})\n--- stdout ---\n{out[-2000:]}"
            f"\n--- stderr ---\n{err[-4000:]}"
        )
        assert f"WORKER_OK {scenario} {pid}" in out
    return outs


@pytest.fixture(scope="module")
def mp_available():
    """Skip the module quickly if jax.distributed can't rendezvous here."""
    return True


@pytest.mark.slow
def test_train_equivalence_across_processes(tmp_path):
    """2-process dp training on per-process batch slices must produce
    identical replicated params on both processes AND match a single-process
    run of the same global batches (the invariant the reference promises via
    DDP allreduce; here via jit-GSPMD over the global batch)."""
    run_workers("train_equiv", str(tmp_path))
    w0 = np.load(tmp_path / "params_p0.npy")
    w1 = np.load(tmp_path / "params_p1.npy")
    np.testing.assert_allclose(w0, w1, rtol=1e-6)  # replicas agree

    # single-process reference over the same deterministic global batches
    import jax.numpy as jnp
    import optax

    from stoke_tpu import Stoke, StokeOptimizer

    params = {
        "w": jnp.asarray(
            np.random.default_rng(7).normal(size=(8, 4)).astype(np.float32) * 0.1
        )
    }
    s = Stoke(
        model=lambda p, x: x @ p["w"],
        optimizer=StokeOptimizer(
            optimizer=optax.adam, optimizer_kwargs={"learning_rate": 1e-2}
        ),
        loss=lambda o, y: jnp.mean((o - y) ** 2),
        params=params,
        batch_size_per_device=32,
        verbose=False,
    )
    for i in range(3):
        r = np.random.default_rng(100 + i)
        x = r.normal(size=(32, 8)).astype(np.float32)
        y = (x @ np.ones((8, 4), np.float32)).astype(np.float32)
        s.backward(s.loss(s.model(x), y))
        s.step()
    np.testing.assert_allclose(
        w0, np.asarray(s.params["w"]), rtol=1e-4, atol=1e-6
    )


def test_consolidated_save_multiprocess(tmp_path):
    """Gather + process-0 write + load-back on every process."""
    run_workers("consolidated_save", str(tmp_path))


@pytest.mark.slow
def test_save_rank_multiprocess(tmp_path):
    """save_rank=1: the non-zero process writes the consolidated payload +
    metadata (reference DDPIO._save_rank, io_ops.py:551-623); barriers must
    not deadlock with a non-default writer, and out-of-range ranks degrade
    via modulo."""
    run_workers("save_rank", str(tmp_path))


@pytest.mark.slow
def test_sharded_save_multiprocess(tmp_path):
    """fsdp + orbax sharded save/load across 2 processes."""
    run_workers("sharded_save", str(tmp_path))


@pytest.mark.slow
def test_async_sharded_save_multiprocess(tmp_path):
    """Multi-host ASYNC sharded save (orbax AsyncCheckpointer): training
    continues during the background write, meta.json appears only after the
    cross-process commit, and the load round-trips exactly (round-3 lift of
    the async_save single-process restriction)."""
    run_workers("async_sharded_save", str(tmp_path))


@pytest.mark.slow
def test_composed_mesh_multiprocess(tmp_path):
    """Pod-style composed meshes across 2 processes × 4 devices: dp×tp
    train step (TP collectives cross the process boundary), dp×seq ring
    attention, dp×pp pipeline — the multi-host counterpart of the dryrun's
    composed scenarios (VERDICT r3 item 5)."""
    run_workers("composed_mesh", str(tmp_path))


@pytest.mark.fleet
def test_fleet_multiprocess(tmp_path):
    """Fleet observability across 2 real processes (ISSUE 5 acceptance):
    worker 1's loader sleeps per item and it reaches every barrier late,
    so rank 0's JSONL must carry per-host ``fleet/*`` fields naming host 1
    the loader-classified straggler, the per-step barrier wait must be
    charged to host 1 (the last arrival), and the health registry must
    record exactly one ``fleet_straggler`` anomaly.  Host 1 lags in every
    window (0.16 s of loader, 0.1 s at the barrier, against milliseconds
    of noise); the assertions still ask for a majority of the windows,
    not all, because the other xdist workers share these cores."""
    run_workers("fleet", str(tmp_path))
    from stoke_tpu.telemetry.events import read_step_events

    records = read_step_events(
        os.path.join(str(tmp_path), "telemetry", "steps.rank0.jsonl")
    )
    assert records, "rank 0 wrote no step events"
    # every exchanged window saw BOTH hosts' rows
    windows = [r for r in records if r.get("fleet/hosts") is not None]
    assert windows and all(r["fleet/hosts"] == 2 for r in windows)
    # skip the warm-up window (compile noise); most steady-state windows
    # must name host 1 the straggler with the lag classified as loader
    steady = windows[1:]
    named = [w for w in steady if w["fleet/straggler_host"] == 1]
    assert 2 * len(named) > len(steady), (len(named), len(steady))
    assert any(w["fleet/skew_class"] == "loader" for w in named)
    assert all((w["fleet/lag_s"] or 0) > 0 for w in named)
    # barrier-wait attribution: the wait is charged to the late host 1,
    # not to host 0 who sat waiting for about LATE_S
    charged = [w for w in steady if w["fleet/barrier_charged_host"] == 1]
    assert 2 * len(charged) > len(steady), (len(charged), len(steady))
    assert any((w["fleet/barrier_wait_s"] or 0) > 0.05 for w in charged)
    # exactly one fleet_straggler anomaly on every process's registry
    for pid in range(NPROC):
        with open(tmp_path / f"fleet_result_p{pid}.json") as f:
            result = json.load(f)
        assert result["n_processes"] == 2
        # 8 steps close 7 windows (the first record anchors the cadence)
        assert result["windows"] >= 6
        # exactly one straggler-streak firing (the sleeping loader may
        # legitimately also trip the PR 3 loader_starvation detector —
        # that one is not under test here)
        assert result["anomalies_by_detector"].get("fleet_straggler") == 1, (
            pid, result["anomalies_by_detector"],
        )
        assert result["straggler_events"][0]["host"] == 1
    # EVERY process wrote its own exposition (prometheus_all_ranks) and
    # each carries its distinguishing labels (multi-host scrape-collision
    # satellite) plus the fleet gauges
    for pid in range(NPROC):
        prom = open(os.path.join(
            str(tmp_path), "telemetry", f"metrics.rank{pid}.prom"
        )).read()
        assert 'host="' in prom and f'process_index="{pid}"' in prom
        assert "stoke_fleet_windows_total" in prom
        assert "stoke_sync_barrier_wait_s_total" in prom
    # the offline twin reproduces the verdict from the rank files alone
    import subprocess as sp

    merge = sp.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "merge_rank_jsonl.py"),
         os.path.join(str(tmp_path), "telemetry"), "--json"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"},
    )
    assert merge.returncode == 0, merge.stderr[-2000:]
    report = json.loads(merge.stdout)
    assert report["hosts"] == [0, 1]
    assert report["modal_straggler"] == 1


def test_rebalance_multiprocess(tmp_path):
    """Skew-reactive input rebalancing across 2 real processes (ISSUE 14
    acceptance): worker 1's per-item-sleeping loader triggers a bounded
    read-share shift within the K-window streak, the device feed stays
    bit-identical to the canonical per-rank plan (asserted in-worker), the
    per-epoch sample set is conserved (shares sum to the slice), and the
    verdict's lag fraction decreases after the shift lands."""
    run_workers("rebalance", str(tmp_path))
    results = []
    for pid in range(NPROC):
        with open(tmp_path / f"rebalance_result_p{pid}.json") as f:
            results.append(json.load(f))
    for pid, result in enumerate(results):
        # the actuator fired, bounded, and moved work OFF host 1
        assert result["shifts"] >= 1, (pid, result)
        shares = result["shares"]
        assert sum(shares) == 32, shares            # global slice conserved
        assert shares[1] < 16, shares               # slow host sheds reads
        assert shares[0] > 16, shares               # fast host picks up
        assert shares[1] >= 8, shares               # max_frac=0.5 bound
        # the device feed never deviated from the canonical plan
        assert result["fed_ok"], (pid, result)
    # both hosts evolved IDENTICAL share state (the agreement protocol)
    assert results[0]["shares"] == results[1]["shares"]
    from stoke_tpu.telemetry.events import read_step_events

    records = read_step_events(
        os.path.join(str(tmp_path), "telemetry", "steps.rank0.jsonl")
    )
    windows = [r for r in records if r.get("fleet/hosts") is not None]
    assert windows and all(r["fleet/hosts"] == 2 for r in windows)
    # rebalance fields ride the records (rebalance ON), and at least one
    # window reports the actuation with host 1 shedding
    shifts = [
        w for w in windows
        if w.get("fleet/rebalance_shift_rows") is not None
    ]
    assert shifts, "no window recorded a rebalance actuation"
    assert all(w["fleet/rebalance_from_host"] == 1 for w in shifts)
    # the loader-skew lag fraction decreases once the shift is live:
    # compare the windows straddling the FIRST actuation
    first_shift = windows.index(shifts[0])
    pre = [w["fleet/lag_frac"] for w in windows[1:first_shift + 1]
           if w["fleet/lag_frac"] is not None]
    post = [w["fleet/lag_frac"] for w in windows[first_shift + 4:]
            if w["fleet/lag_frac"] is not None]
    assert pre and post, (len(windows), first_shift)
    assert np.mean(post) < np.mean(pre), (np.mean(pre), np.mean(post))


@pytest.mark.slow
def test_loader_sampler_enforcement_and_sharding(tmp_path):
    """Sampler required multi-process; shards are disjoint and cover all."""
    run_workers("loader", str(tmp_path))
    s0 = set(json.load(open(tmp_path / "shard_p0.json")))
    s1 = set(json.load(open(tmp_path / "shard_p1.json")))
    assert s0 | s1 == set(range(256))
    assert not (s0 & s1)


@pytest.mark.slow
def test_indivisible_batch_raises_multiprocess(tmp_path):
    run_workers("batch_divisible", str(tmp_path))


@pytest.mark.zero
def test_zero_sharded_update_multiprocess(tmp_path):
    """ISSUE 8 acceptance across 2 real processes: the sharded
    weight-update path (int8 reduce-scatter, per-shard EF, shard-local
    optimizer step, param all-gather) must leave BOTH ranks with
    identical post-step parameters — the all-gathered replicated value —
    and each rank's residual partitioned over the global axis (asserted
    worker-side)."""
    run_workers("zero", str(tmp_path))
    w0 = np.load(tmp_path / "zero_params_p0.npy")
    w1 = np.load(tmp_path / "zero_params_p1.npy")
    np.testing.assert_array_equal(w0, w1)
