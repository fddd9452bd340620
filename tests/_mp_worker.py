"""Worker script for the 2-process CPU harness (tests/test_multiprocess.py).

Each worker calls ``jax.distributed.initialize`` (explicitly, through
``DistributedInitConfig``) against a shared coordinator, builds a Stoke run
over the GLOBAL 8-device mesh (4 local CPU devices per process), and
exercises one scenario named on argv.  This is the rank-coordination
coverage the reference's IO layer is built around (reference
io_ops.py:551-703: barrier → gather/consolidate → rank-0 write → barrier)
and that single-process tests cannot reach.

Usage (explicit argv, as the pytest harness launches it):
    _mp_worker.py <scenario> <process_id> <num_processes> <port> <tmpdir>
Usage (under scripts/launch_local.sh, which exports STOKE_PROCESS_ID /
STOKE_NUM_PROCESSES / JAX_COORDINATOR_ADDRESS per process):
    scripts/launch_local.sh -n 2 -d 4 python tests/_mp_worker.py <scenario> <tmpdir>
Prints ``WORKER_OK <scenario> <process_id>`` on success; any exception
exits non-zero (the pytest side asserts both).
"""

import json
import os
import sys

if len(sys.argv) >= 6:
    SCENARIO, PID, NPROC, PORT, TMP = (
        sys.argv[1],
        int(sys.argv[2]),
        int(sys.argv[3]),
        sys.argv[4],
        sys.argv[5],
    )
else:
    SCENARIO = sys.argv[1]
    TMP = sys.argv[2]
    PID = int(os.environ["STOKE_PROCESS_ID"])
    NPROC = int(os.environ["STOKE_NUM_PROCESSES"])
    PORT = os.environ["JAX_COORDINATOR_ADDRESS"].rsplit(":", 1)[1]
    os.makedirs(TMP, exist_ok=True)

import jax  # noqa: E402  (env set by the launcher BEFORE interpreter start)

# rendezvous FIRST — before anything touches the XLA backend (array
# creation, jax.devices, ...).  The facade's initialize_distributed sees
# "already initialized" and records it.
jax.distributed.initialize(
    coordinator_address=f"localhost:{PORT}",
    num_processes=NPROC,
    process_id=PID,
)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

from stoke_tpu import (  # noqa: E402
    CheckpointConfig,
    CheckpointFormat,
    DistributedInitConfig,
    FSDPConfig,
    Stoke,
    StokeOptimizer,
)

IN, OUT = 8, 4
GLOBAL_BATCH = 32


def make_stoke(fmt=CheckpointFormat.consolidated, fsdp=False, async_save=False,
               save_rank=0, extra_configs=(), oss=False, sddp=False):
    params = {
        "w": jnp.asarray(
            np.random.default_rng(7).normal(size=(IN, OUT)).astype(np.float32) * 0.1
        )
    }
    cfgs = [
        DistributedInitConfig(
            coordinator_address=f"localhost:{PORT}",
            num_processes=NPROC,
            process_id=PID,
        ),
        CheckpointConfig(format=fmt, async_save=async_save,
                         save_rank=save_rank),
    ]
    if fsdp:
        cfgs.append(FSDPConfig(min_weight_size=1))
    cfgs.extend(extra_configs)
    return Stoke(
        model=lambda p, x: x @ p["w"],
        optimizer=StokeOptimizer(
            optimizer=optax.adam, optimizer_kwargs={"learning_rate": 1e-2}
        ),
        loss=lambda o, y: jnp.mean((o - y) ** 2),
        params=params,
        batch_size_per_device=GLOBAL_BATCH // 8,
        distributed="dp",
        fsdp=fsdp,
        oss=oss,
        sddp=sddp,
        verbose=False,
        configs=cfgs,
    )


def local_batch(step: int):
    """This process's contiguous slice of the deterministic global batch
    (the contract of per-process feeding: process p holds rows
    [p*local : (p+1)*local] of the logically-global batch)."""
    r = np.random.default_rng(100 + step)
    x = r.normal(size=(GLOBAL_BATCH, IN)).astype(np.float32)
    W = np.ones((IN, OUT), np.float32)
    y = (x @ W).astype(np.float32)
    local = GLOBAL_BATCH // NPROC
    sl = slice(PID * local, (PID + 1) * local)
    return x[sl], y[sl]


def train(s, steps=3):
    for i in range(steps):
        x, y = local_batch(i)
        out = s.model(x)
        loss = s.loss(out, y)
        s.backward(loss)
        s.step()
    return s


def main():
    if SCENARIO == "train_equiv":
        # 2-proc dp training over per-process local slices; every process
        # must hold identical (replicated) updated params, and they must
        # match the single-process reference (written by the pytest side)
        s = train(make_stoke())
        assert jax.process_count() == NPROC
        w = np.asarray(jax.device_get(s.params["w"]))
        np.save(os.path.join(TMP, f"params_p{PID}.npy"), w)
        # synced loss is a plain host float on every process
        l = s.loss(s.model(local_batch(0)[0]), local_batch(0)[1])
        _ = s.detach_and_sync_loss(l)

    elif SCENARIO == "consolidated_save":
        # gather + process-0 write (reference DDPIO torch.save on rank 0,
        # io_ops.py:551-623) with barriers on both sides
        s = train(make_stoke())
        tag_dir = s.save(os.path.join(TMP, "ckpt"), name="mp")
        s.barrier()
        if PID == 0:
            assert os.path.exists(os.path.join(tag_dir, "variables.npz"))
            assert os.path.exists(os.path.join(tag_dir, "meta.json"))
        # every process loads the consolidated file back identically
        s2 = make_stoke()
        s2.load(os.path.join(TMP, "ckpt"), name="mp")
        assert s2.backward_steps == 3 and s2.optimizer_steps == 3
        np.testing.assert_allclose(
            np.asarray(jax.device_get(s2.params["w"])),
            np.asarray(jax.device_get(s.params["w"])),
            rtol=1e-6,
        )

    elif SCENARIO == "save_rank":
        # configurable writer rank (reference DDPIO._save_rank / OSS
        # consolidate_state_dict(recipient_rank), io_ops.py:551-623):
        # save_rank=1 makes process 1 write payload AND metadata; the
        # payload must still be the gathered GLOBAL state, loadable by all
        s = train(make_stoke(save_rank=1))
        tag_dir = s.save(os.path.join(TMP, "ckpt_rank1"), name="mp")
        s.barrier()
        assert os.path.exists(os.path.join(tag_dir, "variables.npz"))
        assert os.path.exists(os.path.join(tag_dir, "meta.json"))
        if PID == 1:
            # prove THIS process wrote them (same shared fs here, so assert
            # via a writer-side marker: the meta name field round-trips)
            with open(os.path.join(tag_dir, "meta.json")) as f:
                assert json.load(f)["name"] == "mp"
        s2 = make_stoke(save_rank=1)
        s2.load(os.path.join(TMP, "ckpt_rank1"), name="mp")
        assert s2.backward_steps == 3 and s2.optimizer_steps == 3
        np.testing.assert_allclose(
            np.asarray(jax.device_get(s2.params["w"])),
            np.asarray(jax.device_get(s.params["w"])),
            rtol=1e-6,
        )
        # out-of-range rank degrades via modulo instead of never writing
        s3 = train(make_stoke(save_rank=NPROC))
        tag3 = s3.save(os.path.join(TMP, "ckpt_mod"), name="mp")
        s3.barrier()
        assert os.path.exists(os.path.join(tag3, "meta.json"))

    elif SCENARIO == "sharded_save":
        # every host writes its shards via orbax/tensorstore (reference
        # DeepspeedIO sharded path, io_ops.py:389-483), fsdp placement
        from jax.experimental import multihost_utils

        s = train(make_stoke(fmt=CheckpointFormat.sharded, fsdp=True))
        s.save(os.path.join(TMP, "ckpt_sharded"), name="mp")
        s.barrier()
        s2 = make_stoke(fmt=CheckpointFormat.sharded, fsdp=True)
        s2.load(os.path.join(TMP, "ckpt_sharded"), name="mp")
        # fsdp params span non-addressable devices: gather to compare
        a = multihost_utils.process_allgather(s.params["w"], tiled=True)
        b = multihost_utils.process_allgather(s2.params["w"], tiled=True)
        np.testing.assert_allclose(b, a, rtol=1e-6)

    elif SCENARIO == "async_sharded_save":
        # multi-host ASYNC sharded save (round-3): orbax AsyncCheckpointer
        # copies device shards to host on the main thread, writes + runs the
        # cross-process commit in background; meta.json appears only after
        # the global commit, training continues during the write
        import json as _json

        from jax.experimental import multihost_utils

        s = train(make_stoke(fmt=CheckpointFormat.sharded, fsdp=True,
                             async_save=True))
        tag_dir = s.save(os.path.join(TMP, "ckpt_async"), name="mp")
        w_at_save = multihost_utils.process_allgather(s.params["w"], tiled=True)
        s = train(s, steps=2)  # keep training while the save runs
        # wait_for_checkpoint ends with a global barrier, so meta.json is
        # guaranteed on disk for EVERY process right after — no extra
        # barrier needed before loading
        s.wait_for_checkpoint()
        with open(os.path.join(tag_dir, "meta.json")) as f:
            assert _json.load(f)["format"] == "sharded"
        assert os.path.exists(os.path.join(tag_dir, "variables.orbax"))
        s2 = make_stoke(fmt=CheckpointFormat.sharded, fsdp=True)
        s2.load(os.path.join(TMP, "ckpt_async"), name="mp")
        assert s2.backward_steps == 3 and s2.optimizer_steps == 3
        b = multihost_utils.process_allgather(s2.params["w"], tiled=True)
        np.testing.assert_allclose(b, w_at_save, rtol=1e-6)

    elif SCENARIO == "composed_mesh":
        # pod-style composed meshes across 2 PROCESSES x 4 local devices
        # (VERDICT r3 item 5): dp x tp over the global 8-device mesh, then
        # a dp x seq ring and a dp x pp pipeline on the same global pool —
        # the multi-host version of the dryrun's composed scenarios.
        # jax.devices() is process-major (d0-d3 = proc 0, d4-d7 = proc 1),
        # so the naive reshape would keep every NON-data axis inside one
        # process; the interleaved layout below puts consecutive tp/seq/
        # stage neighbors on DIFFERENT processes, forcing the TP
        # all-reduces and the ring/stage ppermutes across the gRPC
        # boundary (the coverage this scenario exists for)
        import jax.numpy as jnp
        import optax
        from jax.sharding import Mesh

        from stoke_tpu import MeshConfig, PartitionRulesConfig
        from stoke_tpu.models import (
            BertForSequenceClassification,
            bert_tensor_parallel_rules,
        )
        from stoke_tpu.utils import init_module

        r = np.random.default_rng(0)
        model = BertForSequenceClassification(
            vocab_size=64, num_classes=2, size_name="tiny", max_len=32,
            dropout_rate=0.0,
        )
        n_global = len(jax.devices())
        assert n_global == 8 and jax.process_count() == NPROC
        # interleave: [d0,d4,d1,d5,d2,d6,d3,d7] — consecutive devices on
        # alternating processes, so any axis of size >= 2 laid out over
        # this order crosses the process boundary
        interleaved = np.asarray(jax.devices()).reshape(NPROC, -1).T.flatten()
        ids_local = r.integers(1, 64, size=(n_global, 16)).astype(np.int32)
        # per-process slice of the global batch (contiguous rows)
        local = n_global // NPROC
        sl = slice(PID * local, (PID + 1) * local)
        variables = init_module(
            model, jax.random.PRNGKey(0), ids_local[:2],
            np.ones((2, 16), np.int32), train=False,
        )
        s = Stoke(
            model=model,
            optimizer=StokeOptimizer(
                optimizer=optax.sgd, optimizer_kwargs={"learning_rate": 0.1}
            ),
            loss=lambda lg, y: optax.softmax_cross_entropy_with_integer_labels(
                lg, y
            ).mean(),
            params=variables,
            batch_size_per_device=1,
            distributed="dp",
            configs=[
                DistributedInitConfig(
                    coordinator_address=f"localhost:{PORT}",
                    num_processes=NPROC,
                    process_id=PID,
                ),
                # tp pairs (d0,d4), (d1,d5), ... — every TP all-reduce
                # crosses gRPC
                MeshConfig(axes=("data", "model"), shape=(4, 2),
                           devices=list(interleaved)),
                PartitionRulesConfig(rules=bert_tensor_parallel_rules()),
            ],
            model_train_kwargs={"train": True},
            model_eval_kwargs={"train": False},
            verbose=False,
        )
        s.train_step(
            (ids_local[sl], np.ones((local, 16), np.int32)),
            np.zeros((local,), np.int64),
        )
        s.block_until_ready()
        assert s.optimizer_steps == 1

        # dp x seq ring attention over the same global pool
        from stoke_tpu.ops import ring_attention

        # seq pairs (d0,d4), ... — ring ppermutes cross gRPC
        mesh_sp = Mesh(interleaved.reshape(-1, 2), ("data", "seq"))
        q = jnp.asarray(r.normal(size=(2, 2, 8, 4)).astype(np.float32))
        jax.grad(
            lambda q: jnp.sum(
                ring_attention(q, q, q, mesh=mesh_sp, axis_name="seq") ** 2
            )
        )(q).block_until_ready()

        # dp x pp pipeline: stage ppermutes cross the process boundary
        from stoke_tpu.parallel import pipeline, stack_stage_params

        # stage rings [d0,d4,d1,d5] / [d2,d6,d3,d7] — every stage-to-stage
        # ppermute hop crosses gRPC
        mesh_pp = Mesh(interleaved.reshape(2, 4), ("data", "stage"))
        stages = stack_stage_params(
            [{"w": jnp.eye(4) * 0.5} for _ in range(4)]
        )
        piped = pipeline(
            lambda p, x: jnp.tanh(x @ p["w"]), mesh_pp, "stage",
            data_axis="data",
        )
        xs = jnp.asarray(r.normal(size=(4, 2, 4)).astype(np.float32))
        jax.grad(lambda p: jnp.sum(piped(p, xs) ** 2))(stages)

    elif SCENARIO == "fleet":
        # fleet observability (ISSUE 5 acceptance): 2 hosts, worker 1's
        # loader sleeps per item -> its loader_wait skews high, and it
        # reaches the per-step barrier late, so worker 0 waits there for
        # it.  Rank 0's JSONL must carry
        # the per-host fleet/* fields with the straggler verdict pointing
        # at host 1 (loader-classified), the barrier wait charged to host
        # 1, and the health registry must record EXACTLY ONE
        # fleet_straggler anomaly (K=5 streak can complete only once in
        # the 7 windows the 8 steps close — the first record anchors).
        import time

        from stoke_tpu import FleetConfig, HealthConfig, TelemetryConfig
        from stoke_tpu.data import BucketedDistributedSampler

        # twice the rows the 8 steps read: the loader fetches two batches
        # ahead, so a dataset that ended with the run would leave the last
        # two windows with no loader lag and noise would name the straggler
        N_ROWS, BATCH_STEPS, SLEEP_S, LATE_S = 512, 8, 0.02, 0.1

        class _SleepyRows:
            """Per-item sleep models a slow input pipeline on ONE host."""

            def __init__(self, sleep_s):
                r = np.random.default_rng(3)
                self.x = r.normal(size=(N_ROWS, IN)).astype(np.float32)
                self.y = (
                    self.x @ np.ones((IN, OUT), np.float32)
                ).astype(np.float32)
                self.sleep_s = sleep_s

            def __len__(self):
                return N_ROWS

            def __getitem__(self, i):
                if self.sleep_s:
                    time.sleep(self.sleep_s)
                return self.x[i], self.y[i]

        out_dir = os.path.join(TMP, "telemetry")
        s = make_stoke(extra_configs=[
            TelemetryConfig(
                output_dir=out_dir,
                log_every_n_steps=1,
                jsonl_all_ranks=True,
                prometheus=True,
                prometheus_all_ranks=True,
                sample_device_time=False,
            ),
            FleetConfig(
                window_steps=1,
                straggler_rel_frac=0.1,
                # K=5 of 8 windows: exactly ONE streak can complete (at
                # window 5, surfacing at step 6's health observation);
                # the second streak is only 3 windows deep at the end
                straggler_windows=5,
                straggler_action="warn",
            ),
            HealthConfig(dump_signals=False, detector_warmup_steps=1000),
        ])
        data = _SleepyRows(SLEEP_S if PID == 1 else 0.0)
        sampler = BucketedDistributedSampler(
            data, buckets=1, batch_size=16,
            sorted_idx=list(range(N_ROWS)),
            num_replicas=NPROC, rank=PID, info_rank=0,
        )
        loader = s.DataLoader(data, sampler=sampler)
        steps = 0
        import warnings as _warnings

        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore")
            for x, y in loader:
                s.train_step(x, (y,))
                if PID == 1:
                    # the step's collective has just aligned the hosts;
                    # host work before the sync makes host 1 the last
                    # arrival by far more than the scheduler's noise
                    time.sleep(LATE_S)
                s.barrier()  # per-step host coordination, the wait source
                steps += 1
                if steps >= BATCH_STEPS:
                    break
        assert steps == BATCH_STEPS, steps
        s.close_telemetry()  # drains any final-window straggler streak
        summary = s.fleet_summary
        by_detector = s.health.anomaly_counts_by_detector()
        with open(os.path.join(TMP, f"fleet_result_p{PID}.json"), "w") as f:
            json.dump({
                "anomalies_by_detector": by_detector,
                "windows": summary["windows"],
                "n_processes": summary["n_processes"],
                "last_verdict": summary["last_verdict"],
                "straggler_events": summary["straggler_events"],
            }, f, default=repr)

    elif SCENARIO == "rebalance":
        # skew-reactive input rebalancing (ISSUE 14 acceptance): worker
        # 1's dataset sleeps per item read.  The fleet verdict classifies
        # it loader-bound, the K=2 streak completes, and the actuator
        # shifts read rows off host 1 — after which host 1's loader wait
        # (and the fleet lag fraction) drops.  Each worker also proves the
        # device feed is UNCHANGED: the rows its devices received each
        # step are exactly the sampler's canonical per-rank plan, shifted
        # reads and the exchange notwithstanding.
        import time

        from stoke_tpu import FleetConfig, TelemetryConfig
        from stoke_tpu.data import BucketedDistributedSampler

        N_ROWS, BATCH_STEPS, SLEEP_S = 512, 16, 0.01

        class _IdRows:
            """Row i carries its index in x[i, 0]; host 1 sleeps per
            read, modeling a slow input pipeline."""

            def __init__(self, sleep_s):
                self.x = np.zeros((N_ROWS, IN), np.float32)
                self.x[:, 0] = np.arange(N_ROWS, dtype=np.float32)
                self.y = np.zeros((N_ROWS, OUT), np.float32)
                self.sleep_s = sleep_s

            def __len__(self):
                return N_ROWS

            def __getitem__(self, i):
                if self.sleep_s:
                    time.sleep(self.sleep_s)
                return self.x[i], self.y[i]

        out_dir = os.path.join(TMP, "telemetry")
        s = make_stoke(extra_configs=[
            TelemetryConfig(
                output_dir=out_dir,
                log_every_n_steps=1,
                jsonl_all_ranks=True,
                prometheus=False,
                sample_device_time=False,
            ),
            FleetConfig(
                window_steps=1,
                straggler_rel_frac=0.1,
                straggler_windows=2,
                straggler_action="record",
                rebalance=True,
                rebalance_rows=4,
                rebalance_max_frac=0.5,
            ),
        ])
        data = _IdRows(SLEEP_S if PID == 1 else 0.0)
        sampler = BucketedDistributedSampler(
            data, buckets=1, batch_size=16,
            sorted_idx=list(range(N_ROWS)),
            num_replicas=NPROC, rank=PID, info_rank=0,
        )
        loader = s.DataLoader(data, sampler=sampler)
        rb = s.fleet.rebalancer
        assert rb is not None, "facade did not attach the rebalancer"
        # the canonical per-rank plan the device feed must keep matching
        expected = [b[PID] for b in sampler.global_batches()]
        steps, fed_ok = 0, True
        import warnings as _warnings

        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore")
            for x, y in loader:
                # this host's addressable rows ARE its canonical batch
                local = np.concatenate([
                    np.asarray(sh.data)[:, 0]
                    for sh in x.addressable_shards
                ])
                want = np.asarray(
                    [float(i) for i in expected[steps]], np.float32
                )
                fed_ok = fed_ok and np.array_equal(np.sort(local),
                                                   np.sort(want))
                s.train_step(x, (y,))
                steps += 1
                if steps >= BATCH_STEPS:
                    break
        assert steps == BATCH_STEPS, steps
        assert fed_ok, "device feed diverged from the canonical plan"
        shares = list(rb.shares)
        s.close_telemetry()
        with open(os.path.join(TMP, f"rebalance_result_p{PID}.json"),
                  "w") as f:
            json.dump({
                "shares": shares,
                "shifts": rb.shifts,
                "rows_moved": rb.rows_moved,
                "fed_ok": bool(fed_ok),
                "summary": (s.fleet_summary or {}).get("rebalance"),
            }, f, default=repr)

    elif SCENARIO == "loader":
        # multi-process DataLoader REQUIRES a distributed sampler
        # (reference stoke.py:822-826); with one, processes see disjoint
        # shards that cover the dataset
        from stoke_tpu.data import BucketedDistributedSampler

        s = make_stoke()
        data = [(np.full((IN,), i, np.float32), np.float32(i)) for i in range(256)]
        try:
            s.DataLoader(data)
            raise AssertionError("sampler-less multi-process loader accepted")
        except ValueError as e:
            assert "sampler" in str(e)
        sampler = BucketedDistributedSampler(
            data,
            buckets=1,
            batch_size=8,
            sorted_idx=list(range(256)),
            num_replicas=NPROC,
            rank=PID,
            info_rank=0,
        )
        # the loader accepts the sampler and yields device-placed batches:
        # per-process loader batch = batch_size_per_device × local devices
        # (16), assembled into the logically-GLOBAL array (32)
        loader = s.DataLoader(data, sampler=sampler)
        assert loader.batch_size == 16, loader.batch_size
        first = next(iter(loader))
        assert first[0].shape[0] == 32, first[0].shape
        seen = list(sampler)
        with open(os.path.join(TMP, f"shard_p{PID}.json"), "w") as f:
            json.dump(sorted(seen), f)

    elif SCENARIO == "batch_divisible":
        # indivisible per-process batches must raise (not silently mix)
        s = make_stoke()
        x = np.zeros((GLOBAL_BATCH // NPROC + 1, IN), np.float32)
        try:
            s._place_batch(x)
            raise AssertionError("indivisible per-process batch accepted")
        except ValueError as e:
            assert "per-process" in str(e)

    elif SCENARIO == "zero":
        # ISSUE 8 acceptance across 2 real processes: int8 quantized
        # reduce-scatter + per-shard error feedback + shard-local update
        # + param all-gather under sddp.  Both ranks must end with
        # IDENTICAL post-step params (the all-gathered replicated value —
        # asserted by the pytest side on the per-rank dumps), and each
        # rank's residual buffers must be partitioned over the global
        # 8-device data axis.
        from jax.sharding import PartitionSpec

        from stoke_tpu import CommConfig, OSSConfig, SDDPConfig
        from stoke_tpu.parallel.zero import ShardedGradTransport

        s = make_stoke(
            oss=True,
            sddp=True,
            extra_configs=(
                CommConfig(dtype="int8", chunk_elems=64, bucket_mb=0.01),
                OSSConfig(min_shard_size=1),
                SDDPConfig(min_shard_size=1),
            ),
        )
        assert isinstance(s._engine.transport, ShardedGradTransport)
        train(s, steps=2)
        assert s.optimizer_steps == 2
        for buf in s._comm_state["residual"]:
            assert buf.sharding.spec == PartitionSpec("data")
            # 8 global devices, 4 local: this process materializes half
            local = sum(
                sh.data.shape[0] for sh in buf.addressable_shards
            )
            assert local * NPROC == buf.shape[0], (local, buf.shape)
        # the wire accounting sees the full 8-wide axis
        assert s.comm_bytes["onwire"] > 0
        assert s.comm_bytes["param_gather"] > 0
        w = np.asarray(jax.device_get(s.params["w"]))
        np.save(os.path.join(TMP, f"zero_params_p{PID}.npy"), w)

    else:
        raise SystemExit(f"unknown scenario {SCENARIO}")

    print(f"WORKER_OK {SCENARIO} {PID}", flush=True)


if __name__ == "__main__":
    main()
