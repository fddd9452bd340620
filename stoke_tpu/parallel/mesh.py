"""Device mesh construction + multi-host rendezvous.

TPU-native replacement for the reference's process-group bootstrap
(stoke/distributed.py:491-538 ``init_process_group`` + MPI discovery;
:759-773 DeepSpeed init; :1308-1316 Horovod init).  One code path:
``jax.distributed.initialize`` for multi-host rendezvous, then a
``jax.sharding.Mesh`` over the global device list.  Collectives become XLA
ops compiled over ICI (intra-slice) / DCN (inter-slice) — there is no NCCL,
no MPI, and no per-backend rendezvous enum (SURVEY.md §2.9).
"""

from __future__ import annotations

import math
import os
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

from stoke_tpu.configs import (
    DeviceOptions,
    DistributedInitConfig,
    MeshConfig,
)
from stoke_tpu.status import StokeValidationError

_DIST_INITIALIZED = False


def _multihost_env_present() -> bool:
    """Detect a multi-host launch environment WITHOUT initializing the JAX
    backend (querying ``jax.process_count()`` here would lock in a
    single-process backend and make a later ``initialize`` ineffective).

    Covers the auto-detection sources ``jax.distributed.initialize`` itself
    uses: explicit JAX coordinator env vars, SLURM/OpenMPI launchers, and
    Cloud TPU pod metadata (the TPU-native replacement for the reference's
    RANK/WORLD_SIZE launcher env + MPI discovery, distributed.py:491-525).
    """
    if os.environ.get("JAX_COORDINATOR_ADDRESS") or os.environ.get(
        "COORDINATOR_ADDRESS"
    ):
        return True
    for var in ("SLURM_NTASKS", "SLURM_JOB_NUM_NODES", "OMPI_COMM_WORLD_SIZE"):
        try:
            if int(os.environ.get(var, "1")) > 1:
                return True
        except ValueError:
            pass
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if hosts and "," in hosts:  # Cloud TPU pod slice: >1 worker
        return True
    try:
        if int(os.environ.get("MEGASCALE_NUM_SLICES", "1")) > 1:
            return True
    except ValueError:
        pass
    return False


def initialize_distributed(cfg: DistributedInitConfig) -> bool:
    """Idempotent multi-host rendezvous via ``jax.distributed.initialize``.

    Replaces the launcher-env (RANK/WORLD_SIZE/MASTER_ADDR) and mpi4py
    discovery paths of the reference (distributed.py:491-525):

    - explicit fields set → explicit rendezvous (bring-your-own-cluster);
    - all fields ``None`` (the common TPU path) → when a multi-host launch
      environment is detected, ``jax.distributed.initialize()`` with no
      arguments lets JAX auto-infer from TPU pod metadata / SLURM / env vars;
    - single-host (no multi-host env detected) → no-op, returns False.

    Returns True if a multi-process rendezvous was (already) performed.
    """
    global _DIST_INITIALIZED
    if _DIST_INITIALIZED:
        return True
    # the launcher may have called jax.distributed.initialize itself (e.g.
    # a multi-process test harness must rendezvous before ANY backend use);
    # record and respect it rather than re-initializing
    if jax.distributed.is_initialized():
        _DIST_INITIALIZED = True
        return True
    explicit = cfg.num_processes is not None or cfg.coordinator_address is not None
    if not explicit and not _multihost_env_present():
        return False
    try:
        if explicit:
            jax.distributed.initialize(
                coordinator_address=cfg.coordinator_address,
                num_processes=cfg.num_processes,
                process_id=cfg.process_id,
                local_device_ids=cfg.local_device_ids,
                initialization_timeout=cfg.initialization_timeout,
            )
        else:
            jax.distributed.initialize(
                initialization_timeout=cfg.initialization_timeout
            )
        _DIST_INITIALIZED = True
        return True
    except RuntimeError as e:
        if "already initialized" in str(e).lower():
            _DIST_INITIALIZED = True
            return True
        raise


def backend_devices(device: DeviceOptions, local: bool = False):
    """Devices of the selected platform (global, or this process's with
    ``local``).  ``device="tpu"`` means a TPU: a process without one raises
    instead of running on whatever backend JAX fell back to."""
    platform = "cpu" if device is DeviceOptions.cpu else "tpu"
    try:
        if local:
            return jax.local_devices(backend=platform)
        return jax.devices(platform)
    except RuntimeError as e:
        raise StokeValidationError(
            f"Stoke -- device={platform!r} but JAX exposes no such "
            f"platform: jax.default_backend()={jax.default_backend()!r}, "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}"
        ) from e


def local_device_count(device: DeviceOptions) -> int:
    if device is DeviceOptions.cpu:
        return len([d for d in jax.local_devices(backend="cpu")])
    return jax.local_device_count()


def build_mesh(
    mesh_config: MeshConfig,
    device: DeviceOptions,
    distributed: bool,
) -> Optional[Mesh]:
    """Build the logical device mesh.

    - not distributed → ``None`` (plain single-device jit; the reference's
      DistributedNull* runners, distributed.py:298-401).
    - distributed → mesh over ALL global devices.  Default 1-D ``("data",)``;
      ``MeshConfig.shape`` reshapes for future model/seq/expert axes.  Axis
      order follows ``jax.sharding.Mesh`` convention: the LAST axis is
      innermost (fastest-varying over ICI neighbors), so put the
      highest-bandwidth-demand axis last when using >1 axis.
    """
    if not distributed:
        return None
    devices = mesh_config.devices
    if devices is None:
        devices = backend_devices(device)
    devices = np.asarray(devices)
    axes = tuple(mesh_config.axes)
    shape = mesh_config.shape
    if shape is None:
        shape = (devices.size,) + (1,) * (len(axes) - 1)
    shape = tuple(shape)
    if -1 in shape:
        known = math.prod(s for s in shape if s != -1)
        if devices.size % known != 0:
            raise ValueError(
                f"Stoke -- cannot infer mesh shape {shape} from {devices.size} devices"
            )
        shape = tuple(devices.size // known if s == -1 else s for s in shape)
    if math.prod(shape) != devices.size:
        raise ValueError(
            f"Stoke -- mesh shape {shape} does not match {devices.size} devices"
        )
    return Mesh(devices.reshape(shape), axes)
