"""Unified checkpoint save/load across sharding tiers.

TPU-native re-design of the reference IO mixins (stoke/io_ops.py:20-746).
The reference needs four strategies (BaseStokeIO/DDPIO/HorovodIO/DeepspeedIO)
because each backend owns state differently (FSDP shard gathering
io_ops.py:569-600, OSS consolidation :584, DeepSpeed engine checkpoints
:389-544).  Here state is a pytree with *declared* shardings, so there are
exactly two layouts:

- ``consolidated``: gather to host and write one portable file set (numpy
  arrays + JSON metadata) — the reference's rank-0 ``torch.save`` path
  (io_ops.py:551-623).  Works across topology changes.
- ``sharded``: every host writes its shards via orbax/tensorstore — the
  reference's DeepSpeed sharded path (io_ops.py:389-483), but
  restorable onto any topology because shardings are re-applied from the
  *target* state at load time (the FSDP shard-extraction of the reference,
  io_ops.py:298-306, is subsumed by "load into the declared shardings").

The payload schema mirrors the reference exactly (io_ops.py:224-236):
counters {backward_step, grad_accum_step, optimizer_step}, the status dict,
model/optimizer/scaler state, and user extras.  Tag scheme:
``stoke-{name}-backward-step-{n}`` (reference io_ops.py:49-87).
"""

from __future__ import annotations

import json
import os
import pickle
import re
import shutil
import threading
from typing import Any, Dict, Optional, Tuple

_ASYNC_SAVES: list = []  # in-flight background save threads
_ASYNC_ERRORS: list = []  # exceptions raised by background saves (surfaced in wait_for_saves)
_INFLIGHT_TAGS: set = set()  # tag dirs being written by async saves (prune must skip)

import jax
import numpy as np

from stoke_tpu.telemetry.tracing import trace_span

from stoke_tpu.configs import CheckpointConfig, CheckpointFormat
from stoke_tpu.utils.printing import make_folder, unrolled_print

_TAG_RE = re.compile(r"^stoke-(?P<name>.+)-backward-step-(?P<step>\d+)$")


def checkpoint_tag(name: str, backward_step: int) -> str:
    """Reference tag scheme ``stoke-{name}-backward-step-{n}.pt``
    (io_ops.py:49-87); here a directory."""
    return f"stoke-{name}-backward-step-{backward_step}"


def _is_multiprocess() -> bool:
    return jax.process_count() > 1


def _writer_rank(config: CheckpointConfig) -> int:
    """The process that writes consolidated payloads + metadata (reference
    ``DDPIO._save_rank`` / OSS ``consolidate_state_dict(recipient_rank)``,
    io_ops.py:551-623).  Modulo process count so a config written for a
    larger pod degrades to a valid rank instead of never writing."""
    return int(config.save_rank) % max(jax.process_count(), 1)


def _gather_to_host(tree: Any) -> Any:
    """Device pytree → host numpy pytree, gathering shards across hosts when
    needed (the consolidation step the reference implements per-backend,
    io_ops.py:569-600)."""
    if _is_multiprocess():
        from jax.experimental import multihost_utils

        return multihost_utils.process_allgather(tree, tiled=True)
    return jax.tree_util.tree_map(lambda x: np.asarray(jax.device_get(x)), tree)


def _flat_arrays(tree: Any):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return leaves, treedef


def _save_consolidated(
    tag_dir: str, state: Dict[str, Any], writer: int = 0
) -> None:
    """One ``.npz`` per state tree, leaves in flatten order (restore relies on
    the target structure, so no treedef serialization is needed).  Multi-host:
    every process gathers (a collective), only the ``writer`` process (config
    ``save_rank``) writes."""
    for key, tree in state.items():
        host = _gather_to_host(tree)
        if jax.process_index() != writer:
            continue
        leaves, _ = _flat_arrays(host)
        np.savez(
            os.path.join(tag_dir, f"{key}.npz"),
            **{f"leaf_{i}": np.asarray(l) for i, l in enumerate(leaves)},
        )


def _load_consolidated(tag_dir: str, key: str, like: Any) -> Any:
    with np.load(os.path.join(tag_dir, f"{key}.npz")) as data:
        leaves_like, treedef = _flat_arrays(like)
        n = len(data.files)
        if n != len(leaves_like):
            raise ValueError(
                f"Stoke -- checkpoint {key} has {n} leaves; current state has "
                f"{len(leaves_like)} (model/optimizer structure changed?)"
            )
        loaded = [data[f"leaf_{i}"] for i in range(n)]
    from stoke_tpu.parallel.sharding import place_global_tree

    placed = []
    for arr, ref in zip(loaded, leaves_like):
        if hasattr(ref, "sharding"):
            placed.append(
                place_global_tree(arr.astype(ref.dtype), ref.sharding)
            )
        else:
            placed.append(arr)
    return jax.tree_util.tree_unflatten(treedef, placed)


def _np_dtype(name: str) -> np.dtype:
    """Dtype from its string name, including the ml_dtypes family numpy
    itself cannot resolve (bfloat16, fp8 variants) — those are looked up on
    the jax.numpy namespace."""
    try:
        return np.dtype(name)
    except TypeError:
        import jax.numpy as jnp

        return np.dtype(getattr(jnp, name))


def _staged_files(key: str, rank: int) -> Tuple[str, str]:
    """(npz, json) file names of one process's staged payload of ``key``."""
    return (
        f"{key}.staged.rank{rank}.npz",
        f"{key}.staged.rank{rank}.json",
    )


def _write_staged_payload(
    tag_dir: str, key: str, rank: int, records: list
) -> None:
    """Write one resolved :class:`~stoke_tpu.offload.StagedSnapshot` as this
    process's shard file pair: raw-byte npz (uint8 spill, the
    DiskOptimizerStore convention — .npy silently degrades ml_dtypes) plus a
    json index mapping each leaf's shards back to normalized global-index
    slices.  Both writes are tmp+rename atomic and the INDEX lands last, so
    a killed writer leaves an index-less (detectably partial) payload."""
    npz_name, json_name = _staged_files(key, rank)
    arrays: Dict[str, np.ndarray] = {}
    index: Dict[str, Any] = {"version": 1, "rank": rank, "leaves": []}
    for i, (kind, rec) in enumerate(records):
        if kind == "static":
            arrays[f"leaf{i}_static"] = np.asarray(rec)
            index["leaves"].append({"kind": "static"})
            continue
        shape, dtype, shards = rec
        entry = {
            "kind": "array",
            "shape": list(shape),
            "dtype": np.dtype(dtype).name,
            "shards": [],
        }
        for j, (norm_idx, data, shard_shape) in enumerate(shards):
            name = f"leaf{i}_shard{j}"
            flat = np.ascontiguousarray(data).reshape(-1)
            arrays[name] = flat.view(np.uint8) if flat.size else flat.astype(
                np.uint8
            )
            entry["shards"].append({
                "name": name,
                "index": [list(t) for t in norm_idx],
                "shape": list(shard_shape),
            })
        index["leaves"].append(entry)
    npz_path = os.path.join(tag_dir, npz_name)
    # ".tmp" suffix is load-bearing: manifest digesting skips in-flight
    # writes by exactly that suffix (resilience._walk_files) — another
    # rank's manifest must never list this file until the rename lands
    tmp = npz_path + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **arrays)
    os.replace(tmp, npz_path)
    json_path = os.path.join(tag_dir, json_name)
    tmp = json_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(index, f)
    os.replace(tmp, json_path)


def _load_staged(tag_dir: str, key: str, like: Any, processes: int) -> Any:
    """Reassemble one state tree from EVERY process's staged shard files
    onto the CURRENT layout.  Shards are written against normalized
    global-index slices, so reassembly is topology-free by construction —
    a v4-32 save restores onto a v4-16 mesh (or any other) because the
    target shardings come from ``like``, not from the writer's mesh (the
    elastic-resume property, ISSUE 14)."""
    from stoke_tpu.parallel.sharding import place_global_tree

    per_rank = []
    for r in range(max(processes, 1)):
        npz_name, json_name = _staged_files(key, r)
        with open(os.path.join(tag_dir, json_name)) as f:
            index = json.load(f)
        data = np.load(os.path.join(tag_dir, npz_name))
        per_rank.append((index, data))
    leaves_like, treedef = _flat_arrays(like)
    n = len(per_rank[0][0]["leaves"])
    if n != len(leaves_like):
        raise ValueError(
            f"Stoke -- staged checkpoint {key} has {n} leaves; current "
            f"state has {len(leaves_like)} (model/optimizer structure "
            f"changed?)"
        )
    placed = []
    for i, ref in enumerate(leaves_like):
        entry = per_rank[0][0]["leaves"][i]
        if entry["kind"] == "static":
            placed.append(per_rank[0][1][f"leaf{i}_static"])
            continue
        shape = tuple(entry["shape"])
        dtype = _np_dtype(entry["dtype"])
        out = np.zeros(shape, dtype)
        for index, data in per_rank:
            for shard in index["leaves"][i]["shards"]:
                raw = data[shard["name"]]
                shard_shape = tuple(shard["shape"])
                value = (
                    raw.view(dtype).reshape(shard_shape)
                    if raw.size
                    else np.zeros(shard_shape, dtype)
                )
                sl = tuple(
                    slice(s, e, st) for s, e, st in shard["index"]
                )
                out[sl] = value
        if hasattr(ref, "sharding"):
            placed.append(
                place_global_tree(
                    out.astype(ref.dtype, copy=False), ref.sharding
                )
            )
        else:
            placed.append(out)
    for _index, data in per_rank:
        data.close()
    return jax.tree_util.tree_unflatten(treedef, placed)


def _orbax_checkpointer():
    import orbax.checkpoint as ocp

    return ocp.StandardCheckpointer()


def _save_sharded(tag_dir: str, state: Dict[str, Any]) -> None:
    ckpt = _orbax_checkpointer()
    for key, tree in state.items():
        ckpt.save(os.path.join(tag_dir, f"{key}.orbax"), tree)
    ckpt.wait_until_finished()


def _save_sharded_async(tag_dir: str, state: Dict[str, Any]) -> list:
    """Kick off orbax async sharded writes; returns the checkpointer handles.

    ``AsyncCheckpointer.save`` copies device shards to host ON THE CALLING
    (main) thread, then serializes + writes in orbax's own background
    machinery — including the cross-process commit coordination (the
    distributed KV-store barriers ride gRPC, not XLA collectives, so they
    are safe off the main thread).  Every process must create/save in the
    same order so the barrier keys line up."""
    import orbax.checkpoint as ocp

    handles = []
    for key, tree in state.items():
        c = ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
        c.save(os.path.join(tag_dir, f"{key}.orbax"),
               args=ocp.args.StandardSave(tree))
        handles.append(c)
    return handles


def _load_sharded(tag_dir: str, key: str, like: Any) -> Any:
    ckpt = _orbax_checkpointer()
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        if hasattr(x, "sharding")
        else x,
        like,
    )
    return ckpt.restore(os.path.join(tag_dir, f"{key}.orbax"), abstract)


def save_checkpoint(
    path: str,
    name: str,
    variables: Any,
    opt_state: Any,
    scaler_state: Any,
    counters: Dict[str, int],
    status: Dict[str, Any],
    extras: Optional[Dict[str, Any]],
    config: CheckpointConfig,
    backward_step: int,
    grad_buf: Any = None,
    manifest: bool = False,
    topology: Optional[Dict[str, Any]] = None,
    chaos: Any = None,
    on_durable: Optional[Any] = None,
) -> str:
    """Write one logical checkpoint; returns the tag directory path.

    Reference flow (io_ops.py:160-243 + per-backend wrappers :551-703):
    barrier → gather/consolidate → write (the ``save_rank`` writer for
    consolidated, all ranks for sharded) → barrier.  Metadata
    (counters/status/extras) is written by the ``save_rank`` writer only
    (reference ``DDPIO._save_rank``, io_ops.py:551-623).  ``grad_buf``
    (the partial accumulation window) is saved
    too so a mid-window resume loses no gradient mass — the reference cannot
    do this (torch ``.grad`` is not in ``state_dict``).

    ``manifest=True`` (ISSUE 7): after ``meta.json``, the writer rank adds
    a ``manifest.json`` of per-file sha256 digests over the completed tag —
    the integrity record ``Stoke.resume()`` validates against before
    trusting a checkpoint (corrupt/partial tags are quarantined, never
    loaded).  Written LAST on both the sync and async paths, so a tag with
    a manifest is a tag whose write finished.

    ``topology`` (ISSUE 14): the saving run's topology/sharding descriptor
    (mesh shape, process count, tier, ``shard_updates``, comm bucket
    layout) embedded in the manifest — what ``Stoke.resume()`` reads to
    re-shard state onto a DIFFERENT mesh and to quarantine genuinely
    incompatible checkpoints with a remedy named.

    ``config.offload_staging`` (ISSUE 14 tentpole a): the async
    consolidated save stages device→host through
    ``offload.StagedSnapshot`` instead of completing a blocking gather on
    the main thread — the step path pays one copy-program dispatch, the
    transfers land off the critical path, and EVERY process writes its own
    ``<key>.staged.rank<N>.npz`` shard files (no collective anywhere on
    the save path).  ``meta.json`` records the staged layout so load and
    the resume-time validator know how many rank files completeness
    requires.

    ``chaos`` (ISSUE 14 satellite): the run's ``ChaosInjector`` — its
    ``kill_during_save`` hook fires from the background writer AFTER the
    payload and BEFORE ``meta.json``, proving a mid-save death leaves a
    detectably partial (never loadable, always quarantined) tag.

    ``on_durable`` (ISSUE 14 satellite): zero-arg callback invoked once
    THIS save's write has fully landed — synchronously for sync saves,
    from the background thread after ``meta.json`` for async ones.  The
    facade's lost-goodput accounting hangs off it: a save only counts as
    "the last durable save" when its own write succeeded, never at
    dispatch (an in-flight or failed save must keep counting as lost).
    """
    root = make_folder(path)
    tag = checkpoint_tag(name, backward_step)
    tag_dir = os.path.join(root, tag)
    is_async = bool(config.async_save)
    if is_async:
        # claim the tag BEFORE creating the dir: a concurrently finishing
        # earlier async save's _prune_old must never classify this (still
        # meta-less) dir as a stale leftover during the gather window.
        # Released on ANY failure before the background thread takes over
        # (the thread then owns the release).
        _INFLIGHT_TAGS.add(tag_dir)
    writer = _writer_rank(config)
    try:
        if jax.process_index() == writer:
            os.makedirs(tag_dir, exist_ok=True)
        _barrier()
    except BaseException:
        _INFLIGHT_TAGS.discard(tag_dir)
        raise
    state = {
        "variables": variables,
        "opt_state": opt_state,
        "scaler_state": scaler_state,
    }
    if grad_buf is not None:
        state["grad_buf"] = grad_buf
    staged_meta: Optional[Dict[str, Any]] = None

    def _write_meta_files(fmt_value: str) -> None:
        """meta.json + extras.pkl — the ``save_rank`` writer only; shared by
        the sync and async paths so the metadata schema can never drift
        between them."""
        if jax.process_index() != writer:
            return
        # extras BEFORE meta.json: meta is the tag's "loadable" marker
        # (verify_checkpoint treats a meta-less tag as a partial write), so
        # a hard kill between the two files must leave the tag UNloadable —
        # the reverse order would let resume silently restore without the
        # rng/EMA/EF-residual extras and break bit-identical resumption
        if extras:
            with open(os.path.join(tag_dir, "extras.pkl"), "wb") as f:
                pickle.dump(extras, f)
        meta = {
            "format": fmt_value,
            "counters": counters,
            "status": status,
            "name": name,
        }
        if staged_meta is not None:
            # staged layout marker (ISSUE 14): load + the resume-time
            # validator derive "which rank files must exist" from this —
            # a kill that stranded another rank's shard file mid-write
            # must read as a partial tag, not a short checkpoint
            meta["staged"] = staged_meta
        with open(os.path.join(tag_dir, "meta.json"), "w") as f:
            json.dump(meta, f, indent=2, default=str)
        if manifest:
            # integrity digests over the finished tag (ISSUE 7) — shared
            # by the sync and async paths like the meta schema above, so
            # the manifest can never claim files a crashed write lost
            from stoke_tpu.resilience import write_manifest

            extra = {"backward_step": backward_step, "name": name}
            if topology is not None:
                # topology/sharding descriptor (ISSUE 14): the record
                # elastic resume re-shards against
                extra["topology"] = topology
            write_manifest(tag_dir, extra=extra)

    def _write_meta():
        if jax.process_index() == writer:
            _write_meta_files(config.format.value)
            _prune_old(root, name, config.max_to_keep)
            unrolled_print(f"Saved checkpoint {tag_dir}")

    if is_async:
        # Async save: anything touching DEVICE arrays or XLA collectives
        # happens HERE, synchronously on the main thread — the compiled
        # steps donate (invalidate) state buffers, and multi-host gather
        # collectives cannot run off-thread.  Only serialization + disk
        # (and orbax's gRPC commit coordination) runs in the background.
        # meta.json is written last — and, multi-process, only after the
        # global commit — so a crash mid-save never leaves a loadable
        # partial tag (load requires meta.json).
        is_writer = jax.process_index() == writer
        if config.format is CheckpointFormat.sharded:
            # orbax AsyncCheckpointer: device→host copy on this thread,
            # sharded tensorstore writes + cross-host commit in background
            try:
                # traced: the async save's main-thread (step-path) cost
                with trace_span("stoke/ckpt_save", track="io",
                                attrs={"tag": tag, "async": True}):
                    handles = _save_sharded_async(tag_dir, state)
            except BaseException:
                _INFLIGHT_TAGS.discard(tag_dir)
                raise

            def _write_payload():
                for h in handles:
                    # returns after THIS process's writes are durable and
                    # the cross-process commit barrier has passed — on
                    # process 0 that makes meta.json a global completeness
                    # marker.  close() releases the checkpointer's
                    # background machinery (a fresh one is built per save;
                    # leaving them open leaks threads across a long run)
                    h.wait_until_finished()
                    h.close()

            fmt_value = CheckpointFormat.sharded.value
        elif getattr(config, "offload_staging", False):
            # zero-stall staged save (ISSUE 14 tentpole a): the main
            # thread issues the decoupling copy + async host transfers and
            # returns — no gather, no collective.  The background thread
            # resolves the landed shards and writes THIS process's shard
            # files; every process writes its own, so the layout needs no
            # cross-host coordination beyond the meta-side completeness
            # marker recorded below.
            from stoke_tpu import offload

            rank = jax.process_index()
            nproc = max(jax.process_count(), 1)
            try:
                # traced: the staged save's main-thread (step-path) cost —
                # ONE copy-program dispatch for the whole state dict.  One
                # snapshot per SAVE, not per state tree: the double buffer
                # bounds in-flight SAVES at two, so staging a save's later
                # trees can never force-resolve its own earlier trees on
                # the main thread (which would be the gather stall under a
                # different name).
                with trace_span("stoke/ckpt_save", track="io",
                                attrs={"tag": tag, "async": True,
                                       "staged": True}):
                    staged_snap = offload.stage_tree(state)
            except BaseException:
                _INFLIGHT_TAGS.discard(tag_dir)
                raise
            staged_meta = {"processes": nproc, "keys": sorted(state)}
            # flatten order of the combined dict is key-sorted; each key's
            # leaves are a contiguous record slice in that order
            key_counts = [
                (k, len(jax.tree_util.tree_leaves(state[k])))
                for k in sorted(state)
            ]

            def _write_payload():
                _treedef, records = staged_snap.resolve()
                off = 0
                for key, n in key_counts:
                    _write_staged_payload(
                        tag_dir, key, rank, records[off:off + n]
                    )
                    off += n

            fmt_value = CheckpointFormat.consolidated.value
        else:
            # consolidated: gather (collective, main thread) → proc-0 write
            try:
                # traced: the async save's main-thread (step-path) cost
                with trace_span("stoke/ckpt_save", track="io",
                                attrs={"tag": tag, "async": True}):
                    host_state = {
                        k: _gather_to_host(v) for k, v in state.items()
                    }
            except BaseException:
                _INFLIGHT_TAGS.discard(tag_dir)  # claim released on gather failure
                raise

            def _write_payload():
                if not is_writer:
                    return
                for key, tree in host_state.items():
                    leaves, _ = _flat_arrays(tree)
                    np.savez(
                        os.path.join(tag_dir, f"{key}.npz"),
                        **{f"leaf_{i}": np.asarray(l)
                           for i, l in enumerate(leaves)},
                    )

            fmt_value = CheckpointFormat.consolidated.value

        def _bg():
            try:
                _write_payload()
                if chaos is not None:
                    # kill_during_save injector (ISSUE 14 satellite):
                    # SIGKILL between payload and meta.json — the
                    # half-staged state a preempted host really leaves
                    chaos.on_async_payload(tag_dir)
                _write_meta_files(fmt_value)
                # meta.json is on disk: this tag is now a complete, loadable
                # checkpoint — leave the in-flight set BEFORE pruning so it
                # counts toward its own keep window
                _INFLIGHT_TAGS.discard(tag_dir)
                if on_durable is not None:
                    try:
                        on_durable()
                    except Exception:
                        pass  # accounting must never fail a landed save
                if is_writer:
                    _prune_old(root, name, config.max_to_keep)
                    unrolled_print(f"Saved checkpoint {tag_dir} (async)")
            except BaseException as e:  # surfaced by wait_for_saves()
                # write-phase failure → remove the partial tag (it can never
                # load without meta.json).  A failure AFTER meta.json exists
                # (e.g. a transient error inside _prune_old) leaves the
                # complete, loadable checkpoint in place.
                if is_writer and not os.path.exists(
                    os.path.join(tag_dir, "meta.json")
                ):
                    shutil.rmtree(tag_dir, ignore_errors=True)
                _ASYNC_ERRORS.append((tag_dir, e))
            finally:
                _INFLIGHT_TAGS.discard(tag_dir)

        t = threading.Thread(target=_bg, name=f"stoke-save-{tag}", daemon=False)
        _ASYNC_SAVES.append(t)
        try:
            t.start()
        except BaseException:
            _ASYNC_SAVES.remove(t)
            _INFLIGHT_TAGS.discard(tag_dir)
            raise
        return tag_dir
    # the save span (ISSUE 10): the synchronous write path end-to-end —
    # gather, payload, metadata, barrier.  The async path above is traced
    # per-phase instead (its main-thread cost is the gather; the
    # background write is off the step path by design).
    with trace_span("stoke/ckpt_save", track="io", attrs={"tag": tag}):
        if config.format is CheckpointFormat.consolidated:
            _save_consolidated(tag_dir, state, writer)
        else:
            _save_sharded(tag_dir, state)
        _write_meta()
        _barrier()
    if on_durable is not None:
        try:
            on_durable()
        except Exception:
            pass
    return tag_dir


def wait_for_saves() -> None:
    """Block until all in-flight async checkpoint saves complete (call
    before exiting or before loading a just-saved checkpoint).

    Multi-process, ends with a global barrier: a non-zero process's
    background thread can finish before process 0 has written ``meta.json``,
    so without the barrier "my threads are done" would not mean "the
    checkpoint is loadable".  The barrier runs before errors are raised so
    a failing process never strands its peers mid-barrier.

    Raises on background-save failure (disk full, serialization error, ...)
    rather than silently dropping it — a checkpoint that was never written
    must not look saved.  EVERY failed tag dir
    is named in the message (ISSUE 7 satellite: an operator deciding which
    checkpoints are trustworthy needs the full casualty list, not the first
    failure with "+2 more"); the first underlying exception chains as the
    cause and the rest are summarized inline."""
    with trace_span("stoke/ckpt_wait", track="io"):
        # staged landing buffers FIRST (ISSUE 14): an offload-staged save
        # still mid-flight holds device-side snapshot copies whose host
        # transfers must land before any synchronous gather this caller
        # runs next (the emergency save's).  Thread joins alone would
        # cover it eventually, but the explicit drain pins the ordering:
        # staging resolves, then writer threads, then the barrier.
        from stoke_tpu.offload import drain_staged

        drain_staged()
        while _ASYNC_SAVES:
            _ASYNC_SAVES.pop().join()
        _barrier()
    if _ASYNC_ERRORS:
        failures = list(_ASYNC_ERRORS)
        _ASYNC_ERRORS.clear()
        _, first_err = failures[0]
        detail = "; ".join(
            f"{tag_dir} ({type(err).__name__}: {err})"
            for tag_dir, err in failures
        )
        raise RuntimeError(
            f"Stoke -- {len(failures)} async checkpoint save"
            f"{'s' if len(failures) > 1 else ''} failed: {detail}"
        ) from first_err


def _prune_old(root: str, name: str, max_to_keep: Optional[int]) -> None:
    """Keep the newest N tags (by backward step) for this name.

    Tags this process is still writing (``_INFLIGHT_TAGS``; async saves
    write ``meta.json`` last) are never pruned — deleting one mid-write
    would corrupt a concurrent save.  Meta-less tags that are NOT in flight
    are leftovers from a crashed/failed save and are pruned like any other
    old tag (they can never load)."""
    if not max_to_keep:
        return
    tags, stale = [], []
    for entry in os.listdir(root):
        m = _TAG_RE.match(entry)
        if m and m.group("name") == name:
            if os.path.join(root, entry) in _INFLIGHT_TAGS:
                continue
            if not os.path.exists(os.path.join(root, entry, "meta.json")):
                stale.append(entry)  # crashed/failed leftover, never loadable
                continue
            tags.append((int(m.group("step")), entry))
    tags.sort()
    # only loadable tags count toward the keep window (a crashed leftover
    # must never displace a loadable checkpoint)
    for entry in stale:
        shutil.rmtree(os.path.join(root, entry), ignore_errors=True)
    for _, entry in tags[:-max_to_keep]:
        shutil.rmtree(os.path.join(root, entry), ignore_errors=True)


def _latest_tag(root: str, name: Optional[str]) -> Optional[str]:
    """Newest tag by backward step, scoped to ``name`` when given (so two
    runs sharing a directory never load each other's state)."""
    best = None
    for entry in os.listdir(root):
        m = _TAG_RE.match(entry)
        if m and (name is None or m.group("name") == name):
            step = int(m.group("step"))
            if best is None or step > best[0]:
                best = (step, entry)
    return best[1] if best else None


def _barrier() -> None:
    # instrumented (ISSUE 5 satellite): checkpoint-coordination waits land
    # in sync/barrier_wait_s of every live telemetry registry — before
    # this, cross-process sync time around IO was invisible to the
    # goodput ledger and un-attributable to the straggler host
    if _is_multiprocess():
        from jax.experimental import multihost_utils

        from stoke_tpu.telemetry.fleet import timed_sync

        with timed_sync("ckpt"):
            multihost_utils.sync_global_devices("stoke_ckpt")


def load_checkpoint(
    path: str,
    tag: Optional[str],
    variables_like: Any,
    opt_state_like: Any,
    scaler_like: Any,
    config: CheckpointConfig,
    name: Optional[str] = None,
    grad_buf_like: Any = None,
) -> Dict[str, Any]:
    """Load a checkpoint onto the CURRENT sharding layout.

    ``tag=None`` loads the newest tag under ``path`` (scoped to ``name`` when
    given).  The on-disk format is read from ``meta.json`` (a consolidated
    checkpoint can be loaded by a sharded run and vice versa — the reference
    cannot do this across backends; SURVEY.md §7 hard part #4).
    """
    root = os.path.abspath(os.path.expanduser(path))
    if tag is None:
        tag = _latest_tag(root, name)
        if tag is None:
            raise FileNotFoundError(f"Stoke -- no checkpoints found under {root}")
    tag_dir = os.path.join(root, tag)
    with open(os.path.join(tag_dir, "meta.json")) as f:
        meta = json.load(f)
    fmt = CheckpointFormat(meta["format"])
    staged = meta.get("staged")
    if staged:
        # offload-staged layout (ISSUE 14): per-process shard files keyed
        # by normalized global indices — reassembled onto the CURRENT
        # shardings, so the writer's topology is irrelevant at load
        import functools

        loader = functools.partial(
            _load_staged, processes=int(staged.get("processes", 1))
        )
    elif fmt is CheckpointFormat.consolidated:
        loader = _load_consolidated
    else:
        loader = _load_sharded
    payload = {
        "variables": loader(tag_dir, "variables", variables_like),
        "opt_state": loader(tag_dir, "opt_state", opt_state_like),
        "scaler_state": loader(tag_dir, "scaler_state", scaler_like),
        "counters": meta["counters"],
        "status": meta["status"],
        "grad_buf": None,
    }
    has_buf = (
        os.path.exists(os.path.join(tag_dir, "grad_buf.npz"))
        or os.path.exists(os.path.join(tag_dir, "grad_buf.orbax"))
        or os.path.exists(
            os.path.join(tag_dir, _staged_files("grad_buf", 0)[0])
        )
    )
    if grad_buf_like is not None and has_buf:
        payload["grad_buf"] = loader(tag_dir, "grad_buf", grad_buf_like)
    extras_path = os.path.join(tag_dir, "extras.pkl")
    if os.path.exists(extras_path):
        with open(extras_path, "rb") as f:
            payload["extras"] = pickle.load(f)
    unrolled_print(f"Loaded checkpoint {tag_dir}")
    return payload
