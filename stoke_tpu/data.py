"""Data layer: device-placing DataLoader + BucketedDistributedSampler.

TPU-native re-design of the reference data side-car (stoke/data.py:24-516):

- :class:`StokeDataLoader` (reference data.py:24-108): wraps a host-side
  loader (torch's, when available — it is the best multi-worker host loader
  and carries zero CUDA dependency on CPU) and yields batches already placed
  in device HBM, *sharded over the mesh data axis*, with one-batch lookahead
  so the host→HBM transfer of batch N+1 overlaps the compute of batch N
  (SURVEY.md §3.3: host loader + double-buffered ``device_put`` replaces
  per-rank ``.cuda()`` pushes).

- :class:`BucketedDistributedSampler` (reference data.py:111-516): buckets a
  pre-sorted index list (e.g. by sequence length) so each batch draws
  similar-length samples, minimizing padding waste.  Re-implemented from the
  reference's documented semantics with the same invariants (per-epoch seeded
  in-bucket shuffle, stride-aligned padding of the short final slice,
  round-robin replica slicing, optional residual "overlap" batches, identical
  ``__len__``).  In this framework a "replica" is a *loading process* (host),
  not a device: one process feeds a contiguous slice of the logically-global
  batch to all its local devices.
"""

from __future__ import annotations

import itertools
import math
import warnings
from collections import deque
from typing import Any, Callable, Iterator, List, Optional, Sequence

import numpy as np

#: one-time flag for the threaded-fallback semantics warning
_WARNED_THREADED = False


# --------------------------------------------------------------------------- #
# Array-backed dataset with native batching
# --------------------------------------------------------------------------- #


class ArrayDataset:
    """Dataset backed by whole numpy arrays (first axis = samples).

    When a ``StokeDataLoader`` receives one of these, it bypasses the
    per-sample ``__getitem__`` + collate path entirely: each batch is
    assembled by the native thread-pool (`stoke_tpu.native.NativeBatcher`)
    as one GIL-free row-gather per array — the input-pipeline hot path the
    reference delegates to torch's C++ DataLoader workers (SURVEY.md §2.6).

    Args:
        *arrays: equal-length numpy arrays (e.g. images, labels).
    """

    def __init__(self, *arrays: np.ndarray):
        if not arrays:
            raise ValueError("ArrayDataset needs at least one array")
        self.arrays = tuple(np.ascontiguousarray(a) for a in arrays)
        n = len(self.arrays[0])
        if any(len(a) != n for a in self.arrays):
            raise ValueError("all arrays must share the sample axis length")

    def __len__(self):
        return len(self.arrays[0])

    def __getitem__(self, i):
        row = tuple(a[i] for a in self.arrays)
        return row if len(row) > 1 else row[0]


class _NativeLoaderBase:
    """Sampler/shuffle/drop_last machinery shared by the native fast-path
    loaders; subclasses implement ``_assemble(idx)``."""

    def __init__(self, dataset, batch_size: int, shuffle: bool = False,
                 sampler=None, drop_last: bool = False, seed: int = 0,
                 **_unused):
        from stoke_tpu.native import NativeBatcher

        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.sampler = sampler
        self.drop_last = drop_last
        self._epoch_seed = seed
        self._batcher = NativeBatcher()

    def __len__(self):
        n = len(self.sampler) if self.sampler is not None else len(self.dataset)
        return n // self.batch_size if self.drop_last else math.ceil(n / self.batch_size)

    def _assemble(self, idx: np.ndarray):
        raise NotImplementedError

    def __iter__(self):
        if self.sampler is not None:
            order = np.fromiter(iter(self.sampler), np.int64)
        else:
            order = np.arange(len(self.dataset), dtype=np.int64)
            if self.shuffle:
                rng = np.random.default_rng(self._epoch_seed)
                self._epoch_seed += 1
                rng.shuffle(order)
        for start in range(0, len(order), self.batch_size):
            idx = order[start : start + self.batch_size]
            if self.drop_last and len(idx) < self.batch_size:
                break
            yield self._assemble(idx)


class _NativeArrayLoader(_NativeLoaderBase):
    """ArrayDataset fast path: one GIL-free row-gather per array."""

    def _assemble(self, idx):
        batch = tuple(
            self._batcher.gather_rows(a, idx) for a in self.dataset.arrays
        )
        return batch if len(batch) > 1 else batch[0]


class RaggedSequenceDataset:
    """Variable-length token sequences in one contiguous ragged buffer, with
    native batch assembly.

    The BERT/bucketed-sampler pipeline's hot path is "gather sampled
    sequences + pad to the batch max + build the attention mask"; with this
    dataset a ``StokeDataLoader`` does all three in one GIL-free native call
    (``NativeBatcher.gather_pad``).  Pairs naturally with
    ``BucketedDistributedSampler`` (use :meth:`sorted_idx`).

    Args:
        sequences: list of 1-D int token arrays.
        labels: optional per-sequence labels.
        pad_multiple: pad batch max-length up to a multiple (bounds XLA
            recompilation and satisfies flash/ring divisibility).
    """

    def __init__(self, sequences, labels=None, pad_multiple: int = 32):
        self.lengths = np.asarray([len(s) for s in sequences], np.int32)
        self.offsets = np.concatenate(
            [[0], np.cumsum(self.lengths[:-1], dtype=np.int64)]
        ).astype(np.int64)
        self.ragged = (
            np.concatenate([np.asarray(s, np.int32) for s in sequences])
            if len(sequences)
            else np.zeros((0,), np.int32)
        )
        self.labels = None if labels is None else np.asarray(labels)
        self.pad_multiple = int(pad_multiple)

    def __len__(self):
        return len(self.lengths)

    def __getitem__(self, i):
        s = self.ragged[self.offsets[i] : self.offsets[i] + self.lengths[i]]
        return (s, self.labels[i]) if self.labels is not None else s

    def sorted_idx(self):
        """Indices sorted by length — feed to BucketedDistributedSampler."""
        return list(np.argsort(self.lengths, kind="stable"))


class _NativeRaggedLoader(_NativeLoaderBase):
    """RaggedSequenceDataset fast path: native gather+pad+mask in one call,
    yielding ({input_ids, attention_mask}, labels?)."""

    def _assemble(self, idx):
        ds = self.dataset
        ids, mask = self._batcher.gather_pad(
            ds.ragged, ds.offsets, ds.lengths, idx,
            pad_multiple=ds.pad_multiple,
        )
        batch = {"input_ids": ids, "attention_mask": mask}
        if ds.labels is not None:
            return batch, ds.labels[idx]
        return batch


# --------------------------------------------------------------------------- #
# Skew-reactive input rebalancing (ISSUE 14 tentpole c)
# --------------------------------------------------------------------------- #
#
# The fleet monitor (PR 5) can NAME the host whose input pipeline drags the
# pod; this layer is what finally acts on it.  Each global batch ("slice",
# batch_size × num_replicas rows) has a canonical per-host split — host r
# feeds rows [r·B, (r+1)·B) of the canonical order to its devices.  The
# rebalancer moves the READ work instead: host r reads a contiguous
# ``shares[r]``-row range of the canonical slice (shares sum to the slice,
# equal shares ≡ today's behavior: every host reads exactly its own rows
# and no collective runs).  When shares are shifted, the surplus rows ride
# ONE host-side allgather back to their canonical host — so the global
# batch, the per-epoch sample set, and every host's device feed are
# unchanged by construction; only who pays the disk/decode cost moves.
#
# Fleet-wide agreement without extra collectives: share updates are
# computed on the IDENTICAL exchanged fleet matrix on every host (the
# monitor's actuation is deterministic), and take effect at a future fetch
# index no host can have reached yet (yields are lockstep across SPMD
# hosts; fetches lead yields by at most the prefetch depth, so
# ``yields + apply_slack`` with slack > prefetch is a safe apply point).


class InputRebalancer:
    """Per-host read-share state + the deterministic apply protocol.

    ``shares[r]`` is how many rows of each canonical slice host ``r``
    reads; all hosts hold identical copies and evolve them identically.
    ``propose_shift`` (called by the fleet monitor at straggler-streak
    boundaries) schedules a bounded share move that becomes effective at a
    fetch index strictly ahead of every host's loader; the loader calls
    ``shares_for_fetch`` once per batch fetch and ``note_yield`` once per
    delivered batch.
    """

    def __init__(
        self,
        n_hosts: int,
        rank: int,
        batch_size: int,
        max_frac: float = 0.25,
        apply_slack: int = 4,
    ):
        if not (0 <= rank < max(n_hosts, 1)):
            raise ValueError(
                f"Stoke -- rebalancer rank {rank} out of range for "
                f"{n_hosts} hosts"
            )
        self.n_hosts = max(int(n_hosts), 1)
        self.rank = int(rank)
        self.batch_size = int(batch_size)
        #: hard bound: no host's share may leave
        #: [batch - max_shift, batch + max_shift]
        self.max_shift = int(float(max_frac) * self.batch_size)
        if self.max_shift < 1:
            # a bound that truncated to zero is a permanently-dead
            # actuator — the silently-ignored-knob anti-pattern the status
            # rules exist to prevent; loud, never a silent no-op
            raise ValueError(
                f"Stoke -- rebalance_max_frac={max_frac} of per-host "
                f"batch {self.batch_size} rounds to a zero-row share "
                f"bound; the actuator could never move work. Raise "
                f"rebalance_max_frac or the per-host batch, or drop "
                f"rebalance"
            )
        self.apply_slack = max(int(apply_slack), 1)
        self.shares: List[int] = [self.batch_size] * self.n_hosts
        self._pending: List[Any] = []  # (effective_fetch, shares) FIFO
        self._fetches = 0
        self._yields = 0
        self.shifts = 0
        self.rows_moved = 0

    def share_of(self, host: int) -> int:
        """The latest scheduled share of ``host`` (pending updates
        included — the value gauges/JSONL report)."""
        target = self._pending[-1][1] if self._pending else self.shares
        return int(target[host])

    @property
    def shifted(self) -> bool:
        target = self._pending[-1][1] if self._pending else self.shares
        return len(set(target)) > 1

    def note_yield(self) -> None:
        """One batch delivered to the training loop (lockstep across
        hosts — the apply-point anchor)."""
        self._yields += 1

    def propose_shift(self, from_host: int, to_host: int, rows: int) -> int:
        """Schedule moving ``rows`` of read work ``from_host → to_host``,
        clamped to the per-host bound; returns the rows actually moved
        (0 when the bound already binds).  Deterministic given identical
        call sequences — the fleet-wide agreement contract."""
        if from_host == to_host or rows <= 0:
            return 0
        base = list(self._pending[-1][1]) if self._pending else list(
            self.shares
        )
        lo = self.batch_size - self.max_shift
        hi = self.batch_size + self.max_shift
        rows = int(min(rows, base[from_host] - lo, hi - base[to_host]))
        if rows <= 0:
            return 0
        base[from_host] -= rows
        base[to_host] += rows
        eff = self._yields + self.apply_slack
        if self._pending:
            eff = max(eff, self._pending[-1][0])
        self._pending.append((eff, base))
        self.shifts += 1
        self.rows_moved += rows
        return rows

    def shares_for_fetch(self) -> List[int]:
        """The share vector governing the NEXT fetched batch; advances the
        fetch counter and applies any update whose effective index has
        arrived.  Every host calls this once per batch in the same order,
        so fetch ``f`` sees the same shares fleet-wide."""
        f = self._fetches
        self._fetches += 1
        while self._pending and self._pending[0][0] <= f:
            self.shares = list(self._pending.pop(0)[1])
        return list(self.shares)


def _tree_map_arrays(fn, tree):
    """Map ``fn`` over the array leaves of a batch pytree (jax's tree_map,
    imported lazily — this module stays importable without touching a
    backend; covers every container collate functions produce)."""
    import jax

    return jax.tree_util.tree_map(fn, tree)


def _pad_rows(tree, n: int):
    """Zero-pad every leaf's leading (row) axis to exactly ``n`` — the
    fixed-shape payload the exchange collective needs."""

    def leaf(x):
        x = np.asarray(x)
        if x.shape[0] == n:
            return x
        pad = np.zeros((n - x.shape[0],) + x.shape[1:], x.dtype)
        return np.concatenate([x, pad], axis=0)

    return _tree_map_arrays(leaf, tree)


def _default_allgather(tree):
    """Cross-host exchange of the padded read payload: every leaf gains a
    leading ``[n_hosts]`` axis.  Only invoked while shares are actually
    shifted — a balanced fleet reads its own rows and never collects."""
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(tree)
    return _tree_map_arrays(np.asarray, gathered)


def reassemble_from_gathered(gathered, shares, rank: int, batch_size: int):
    """Pick this host's canonical batch rows out of the gathered per-host
    read payloads.  Canonical row ``j`` was read by the host whose share
    range covers ``j``; the math is pure so the mp harness and the
    simulated-host unit tests exercise the SAME code."""
    cuts = np.concatenate([[0], np.cumsum(np.asarray(shares, np.int64))])
    j = np.arange(rank * batch_size, (rank + 1) * batch_size)
    host_of = np.searchsorted(cuts, j, side="right") - 1
    off = j - cuts[host_of]

    def leaf(x):
        x = np.asarray(x)  # [n_hosts, slice_size, ...]
        return x[host_of, off]

    return _tree_map_arrays(leaf, gathered)


def assemble_rebalanced_batch(
    per_replica, shares, rank: int, batch_size: int, assemble, allgather=None
):
    """One rebalanced batch: read this host's share of the canonical
    slice, exchange only when shares are shifted, return this host's
    canonical batch.  ``per_replica`` is the sampler's per-host index plan
    for one batch (``BucketedDistributedSampler.global_batches()`` entry);
    ``assemble(idx)`` reads + collates rows; ``allgather`` is injectable
    so single-process tests can simulate a fleet."""
    canonical = [i for sub in per_replica for i in sub]
    cuts = np.concatenate([[0], np.cumsum(np.asarray(shares, np.int64))])
    if int(cuts[-1]) != len(canonical):
        raise ValueError(
            f"Stoke -- rebalance shares {list(shares)} do not cover the "
            f"slice ({len(canonical)} rows)"
        )
    mine = canonical[int(cuts[rank]):int(cuts[rank + 1])]
    rows = assemble(mine)
    if max(shares) == min(shares):
        # balanced: this host read exactly its canonical batch — no
        # exchange, byte-identical to the non-rebalanced loader's output
        return rows
    # pad to the LARGEST share, not the whole slice: shares are identical
    # fleet-wide (the deterministic agreement protocol), so max(shares)
    # is a valid uniform collective shape at a fraction of the bytes —
    # reassembly only ever indexes off < shares[host]
    payload = _pad_rows(rows, int(max(shares)))
    gather = allgather if allgather is not None else _default_allgather
    return reassemble_from_gathered(
        gather(payload), shares, rank, batch_size
    )


class _RebalancedLoader:
    """Inner loader for the rebalanced read path: walks the sampler's
    GLOBAL batch plan, reads this host's share of each slice, and yields
    this host's canonical (host-side) batches.  Wrapped by
    :class:`StokeDataLoader` like any other inner loader, so placement,
    telemetry wait accounting, and prefetch are unchanged."""

    def __init__(
        self,
        dataset,
        sampler,
        batch_size: int,
        rebalancer: InputRebalancer,
        collate_fn=None,
        allgather=None,
    ):
        self.dataset = dataset
        self.sampler = sampler
        self.batch_size = int(batch_size)
        self.rebalancer = rebalancer
        self._collate = collate_fn or _default_collate
        self._allgather = allgather
        self._batcher = None
        if isinstance(dataset, ArrayDataset):
            from stoke_tpu.native import NativeBatcher

            self._batcher = NativeBatcher()

    def __len__(self):
        return len(self.sampler) // self.batch_size

    def _assemble(self, idx):
        if self._batcher is not None:
            gathered = np.asarray(idx, np.int64)
            batch = tuple(
                self._batcher.gather_rows(a, gathered)
                for a in self.dataset.arrays
            )
            return batch if len(batch) > 1 else batch[0]
        return self._collate([self.dataset[int(i)] for i in idx])

    def __iter__(self):
        rb = self.rebalancer
        for per_replica in self.sampler.global_batches():
            shares = rb.shares_for_fetch()
            yield assemble_rebalanced_batch(
                per_replica,
                shares,
                rb.rank,
                self.batch_size,
                self._assemble,
                self._allgather,
            )


# --------------------------------------------------------------------------- #
# Loader
# --------------------------------------------------------------------------- #


def _default_collate(samples: List[Any]):
    """Minimal numpy collate for the torch-free fallback path: stacks arrays
    (and array-likes) leaf-wise over tuples/lists/dicts."""
    first = samples[0]
    if isinstance(first, (tuple, list)):
        return type(first)(_default_collate(list(s)) for s in zip(*samples))
    if isinstance(first, dict):
        return {k: _default_collate([s[k] for s in samples]) for k in first}
    return np.stack([np.asarray(s) for s in samples])


class _FallbackLoader:
    """Dependency-free map-style loader used when torch is not importable.
    Supports batch_size/shuffle/sampler/drop_last/collate_fn, plus a
    thread-pool parallel path for ``num_workers > 0`` (the reference
    inherits torch's C++ multi-worker loader, SURVEY.md §2.6 #24; a
    torch-free image otherwise has no parallel path for generic map-style
    datasets).

    Threads, not processes: dataset ``__getitem__`` for real workloads is
    IO/decode/numpy-bound (all GIL-releasing), batches need no pickling,
    and the in-repo native batcher already covers the pure-indexing
    ``ArrayDataset``/``RaggedSequenceDataset`` cases where threads would
    not help.  ``num_workers * prefetch_factor`` batches are assembled
    ahead, yielded strictly in order.

    THREAD-SAFETY CONTRACT (differs from torch!): torch's ``num_workers``
    forks per-worker dataset copies, so a dataset holding shared mutable
    state (e.g. one open file handle it seeks) is safe there but NOT here —
    ``__getitem__`` is called concurrently on the ONE shared dataset
    object.  Keep ``__getitem__`` stateless (open file handles per call,
    or guard shared state with a lock), or use ``num_workers=0``.
    """

    def __init__(
        self,
        dataset,
        batch_size: int = 1,
        shuffle: bool = False,
        sampler: Optional[Sequence[int]] = None,
        drop_last: bool = False,
        collate_fn: Optional[Callable] = None,
        seed: int = 0,
        num_workers: int = 0,
        prefetch_factor: int = 2,
        **_unused,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.sampler = sampler
        self.drop_last = drop_last
        self.collate_fn = collate_fn or _default_collate
        self._epoch_seed = seed
        self.num_workers = max(0, int(num_workers))
        self.prefetch_factor = max(1, int(prefetch_factor))

    def __len__(self):
        n = len(self.sampler) if self.sampler is not None else len(self.dataset)
        return n // self.batch_size if self.drop_last else math.ceil(n / self.batch_size)

    def _batch_indices(self):
        if self.sampler is not None:
            order = list(iter(self.sampler))
        else:
            order = list(range(len(self.dataset)))
            if self.shuffle:
                rng = np.random.default_rng(self._epoch_seed)
                self._epoch_seed += 1
                rng.shuffle(order)
        for start in range(0, len(order), self.batch_size):
            idx = order[start : start + self.batch_size]
            if self.drop_last and len(idx) < self.batch_size:
                break
            yield idx

    def _assemble(self, idx):
        return self.collate_fn([self.dataset[i] for i in idx])

    def __iter__(self):
        if self.num_workers <= 0:
            for idx in self._batch_indices():
                yield self._assemble(idx)
            return
        # num_workers > 0 without torch: __getitem__ now runs CONCURRENTLY
        # on the one shared dataset object (torch would fork per-worker
        # copies).  Surface the semantic change once so a dataset with
        # shared mutable state (e.g. a seeked file handle) isn't silently
        # raced.
        global _WARNED_THREADED
        if not _WARNED_THREADED:
            _WARNED_THREADED = True
            warnings.warn(
                "torch-free fallback loader: num_workers>0 uses a THREAD "
                "pool over the shared dataset object; __getitem__ must be "
                "thread-safe (pass num_workers=0 for the sequential path)",
                stacklevel=2,
            )
        from concurrent.futures import ThreadPoolExecutor

        window = self.num_workers * self.prefetch_factor
        with ThreadPoolExecutor(
            max_workers=self.num_workers,
            thread_name_prefix="stoke-loader",
        ) as pool:
            pending: deque = deque()
            batches = self._batch_indices()
            try:
                for idx in batches:
                    pending.append(pool.submit(self._assemble, idx))
                    if len(pending) >= window:
                        yield pending.popleft().result()
                while pending:
                    yield pending.popleft().result()
            finally:
                # a consumer abandoning the iterator mid-epoch must not
                # leave workers assembling unwanted batches
                for f in pending:
                    f.cancel()


class StokeDataLoader:
    """Loader facade yielding device-resident, mesh-sharded batches.

    Built via ``Stoke.DataLoader`` (reference stoke.py:737-851), which injects
    ``batch_size`` (per-process) and ``place_fn`` (host batch → sharded device
    arrays) from the validated status — preserving the reference paradigm that
    "the flags only need to be set and never handled" (data.py:44-47).

    Accepts the torch DataLoader surface (num_workers, pin_memory is ignored,
    sampler, collate_fn, ...) and falls back to a dependency-free loader when
    torch is absent (``num_workers > 0`` then means a THREAD pool over the
    one shared dataset object — see the ``_FallbackLoader`` thread-safety
    contract — rather than torch's per-worker process copies).

    Args:
        prefetch: number of batches to keep in flight on device (default 2 =
            double buffering).  Transfers are async dispatches; lookahead
            overlaps host→HBM copy with device compute.
        place: set False to get host batches (escape hatch).
        telemetry: optional ``stoke_tpu.telemetry.Telemetry`` — the loader
            then accounts host-loader wait time (``data/loader_wait_s``)
            and post-warmup starvation (``data/starvation_s``: time the
            training loop sat blocked on ``next()`` after the prefetch
            window was primed — the input-pipeline-bound signal) into its
            registry.  Wired automatically by ``Stoke.DataLoader``.
    """

    def __init__(
        self,
        dataset,
        batch_size: int,
        place_fn: Optional[Callable] = None,
        prefetch: int = 2,
        place: bool = True,
        telemetry=None,
        rebalancer: Optional[InputRebalancer] = None,
        rebalance_allgather=None,
        **kwargs,
    ):
        self._place_fn = place_fn if place else None
        self._prefetch = max(int(prefetch), 1)
        self._telemetry = telemetry
        self.batch_size = batch_size
        self._rebalancer = rebalancer
        if rebalancer is not None:
            # skew-reactive read rebalancing (ISSUE 14): needs the GLOBAL
            # batch plan, so the sampler must expose it
            sampler = kwargs.get("sampler")
            if sampler is None or not hasattr(sampler, "global_batches"):
                raise ValueError(
                    "Stoke -- input rebalancing (FleetConfig.rebalance) "
                    "requires a sampler exposing global_batches() — use "
                    "BucketedDistributedSampler (or drop rebalance)"
                )
            unconsumed = set(kwargs) - {"sampler", "collate_fn"}
            if unconsumed:
                # the rebalanced read path assembles rows itself — a
                # num_workers/drop_last/... silently ignored here would
                # change read semantics without a diagnostic (the
                # silently-ignored-knob anti-pattern)
                raise ValueError(
                    f"Stoke -- the rebalanced loader path consumes only "
                    f"sampler/collate_fn; {sorted(unconsumed)} would be "
                    f"silently ignored — drop them or turn off "
                    f"FleetConfig.rebalance"
                )
            self._loader = _RebalancedLoader(
                dataset,
                sampler,
                batch_size,
                rebalancer,
                collate_fn=kwargs.get("collate_fn"),
                allgather=rebalance_allgather,
            )
            return
        if isinstance(dataset, ArrayDataset):
            # native fast path: one GIL-free row-gather per array per batch
            self._loader = _NativeArrayLoader(dataset, batch_size=batch_size, **kwargs)
            return
        if isinstance(dataset, RaggedSequenceDataset):
            # native ragged fast path: gather + pad + mask in one call
            self._loader = _NativeRaggedLoader(dataset, batch_size=batch_size, **kwargs)
            return
        try:
            from torch.utils import data as torch_data

            if "collate_fn" not in kwargs:
                kwargs["collate_fn"] = _numpy_safe_torch_collate()
            if kwargs.get("num_workers", 0) > 0 and (
                "multiprocessing_context" not in kwargs
            ):
                # fork()ing a JAX process (multithreaded) can deadlock the
                # workers; default to forkserver, the same fix the reference
                # applies for horovod (stoke.py:809-820)
                import multiprocessing

                if "forkserver" in multiprocessing.get_all_start_methods():
                    kwargs["multiprocessing_context"] = "forkserver"
            self._loader = torch_data.DataLoader(
                dataset, batch_size=batch_size, **kwargs
            )
        except ImportError:
            self._loader = _FallbackLoader(dataset, batch_size=batch_size, **kwargs)

    def __len__(self):
        return len(self._loader)

    @property
    def sampler(self):
        return getattr(self._loader, "sampler", None)

    def set_epoch(self, epoch: int) -> None:
        """Forward to a distributed sampler when present (reference users call
        ``loader.sampler.set_epoch`` directly; this is a convenience)."""
        s = self.sampler
        if s is not None and hasattr(s, "set_epoch"):
            s.set_epoch(epoch)

    def _next_timed(self, it, wait_counter, starve_counter=None):
        """``next(it)`` with host-loader wait accounting: all wait lands in
        ``data/loader_wait_s``; post-warmup wait additionally counts as
        starvation (the device had nothing prefetched to hide it behind)."""
        import time

        t0 = time.perf_counter()
        try:
            return next(it)
        finally:
            dt = time.perf_counter() - t0
            wait_counter.inc(dt)
            if starve_counter is not None:
                starve_counter.inc(dt)

    def __iter__(self):
        if self._telemetry is None:
            yield from self._iter_batches()
            return
        reg = self._telemetry.registry
        wait = reg.counter(
            "data/loader_wait_s",
            help="host seconds blocked on the host-side loader",
        )
        starve = reg.counter(
            "data/starvation_s",
            help="post-warmup loader wait (device-starving portion)",
        )
        yield from self._iter_batches(wait, starve)

    def _iter_batches(self, wait_counter=None, starve_counter=None):
        from stoke_tpu.telemetry.tracing import trace_span

        def fetch(it, warm: bool):
            with trace_span("stoke/io", track="data"):
                if wait_counter is None:
                    return next(it)
                return self._next_timed(
                    it, wait_counter, starve_counter if warm else None
                )

        if self._place_fn is None:
            it = iter(self._loader)
            warm = False
            while True:
                try:
                    batch = fetch(it, warm)
                except StopIteration:
                    return
                warm = True
                self._note_yield()
                yield batch
            return
        # lookahead pipeline: keep `prefetch` placed batches in flight
        queue: List[Any] = []
        it = iter(self._loader)
        try:
            for _ in range(self._prefetch):
                queue.append(self._place_fn(fetch(it, warm=False)))
        except StopIteration:
            pass
        while queue:
            out = queue.pop(0)
            try:
                queue.append(self._place_fn(fetch(it, warm=True)))
            except StopIteration:
                pass
            self._note_yield()
            yield out

    def _note_yield(self) -> None:
        # rebalancer apply-point anchor (ISSUE 14): delivered-batch counts
        # are lockstep across SPMD hosts, unlike fetch counts, which lead
        # by up to the prefetch depth
        if self._rebalancer is not None:
            self._rebalancer.note_yield()


class _NumpySafeTorchCollate:
    """torch's default collate, post-converted to numpy so downstream device
    placement never touches torch dtypes XLA can't ingest (bf16 etc. stay on
    the JAX side of the fence).  A module-level class so multiprocessing
    workers (forkserver/spawn) can pickle it."""

    @staticmethod
    def _to_np(x):
        if hasattr(x, "detach"):
            return x.detach().cpu().numpy()
        return x

    def __call__(self, samples):
        from torch.utils.data._utils.collate import default_collate

        batch = default_collate(samples)
        if isinstance(batch, (tuple, list)):
            return type(batch)(self._to_np(b) for b in batch)
        if isinstance(batch, dict):
            return {k: self._to_np(v) for k, v in batch.items()}
        return self._to_np(batch)


def _numpy_safe_torch_collate():
    return _NumpySafeTorchCollate()


# --------------------------------------------------------------------------- #
# Bucketed distributed sampler (reference data.py:111-516)
# --------------------------------------------------------------------------- #


class BucketedDistributedSampler:
    """Distributed sampler drawing each batch from one similar-length bucket.

    Semantics mirror the reference (stoke/data.py:111-516): the caller
    provides ``sorted_idx`` — dataset indices pre-sorted by the bucketing
    characteristic (e.g. sequence length).  The index list is split into
    ``buckets`` contiguous buckets; every epoch each bucket is shuffled
    internally (seeded by ``seed + epoch``), carved into *slices* of
    ``batch_size × num_replicas``, and each replica takes a strided
    (``rank::num_replicas``) sub-batch of every slice, so all replicas see
    equal-size, similar-length batches.  Short final slices are padded by
    borrowing stride-aligned indices from the bucket head (reference
    data.py:450-498); with ``drop_last + allow_bucket_overlap`` the dropped
    residuals are regrouped into extra (mixed-length) batches
    (reference data.py:419-434).  Batch order is then shuffled across buckets
    so consecutive batches don't walk monotonically through lengths.

    Invariants (property-tested in tests/test_data.py, mirroring the asserts
    at reference data.py:409 and :447):
      * every yielded epoch has exactly ``len(self)`` indices;
      * each padded bucket expands to exactly
        ``num_slices_per_bucket × slice_size`` indices;
      * the union of all replicas' indices per slice is the slice itself.

    Args:
        dataset: sized dataset (only ``len`` is used).
        buckets: number of contiguous buckets.
        batch_size: per-replica batch size (for this framework: the
            *per-process* batch — batch_size_per_device × local mesh share).
        sorted_idx: dataset indices sorted by the bucketing key.
        num_replicas: loading processes (default ``jax.process_count()``).
        rank: this process (default ``jax.process_index()``).
        allow_bucket_overlap / shuffle / seed / drop_last / info_rank: as in
            the reference.
    """

    def __init__(
        self,
        dataset,
        buckets: int,
        batch_size: int,
        sorted_idx: Sequence[int],
        allow_bucket_overlap: bool = False,
        num_replicas: Optional[int] = None,
        rank: Optional[int] = None,
        shuffle: bool = True,
        seed: int = 0,
        drop_last: bool = False,
        info_rank: int = 0,
        backend: Any = None,  # parity arg; topology comes from JAX, not enums
    ):
        if num_replicas is None or rank is None:
            import jax

            num_replicas = num_replicas if num_replicas is not None else jax.process_count()
            rank = rank if rank is not None else jax.process_index()
        if not (0 <= rank < num_replicas):
            raise ValueError(
                f"Stoke -- sampler rank {rank} out of range for {num_replicas} replicas"
            )
        self.num_replicas = int(num_replicas)
        self.rank = int(rank)
        self.epoch = 0
        self.drop_last = drop_last
        self.shuffle = shuffle
        self.seed = seed
        self.buckets = int(buckets)
        self.batch_size = int(batch_size)
        self.sorted_idx = list(sorted_idx)
        self.allow_bucket_overlap = allow_bucket_overlap

        self.slice_size = self.batch_size * self.num_replicas
        n = len(dataset)
        self.num_samples_per_bucket = self._split_size(n, self.buckets, drop_last)
        self.num_slices_per_bucket = self._split_size(
            self.num_samples_per_bucket, self.slice_size, drop_last
        )
        # sanity gates mirroring reference data.py:228-243
        if self.num_samples_per_bucket < self.slice_size:
            raise ValueError(
                f"Stoke -- samples per bucket ({self.num_samples_per_bucket}) is "
                f"smaller than one slice (batch × replicas = {self.slice_size})"
            )
        if self.num_slices_per_bucket < 2:
            raise ValueError(
                f"Stoke -- only {self.num_slices_per_bucket} slice(s) per bucket; "
                f"need >= 2 (use fewer buckets or a smaller batch)"
            )
        if self.num_samples_per_bucket < 100:
            raise ValueError(
                f"Stoke -- {self.num_samples_per_bucket} samples per bucket < 100 "
                f"would drop excessive data (use fewer buckets)"
            )
        self.bucket_idx = [
            list(chunk) for chunk in np.array_split(np.asarray(self.sorted_idx), self.buckets)
        ]
        self.rounded_num_samples_per_bucket = (
            self.num_slices_per_bucket * self.slice_size
        )
        self.rounded_num_samples_per_replica = (
            self.num_slices_per_bucket * self.batch_size * self.buckets
        )
        if self.allow_bucket_overlap:
            residual = n - self.rounded_num_samples_per_bucket * self.buckets
            self.rounded_num_samples_per_replica += (
                residual // self.slice_size
            ) * self.batch_size
        if self.rank == info_rank:
            print(
                f"Stoke -- BucketedDistributedSampler -- samples/bucket: "
                f"{self.rounded_num_samples_per_bucket}, samples/replica: "
                f"{self.rounded_num_samples_per_replica}"
            )

    @staticmethod
    def _split_size(total: int, parts: int, drop_last: bool) -> int:
        return total // parts if drop_last else math.ceil(total / parts)

    # ------------------------------------------------------------------ #

    def _pad_bucket(self, bucket: List[int]) -> List[int]:
        """Extend a short bucket to exactly ``num_slices × slice_size``
        entries so the strided replica slicing stays aligned (reference
        ``_handle_padding``, data.py:450-498).

        The final (short) slice is padded by borrowing indices from the
        bucket head with stride ``num_replicas``, interleaved so that each
        replica's strided sub-batch reaches exactly ``batch_size``.
        """
        full = (self.num_slices_per_bucket - 1) * self.slice_size
        head, short = bucket[:full], bucket[full:]
        # how many each replica is short: replica r owns positions
        # r, r+num_replicas, ... of the slice
        per_replica = [
            len(short[r :: self.num_replicas]) for r in range(self.num_replicas)
        ]
        need = [self.batch_size - c for c in per_replica]
        # borrow stride-aligned values from the bucket head for each replica
        donors = [
            bucket[r : self.num_replicas * need[r] : self.num_replicas]
            for r in range(self.num_replicas)
        ]
        # if replicas need unequal amounts, rotate so the longest-need replica
        # leads and the interleave stays stride-consistent
        if len(set(need)) > 1:
            lead = need.index(max(need))
            donors = donors[lead:] + donors[:lead]
        pad = [
            v
            for v in itertools.chain(*itertools.zip_longest(*donors))
            if v is not None
        ]
        return head + short + pad

    def _epoch_rng(self) -> np.random.Generator:
        return np.random.default_rng(self.seed + self.epoch)

    def _epoch_slices(self) -> List[List[int]]:
        """This epoch's global slices (``slice_size`` indices each), in
        final yielded order — the rng call sequence (per-bucket shuffles,
        then the cross-bucket batch-order shuffle) is byte-identical to
        the pre-refactor ``__iter__``, so per-epoch streams are unchanged.
        Shared by ``__iter__`` (this replica's strided sub-batches) and
        ``global_batches`` (every replica's — the rebalanced read path)."""
        rng = self._epoch_rng()
        if self.shuffle:
            buckets = [list(np.asarray(b)[rng.permutation(len(b))]) for b in self.bucket_idx]
        else:
            buckets = [list(b) for b in self.bucket_idx]
        # pad any bucket that cannot fill its slices
        for i, b in enumerate(buckets):
            if len(b) < self.rounded_num_samples_per_bucket:
                padded = self._pad_bucket(b)
                assert len(padded) == self.rounded_num_samples_per_bucket
                buckets[i] = padded
        # carve into slices
        slices: List[List[int]] = []
        for b in buckets:
            for s in range(self.num_slices_per_bucket):
                slices.append(b[s * self.slice_size : (s + 1) * self.slice_size])
        # regroup dropped residuals into extra mixed slices
        if self.drop_last and self.allow_bucket_overlap:
            residual = list(
                itertools.chain(
                    *[b[self.rounded_num_samples_per_bucket :] for b in buckets]
                )
            )
            for s in range(len(residual) // self.slice_size):
                slices.append(residual[s * self.slice_size : (s + 1) * self.slice_size])
        if self.shuffle:
            order = rng.permutation(len(slices))
            slices = [slices[i] for i in order]
        return slices

    def _replica_batch(self, sl: List[int], rank: int) -> List[int]:
        return sl[rank : self.slice_size : self.num_replicas]

    def global_batches(self) -> List[List[List[int]]]:
        """EVERY replica's read plan for this epoch (ISSUE 14, the
        rebalanced loader's input): one entry per yielded batch, each a
        ``num_replicas``-list of canonical per-replica index lists.  Entry
        ``b[rank]`` equals batch ``b`` of this epoch's ``__iter__``
        stream for that rank — all replicas derive the identical plan."""
        return [
            [self._replica_batch(sl, r) for r in range(self.num_replicas)]
            for sl in self._epoch_slices()
        ]

    def __iter__(self) -> Iterator[int]:
        batches = [
            self._replica_batch(sl, self.rank) for sl in self._epoch_slices()
        ]
        flat = [int(i) for i in itertools.chain(*batches)]
        assert len(flat) == self.rounded_num_samples_per_replica
        return iter(flat)

    def __len__(self) -> int:
        return self.rounded_num_samples_per_replica

    def set_epoch(self, epoch: int) -> None:
        """Per-epoch reseed so replicas reshuffle consistently (reference
        data.py:503-516)."""
        self.epoch = epoch
