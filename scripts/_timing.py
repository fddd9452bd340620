"""Shared delta-timing rig for the on-TPU measurement scripts.

(t(2n) - t(n)) / n cancels the fixed host sync overhead every fetch
carries.  Two rules this module enforces that
hand-rolled copies kept getting wrong:

- BLOCK after warmup (async dispatch otherwise bleeds queued warmup
  executions into the first timed segment);
- sync on a SCALAR element, not the full output (np.asarray on a jax
  array fetches the whole buffer — 128 MB for an 8k x 8k bf16 matmul).
"""

from __future__ import annotations

import time


def sync(out) -> None:
    """Force completion of ``out`` by fetching one scalar element."""
    import jax
    import numpy as np

    leaf = jax.tree_util.tree_leaves(out)[0]
    idx = (0,) * getattr(leaf, "ndim", 0)
    np.asarray(leaf[idx] if idx else leaf)


def delta_time(fn, reps: int) -> float:
    """Per-call seconds of ``fn()`` via delta timing (compile + warm first)."""
    fn()          # compile
    sync(fn())    # warm, and drain the queue before t0
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn()
    sync(out)
    t1 = time.perf_counter()
    for _ in range(2 * reps):
        out = fn()
    sync(out)
    return max((time.perf_counter() - t1) - (t1 - t0), 1e-9) / reps
