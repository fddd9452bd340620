"""Per-layer numerics observatory (ISSUE 12 tentpole): module sentinels,
NaN provenance, and quantization-error attribution.

Every observability layer so far reports whole-model aggregates — one
global grad norm, one nonfinite-leaf count — so when a run diverges or an
int8 path distorts quality the framework can say *that* something broke
but never *where*.  EQuARX (arXiv:2506.17615) shows quantized-collective
error is strongly layer-dependent, and the Gemma-on-TPU comparison
(arXiv:2605.25645) treats per-layer quality attribution as table stakes
for serving quantized checkpoints.  Three signal families, one shared
grouping:

1. **Per-layer gradient/param/update stats** — the grads pytree is
   already layer-structured; :func:`module_groups` prefix-groups the
   flattened leaves by top-level module and :func:`compute_group_stats`
   packs raw sums (grad sum-of-squares / absmax / nonfinite-element
   count, param and update sum-of-squares) into one fixed-layout
   ``[n_groups, n_stats]`` f32 array *inside* the already-compiled step
   program (the PR-3 sentinel discipline: zero extra dispatches; the
   matrix is fetched with the existing sentinel row).  Raw sums — not
   rms — ride the wire so the per-group rows recombine EXACTLY to the
   global grad-norm sentinel (``norm² = Σ_g grad_sumsq_g``), which the
   acceptance test pins against silent leaf drops.
2. **NaN/Inf provenance** — the first offending group index + field is
   derived host-side from the fetched matrix and surfaced through the
   health detector registry (``numerics_provenance``:
   record/warn/dump/halt), the JSONL block, and flight-recorder bundles
   (``numerics.json``).
3. **Quantization-error attribution** — per-layer wire error for the
   PR-8 sharded transport (per-bucket error-feedback residual norms
   mapped back to module groups through the bucket layout) and per-layer
   dequant error for PR-9 ``QuantizedTensor`` serving weights (int8 vs
   source absmax / relative rms, computed once at quantize time).

Everything is default-OFF behind ``NumericsConfig``; without it the
compiled step programs are bit-identical and no ``numerics/*`` field or
gauge exists anywhere.
"""

from __future__ import annotations

import warnings
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from stoke_tpu.telemetry.health import Detector, _RunningStats

#: group-stats matrix column layout: stat name -> index.  This is a wire
#: format (the packed [n_groups, n_stats] array the compiled step
#: returns); never reorder, only append.  Raw sums ride the wire — the
#: host derives rms from them (``rms = sqrt(sumsq / n)``) so per-group
#: rows recombine exactly to the global norms.
NUMERICS_STATS = (
    "grad_sumsq",      # Σ g² over the group's gradient elements (f32)
    "grad_absmax",     # max |g| over the group
    "grad_nonfinite",  # count of non-finite gradient ELEMENTS in the group
    "param_sumsq",     # Σ p² over the group's UPDATED parameters
    "update_sumsq",    # Σ (p_new - p_old)² over the group
)
NUMERICS_INDEX = {name: i for i, name in enumerate(NUMERICS_STATS)}
N_NUMERICS_STATS = len(NUMERICS_STATS)

#: per-group stats the JSONL block / gauges / summary expose (host-derived
#: from the wire sums; ``wire_err`` joins when the transport residual is
#: observed, ``quant_err`` when serving weights were quantized)
GROUP_REPORT_FIELDS = (
    "grad_rms", "grad_absmax", "nonfinite", "param_rms", "update_rms",
)

#: warnings the monitor emits itself (no HealthConfig to route through)
#: before degrading to record-only — the fleet-monitor discipline
_MAX_PROVENANCE_WARNINGS = 5

#: provenance events retained for the summary / numerics.json
_RECENT_PROVENANCE_MAX = 64


class ModuleGroup(NamedTuple):
    """One top-level module of the param tree: its name, the indices of
    its leaves in ``jax.tree_util.tree_flatten`` order, and each leaf's
    element count.  The leaf-index list against the FLATTENED tree is the
    contract that keeps the traced packing (:func:`compute_group_stats`)
    and every host-side consumer grouping identically."""

    name: str
    leaf_indices: Tuple[int, ...]
    leaf_elems: Tuple[int, ...]

    @property
    def n_elems(self) -> int:
        return int(sum(self.leaf_elems))


def _key_str(entry) -> str:
    """Render one tree-path entry (DictKey/SequenceKey/GetAttrKey/...) to
    a stable string."""
    for attr in ("key", "idx", "name"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def _sanitize(name: str) -> str:
    """Group names become gauge-name segments and JSONL keys — keep them
    to a conservative charset."""
    return "".join(c if (c.isalnum() or c in "_-.") else "_" for c in name)


def module_groups(tree: Any) -> List[ModuleGroup]:
    """Prefix-group a param-shaped pytree's leaves by top-level module.

    The group of a leaf is the FIRST entry of its tree path (flax:
    the top-level module name, e.g. ``layer_0`` / ``conv_init`` /
    ``lm_head``); a bare-leaf tree groups as ``params``.  Groups are
    ordered by first appearance in flatten order, so the resulting
    index ↔ name mapping is deterministic for a given tree structure —
    the wire-format stability the drift-guard tests pin across
    GPT/ResNet/MoE trees.
    """
    import jax

    leaves_with_path = jax.tree_util.tree_flatten_with_path(tree)[0]
    order: List[str] = []
    members: Dict[str, List[int]] = {}
    elems: Dict[str, List[int]] = {}
    for i, (path, leaf) in enumerate(leaves_with_path):
        name = _sanitize(_key_str(path[0])) if path else "params"
        if name not in members:
            order.append(name)
            members[name] = []
            elems[name] = []
        members[name].append(i)
        n = int(np.prod(leaf.shape)) if getattr(leaf, "shape", ()) else 1
        elems[name].append(n)
    return [
        ModuleGroup(name, tuple(members[name]), tuple(elems[name]))
        for name in order
    ]


def leaf_path_names(tree: Any) -> List[str]:
    """``"a/b/c"``-style path string per flattened leaf — the lookup the
    :class:`~stoke_tpu.telemetry.health.NonFiniteDetector` uses to name
    the first offending gradient leaf in its anomaly."""
    import jax

    leaves_with_path = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [
        "/".join(_key_str(e) for e in path) if path else "params"
        for path, _ in leaves_with_path
    ]


# --------------------------------------------------------------------------- #
# traced packing (called inside the engine's compiled apply)
# --------------------------------------------------------------------------- #


def compute_group_stats(grads: Any, new_params: Any, old_params: Any):
    """Pack the per-group diagnostics matrix — TRACED inside the engine's
    apply core, so every value is a fused reduction in the existing XLA
    program (zero extra dispatches; the tiny ``[n_groups, n_stats]``
    output is fetched alongside the sentinel row).

    ``grads`` are the unscaled post-transport, pre-clip gradients (same
    tap point as the sentinel grad norm, so the recombination identity
    ``grad_norm² == Σ_g grad_sumsq_g`` holds exactly); ``new_params`` /
    ``old_params`` the parameter trees after/before the update.  All
    three share the params treedef, so one :func:`module_groups` plan
    (static, host-side) indexes all of them.
    """
    import jax
    import jax.numpy as jnp

    groups = module_groups(grads)
    g_leaves = jax.tree_util.tree_leaves(grads)
    new_leaves = jax.tree_util.tree_leaves(new_params)
    old_leaves = jax.tree_util.tree_leaves(old_params)

    def _f32(leaf):
        return jnp.asarray(leaf, jnp.float32)

    rows = []
    for group in groups:
        gs = [_f32(g_leaves[i]) for i in group.leaf_indices]
        grad_sumsq = sum(jnp.sum(jnp.square(g)) for g in gs)
        grad_absmax = jnp.max(
            jnp.stack([jnp.max(jnp.abs(g)) for g in gs])
        )
        grad_nonfinite = sum(
            jnp.sum((~jnp.isfinite(g)).astype(jnp.float32)) for g in gs
        )
        param_sumsq = sum(
            jnp.sum(jnp.square(_f32(new_leaves[i])))
            for i in group.leaf_indices
        )
        update_sumsq = sum(
            jnp.sum(jnp.square(_f32(new_leaves[i]) - _f32(old_leaves[i])))
            for i in group.leaf_indices
        )
        rows.append(jnp.stack([
            grad_sumsq, jnp.asarray(grad_absmax, jnp.float32),
            grad_nonfinite, param_sumsq, update_sumsq,
        ]))
    return jnp.stack(rows)


def unpack_group_stats(
    row: np.ndarray, groups: List[ModuleGroup]
) -> Dict[str, Dict[str, float]]:
    """Host-side view of one ``[n_groups, n_stats]`` matrix as
    ``{group_name: {report_field: value}}`` (rms derived from the wire
    sums)."""
    m = np.asarray(row, np.float64).reshape(len(groups), N_NUMERICS_STATS)
    out: Dict[str, Dict[str, float]] = {}
    for g, group in enumerate(groups):
        n = max(group.n_elems, 1)
        out[group.name] = {
            "grad_rms": float(np.sqrt(m[g, NUMERICS_INDEX["grad_sumsq"]] / n)),
            "grad_absmax": float(m[g, NUMERICS_INDEX["grad_absmax"]]),
            "nonfinite": float(m[g, NUMERICS_INDEX["grad_nonfinite"]]),
            "param_rms": float(
                np.sqrt(m[g, NUMERICS_INDEX["param_sumsq"]] / n)
            ),
            "update_rms": float(
                np.sqrt(m[g, NUMERICS_INDEX["update_sumsq"]] / n)
            ),
        }
    return out


def provenance_of(
    row: np.ndarray, groups: List[ModuleGroup]
) -> Optional[Dict[str, Any]]:
    """First offending (group, field) of one stats matrix, or None when
    every value is finite.  Field precedence per group: ``grad`` (any
    non-finite gradient element, or a non-finite grad sum), then
    ``param``, then ``update`` — gradients go bad first in practice, and
    a NaN param implies the grad NaN already fired a step earlier."""
    m = np.asarray(row, np.float64).reshape(len(groups), N_NUMERICS_STATS)
    for g, group in enumerate(groups):
        if (
            m[g, NUMERICS_INDEX["grad_nonfinite"]] > 0
            or not np.isfinite(m[g, NUMERICS_INDEX["grad_sumsq"]])
        ):
            field = "grad"
        elif not np.isfinite(m[g, NUMERICS_INDEX["param_sumsq"]]):
            field = "param"
        elif not np.isfinite(m[g, NUMERICS_INDEX["update_sumsq"]]):
            field = "update"
        else:
            continue
        return {
            "group": g,
            "name": group.name,
            "field": field,
            "nonfinite_elems": float(
                m[g, NUMERICS_INDEX["grad_nonfinite"]]
            ),
        }
    return None


# --------------------------------------------------------------------------- #
# quantization-error attribution (wire + serving weights)
# --------------------------------------------------------------------------- #


def wire_residual_group_norms(
    transport: Any, comm_state: Optional[Dict[str, Any]], params: Any,
    groups: Optional[List[ModuleGroup]] = None,
) -> Optional[Dict[str, float]]:
    """Per-module-group norm of the error-feedback residual — the
    "quantization error currently being carried per layer" view of the
    PR-2/PR-8 transports.

    Replicated transport: the residual is a per-leaf pytree, so the
    grouping is exact (``group_norm² = Σ leaf_norm²``).  Sharded
    transport (PR 8): the residual is one flat buffer per BUCKET; each
    bucket's norm² is attributed to groups proportionally to the element
    share its member leaves contribute (buckets hold whole leaves, so
    the only approximation is within-bucket mixing).  Returns None when
    no residual is carried (no transport / no error feedback) — and on
    multi-host meshes where the sharded residual's non-addressable
    shards cannot be fetched, callers should catch and skip.
    """
    import jax

    residual = (comm_state or {}).get("residual")
    if residual is None:
        return None
    if groups is None:
        groups = module_groups(params)
    group_sq = {g.name: 0.0 for g in groups}
    if isinstance(residual, tuple):
        # sharded path: per-bucket flat buffers, mapped through the layout
        norms = [
            float(n)
            for n in jax.device_get(
                [jax.numpy.linalg.norm(r.astype(jax.numpy.float32))
                 for r in residual]
            )
        ]
        bucket_members = transport.bucket_leaf_elems(params)
        leaf_group = {}
        for g in groups:
            for i in g.leaf_indices:
                leaf_group[i] = g.name
        for b, members in enumerate(bucket_members):
            if b >= len(norms):
                break
            total = float(sum(n for _, n in members)) or 1.0
            for leaf_idx, n_elems in members:
                group_sq[leaf_group[leaf_idx]] += (
                    norms[b] ** 2 * (n_elems / total)
                )
    else:
        # replicated path: per-leaf residual pytree — exact grouping
        leaves = jax.tree_util.tree_leaves(residual)
        leaf_sq = [
            float(v) ** 2
            for v in jax.device_get(
                [jax.numpy.linalg.norm(l.astype(jax.numpy.float32))
                 for l in leaves]
            )
        ]
        for g in groups:
            for i in g.leaf_indices:
                if i < len(leaf_sq):
                    group_sq[g.name] += leaf_sq[i]
    return {name: float(np.sqrt(sq)) for name, sq in group_sq.items()}


def quant_error_by_group(
    errors_by_path: Dict[str, Dict[str, float]],
    groups: List[ModuleGroup],
    paths: List[str],
) -> Dict[str, Dict[str, float]]:
    """Fold per-leaf dequant errors (``serving.quant.quantization_error``)
    into per-module-group worst-case numbers: max relative rms and max
    absolute error over the group's quantized leaves.  Groups with no
    quantized leaf are omitted (nothing to attribute)."""
    path_group: Dict[str, str] = {}
    for g in groups:
        for i in g.leaf_indices:
            if i < len(paths):
                path_group[paths[i]] = g.name
    out: Dict[str, Dict[str, float]] = {}
    for path, err in errors_by_path.items():
        name = path_group.get(path)
        if name is None:
            # a path outside the grouping plan (shouldn't happen; be loud
            # in the value rather than dropping the error silently)
            name = path.split("/", 1)[0]
        slot = out.setdefault(
            name, {"rel_rms": 0.0, "abs_err_max": 0.0, "leaves": 0}
        )
        slot["rel_rms"] = max(slot["rel_rms"], float(err["rel_rms"]))
        slot["abs_err_max"] = max(
            slot["abs_err_max"], float(err["abs_err_max"])
        )
        slot["leaves"] += 1
    return out


def max_quant_error(
    by_group: Dict[str, Dict[str, float]],
) -> Tuple[Optional[str], Optional[float]]:
    """``(group_name, rel_rms)`` of the worst-quantized module — the
    layer that bounds int8 quality."""
    if not by_group:
        return None, None
    name = max(by_group, key=lambda k: by_group[k]["rel_rms"])
    return name, by_group[name]["rel_rms"]


# --------------------------------------------------------------------------- #
# the monitor
# --------------------------------------------------------------------------- #


class NumericsMonitor:
    """Owns the host side of the observatory: unpacks fetched group-stats
    matrices, derives provenance, publishes ``numerics/*`` gauges,
    assembles the per-group JSONL block, and ranks groups for the
    end-of-run summary.

    The facade constructs one per run when a ``NumericsConfig`` is
    supplied, feeds it every fetched matrix window
    (:meth:`observe_window`), and attaches it to the telemetry pipeline
    (``Telemetry.numerics``) so ``record_step`` pulls
    :meth:`event_fields` at the logging cadence.  NaN provenance reaches
    the health anomaly pipeline through
    :class:`NumericsProvenanceDetector` when a ``HealthConfig`` is
    present; otherwise the monitor warns (bounded) itself.
    """

    def __init__(
        self,
        cfg,
        registry,
        groups: List[ModuleGroup],
        *,
        leaf_paths: Optional[List[str]] = None,
        rank: int = 0,
    ):
        self.cfg = cfg
        self.registry = registry
        self.groups = list(groups)
        self.leaf_paths = list(leaf_paths or [])
        self.rank = int(rank)
        self.windows = 0
        self.last_step: Optional[int] = None
        self.last_per_group: Optional[Dict[str, Dict[str, float]]] = None
        self.last_provenance: Optional[Dict[str, Any]] = None
        self.wire_err: Optional[Dict[str, float]] = None
        self.quant_err: Optional[Dict[str, Dict[str, float]]] = None
        # FIFO of provenance events awaiting the health pipeline: a
        # train_steps window can surface SEVERAL events (one per bad
        # step), and the facade runs one health observation per covered
        # step — each drains one event, so none is lost or re-stamped
        self._pending_provenance: List[Dict[str, Any]] = []
        self._provenance_events: List[Dict[str, Any]] = []
        self._warnings = 0
        # grad-noise ranking state: running mean/variance of each group's
        # grad rms (EW stats — the health z-score machinery reused); the
        # summary ranks groups by the coefficient of variation std/mean,
        # the "which layer's gradients are the noisiest" lens
        self._grad_stats: Dict[str, _RunningStats] = {
            g.name: _RunningStats(alpha=0.1) for g in self.groups
        }
        registry.counter(
            "numerics/windows_total",
            help="group-stats matrices observed",
        )
        registry.counter(
            "numerics/provenance_total",
            help="non-finite per-layer provenance events",
        )

    # ------------------------------ observe ---------------------------- #

    def observe_window(self, first_step: int, rows: np.ndarray) -> None:
        """Consume the fetched group-stats matrices of one dispatch
        (``rows`` is ``[window, n_groups, n_stats]``; a single step passes
        window=1).  Derives provenance per row (so a NaN mid-segment is
        attributed to its own step), updates the noise stats, and caches
        the latest per-group view for gauges/JSONL/summary."""
        rows = np.asarray(rows, np.float64)
        if rows.ndim == 2:
            rows = rows[None]
        for i in range(rows.shape[0]):
            step = int(first_step + i)
            self.windows += 1
            self.registry.counter("numerics/windows_total").inc()
            prov = provenance_of(rows[i], self.groups)
            if prov is not None:
                prov = {**prov, "step": step}
                self.registry.counter("numerics/provenance_total").inc()
                self._provenance_events.append(prov)
                del self._provenance_events[:-_RECENT_PROVENANCE_MAX]
                self.last_provenance = prov
                self._pending_provenance.append(prov)
                del self._pending_provenance[:-_RECENT_PROVENANCE_MAX]
                self._self_apply(prov)
            per_group = unpack_group_stats(rows[i], self.groups)
            for name, stats in per_group.items():
                rms = stats["grad_rms"]
                if np.isfinite(rms):
                    self._grad_stats[name].update(rms)
            self.last_step = step
            self.last_per_group = per_group
        self._publish_gauges()

    def _publish_gauges(self) -> None:
        if self.last_per_group is None:
            return
        g = self.registry.gauge
        for name, stats in self.last_per_group.items():
            g(f"numerics/{name}/grad_rms").set(stats["grad_rms"])
            g(f"numerics/{name}/update_rms").set(stats["update_rms"])
            g(f"numerics/{name}/nonfinite").set(stats["nonfinite"])
        if self.wire_err is not None:
            for name, v in self.wire_err.items():
                g(f"numerics/{name}/wire_residual_norm").set(v)
        prov = self.last_provenance
        g("numerics/provenance_group").set(
            float(prov["group"]) if prov is not None else -1.0
        )

    def _self_apply(self, prov: Dict[str, Any]) -> None:
        """Warn-path fallback when no health registry will consume the
        pending provenance (the facade drains it through
        :class:`NumericsProvenanceDetector` when a ``HealthConfig`` is
        present)."""
        if self.cfg.provenance_action == "record":
            return
        if self._warnings >= _MAX_PROVENANCE_WARNINGS:
            return
        self._warnings += 1
        warnings.warn(f"Stoke -- numerics: {describe_provenance(prov)}")

    def consume_provenance(self) -> Optional[Dict[str, Any]]:
        """Pop the OLDEST pending provenance event (the detector adapter
        drains this into the health anomaly pipeline — FIFO, one per
        health observation, so a multi-step window's events each fire
        with their own step)."""
        if not self._pending_provenance:
            return None
        return self._pending_provenance.pop(0)

    # -------------------- quantization-error inputs -------------------- #

    def observe_wire(
        self, group_norms: Optional[Dict[str, float]]
    ) -> None:
        """Install the latest per-group wire (error-feedback residual)
        norms — computed by the facade at the logging cadence via
        :func:`wire_residual_group_norms`."""
        if group_norms is None:
            return
        self.wire_err = dict(group_norms)

    def set_quant_errors(
        self, by_group: Dict[str, Dict[str, float]]
    ) -> None:
        """Install per-group serving-weight dequant errors (computed once
        at quantize time — :func:`quant_error_by_group`) and publish the
        matching gauges."""
        self.quant_err = dict(by_group)
        g = self.registry.gauge
        for name, err in by_group.items():
            g(f"numerics/{name}/quant_err_rel_rms").set(err["rel_rms"])

    # ------------------------------ outputs ----------------------------- #

    def event_fields(self) -> Dict[str, Any]:
        """The ``numerics/*`` JSONL step-event block (keys present only
        when a monitor is attached; the per-group block is nullable and
        omitted between observations or when ``per_group_jsonl`` is
        off)."""
        per_group = None
        if self.cfg.per_group_jsonl:
            # the block merges whatever signal families have data — a
            # grad_stats=False (wire/quant-only) config still emits it,
            # so numerics_diff.py --stat wire_err can align such runs
            per_group = {
                name: dict(stats)
                for name, stats in (self.last_per_group or {}).items()
            }
            if self.wire_err is not None:
                for name, v in self.wire_err.items():
                    per_group.setdefault(name, {})["wire_err"] = v
            if self.quant_err is not None:
                for name, err in self.quant_err.items():
                    per_group.setdefault(name, {})["quant_err"] = (
                        err["rel_rms"]
                    )
            per_group = per_group or None
        prov = self.last_provenance
        q_layer, q_max = (
            max_quant_error(self.quant_err)
            if self.quant_err is not None
            else (None, None)
        )
        return {
            "numerics/groups": len(self.groups),
            "numerics/per_group": per_group,
            "numerics/provenance_group": (
                None if prov is None else prov["group"]
            ),
            "numerics/provenance_name": (
                None if prov is None else prov["name"]
            ),
            "numerics/provenance_field": (
                None if prov is None else prov["field"]
            ),
            "numerics/quant_err_max": q_max,
            "numerics/quant_err_group": q_layer,
        }

    def grad_noise(self) -> Dict[str, float]:
        """Per-group gradient-noise score: the running coefficient of
        variation (std/mean) of the group's grad rms — scale-free, so a
        tiny layernorm and a huge matmul rank comparably."""
        out = {}
        for name, stats in self._grad_stats.items():
            if stats.mean is None or stats.mean <= 0:
                out[name] = 0.0
            else:
                out[name] = float((stats.var ** 0.5) / stats.mean)
        return out

    def snapshot(self) -> Dict[str, Any]:
        """Bundle payload (``numerics.json``): the latest per-group view,
        provenance history, and quantization-error attribution — "which
        layer was bad at time of death"."""
        return {
            "rank": self.rank,
            "step": self.last_step,
            "windows": self.windows,
            "groups": [g.name for g in self.groups],
            "group_elems": {g.name: g.n_elems for g in self.groups},
            "per_group": self.last_per_group,
            "grad_noise": self.grad_noise(),
            "wire_err": self.wire_err,
            "quant_err": self.quant_err,
            "provenance": self.last_provenance,
            "provenance_events": list(self._provenance_events),
        }

    def summary(self) -> Dict[str, Any]:
        """End-of-run ranking (the ``Stoke.numerics_summary`` surface):
        groups ordered by grad-noise and by quant error, plus the latest
        per-group stats and every provenance event."""
        noise = self.grad_noise()
        top_k = max(int(self.cfg.top_k), 1)
        by_noise = sorted(
            noise.items(), key=lambda kv: kv[1], reverse=True
        )[:top_k]
        by_quant: List[Tuple[str, float]] = []
        if self.quant_err:
            by_quant = sorted(
                ((n, e["rel_rms"]) for n, e in self.quant_err.items()),
                key=lambda kv: kv[1], reverse=True,
            )[:top_k]
        by_wire: List[Tuple[str, float]] = []
        if self.wire_err:
            by_wire = sorted(
                self.wire_err.items(), key=lambda kv: kv[1], reverse=True
            )[:top_k]
        out = self.snapshot()
        out["top_grad_noise"] = [
            {"group": n, "noise": v} for n, v in by_noise
        ]
        out["top_quant_err"] = [
            {"group": n, "rel_rms": v} for n, v in by_quant
        ]
        out["top_wire_err"] = [
            {"group": n, "residual_norm": v} for n, v in by_wire
        ]
        out["provenance_total"] = int(
            self.registry.counter("numerics/provenance_total").value
        )
        return out


def describe_provenance(prov: Dict[str, Any]) -> str:
    n = prov.get("nonfinite_elems") or 0
    detail = (
        f" ({int(n)} non-finite gradient elements)" if n else ""
    )
    return (
        f"non-finite {prov['field']} values first appear in module group "
        f"{prov['name']!r} (index {prov['group']}) at step "
        f"{prov.get('step', '?')}{detail}"
    )


class NumericsProvenanceDetector(Detector):
    """Health-registry adapter (PR 3 registry contract): when the
    numerics monitor derived a fresh non-finite provenance since the last
    health observation, surface it as a ``numerics_provenance`` anomaly
    (action from ``NumericsConfig.provenance_action``) so the culprit
    layer lands in the anomaly counters, the flight-recorder ring, and
    post-mortem bundles — and a ``halt`` action stops the run AT the
    facade boundary with the layer named."""

    name = "numerics_provenance"

    def __init__(self, monitor: NumericsMonitor, action: str = "warn"):
        super().__init__(action)
        self.monitor = monitor
        # the monitor's own warn fallback would double-report next to the
        # health pipeline's warning
        monitor._warnings = _MAX_PROVENANCE_WARNINGS

    def check(self, step, sentinels, ctx):
        event = self.monitor.consume_provenance()
        if event is None:
            return None
        # stamp the anomaly with the EVENT's step, not the observation's:
        # a train_steps window drains its events across the per-step
        # health observations, and the ring/bundle must key each firing
        # to the step the NaN actually appeared at
        anomaly = self._fire(
            int(event.get("step", step)),
            f"numerics provenance: {describe_provenance(event)}",
            value=float(event["group"]),
        )
        anomaly.context = dict(event)
        return anomaly
