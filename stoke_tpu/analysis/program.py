"""Program auditor: static analysis over lowered jaxpr/HLO step programs
(ISSUE 15 tentpole, jax half).

The invariants every subsystem asserts per-PR with bespoke tests —
donation integrity, zero hidden host round-trips, bounded recompiles,
accounted collectives — become one pass over the LOWERED text of the
programs a live build actually dispatches.  The engine and serving
engine record one :class:`ProgramSpec` per (program, shape signature) at
their dispatch funnels (``StepEngine._aot_call`` /
``ServingEngine._dispatch``): the program name, the jitted callable, the
ABSTRACT argument tree (``jax.ShapeDtypeStruct`` per array leaf, shapes/
dtypes/shardings only — never live buffers, which the next step's
donation deletes), and the declared ``donate_argnums``.  Auditing lowers
each spec (``fn.lower`` — tracing only, no compile, no dispatch: the
``Stoke.audit()`` acceptance asserts dispatch-count equality) and walks
the normalized StableHLO/HLO text.

Checks (rule ids; every finding names the remedy):

- ``audit-donation`` — a program that DECLARES donated argnums whose
  lowered text carries no input/output aliasing annotation for them
  (``tf.aliasing_output`` / ``jax.buffer_donor``): the donation was
  silently lost, which means the in-place state update the engine's
  memory budget assumes is actually a copy.
- ``audit-deserialized`` — a dispatch callable that is NOT a plain
  ``jax.jit`` wrapper (no ``.lower``): the PR-6/PR-14 hazard class —
  deserialized executables lose donated-input bookkeeping, and chaining
  them over carried training state silently corrupts numerics
  (tests/test_compile_cache.py pins the evidence; a stale host
  reference read after its buffer was donated is the same class).
- ``audit-hidden-transfer`` — host callbacks (``pure_callback`` /
  ``io_callback`` / debug callbacks) or infeed/outfeed inside a step
  program: a host round-trip per dispatch, breaking the PR-3
  zero-extra-dispatch sentinel discipline.
- ``audit-weak-type`` — weak-typed or raw-Python-scalar argument
  leaves: a closure/argument leak that re-traces (and silently
  recompiles) whenever the surrounding dtype context changes.
- ``audit-recompile-churn`` — a program whose recorded shape-signature
  count exceeds the churn threshold (ragged batches / drifting pad
  lengths), or approaches the engine's 1024-entry memo cap, beyond
  which recompile detection and the AOT ledger disengage.
- ``audit-replicated-bytes`` — tensors whose Shardy annotation
  (``sdy.sharding`` / ``sdy.sharding_constraint``) names no mesh axis,
  above a byte threshold in a partitioned (``mhlo.num_partitions > 1``)
  program: each device holds a full copy of something the mesh was
  supposed to shard.
- ``audit-comm-bytes`` — cross-check against the gradient transport's
  analytic accounting: an active transport claiming bytes-on-wire whose
  apply-family program contains no explicit collective (the accounting
  drifted from the program), or manual collectives in an apply-family
  program with NO active transport (traffic nothing accounts —
  ``bytes_per_step`` would under-report the wire).
- ``audit-cost-drift`` (ISSUE 18) — serve-program analytic cost vs the
  committed ``analysis/manifests/program_costs.json`` manifest: each
  serve spec is re-lowered for its XLA cost analysis (FLOPs / bytes
  accessed — the same numbers the roofline observatory's cards carry)
  and compared against the pinned entry at matching shape signature.
  A relative deviation beyond the manifest tolerance fires IN BOTH
  directions (golden-file semantics: a silent bloat is a perf
  regression; a silent shrink means the pin is stale), so a refactor
  that quietly inflates a serve program fails CI on CPU with no
  hardware in the loop.  Unpinned serve programs fire too — a new
  program must be pinned when it lands.  Signature mismatches (the
  engine geometry changed) and backends without cost analysis are
  NOTES, not findings: geometry changes re-pin via ``scripts/
  stoke_lint.py --update-costs``.

Program findings use a ``<jit:NAME>`` pseudo-file and line 0 — the
"file" is the compiled program, not a source line.
"""

from __future__ import annotations

import hashlib
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from stoke_tpu.analysis.hlo_text import normalize_module_name
from stoke_tpu.analysis.invariants import Finding

#: step programs whose apply boundary runs the gradient transport — the
#: comm cross-check applies to these only (accum/fused_nb micro-steps
#: never exchange gradients; serve programs have no transport at all)
APPLY_FAMILY = ("apply", "fused", "window", "multi")

#: shape-signature count above which a program is churn-flagged (serve
#: prefill legitimately owns one signature per pad bucket, so the
#: default sits well above any bounded bucket ladder)
DEFAULT_CHURN_THRESHOLD = 32

#: replicated-tensor byte floor for the sharding audit (64 MiB — big
#: enough that real models' replicated biases/norms never trip it)
DEFAULT_REPLICATED_BYTES = 64 << 20

#: MLIR element-type byte widths (for tensor<...> byte accounting)
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8E4M3FN": 1, "f8E5M2": 1,
    "i64": 8, "i32": 4, "i16": 2, "i8": 1, "i1": 1,
    "ui64": 8, "ui32": 4, "ui16": 2, "ui8": 1, "pred": 1,
}

_COLLECTIVE_RE = re.compile(
    r"stablehlo\.(all_reduce|all_gather|reduce_scatter|collective_permute|"
    r"all_to_all)\b|\b(all-reduce|all-gather|reduce-scatter|"
    r"collective-permute|all-to-all)\b"
)
_CALLBACK_RE = re.compile(r"custom_call\s+@([\w.]*callback[\w.]*)")
_INOUTFEED_RE = re.compile(r"stablehlo\.(infeed|outfeed)\b|\b(infeed|outfeed)\(")
_DONOR_ATTR_RE = re.compile(r"tf\.aliasing_output|jax\.buffer_donor")
_PARTITIONS_RE = re.compile(r"mhlo\.num_partitions = (\d+)")
_ARG_SPLIT_RE = re.compile(r"(?=%arg\d+: )")
_ARG_NUM_RE = re.compile(r"%arg(\d+): ")
#: a tensor type IMMEDIATELY followed by the opening brace of its attr
#: dict (arg/result annotations); the dict itself is brace-matched by
#: :func:`_annotated_tensors` because Shardy attrs nest braces
_TENSOR_ATTRS_OPEN_RE = re.compile(r"tensor<([^>]+)>\s\{")
#: the per-dimension axis lists of a Shardy sharding: ``[{"data"}, {}]``
_SDY_SHARDING_RE = re.compile(r"#sdy\.sharding<@\w+, \[([^\]]*)\]")
#: ``sdy.sharding_constraint %x <@mesh, [{}, {}]> : tensor<...>``
_SDY_CONSTRAINT_RE = re.compile(
    r"sdy\.sharding_constraint\s+%\S+\s+<@\w+, \[([^\]]*)\][^:\n]*:"
    r"\s*tensor<([^>]+)>"
)


@dataclass
class ProgramSpec:
    """One registered step/serve program, recorded at its dispatch
    funnel: everything the auditor needs to re-lower it without touching
    (or retaining) live buffers."""

    program: str
    fn: Any
    abstract_args: Tuple[Any, ...]
    donate_argnums: Tuple[int, ...] = ()
    #: descriptions of weak-typed / raw-Python-scalar arg leaves found at
    #: record time (the aval conversion would erase weakness, so it is
    #: detected before conversion)
    weak_leaves: Tuple[str, ...] = ()
    #: where the spec came from ("engine" / "serve") — display only
    source: str = "engine"


@dataclass
class AuditReport:
    """The program-audit result: per-program findings plus the audited
    program inventory (so "zero findings" is distinguishable from
    "nothing was audited")."""

    findings: List[Finding] = field(default_factory=list)
    programs: List[str] = field(default_factory=list)
    #: rules that could NOT run (e.g. churn without signature tracking)
    #: — a clean report must be distinguishable from an unchecked one
    notes: List[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.findings

    def format(self) -> str:
        head = (
            f"program audit: {len(self.programs)} program(s), "
            f"{len(self.findings)} finding(s)"
        )
        lines = [head] + [f.format() for f in self.findings]
        lines += [f"note: {n}" for n in self.notes]
        return "\n".join(lines)


def abstractify_args(args: tuple) -> Tuple[tuple, Tuple[str, ...]]:
    """Live dispatch args → (abstract arg tree, weak-leaf descriptions).

    Array leaves become ``ShapeDtypeStruct`` (sharding preserved when it
    is a mesh placement — lowering under the run's real shardings keeps
    the audited text the dispatched program's); scalars and everything
    else pass through unchanged.  Weakness is recorded HERE because the
    aval conversion erases it: jax arrays flagged ``weak_type`` and raw
    Python ints/floats/complex both re-trace on dtype-context changes.
    """
    from jax.sharding import NamedSharding

    weak: List[str] = []
    flat, treedef = jax.tree_util.tree_flatten(args)
    out = []
    for i, leaf in enumerate(flat):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            if getattr(leaf, "weak_type", False):
                weak.append(
                    f"leaf {i}: weak-typed {leaf.dtype} array "
                    f"(a Python scalar promoted at trace time)"
                )
            sh = getattr(leaf, "sharding", None)
            if isinstance(sh, NamedSharding):
                out.append(
                    jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sh)
                )
            else:
                out.append(jax.ShapeDtypeStruct(leaf.shape, leaf.dtype))
        else:
            if isinstance(leaf, (int, float, complex)) and not isinstance(
                leaf, bool
            ):
                weak.append(
                    f"leaf {i}: raw Python {type(leaf).__name__} argument"
                )
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out), tuple(weak)


# --------------------------------------------------------------------------- #
# lowered-text helpers
# --------------------------------------------------------------------------- #


def _main_signature(text: str) -> str:
    """The argument list of ``func.func public @main(...)`` — extracted
    by paren balance so nested region block-args (whose ``%argN`` names
    restart) never alias into the mapping."""
    marker = "@main("
    start = text.find(marker)
    if start < 0:
        return ""
    i = start + len(marker) - 1  # at the opening paren
    depth = 0
    for j in range(i, len(text)):
        if text[j] == "(":
            depth += 1
        elif text[j] == ")":
            depth -= 1
            if depth == 0:
                return text[i : j + 1]
    return ""


def _annotated_tensors(text: str):
    """``(tensor payload, attr-dict text)`` for every tensor type directly
    followed by its attr dict.  Brace-matched and quote-aware: a Shardy
    annotation (``sdy.sharding = #sdy.sharding<@mesh, [{"data"}, {}]>``)
    nests braces inside the dict, which no flat regex can delimit."""
    for m in _TENSOR_ATTRS_OPEN_RE.finditer(text):
        depth, j = 1, m.end()
        while j < len(text) and depth:
            c = text[j]
            if c == '"':
                j = text.find('"', j + 1)
                if j < 0:
                    break
            elif c == "{":
                depth += 1
            elif c == "}":
                depth -= 1
            j += 1
        if depth == 0:
            yield m.group(1), text[m.end() : j - 1]


def _sdy_replicated(dims: str) -> bool:
    """True when a Shardy dimension-sharding list names no mesh axis
    (axis names are the only quoted tokens in it): ``{}, {?}``."""
    return '"' not in dims


def _tensor_bytes(content: str) -> Optional[int]:
    """``tensor<...>`` payload → bytes: the x-separated dims with the
    element type as the final segment (``1024x1024xf32``); None on
    dynamic dims or exotic element types (skipped, never guessed)."""
    parts = content.split("x")
    width = _DTYPE_BYTES.get(parts[-1])
    if width is None:
        return None
    n = 1
    for d in parts[:-1]:
        if not d.isdigit():
            return None  # dynamic dim: skip
        n *= int(d)
    return n * width


def _arg_leaf_ranges(abstract_args: tuple) -> List[Tuple[int, int]]:
    """Flat-leaf index range per positional argument — the map from a
    ``donate_argnums`` entry to the MLIR ``%argN`` positions it covers
    (valid only when jit kept every leaf; callers cross-check counts)."""
    ranges = []
    pos = 0
    for arg in abstract_args:
        n = len(jax.tree_util.tree_leaves(arg))
        ranges.append((pos, pos + n))
        pos += n
    return ranges


# --------------------------------------------------------------------------- #
# analytic program cost (ISSUE 18: the cost-drift gate's measurement leg)
# --------------------------------------------------------------------------- #

#: default relative FLOPs/bytes deviation above which audit-cost-drift
#: fires (the manifest's "tolerance" key overrides; XLA's CPU cost model
#: is deterministic for a fixed program, so the slack absorbs cross-
#: version cost-model drift, not noise)
DEFAULT_COST_TOLERANCE = 0.05


def cost_signature(abstract_args: tuple) -> str:
    """Stable digest of a spec's argument geometry (shapes + dtypes of
    every array leaf, order-preserving).  Pinned beside the manifest's
    analytic numbers so a cost comparison against a DIFFERENT engine
    geometry (resized batch, longer context) reads as "not comparable"
    instead of a false drift finding."""
    leaves = [
        (tuple(l.shape), str(l.dtype))
        for l in jax.tree_util.tree_leaves(abstract_args)
        if hasattr(l, "shape") and hasattr(l, "dtype")
    ]
    return hashlib.sha256(repr(leaves).encode()).hexdigest()[:16]


def spec_cost_entry(spec: ProgramSpec) -> Optional[Dict[str, Any]]:
    """One manifest entry for a serve spec: the XLA cost analysis of the
    re-lowered program (lowering only — no compile, no dispatch) plus
    the geometry signature.  None when the backend reports no cost
    analysis (the gate then notes itself unchecked, never guesses)."""
    from stoke_tpu.telemetry.attribution import cost_analysis_of

    if not hasattr(spec.fn, "lower"):
        return None
    cost = cost_analysis_of(spec.fn, *spec.abstract_args)
    if cost is None:
        return None
    flops = float(cost.get("flops", 0.0) or 0.0)
    if flops <= 0:
        return None
    nbytes = cost.get("bytes accessed")
    return {
        "sig": cost_signature(spec.abstract_args),
        "flops": flops,
        "bytes_accessed": float(nbytes) if nbytes else None,
    }


def _rel_dev(measured: float, pinned: float) -> float:
    return abs(measured - pinned) / max(abs(pinned), 1e-12)


# --------------------------------------------------------------------------- #
# per-program memory (ISSUE 19: the memory-drift gate's measurement leg)
# --------------------------------------------------------------------------- #

#: default relative temp/peak-bytes deviation above which
#: audit-memory-drift fires (the manifest's "tolerance" key overrides).
#: Looser than DEFAULT_COST_TOLERANCE on purpose: XLA's temp-buffer
#: allocation shifts across compiler versions far more than its analytic
#: FLOP count does — the gate exists to catch a refactor DOUBLING a
#: buffer, not a version bump nudging padding
DEFAULT_MEM_TOLERANCE = 0.25


def spec_memory_entry(spec: ProgramSpec) -> Optional[Dict[str, Any]]:
    """One memory-manifest entry for a serve spec: the compiled
    executable's ``memory_analysis`` temp/peak bytes plus the geometry
    signature.  Unlike :func:`spec_cost_entry` this REQUIRES a compile
    (``memory_analysis`` lives on the executable, not the lowering) — so
    the memory gate runs only where the cost gate's lowering-only
    contract does not apply (``stoke_lint.py --programs``'s throwaway
    engines, never ``Stoke.audit()``'s dispatch-count-pinned path unless
    a manifest is explicitly supplied).  None when the backend reports
    no memory analysis (the gate then notes itself unchecked)."""
    from stoke_tpu.telemetry.attribution import memory_analysis_stats

    if not hasattr(spec.fn, "lower"):
        return None
    stats = memory_analysis_stats(spec.fn, *spec.abstract_args)
    if stats is None:
        return None
    peak = float(stats.get("peak_bytes", 0.0) or 0.0)
    if peak <= 0:
        return None
    return {
        "sig": cost_signature(spec.abstract_args),
        "temp_bytes": float(stats.get("temp_bytes", 0.0) or 0.0),
        "peak_bytes": peak,
    }


def _audit_memory_drift(
    specs: Sequence[ProgramSpec],
    report: "AuditReport",
    mem_manifest: Dict[str, Any],
    tolerance: float,
) -> None:
    """The memory-drift gate: serve specs' re-compiled memory_analysis
    temp/peak bytes vs the committed manifest, both directions
    (golden-file semantics, the _audit_cost_drift pattern)."""
    pinned = mem_manifest.get("programs", {}) or {}
    seen = set()
    for spec in specs:
        if spec.source != "serve" or spec.program in seen:
            continue
        seen.add(spec.program)
        entry = spec_memory_entry(spec)
        if entry is None:
            report.notes.append(
                f"audit-memory-drift not checked for {spec.program!r}: "
                f"backend reports no XLA memory analysis"
            )
            continue
        pin = pinned.get(spec.program)
        if pin is None:
            report.findings.append(
                Finding(
                    rule="audit-memory-drift",
                    file=f"<jit:{spec.program}>",
                    line=0,
                    message=(
                        f"serve program {spec.program!r} "
                        f"({entry['peak_bytes']:.0f} peak bytes) has no "
                        f"pinned entry in the program-memory manifest — "
                        f"its HBM regressions would be invisible to CI"
                    ),
                    remedy=(
                        "pin it: scripts/stoke_lint.py --update-mem "
                        "rewrites analysis/manifests/program_memory.json "
                        "from the live engines"
                    ),
                )
            )
            continue
        if pin.get("sig") != entry["sig"]:
            report.notes.append(
                f"audit-memory-drift not checked for {spec.program!r}: "
                f"argument geometry changed (sig {entry['sig']} vs "
                f"pinned {pin.get('sig')}) — re-pin with "
                f"scripts/stoke_lint.py --update-mem"
            )
            continue
        for field_name, measured in (
            ("temp_bytes", entry["temp_bytes"]),
            ("peak_bytes", entry["peak_bytes"]),
        ):
            pinned_v = pin.get(field_name)
            if pinned_v is None or measured is None:
                continue
            dev = _rel_dev(measured, pinned_v)
            if dev <= tolerance:
                continue
            direction = "grew" if measured > pinned_v else "shrank"
            report.findings.append(
                Finding(
                    rule="audit-memory-drift",
                    file=f"<jit:{spec.program}>",
                    line=0,
                    message=(
                        f"serve program {spec.program!r} "
                        f"{field_name} {direction} {dev:.1%} vs the "
                        f"pinned manifest ({measured:.0f} vs "
                        f"{pinned_v:.0f}, tolerance {tolerance:.0%}) at "
                        f"UNCHANGED argument geometry — a refactor "
                        f"changed this program's HBM footprint per "
                        f"dispatch"
                    ),
                    remedy=(
                        "if the footprint change is intentional, re-pin "
                        "with scripts/stoke_lint.py --update-mem; "
                        "otherwise find the buffer the refactor "
                        "grew/dropped (compare memory_analysis against "
                        "the last good commit)"
                    ),
                )
            )


def _audit_cost_drift(
    specs: Sequence[ProgramSpec],
    report: "AuditReport",
    cost_manifest: Dict[str, Any],
    tolerance: float,
) -> None:
    """The cost-drift gate: serve specs' re-lowered analytic cost vs the
    committed manifest, both directions (golden-file semantics)."""
    pinned = cost_manifest.get("programs", {}) or {}
    seen = set()
    for spec in specs:
        if spec.source != "serve" or spec.program in seen:
            continue
        seen.add(spec.program)
        entry = spec_cost_entry(spec)
        if entry is None:
            report.notes.append(
                f"audit-cost-drift not checked for {spec.program!r}: "
                f"backend reports no XLA cost analysis"
            )
            continue
        pin = pinned.get(spec.program)
        if pin is None:
            report.findings.append(
                Finding(
                    rule="audit-cost-drift",
                    file=f"<jit:{spec.program}>",
                    line=0,
                    message=(
                        f"serve program {spec.program!r} "
                        f"({entry['flops']:.0f} analytic FLOPs) has no "
                        f"pinned entry in the program-cost manifest — "
                        f"its cost regressions would be invisible to CI"
                    ),
                    remedy=(
                        "pin it: scripts/stoke_lint.py --update-costs "
                        "rewrites analysis/manifests/program_costs.json "
                        "from the live engines"
                    ),
                )
            )
            continue
        if pin.get("sig") != entry["sig"]:
            report.notes.append(
                f"audit-cost-drift not checked for {spec.program!r}: "
                f"argument geometry changed (sig {entry['sig']} vs "
                f"pinned {pin.get('sig')}) — re-pin with "
                f"scripts/stoke_lint.py --update-costs"
            )
            continue
        for field_name, measured in (
            ("flops", entry["flops"]),
            ("bytes_accessed", entry["bytes_accessed"]),
        ):
            pinned_v = pin.get(field_name)
            if pinned_v is None or measured is None:
                continue
            dev = _rel_dev(measured, pinned_v)
            if dev <= tolerance:
                continue
            direction = "grew" if measured > pinned_v else "shrank"
            report.findings.append(
                Finding(
                    rule="audit-cost-drift",
                    file=f"<jit:{spec.program}>",
                    line=0,
                    message=(
                        f"serve program {spec.program!r} analytic "
                        f"{field_name} {direction} {dev:.1%} vs the "
                        f"pinned manifest ({measured:.0f} vs "
                        f"{pinned_v:.0f}, tolerance {tolerance:.0%}) at "
                        f"UNCHANGED argument geometry — a refactor "
                        f"changed what this program computes per "
                        f"dispatch"
                    ),
                    remedy=(
                        "if the cost change is intentional, re-pin with "
                        "scripts/stoke_lint.py --update-costs; otherwise "
                        "find the op the refactor added/dropped "
                        "(compare lowered HLO against the last good "
                        "commit)"
                    ),
                )
            )


# --------------------------------------------------------------------------- #
# the audit
# --------------------------------------------------------------------------- #


def _where(spec: ProgramSpec) -> str:
    return f"<jit:{spec.program}>"


def _audit_one(
    spec: ProgramSpec,
    findings: List[Finding],
    *,
    transport_active: bool,
    comm_bytes: Optional[Dict[str, Any]],
    replicated_bytes_threshold: int,
) -> None:
    if not hasattr(spec.fn, "lower"):
        findings.append(
            Finding(
                rule="audit-deserialized",
                file=_where(spec),
                line=0,
                message=(
                    f"program {spec.program!r} dispatches through a "
                    f"callable with no .lower — a deserialized/pre-"
                    f"compiled executable.  Deserialization loses "
                    f"donated-input bookkeeping: chaining such calls "
                    f"over carried training state reads stale host "
                    f"references after their buffers were donated and "
                    f"silently corrupts numerics (the PR-6/PR-14 hazard "
                    f"class, pinned in tests/test_compile_cache.py)"
                ),
                remedy=(
                    "dispatch step programs through plain jax.jit only; "
                    "serve warm starts from the persistent XLA cache "
                    "(CompileConfig) and keep serialized artifacts for "
                    "one-shot offline use"
                ),
            )
        )
        return

    # weak-typed inputs recompile when the dtype context shifts — checked
    # from record-time leaf descriptions (conversion to avals erases it)
    if spec.weak_leaves:
        findings.append(
            Finding(
                rule="audit-weak-type",
                file=_where(spec),
                line=0,
                message=(
                    f"program {spec.program!r} takes weak-typed / raw "
                    f"Python scalar arguments "
                    f"({'; '.join(spec.weak_leaves)}) — each dtype-"
                    f"context change re-traces and silently recompiles "
                    f"against the engine's shape-signature memo"
                ),
                remedy=(
                    "pass scalars as typed arrays "
                    "(jnp.asarray(v, dtype)) or bake them into the "
                    "program as closed-over constants"
                ),
            )
        )

    try:
        lowered = spec.fn.lower(*spec.abstract_args)
        text = normalize_module_name(lowered.as_text())
    except Exception as e:  # pragma: no cover - depends on runtime
        findings.append(
            Finding(
                rule="audit-lowering",
                file=_where(spec),
                line=0,
                message=(
                    f"program {spec.program!r} could not be re-lowered "
                    f"for audit ({e!r})"
                ),
                remedy=(
                    "audit with the run's real mesh/backend live (the "
                    "recorded abstract args carry its shardings)"
                ),
            )
        )
        return

    # --- donation integrity ---------------------------------------- #
    donated = [
        a
        for a in spec.donate_argnums
        if a < len(spec.abstract_args)
        and any(
            hasattr(l, "shape")
            for l in jax.tree_util.tree_leaves(spec.abstract_args[a])
        )
    ]
    if donated:
        sig = _main_signature(text)
        # split on "%argN: " boundaries so each segment carries one
        # argument's full attr dict — attr values nest braces
        # (sdy.sharding = #sdy.sharding<@mesh, [{}, {}]>), which defeats a
        # flat regex
        sig_args = {}
        for part in _ARG_SPLIT_RE.split(sig):
            m = _ARG_NUM_RE.match(part)
            if m:
                sig_args[int(m.group(1))] = bool(
                    _DONOR_ATTR_RE.search(part)
                )
        ranges = _arg_leaf_ranges(spec.abstract_args)
        total_leaves = ranges[-1][1] if ranges else 0
        per_argnum_valid = len(sig_args) == total_leaves
        for a in donated:
            if per_argnum_valid:
                lo, hi = ranges[a]
                ok = any(sig_args.get(i, False) for i in range(lo, hi))
            else:
                # jit pruned/merged inputs: fall back to whole-program
                # donor presence (still catches fully-lost donation)
                ok = any(sig_args.values()) or bool(
                    _DONOR_ATTR_RE.search(sig)
                )
            if not ok:
                findings.append(
                    Finding(
                        rule="audit-donation",
                        file=_where(spec),
                        line=0,
                        message=(
                            f"program {spec.program!r} declares "
                            f"donate_argnums={spec.donate_argnums} but "
                            f"argument {a} carries no input/output "
                            f"aliasing annotation in the lowered "
                            f"program — the donation was silently "
                            f"dropped (no matching output shape), so "
                            f"the 'in-place' state update is actually "
                            f"a full copy"
                        ),
                        remedy=(
                            "return an output whose shape/dtype matches "
                            "every donated buffer (state threads "
                            "through), or stop declaring the argnum "
                            "donated — a silently-copied donation "
                            "double-books device memory"
                        ),
                    )
                )

    # --- hidden host round-trips ------------------------------------ #
    cb = _CALLBACK_RE.search(text)
    feed = _INOUTFEED_RE.search(text)
    if cb or feed:
        what = cb.group(1) if cb else (feed.group(1) or feed.group(2))
        findings.append(
            Finding(
                rule="audit-hidden-transfer",
                file=_where(spec),
                line=0,
                message=(
                    f"program {spec.program!r} embeds a host round-trip "
                    f"({what}) — every dispatch blocks on a host "
                    f"callback/transfer, breaking the zero-extra-"
                    f"dispatch sentinel discipline (PR 3) and "
                    f"serializing the async pipeline"
                ),
                remedy=(
                    "compute diagnostics INSIDE the compiled program "
                    "and fetch them with the sentinel row at the "
                    "telemetry cadence; move true host work outside "
                    "the step program"
                ),
            )
        )

    # --- sharding: big replicated tensors on a partitioned program -- #
    pm = _PARTITIONS_RE.search(text)
    n_partitions = int(pm.group(1)) if pm else 1
    if n_partitions > 1:
        # each candidate is matched to ITS OWN sharding annotation —
        # a per-line scan would attribute a small replicated arg's
        # annotation to every big SHARDED tensor sharing the (single-
        # line) @main signature and false-fire on real models
        repl_sizes = []
        for content, attrs in _annotated_tensors(text):
            m = _SDY_SHARDING_RE.search(attrs)
            if m and _sdy_replicated(m.group(1)):
                repl_sizes.append(_tensor_bytes(content))
        # sharding-constraint intermediates carry the sharding before
        # the type
        repl_sizes += [
            _tensor_bytes(content)
            for dims, content in _SDY_CONSTRAINT_RE.findall(text)
            if _sdy_replicated(dims)
        ]
        # one finding per distinct size: the same value annotated at its
        # arg AND result position is one replication, not two
        flagged = 0
        for nbytes in sorted(
            {b for b in repl_sizes if b is not None}, reverse=True
        ):
            if nbytes <= replicated_bytes_threshold:
                continue
            findings.append(
                Finding(
                    rule="audit-replicated-bytes",
                    file=_where(spec),
                    line=0,
                    message=(
                        f"program {spec.program!r} keeps a "
                        f"{nbytes / 2**20:.1f} MiB tensor "
                        f"replicated across {n_partitions} "
                        f"partitions (> {replicated_bytes_threshold / 2**20:.0f}"
                        f" MiB threshold) — every device holds "
                        f"a full copy"
                    ),
                    remedy=(
                        "give the value a sharded placement "
                        "(partition rules / tier shardings) or "
                        "raise the audit threshold if the "
                        "replication is intentional"
                    ),
                )
            )
            flagged += 1
            if flagged >= 4:  # bound the noise per program
                break

    # --- collectives vs the transport's analytic bytes --------------- #
    if spec.program in APPLY_FAMILY:
        has_collective = bool(_COLLECTIVE_RE.search(text))
        onwire = (comm_bytes or {}).get("onwire", 0) or 0
        if transport_active and onwire > 0 and not has_collective:
            findings.append(
                Finding(
                    rule="audit-comm-bytes",
                    file=_where(spec),
                    line=0,
                    message=(
                        f"the gradient transport accounts {onwire} "
                        f"bytes-on-wire per step but program "
                        f"{spec.program!r} contains no explicit "
                        f"collective — bytes_per_step has drifted from "
                        f"the compiled program"
                    ),
                    remedy=(
                        "re-derive GradTransport.bytes_per_step from "
                        "the schedule the program actually lowers "
                        "(parallel/collectives.py _wire_bytes), or fix "
                        "the transport wiring"
                    ),
                )
            )
        elif not transport_active and has_collective:
            findings.append(
                Finding(
                    rule="audit-comm-bytes",
                    file=_where(spec),
                    line=0,
                    message=(
                        f"program {spec.program!r} lowers explicit "
                        f"(manual/shard_map) collectives but no "
                        f"gradient transport is active — this traffic "
                        f"is invisible to the analytic bytes-on-wire "
                        f"accounting (comm_bytes_* telemetry would "
                        f"under-report the wire)"
                    ),
                    remedy=(
                        "route manual collectives through the "
                        "GradTransport layer (parallel/collectives.py) "
                        "so their bytes are accounted, or extend "
                        "bytes_per_step for the new exchange"
                    ),
                )
            )


def audit_program_specs(
    specs: Sequence[ProgramSpec],
    *,
    transport_active: bool = False,
    comm_bytes: Optional[Dict[str, Any]] = None,
    shape_sig_counts: Optional[Dict[str, int]] = None,
    churn_threshold: int = DEFAULT_CHURN_THRESHOLD,
    memo_cap: int = 1024,
    replicated_bytes_threshold: int = DEFAULT_REPLICATED_BYTES,
    cost_manifest: Optional[Dict[str, Any]] = None,
    cost_tolerance: Optional[float] = None,
    mem_manifest: Optional[Dict[str, Any]] = None,
    mem_tolerance: Optional[float] = None,
) -> AuditReport:
    """Audit every recorded program spec.  Lowering/tracing only — no
    compile, no dispatch (``Stoke.audit()`` asserts dispatch-count
    equality on top of this contract) — EXCEPT the opt-in memory-drift
    gate below, whose measurement requires a compile.

    ``cost_manifest`` (ISSUE 18) arms the cost-drift gate: the parsed
    ``analysis/manifests/program_costs.json`` dict, against which every
    serve spec's re-lowered analytic FLOPs/bytes are compared
    (``cost_tolerance`` overrides the manifest's own tolerance).

    ``mem_manifest`` (ISSUE 19) arms the memory-drift gate the same way
    with ``analysis/manifests/program_memory.json``: every serve spec is
    re-COMPILED (``memory_analysis`` lives on the executable — supplying
    this manifest opts out of the no-compile contract for those specs)
    and its temp/peak bytes compared both directions at matching
    geometry signature (``mem_tolerance`` overrides the manifest's
    own)."""
    report = AuditReport()
    for spec in specs:
        report.programs.append(spec.program)
        _audit_one(
            spec,
            report.findings,
            transport_active=transport_active,
            comm_bytes=comm_bytes,
            replicated_bytes_threshold=replicated_bytes_threshold,
        )
    # recompile hazards are per-PROGRAM, not per-spec: the signature
    # count is the engine's churn ledger.  None means the ledger never
    # ran (the engine only tracks signatures when a telemetry
    # CompileTracker is attached) — say so instead of reporting a
    # silently-unchecked rule as clean
    if shape_sig_counts is None:
        report.notes.append(
            "audit-recompile-churn not checked: shape-signature "
            "tracking is off (add a TelemetryConfig to enable it)"
        )
    for program, count in (shape_sig_counts or {}).items():
        if count >= memo_cap:
            findings_msg = (
                f"program {program!r} hit the {memo_cap}-entry shape-"
                f"signature memo cap — recompile detection and the AOT "
                f"ledger have DISENGAGED for it"
            )
        elif count > churn_threshold:
            findings_msg = (
                f"program {program!r} has compiled {count} distinct "
                f"input-shape signatures (churn threshold "
                f"{churn_threshold}) — each new signature is a silent "
                f"full XLA recompile"
            )
        else:
            continue
        report.findings.append(
            Finding(
                rule="audit-recompile-churn",
                file=f"<jit:{program}>",
                line=0,
                message=findings_msg,
                remedy=(
                    "bucket/pad inputs to a bounded shape ladder (the "
                    "serve prefill_pad_multiple discipline) so the "
                    "program count stays finite"
                ),
            )
        )
    # cost-drift gate (ISSUE 18): armed only when a manifest is supplied
    # — the rule applies to serve specs (step-program cost has no pinned
    # manifest yet), and an unsupplied manifest is a note, not silence
    if cost_manifest is not None:
        tol = (
            cost_tolerance
            if cost_tolerance is not None
            else float(
                cost_manifest.get("tolerance", DEFAULT_COST_TOLERANCE)
            )
        )
        _audit_cost_drift(specs, report, cost_manifest, tol)
    elif any(spec.source == "serve" for spec in specs):
        report.notes.append(
            "audit-cost-drift not checked: no program-cost manifest "
            "supplied (scripts/stoke_lint.py --programs passes the "
            "committed analysis/manifests/program_costs.json)"
        )
    # memory-drift gate (ISSUE 19): armed only when a manifest is
    # supplied — same serve-spec scope and note-not-silence discipline as
    # the cost gate, but the measurement compiles (see docstring)
    if mem_manifest is not None:
        tol = (
            mem_tolerance
            if mem_tolerance is not None
            else float(
                mem_manifest.get("tolerance", DEFAULT_MEM_TOLERANCE)
            )
        )
        _audit_memory_drift(specs, report, mem_manifest, tol)
    elif any(spec.source == "serve" for spec in specs):
        report.notes.append(
            "audit-memory-drift not checked: no program-memory manifest "
            "supplied (scripts/stoke_lint.py --programs passes the "
            "committed analysis/manifests/program_memory.json)"
        )
    return report
