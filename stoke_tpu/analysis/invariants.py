"""Invariant linter: the repo's codified disciplines as machine-checked
rules (ISSUE 15 tentpole, linter half).

Every subsystem since PR 1 hand-writes the same correctness guards as
per-PR tests — append-only wire formats, "every config knob reachable by
a status rule", nullable JSONL fields, no ``device_get`` on hot paths,
jax-free driver modules — so each NEW PR can silently break a discipline
no test yet covers.  This module turns those conventions into static
checks over the source tree:

- **Wire-format append-only** (``wire-append-only``): the packed-vector
  layouts that cross process or version boundaries (``SENTINEL_FIELDS``,
  ``FLEET_SIGNALS``, ``NUMERICS_STATS``) are pinned in a committed
  manifest (``analysis/manifests/wire_formats.json``); the lint fails on
  any reorder/remove/insert, and on an append that did not update the
  manifest in the same PR (the manifest IS the reviewed wire contract).
- **Config-field status-rule coverage** (``config-guard``): every
  dataclass field in ``configs.py`` must be *reachable* from the
  validation layer — its name read as an attribute or named as an
  identifier string in ``status.py`` (or in ``configs.py``'s own
  resolver functions, e.g. ``comm_shard_updates``) — or explicitly
  waived with a reason in ``analysis/manifests/config_waivers.json``.
  The silently-ignored-knob anti-pattern, re-litigated in every PR
  since 2, becomes a lint failure.  Unknown waiver entries are
  themselves findings (``config-waiver-unknown``) — a stale waiver must
  not shadow a real regression.
- **Nullable-JSONL discipline** (``jsonl-schema``): every namespaced
  step-event key a subsystem's ``event_fields`` emitter can produce
  must exist in ``events.py``'s ``STEP_EVENT_FIELDS`` with a nullable,
  non-required kind (conditionally-emitted keys that the schema does
  not know are exactly how a dashboard breaks at 3am).
- **Banned APIs** (``banned-jax-import`` / ``banned-device-get``):
  module-scope ``jax``/``jaxlib`` imports in the jax-free modules (the
  supervisor/lint drivers: the chip belongs to one process, so
  a parent that starts chip-owning children stays off JAX — including
  this linter's own CLI), and
  ``device_get`` anywhere in the engine/serving hot paths (the
  zero-extra-dispatch sentinel discipline: diagnostics ride the
  compiled programs or the telemetry cadence, never a per-dispatch
  fetch).

Deliberately **jax-free and AST-based** (stdlib only: ``ast``, ``json``,
``os``, ``dataclasses``) so ``scripts/stoke_lint.py`` can load this file
directly (by FILE, bypassing the package ``__init__`` whose facade
import would pull jax in — as ``scripts/run_resilient.py`` loads
``resilience.py``) and run in CI before any backend exists.  The jax-dependent half — the
program auditor over lowered jaxpr/HLO step programs — lives in
:mod:`stoke_tpu.analysis.program` and shares this module's
:class:`Finding` type.
"""

from __future__ import annotations

import ast
import json
import os
from dataclasses import dataclass
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

#: linter identity, stamped into --json output
LINT_VERSION = "stoke_tpu.analysis/v1"

#: committed manifests (repo-relative)
WIRE_MANIFEST_PATH = "stoke_tpu/analysis/manifests/wire_formats.json"
CONFIG_WAIVERS_PATH = "stoke_tpu/analysis/manifests/config_waivers.json"

#: the config/validation pair the coverage rule reads
CONFIGS_PATH = "stoke_tpu/configs.py"
STATUS_PATH = "stoke_tpu/status.py"
#: the step-event schema the JSONL rule reads
EVENTS_SCHEMA_PATH = "stoke_tpu/telemetry/events.py"

#: modules that must never import jax/jaxlib at MODULE scope: the chip
#: belongs to one process at a time, so a supervisor or driver that has
#: touched JAX would hold the chip its worker needs (function-local
#: imports are fine — resilience.py's contract)
JAX_FREE_MODULES: Tuple[str, ...] = (
    "stoke_tpu/resilience.py",
    "stoke_tpu/analysis/invariants.py",  # the CLI loads THIS in-process
    "scripts/run_resilient.py",
    "scripts/stoke_lint.py",
)

#: hot-path modules where ``device_get`` is banned outright (fetches ride
#: the sentinel row / telemetry cadence instead — PR 3's discipline)
DEVICE_GET_BANNED_MODULES: Tuple[str, ...] = (
    "stoke_tpu/engine.py",
    "stoke_tpu/serving/engine.py",
)

#: modules whose ``event_fields``-family functions emit namespaced JSONL
#: keys conditionally (the nullable-block discipline)
JSONL_EMITTER_MODULES: Tuple[str, ...] = (
    "stoke_tpu/telemetry/fleet.py",
    "stoke_tpu/telemetry/numerics.py",
    "stoke_tpu/resilience.py",
    "stoke_tpu/serving/telemetry.py",
    "stoke_tpu/serving/slo.py",
    "stoke_tpu/serving/roofline.py",
    "stoke_tpu/telemetry/memory.py",
)
#: emitter function names the JSONL rule inspects
_JSONL_EMITTER_FNS = ("event_fields", "_event_fields", "_base_event_fields")
#: namespaced key prefixes that identify a conditionally-emitted field
_JSONL_NAMESPACES = ("fleet/", "resilience/", "serve/", "numerics/", "mem/")


@dataclass
class Finding:
    """One lint/audit violation: where, which rule, and — always — the
    remedy, named the way status.py rules name theirs.  Shared by the
    jax-free linter and the jax-side program auditor (whose findings use
    a ``<jit:program>`` pseudo-file and line 0)."""

    rule: str
    file: str
    line: int
    message: str
    remedy: str
    severity: str = "error"

    def format(self) -> str:
        return (
            f"{self.file}:{self.line}: [{self.rule}] {self.message} "
            f"— remedy: {self.remedy}"
        )

    def to_dict(self) -> Dict[str, Any]:
        return {
            "rule": self.rule,
            "file": self.file,
            "line": self.line,
            "message": self.message,
            "remedy": self.remedy,
            "severity": self.severity,
        }


# --------------------------------------------------------------------------- #
# shared AST helpers
# --------------------------------------------------------------------------- #


def _parse(path: str) -> ast.Module:
    with open(path, "r") as f:
        return ast.parse(f.read(), filename=path)


def _rel(repo_root: str, path: str) -> str:
    try:
        return os.path.relpath(path, repo_root)
    except ValueError:
        return path


def _find_tuple_assign(
    tree: ast.Module, name: str
) -> Optional[Tuple[List[str], int]]:
    """Top-level ``NAME = ("a", "b", ...)`` → (fields, lineno); None when
    the symbol is missing or not a literal string tuple/list."""
    for node in tree.body:
        if isinstance(node, ast.Assign):
            targets = [
                t.id for t in node.targets if isinstance(t, ast.Name)
            ]
            if name not in targets:
                continue
            if isinstance(node.value, (ast.Tuple, ast.List)) and all(
                isinstance(e, ast.Constant) and isinstance(e.value, str)
                for e in node.value.elts
            ):
                return (
                    [e.value for e in node.value.elts],
                    node.lineno,
                )
            return None
    return None


def _module_scope_walk(tree: ast.Module):
    """Yield nodes reachable WITHOUT entering a function/lambda body —
    module scope including ``if``/``try`` blocks, which is exactly where
    an eager import hides."""
    stack: List[ast.AST] = [tree]
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(
                child,
                (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda),
            ):
                continue
            stack.append(child)


# --------------------------------------------------------------------------- #
# rule: wire-format append-only
# --------------------------------------------------------------------------- #


def load_wire_manifest(repo_root: str) -> Optional[List[Dict[str, Any]]]:
    path = os.path.join(repo_root, WIRE_MANIFEST_PATH)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)["wire_formats"]


def check_wire_formats(
    repo_root: str,
    manifest: Optional[Sequence[Dict[str, Any]]] = None,
) -> List[Finding]:
    """Append-only wire formats: for each manifest entry ``{file, name,
    fields}``, the committed field list must be a PREFIX of the current
    tuple (reorder/remove/insert between pinned fields = a host on the
    old code misreads every later slot), and the current tuple must not
    have grown past the manifest without the manifest growing with it
    (the manifest is the reviewed contract, not a cache)."""
    findings: List[Finding] = []
    if manifest is None:
        manifest = load_wire_manifest(repo_root)
        if manifest is None:
            return [
                Finding(
                    rule="wire-append-only",
                    file=WIRE_MANIFEST_PATH,
                    line=0,
                    message="wire-format manifest is missing",
                    remedy=(
                        "commit analysis/manifests/wire_formats.json "
                        "seeded from the current SENTINEL_FIELDS / "
                        "FLEET_SIGNALS / NUMERICS_STATS tuples"
                    ),
                )
            ]
    for entry in manifest:
        rel = entry["file"]
        name = entry["name"]
        pinned = list(entry["fields"])
        path = os.path.join(repo_root, rel)
        if not os.path.exists(path):
            findings.append(
                Finding(
                    rule="wire-append-only",
                    file=rel,
                    line=0,
                    message=f"wire-format module {rel!r} not found",
                    remedy=(
                        f"restore the module or update the {name} entry "
                        f"in {WIRE_MANIFEST_PATH}"
                    ),
                )
            )
            continue
        found = _find_tuple_assign(_parse(path), name)
        if found is None:
            findings.append(
                Finding(
                    rule="wire-append-only",
                    file=rel,
                    line=0,
                    message=(
                        f"{name} is not a top-level literal string tuple "
                        f"(the lintable wire-format form)"
                    ),
                    remedy=(
                        f"keep {name} a module-level tuple of string "
                        f"literals so the append-only check can read it"
                    ),
                )
            )
            continue
        current, line = found
        if current[: len(pinned)] != pinned:
            # name the first divergent slot — that is the field a host on
            # the old layout would misread
            idx = next(
                (
                    i
                    for i, p in enumerate(pinned)
                    if i >= len(current) or current[i] != p
                ),
                0,
            )
            got = current[idx] if idx < len(current) else "<removed>"
            findings.append(
                Finding(
                    rule="wire-append-only",
                    file=rel,
                    line=line,
                    message=(
                        f"{name} is a wire format and its committed "
                        f"layout was reordered/removed: slot {idx} is "
                        f"pinned to {pinned[idx]!r} but the tree has "
                        f"{got!r} (hosts on mixed code versions would "
                        f"silently misread every later slot)"
                    ),
                    remedy=(
                        f"never reorder or remove {name} entries — "
                        f"append new fields at the end and keep old "
                        f"slots in place (docs/analysis.md, "
                        f"'append-only wire formats')"
                    ),
                )
            )
        elif len(current) > len(pinned):
            extra = current[len(pinned):]
            findings.append(
                Finding(
                    rule="wire-append-only",
                    file=rel,
                    line=line,
                    message=(
                        f"{name} grew {extra} past the committed "
                        f"manifest (append is legal but must be "
                        f"reviewed as a wire-format change)"
                    ),
                    remedy=(
                        f"append {extra} to the {name} entry in "
                        f"{WIRE_MANIFEST_PATH} in the same PR"
                    ),
                )
            )
    return findings


# --------------------------------------------------------------------------- #
# rule: config-field status coverage
# --------------------------------------------------------------------------- #


def _dataclass_fields(
    tree: ast.Module,
) -> Dict[str, List[Tuple[str, int]]]:
    """``{class_name: [(field, lineno), ...]}`` for every @dataclass in
    the module."""
    out: Dict[str, List[Tuple[str, int]]] = {}
    for node in tree.body:
        if not isinstance(node, ast.ClassDef):
            continue
        if not any(
            (isinstance(d, ast.Name) and d.id == "dataclass")
            or (isinstance(d, ast.Attribute) and d.attr == "dataclass")
            or (
                isinstance(d, ast.Call)
                and (
                    (
                        isinstance(d.func, ast.Name)
                        and d.func.id == "dataclass"
                    )
                    or (
                        isinstance(d.func, ast.Attribute)
                        and d.func.attr == "dataclass"
                    )
                )
            )
            for d in node.decorator_list
        ):
            continue
        fields = [
            (stmt.target.id, stmt.lineno)
            for stmt in node.body
            if isinstance(stmt, ast.AnnAssign)
            and isinstance(stmt.target, ast.Name)
        ]
        out[node.name] = fields
    return out


def _guarded_names(status_tree: ast.Module, configs_tree: ast.Module) -> set:
    """Names the validation layer can 'reach': attribute accesses on
    simple names (``cfg.dtype`` — NOT call results, string methods, or
    dotted modules like ``os.path.join``, whose ``.join``/``.get``
    would silently 'cover' any config field sharing a common method
    name) and identifier string constants (the ``getattr(cfg, name)``
    loop form) anywhere in status.py, plus the same inside configs.py's
    module-level FUNCTIONS (the resolver-function allowance —
    ``comm_shard_updates`` is the single source of truth status rules
    call into, so the fields it reads are guarded)."""

    def _collect(nodes, names):
        for node in nodes:
            if isinstance(node, ast.Attribute):
                # Name base: cfg.dtype; Subscript base: the rule-table
                # s["grad_clip"].clip_value form.  Calls, string
                # literals, and dotted modules stay excluded.
                if isinstance(node.value, (ast.Name, ast.Subscript)):
                    names.add(node.attr)
            elif isinstance(node, ast.Constant) and isinstance(
                node.value, str
            ):
                if node.value.isidentifier():
                    names.add(node.value)

    names: set = set()
    _collect(ast.walk(status_tree), names)
    for node in configs_tree.body:
        if isinstance(node, ast.FunctionDef):
            _collect(ast.walk(node), names)
    return names


def load_config_waivers(repo_root: str) -> Optional[Dict[str, str]]:
    path = os.path.join(repo_root, CONFIG_WAIVERS_PATH)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)["waivers"]


def check_config_coverage(
    repo_root: str,
    configs_path: Optional[str] = None,
    status_path: Optional[str] = None,
    waivers: Optional[Dict[str, str]] = None,
) -> List[Finding]:
    """Every dataclass field in configs.py must be reachable from a
    status.py rule (attribute access or identifier-string reference) or
    explicitly waived with a reason.  Waiver entries naming a class or
    field that does not exist are findings themselves — a stale waiver
    silently re-opens the hole it once documented."""
    findings: List[Finding] = []
    configs_path = configs_path or os.path.join(repo_root, CONFIGS_PATH)
    status_path = status_path or os.path.join(repo_root, STATUS_PATH)
    if waivers is None:
        waivers = load_config_waivers(repo_root)
        if waivers is None:
            return [
                Finding(
                    rule="config-guard",
                    file=CONFIG_WAIVERS_PATH,
                    line=0,
                    message="config-waiver manifest is missing",
                    remedy=(
                        "commit analysis/manifests/config_waivers.json "
                        "({\"waivers\": {\"Class.field\": \"reason\"}})"
                    ),
                )
            ]
    configs_tree = _parse(configs_path)
    status_tree = _parse(status_path)
    classes = _dataclass_fields(configs_tree)
    guarded = _guarded_names(status_tree, configs_tree)
    configs_rel = _rel(repo_root, configs_path)

    # loud waiver validation first: unknown entries are findings
    for key, reason in waivers.items():
        cls, _, fname = key.partition(".")
        known = cls in classes and fname in {f for f, _ in classes[cls]}
        if not known:
            findings.append(
                Finding(
                    rule="config-waiver-unknown",
                    file=CONFIG_WAIVERS_PATH,
                    line=0,
                    message=(
                        f"waiver names unknown config field {key!r} "
                        f"(reason on file: {reason!r})"
                    ),
                    remedy=(
                        "remove the stale waiver entry or fix its "
                        "Class.field spelling — a waiver that matches "
                        "nothing guards nothing"
                    ),
                )
            )
        elif not (isinstance(reason, str) and reason.strip()):
            findings.append(
                Finding(
                    rule="config-waiver-unknown",
                    file=CONFIG_WAIVERS_PATH,
                    line=0,
                    message=f"waiver {key!r} has no reason",
                    remedy=(
                        "every waiver documents WHY the knob needs no "
                        "status rule — write the reason"
                    ),
                )
            )

    for cls, fields in classes.items():
        for fname, line in fields:
            if fname in guarded:
                continue
            if f"{cls}.{fname}" in waivers:
                continue
            findings.append(
                Finding(
                    rule="config-guard",
                    file=configs_rel,
                    line=line,
                    message=(
                        f"{cls}.{fname} is not reachable from any "
                        f"status.py rule — an illegal or typo'd value "
                        f"would be silently ignored (the anti-pattern "
                        f"every PR since 2 re-litigates)"
                    ),
                    remedy=(
                        f"add a status.py rule that validates "
                        f"{cls}.{fname} (rejecting illegal combinations "
                        f"with the remedy named), or waive it with a "
                        f"reason in {CONFIG_WAIVERS_PATH}"
                    ),
                )
            )
    return findings


# --------------------------------------------------------------------------- #
# rule: nullable-JSONL discipline
# --------------------------------------------------------------------------- #


def _schema_fields(events_tree: ast.Module) -> Dict[str, Tuple[bool, str]]:
    """Parse ``STEP_EVENT_FIELDS`` from events.py's AST: ``{field:
    (required, kind)}``."""
    for node in events_tree.body:
        if isinstance(node, ast.AnnAssign) and isinstance(
            node.target, ast.Name
        ):
            if node.target.id != "STEP_EVENT_FIELDS":
                continue
            value = node.value
        elif isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == "STEP_EVENT_FIELDS"
            for t in node.targets
        ):
            value = node.value
        else:
            continue
        out: Dict[str, Tuple[bool, str]] = {}
        if isinstance(value, ast.Dict):
            for k, v in zip(value.keys, value.values):
                if not (
                    isinstance(k, ast.Constant)
                    and isinstance(k.value, str)
                    and isinstance(v, ast.Tuple)
                    and len(v.elts) == 2
                    and all(isinstance(e, ast.Constant) for e in v.elts)
                ):
                    continue
                out[k.value] = (bool(v.elts[0].value), str(v.elts[1].value))
        return out
    return {}


def _emitted_keys(tree: ast.Module) -> List[Tuple[str, int]]:
    """Namespaced string keys an ``event_fields``-family function can
    emit: literal dict keys and ``out["key"] = ...`` subscript stores."""
    keys: List[Tuple[str, int]] = []
    for node in ast.walk(tree):
        if (
            isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            and node.name in _JSONL_EMITTER_FNS
        ):
            for sub in ast.walk(node):
                if isinstance(sub, ast.Dict):
                    for k in sub.keys:
                        if isinstance(k, ast.Constant) and isinstance(
                            k.value, str
                        ):
                            keys.append((k.value, k.lineno))
                elif isinstance(sub, ast.Assign):
                    for t in sub.targets:
                        if (
                            isinstance(t, ast.Subscript)
                            and isinstance(t.slice, ast.Constant)
                            and isinstance(t.slice.value, str)
                        ):
                            keys.append((t.slice.value, t.lineno))
    return [
        (k, ln)
        for k, ln in keys
        if any(k.startswith(p) for p in _JSONL_NAMESPACES)
    ]


def check_jsonl_schema(
    repo_root: str,
    emitters: Optional[Sequence[str]] = None,
    schema_path: Optional[str] = None,
) -> List[Finding]:
    """Conditionally-emitted JSONL keys must exist in the step-event
    schema with a NULLABLE, non-required kind: a key the schema does not
    know fails validation at emit time (or worse, silently passes when
    validation is off and breaks every reader), and a required kind
    contradicts 'the field is absent without the config'."""
    findings: List[Finding] = []
    schema_path = schema_path or os.path.join(repo_root, EVENTS_SCHEMA_PATH)
    schema = _schema_fields(_parse(schema_path))
    if not schema:
        return [
            Finding(
                rule="jsonl-schema",
                file=_rel(repo_root, schema_path),
                line=0,
                message=(
                    "STEP_EVENT_FIELDS not found as a literal dict — the "
                    "JSONL discipline cannot be checked"
                ),
                remedy=(
                    "keep STEP_EVENT_FIELDS a module-level literal dict "
                    "of field -> (required, kind)"
                ),
            )
        ]
    for rel in emitters if emitters is not None else JSONL_EMITTER_MODULES:
        path = os.path.join(repo_root, rel)
        if not os.path.exists(path):
            continue
        for key, line in _emitted_keys(_parse(path)):
            if key not in schema:
                findings.append(
                    Finding(
                        rule="jsonl-schema",
                        file=rel,
                        line=line,
                        message=(
                            f"event_fields emits {key!r} which is not in "
                            f"events.py STEP_EVENT_FIELDS — "
                            f"validate_step_event would reject every "
                            f"record carrying it"
                        ),
                        remedy=(
                            f"declare {key!r} in STEP_EVENT_FIELDS with "
                            f"a nullable kind (and document its "
                            f"semantics there — the schema is the "
                            f"single source of truth)"
                        ),
                    )
                )
                continue
            required, kind = schema[key]
            if required or not kind.startswith("nullable"):
                findings.append(
                    Finding(
                        rule="jsonl-schema",
                        file=rel,
                        line=line,
                        message=(
                            f"conditionally-emitted key {key!r} is "
                            f"declared {'required' if required else ''}"
                            f"{' ' if required else ''}kind={kind!r} in "
                            f"the schema — but subsystem fields are "
                            f"ABSENT without their config, so the "
                            f"schema must allow that"
                        ),
                        remedy=(
                            f"declare {key!r} optional with a "
                            f"nullable_* kind in STEP_EVENT_FIELDS"
                        ),
                    )
                )
    return findings


# --------------------------------------------------------------------------- #
# rule: banned APIs
# --------------------------------------------------------------------------- #


def check_banned_apis(
    repo_root: str,
    jax_free: Optional[Sequence[str]] = None,
    no_device_get: Optional[Sequence[str]] = None,
) -> List[Finding]:
    """Module-scope jax imports in the jax-free drivers, and
    ``device_get`` anywhere in the engine/serving hot paths."""
    findings: List[Finding] = []
    for rel in jax_free if jax_free is not None else JAX_FREE_MODULES:
        path = os.path.join(repo_root, rel)
        if not os.path.exists(path):
            continue
        tree = _parse(path)
        for node in _module_scope_walk(tree):
            mods: List[str] = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module:
                mods = [node.module]
            for mod in mods:
                root = mod.split(".")[0]
                if root in ("jax", "jaxlib"):
                    findings.append(
                        Finding(
                            rule="banned-jax-import",
                            file=rel,
                            line=node.lineno,
                            message=(
                                f"module-scope import of {mod!r} in a "
                                f"jax-free module — the chip belongs to "
                                f"one process, and a supervisor/driver "
                                f"that touches JAX holds it against the "
                                f"worker it starts"
                            ),
                            remedy=(
                                "move the import inside the function "
                                "that needs it, or run the jax-"
                                "dependent work in a subprocess"
                            ),
                        )
                    )
    for rel in (
        no_device_get if no_device_get is not None
        else DEVICE_GET_BANNED_MODULES
    ):
        path = os.path.join(repo_root, rel)
        if not os.path.exists(path):
            continue
        for node in ast.walk(_parse(path)):
            hit = (
                isinstance(node, ast.Attribute)
                and node.attr == "device_get"
            ) or (isinstance(node, ast.Name) and node.id == "device_get")
            if hit:
                findings.append(
                    Finding(
                        rule="banned-device-get",
                        file=rel,
                        line=node.lineno,
                        message=(
                            "device_get in an engine/serving hot path — "
                            "a synchronous per-dispatch host fetch "
                            "breaks the zero-extra-dispatch sentinel "
                            "discipline (PR 3) and serializes the "
                            "async dispatch pipeline"
                        ),
                        remedy=(
                            "compute the value INSIDE the compiled "
                            "program and fetch it with the sentinel "
                            "row / telemetry cadence; save paths use "
                            "io_ops' collective-safe gather instead"
                        ),
                    )
                )
    return findings


# --------------------------------------------------------------------------- #
# the full lint
# --------------------------------------------------------------------------- #


def run_invariant_lints(repo_root: str) -> List[Finding]:
    """Run every jax-free rule over the tree; [] on a clean tree (the
    merged-tree contract ``make lint`` enforces)."""
    findings: List[Finding] = []
    findings += check_wire_formats(repo_root)
    findings += check_config_coverage(repo_root)
    findings += check_jsonl_schema(repo_root)
    findings += check_banned_apis(repo_root)
    return findings
