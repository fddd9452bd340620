"""stoke_lint: the repo's codified disciplines as a CLI (ISSUE 15).

One command, two halves:

- **Invariant linter** (default): the jax-free AST rules over the source
  tree — append-only wire formats against the committed manifest,
  config-knob status-rule coverage against the waiver file,
  nullable-JSONL schema discipline, and the banned-API rules
  (module-scope jax imports in jax-free modules — including THIS script
  — and ``device_get`` in engine/serving hot paths).
- **Program auditor** (``--programs``): builds a tiny live ``Stoke`` on
  the simulated CPU mesh in a SUBPROCESS, drives all four step APIs plus
  a serving engine, and runs ``Stoke.audit()`` over the lowered
  programs (donation integrity, hidden host round-trips, recompile
  hazards, sharding/collective accounting).

Usage (CI runs the default mode via ``make lint``):

    python scripts/stoke_lint.py                # lint the repo; exit 1 on findings
    python scripts/stoke_lint.py --json         # machine-readable findings
    python scripts/stoke_lint.py --programs     # + the live program audit (subprocess)

Exit codes: 0 clean, 1 findings, 2 usage/internal error.

Like ``scripts/run_resilient.py``, this
process NEVER imports jax (a parent that touches JAX holds the chip its
worker needs — and CI lint must not depend on a backend at all): the
linter module is loaded from ``stoke_tpu/analysis/invariants.py`` by
FILE, bypassing the package ``__init__`` whose facade import would pull
jax in, and the program audit runs in a subprocess with a pinned CPU
environment.  The linter's own banned-API rule enforces this contract
on this very file.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import subprocess
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)
_INVARIANTS_PY = os.path.join(
    _REPO, "stoke_tpu", "analysis", "invariants.py"
)


def _load_invariants(repo_root: str):
    """Load the linter by FILE (never through the package __init__ —
    that imports the facade and therefore jax)."""
    path = os.path.join(repo_root, "stoke_tpu", "analysis", "invariants.py")
    if not os.path.exists(path):
        path = _INVARIANTS_PY
    spec = importlib.util.spec_from_file_location(
        "_stoke_analysis_invariants", path
    )
    mod = importlib.util.module_from_spec(spec)
    # dataclass field-type resolution looks the module up in sys.modules
    # — register before exec
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


#: the subprocess body for --programs: build a tiny Stoke on the 8-device
#: CPU mesh, drive all four step APIs + a serve engine, audit, and print
#: one JSON line of findings.  Runs under a PINNED environment so it can
#: never claim a real accelerator.
_PROGRAM_WORKER = r"""
import json, sys
import numpy as np
import jax, jax.numpy as jnp
import optax
from stoke_tpu import Stoke

def model(p, x):
    return x @ p["w"]

def mse(o, y):
    return jnp.mean((o - y) ** 2)

def mk(**kw):
    return Stoke(model=model, optimizer=optax.sgd(0.1), loss=mse,
                 params={"w": np.zeros((8, 4), np.float32)},
                 batch_size_per_device=2, distributed="dp", verbose=False,
                 **kw)

rng = np.random.default_rng(0)
x = rng.normal(size=(16, 8)).astype(np.float32)
y = rng.normal(size=(16, 4)).astype(np.float32)

s = mk()
s.train_step(x, y)                                   # fused
out = s.model(x); s.backward(s.loss(out, y)); s.step()  # 4-call accum+apply
s2 = mk(grad_accum=2)
xs, ys = np.stack([x, x]), np.stack([y, y])
s2.train_step_window(xs, ys)                         # window
s2.train_steps(np.stack([xs, xs]), np.stack([ys, ys]))  # multi

# serving engine over a tiny GPT (the serve-program half)
from stoke_tpu.configs import ServeConfig
from stoke_tpu.models.gpt import GPT
from stoke_tpu.serving import ServingEngine
from stoke_tpu.utils import init_module
gpt = GPT(vocab_size=257, size_name="tiny", max_len=128, dropout_rate=0.0)
variables = init_module(gpt, jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32), train=False)
cfg = ServeConfig(max_seqs=2, kv_block_size=8, max_seq_len=64,
                  max_new_tokens=4, prefill_pad_multiple=16)
eng = ServingEngine(gpt, variables["params"], cfg)
eng.submit(np.array([5, 6, 7], np.int32))
eng.run()
# speculative + chunked engine (ISSUE 17): drives the serve_verify and
# serve_prefill_chunk_packed programs through the auditor too (s keeps
# the default engine, so the non-speculative serve_prefill/serve_decode
# programs stay covered)
spec_cfg = ServeConfig(max_seqs=2, kv_block_size=8, max_seq_len=64,
                       max_new_tokens=4, prefill_pad_multiple=16,
                       prefill_chunk_tokens=16, sampling=True,
                       speculative_k=3)
spec_eng = ServingEngine(gpt, variables["params"], spec_cfg)
spec_eng.submit(np.array([5, 9, 3] * 7, np.int32))  # 21 tokens -> 2 chunks
spec_eng.run()
# plain chunked engine (ISSUE 18): serve_prefill_chunk is the one serve
# program neither engine above dispatches (the speculative engine packs
# its chunks) — drive it so the cost manifest pins all five programs
chunk_cfg = ServeConfig(max_seqs=2, kv_block_size=8, max_seq_len=64,
                        max_new_tokens=4, prefill_pad_multiple=16,
                        prefill_chunk_tokens=16)
chunk_eng = ServingEngine(gpt, variables["params"], chunk_cfg)
chunk_eng.submit(np.array([5, 9, 3] * 7, np.int32))
chunk_eng.run()

# cost-drift gate (ISSUE 18): the committed analytic-cost manifest rides
# in via STOKE_COST_MANIFEST; the worker also reports every serve spec's
# measured cost so --update-costs can re-pin the manifest.  The
# memory-drift gate (ISSUE 19) mirrors it via STOKE_MEM_MANIFEST /
# --update-mem over memory_analysis temp/peak bytes.
import os
cost_manifest = None
manifest_path = os.environ.get("STOKE_COST_MANIFEST")
if manifest_path:
    with open(manifest_path) as fh:
        cost_manifest = json.load(fh)
mem_manifest = None
mem_path = os.environ.get("STOKE_MEM_MANIFEST")
if mem_path:
    with open(mem_path) as fh:
        mem_manifest = json.load(fh)

from stoke_tpu.analysis.program import (
    audit_program_specs,
    spec_cost_entry,
    spec_memory_entry,
)

findings = []
programs = []
notes = []
costs = {}
mems = {}
for st, serve_eng in ((s, eng), (s2, spec_eng)):
    before = st.dispatch_count
    rep = st.audit(serve=serve_eng, cost_manifest=cost_manifest,
                   mem_manifest=mem_manifest)
    assert st.dispatch_count == before, "audit dispatched a program"
    findings += [f.to_dict() for f in rep.findings]
    programs += rep.programs
    notes += rep.notes
# the chunked engine rides a standalone serve-spec audit (its step-side
# twin is already covered above)
rep = audit_program_specs(chunk_eng.audit_specs(),
                          cost_manifest=cost_manifest,
                          mem_manifest=mem_manifest)
findings += [f.to_dict() for f in rep.findings]
programs += rep.programs
# engines share programs (serve_decode is dispatched by two of them) —
# one defect, one finding
deduped, seen_f = [], set()
for f in findings:
    key = (f["rule"], f["file"], f["message"])
    if key not in seen_f:
        seen_f.add(key)
        deduped.append(f)
findings = deduped
for serve_eng in (eng, spec_eng, chunk_eng):
    for spec in serve_eng.audit_specs():
        if spec.program not in costs:
            entry = spec_cost_entry(spec)
            if entry is not None:
                costs[spec.program] = entry
        if spec.program not in mems:
            entry = spec_memory_entry(spec)
            if entry is not None:
                mems[spec.program] = entry
print(json.dumps({"programs": programs, "findings": findings,
                  "notes": notes, "costs": costs, "mems": mems}))
"""

#: the committed analytic-cost manifest the drift gate compares against
_COST_MANIFEST = os.path.join(
    "stoke_tpu", "analysis", "manifests", "program_costs.json"
)

#: the committed program-memory manifest (ISSUE 19) the memory-drift
#: gate compares against
_MEM_MANIFEST = os.path.join(
    "stoke_tpu", "analysis", "manifests", "program_memory.json"
)


def run_program_audit(
    repo_root: str,
    cost_manifest_path: str | None = None,
    mem_manifest_path: str | None = None,
) -> dict:
    """Spawn the jax-side program audit with a pinned CPU environment;
    returns the worker's JSON payload.  ``cost_manifest_path`` arms the
    audit-cost-drift gate and ``mem_manifest_path`` the
    audit-memory-drift gate (each defaults to its committed manifest
    when it exists; pass "" to disarm)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    if cost_manifest_path is None:
        default = os.path.join(repo_root, _COST_MANIFEST)
        cost_manifest_path = default if os.path.exists(default) else ""
    if cost_manifest_path:
        env["STOKE_COST_MANIFEST"] = os.path.abspath(cost_manifest_path)
    else:
        env.pop("STOKE_COST_MANIFEST", None)
    if mem_manifest_path is None:
        default = os.path.join(repo_root, _MEM_MANIFEST)
        mem_manifest_path = default if os.path.exists(default) else ""
    if mem_manifest_path:
        env["STOKE_MEM_MANIFEST"] = os.path.abspath(mem_manifest_path)
    else:
        env.pop("STOKE_MEM_MANIFEST", None)
    proc = subprocess.run(
        [sys.executable, "-c", _PROGRAM_WORKER],
        capture_output=True,
        text=True,
        env=env,
        cwd=repo_root,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"program-audit worker failed (exit {proc.returncode}):\n"
            f"{proc.stderr[-2000:]}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="stoke_tpu invariant linter + program auditor"
    )
    ap.add_argument(
        "--repo-root",
        default=_REPO,
        help="tree to lint (default: this script's repo)",
    )
    ap.add_argument(
        "--json",
        action="store_true",
        help="emit one JSON object instead of human-readable lines",
    )
    ap.add_argument(
        "--programs",
        action="store_true",
        help="also run the live program audit (subprocess, CPU mesh)",
    )
    ap.add_argument(
        "--cost-manifest",
        default=None,
        metavar="PATH",
        help="program-cost manifest for the audit-cost-drift gate "
        "(default: the committed "
        "stoke_tpu/analysis/manifests/program_costs.json; pass an "
        "empty string to disarm)",
    )
    ap.add_argument(
        "--update-costs",
        action="store_true",
        help="with --programs: rewrite the committed program-cost "
        "manifest from the live engines' measured analytic costs "
        "(run after an INTENTIONAL serve-program cost change)",
    )
    ap.add_argument(
        "--mem-manifest",
        default=None,
        metavar="PATH",
        help="program-memory manifest for the audit-memory-drift gate "
        "(default: the committed "
        "stoke_tpu/analysis/manifests/program_memory.json; pass an "
        "empty string to disarm)",
    )
    ap.add_argument(
        "--update-mem",
        action="store_true",
        help="with --programs: rewrite the committed program-memory "
        "manifest from the live engines' measured memory_analysis "
        "temp/peak bytes (run after an INTENTIONAL footprint change)",
    )
    args = ap.parse_args(argv)
    repo_root = os.path.abspath(args.repo_root)
    if not os.path.isdir(repo_root):
        print(f"stoke_lint: no such directory {repo_root!r}", file=sys.stderr)
        return 2

    if args.update_costs and not args.programs:
        print("stoke_lint: --update-costs requires --programs",
              file=sys.stderr)
        return 2
    if args.update_mem and not args.programs:
        print("stoke_lint: --update-mem requires --programs",
              file=sys.stderr)
        return 2

    inv = _load_invariants(repo_root)
    findings = [f.to_dict() for f in inv.run_invariant_lints(repo_root)]
    programs = []
    if args.programs:
        try:
            payload = run_program_audit(
                repo_root,
                # an update pass must MEASURE, not judge against the
                # stale pins it is about to replace
                cost_manifest_path="" if args.update_costs
                else args.cost_manifest,
                mem_manifest_path="" if args.update_mem
                else args.mem_manifest,
            )
        except Exception as e:
            print(f"stoke_lint: {e}", file=sys.stderr)
            return 2
        findings += payload["findings"]
        programs = payload["programs"]
        if args.update_costs:
            manifest_path = os.path.join(repo_root, _COST_MANIFEST)
            manifest = {
                "_comment": [
                    "ISSUE 18 analytic program-cost manifest: the",
                    "audit-cost-drift gate re-lowers every serve program",
                    "and compares its XLA cost analysis (FLOPs / bytes",
                    "accessed) against these pins at matching argument-",
                    "geometry signature.  Deviations beyond the tolerance",
                    "fail CI in BOTH directions (golden-file semantics).",
                    "Regenerate after an INTENTIONAL cost change with:",
                    "  python scripts/stoke_lint.py --programs --update-costs",
                ],
                "tolerance": 0.05,
                "programs": dict(sorted(payload["costs"].items())),
            }
            with open(manifest_path, "w") as fh:
                json.dump(manifest, fh, indent=2)
                fh.write("\n")
            print(
                f"stoke_lint: pinned {len(manifest['programs'])} "
                f"program cost(s) -> {manifest_path}"
            )
        if args.update_mem:
            manifest_path = os.path.join(repo_root, _MEM_MANIFEST)
            manifest = {
                "_comment": [
                    "ISSUE 19 program-memory manifest: the",
                    "audit-memory-drift gate re-compiles every serve",
                    "program and compares its memory_analysis temp/peak",
                    "bytes against these pins at matching argument-",
                    "geometry signature.  Deviations beyond the tolerance",
                    "fail CI in BOTH directions (golden-file semantics;",
                    "looser than the cost gate — XLA temp allocation",
                    "shifts more across versions than analytic FLOPs).",
                    "Regenerate after an INTENTIONAL footprint change:",
                    "  python scripts/stoke_lint.py --programs --update-mem",
                ],
                "tolerance": 0.25,
                "programs": dict(sorted(payload["mems"].items())),
            }
            with open(manifest_path, "w") as fh:
                json.dump(manifest, fh, indent=2)
                fh.write("\n")
            print(
                f"stoke_lint: pinned {len(manifest['programs'])} "
                f"program memory entr(y/ies) -> {manifest_path}"
            )

    if args.json:
        print(
            json.dumps(
                {
                    "version": inv.LINT_VERSION,
                    "findings": findings,
                    "programs_audited": programs,
                }
            )
        )
    else:
        for f in findings:
            print(
                f"{f['file']}:{f['line']}: [{f['rule']}] {f['message']} "
                f"— remedy: {f['remedy']}"
            )
        tail = f", {len(programs)} program(s) audited" if args.programs else ""
        print(f"stoke_lint: {len(findings)} finding(s){tail}")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
