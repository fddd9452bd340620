"""Regenerate docs/api.md from the public API surface.

Usage (CPU-safe):
    env JAX_PLATFORMS=cpu python scripts/gen_api_md.py
    env JAX_PLATFORMS=cpu python scripts/gen_api_md.py --check

``--check`` (ISSUE 15 satellite) renders the current surface and
compares it to the committed file WITHOUT writing: exit 2 on drift, so
"regenerate api.md" stops being an honor-system step — CI's lint job
runs it before pytest.
"""

from __future__ import annotations

import argparse
import inspect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_OUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "docs",
    "api.md",
)

STOKE_METHODS = [
    "model", "loss", "backward", "step", "train_step", "train_step_window",
    "train_steps", "DataLoader", "serve", "save", "load", "maybe_resume",
    "resume", "barrier", "audit",
    "print_ema_loss", "print_mean_accumulated_loss", "detach_and_sync_loss",
    "profile_trace", "estimate_step_flops", "estimate_step_cost",
    "print_wall_clock_breakdown", "trace_summary", "export_trace",
    "numerics_summary", "topology_descriptor",
    "reset", "train", "eval", "print_status", "print_num_model_parameters",
    "dump_model_parameter_info", "block_until_ready",
]


def _kind(obj) -> str:
    if inspect.isclass(obj):
        return "class"
    if isinstance(obj, property):
        return "property"
    return "fn" if callable(obj) else "value"


def one_liner(obj) -> str:
    if isinstance(obj, property):
        # properties are neither classes nor callable; their doc lives on
        # the getter — without this they render as "`property` constant"
        obj = obj.fget
    if not (inspect.isclass(obj) or callable(obj)):
        # plain values inherit their type's docstring (dict, tuple...) —
        # useless noise; describe the constant instead
        return f"`{type(obj).__name__}` constant"
    doc = inspect.getdoc(obj) or ""
    # first paragraph (blank-line delimited), newlines collapsed — a first
    # SOURCE line would cut wrapped sentences mid-phrase
    para = doc.split("\n\n", 1)[0].strip()
    line = " ".join(para.split())
    if len(line) > 160:
        # truncate at a word boundary, never inside an inline-code span
        # (a dangling `` garbles the whole markdown table row)
        cut = line[:157].rsplit(" ", 1)[0]
        if cut.count("`") % 2:
            cut = cut.rsplit("`", 1)[0].rstrip("`").rstrip()
        line = cut + "..."
    return line if line else "—"


def render() -> str:
    """The full api.md content for the live API surface."""
    import stoke_tpu

    rows = []
    for name in stoke_tpu.__all__:
        obj = getattr(stoke_tpu, name)
        rows.append(f"| `{name}` ({_kind(obj)}) | {one_liner(obj)} |")

    method_rows = []
    for name in STOKE_METHODS:
        m = getattr(stoke_tpu.Stoke, name, None)
        if m is None:
            continue
        method_rows.append(f"| `Stoke.{name}` | {one_liner(m)} |")

    from stoke_tpu import models, ops, parallel

    extra = []
    for mod, label in ((models, "stoke_tpu.models"), (ops, "stoke_tpu.ops"),
                       (parallel, "stoke_tpu.parallel")):
        extra.append(f"\n## `{label}`\n")
        extra.append("| Symbol | Summary |\n|---|---|")
        for name in getattr(mod, "__all__", []):
            obj = getattr(mod, name)
            extra.append(f"| `{name}` ({_kind(obj)}) | {one_liner(obj)} |")

    return (
        "# API reference (summary)\n\n"
        "Generated by `scripts/gen_api_md.py` — do not edit by hand.\n\n"
        "## `stoke_tpu`\n\n| Symbol | Summary |\n|---|---|\n"
        + "\n".join(rows)
        + "\n\n## `Stoke` methods\n\n| Method | Summary |\n|---|---|\n"
        + "\n".join(method_rows)
        + "\n"
        + "\n".join(extra)
        + "\n"
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="regenerate docs/api.md")
    ap.add_argument(
        "--check",
        action="store_true",
        help="compare against the committed file, write nothing; "
        "exit 2 on drift",
    )
    ap.add_argument(
        "--out",
        default=DEFAULT_OUT,
        help="target file (default: docs/api.md)",
    )
    args = ap.parse_args(argv)
    content = render()
    if args.check:
        try:
            with open(args.out) as f:
                committed = f.read()
        except OSError:
            print(f"gen_api_md --check: {args.out} is missing")
            return 2
        if committed != content:
            print(
                f"gen_api_md --check: {args.out} is STALE — regenerate "
                f"with `make lint`'s sibling step: env PYTHONPATH=. "
                f"JAX_PLATFORMS=cpu python scripts/gen_api_md.py"
            )
            return 2
        print(f"gen_api_md --check: {args.out} is up to date")
        return 0
    with open(args.out, "w") as f:
        f.write(content)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
