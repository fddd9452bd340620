"""Supervised restarts with backoff: the restart half of ISSUE 7's
detect→save→restart→resume loop.

Runs a training command under a bounded restart loop:

    python scripts/run_resilient.py --max-restarts 8 --record restarts.jsonl \
        -- python my_train.py --flags...

- **Classification**: the worker's exit code decides the next move.
  ``0`` = done.  The resumable codes — the health watchdog's 113 ("hung
  and self-killed"; a fresh process usually un-wedges it), the preemption
  drain's 114 ("emergency checkpoint written"), and signal deaths
  (negative returncodes: SIGKILL'd by a preempted VM or the OOM killer) —
  restart after a backoff.  Everything else (including a generic python
  crash, e.g. a status-validation error) is FATAL: restarting a
  deterministic bug burns the restart budget without ever progressing.
- **Backoff**: exponential with jitter (``RestartBackoff`` — a fleet of
  preempted workers must not restart in lockstep) and a restart budget.
- **Records**: one JSONL line per attempt (exit code, classification,
  backoff delay, flight-recorder bundle paths via the
  ``STOKE_HEALTH_BUNDLE_FILE`` handshake, and — when a bundle carries a
  ``fleet.json`` — the fleet straggler verdict, so the restart record
  shows WHY the host died, not just that it did).
- **Attempt number**: each restart runs with ``STOKE_RESTART_ATTEMPT=<n>``
  so the worker's ``resilience/restarts`` gauge and JSONL column reflect
  the supervision history.

The worker is expected to call ``Stoke.resume()`` at startup (or
``maybe_resume``) so a restart continues from the emergency checkpoint
instead of step 0 — see docs/multihost.md "Surviving preemption".

This process NEVER imports jax (the chip belongs to one process — the worker):
the jax-free resilience primitives are loaded from
``stoke_tpu/resilience.py`` by FILE, bypassing the package ``__init__``
whose facade import would pull jax in.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import random
import subprocess
import sys
import tempfile
import time
from typing import Any, Callable, Dict, Optional, Sequence

_HERE = os.path.dirname(os.path.abspath(__file__))
_RESILIENCE_PY = os.path.join(
    os.path.dirname(_HERE), "stoke_tpu", "resilience.py"
)

#: env var the flight recorder appends bundle paths to (kept in sync with
#: stoke_tpu/telemetry/recorder.py BUNDLE_FILE_ENV, which this jax-free
#: process cannot import)
BUNDLE_FILE_ENV = "STOKE_HEALTH_BUNDLE_FILE"


def _read_bundles(path: str) -> list[str]:
    """Bundle paths the worker's flight recorder reported (empty when no
    bundle was written or the handshake file is unreadable)."""
    try:
        with open(path) as f:
            return [ln.strip() for ln in f if ln.strip()]
    except OSError:
        return []


def load_resilience():
    """The jax-free resilience primitives (RestartBackoff, classify_exit,
    RESTART_ATTEMPT_ENV, ...) loaded by file path — the package __init__
    imports the facade, which imports jax."""
    spec = importlib.util.spec_from_file_location(
        "_stoke_resilience_supervisor", _RESILIENCE_PY
    )
    mod = importlib.util.module_from_spec(spec)
    # registered BEFORE exec: the @dataclass decorator inside resolves its
    # defining module through sys.modules at class-creation time
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _lost_goodput_estimate(bundles: Sequence[str]) -> Optional[dict]:
    """Restart-cost estimate from the NEWEST bundle carrying the
    preemption dump's accounting (ISSUE 14 satellite): the dying worker
    stamps ``step_ema_s`` (host-wall EMA of one optimizer step) and
    ``lost_steps_estimate`` (0 when the emergency save landed; steps since
    the last durable save when it failed) into the bundle manifest — their
    product prices the attempt's lost goodput in seconds without replaying
    any JSONL.  None when no bundle carries the fields."""
    for bundle in reversed(list(bundles)):
        try:
            with open(os.path.join(bundle, "manifest.json")) as f:
                extra = (json.load(f) or {}).get("extra") or {}
        except (OSError, ValueError):
            continue
        lost = extra.get("lost_steps_estimate")
        ema = extra.get("step_ema_s")
        if lost is None:
            continue
        out = {"lost_steps_estimate": int(lost)}
        if ema is not None:
            out["step_ema_s"] = round(float(ema), 6)
            out["lost_goodput_s_est"] = round(int(lost) * float(ema), 3)
        return out
    return None


def _fleet_verdict(bundles: Sequence[str]) -> Optional[dict]:
    """The fleet straggler verdict of the NEWEST bundle carrying one
    (ISSUE 5's fleet.json) — surfaces WHY the host died in the restart
    record.  None when no bundle has a fleet view."""
    for bundle in reversed(list(bundles)):
        try:
            with open(os.path.join(bundle, "fleet.json")) as f:
                fleet = json.load(f)
        except (OSError, ValueError):
            continue
        verdict = fleet.get("verdict") or fleet.get("last_verdict")
        if verdict:
            return verdict
    return None


def _default_run(argv: Sequence[str], env: Dict[str, str]) -> int:
    """Run one worker attempt to completion, relaying its streams."""
    proc = subprocess.Popen(list(argv), env=env)
    return proc.wait()


def run_resilient(
    argv: Sequence[str],
    *,
    max_restarts: int = 8,
    base_s: float = 1.0,
    factor: float = 2.0,
    max_s: float = 60.0,
    jitter_frac: float = 0.5,
    extra_resumable: Sequence[int] = (),
    record_path: Optional[str] = None,
    seed: Optional[int] = None,
    env: Optional[Dict[str, str]] = None,
    run: Callable[[Sequence[str], Dict[str, str]], int] = _default_run,
    sleep: Callable[[float], None] = time.sleep,
) -> Dict[str, Any]:
    """Drive ``argv`` under the bounded restart loop; returns a summary
    dict (``ok`` / ``fatal`` / ``exhausted``, attempts, records).

    ``run`` and ``sleep`` are injectable so the backoff/budget tests run
    deterministic and instantaneous (no subprocesses, no real sleeps);
    ``seed`` pins the jitter rng.
    """
    rz = load_resilience()
    backoff = rz.RestartBackoff(
        base_s=base_s,
        factor=factor,
        max_s=max_s,
        jitter_frac=jitter_frac,
        max_restarts=max_restarts,
        rng=random.Random(seed) if seed is not None else None,
    )
    records = []
    attempt = 0
    outcome: Dict[str, Any] = {"ok": False}
    while True:
        bundle_fd, bundle_file = tempfile.mkstemp(prefix="stoke-bundles-")
        os.close(bundle_fd)
        attempt_env = {
            **(env if env is not None else os.environ),
            rz.RESTART_ATTEMPT_ENV: str(attempt),
            BUNDLE_FILE_ENV: bundle_file,
        }
        t0 = time.monotonic()
        code = run(argv, attempt_env)
        elapsed_s = time.monotonic() - t0
        bundles = _read_bundles(bundle_file)
        try:
            os.remove(bundle_file)
        except OSError:
            pass
        classification = rz.classify_exit(code, extra_resumable)
        record = {
            "attempt": attempt,
            "exit_code": code,
            "class": classification,
            # restart cost, readable straight off the record (ISSUE 14):
            # attempt wall clock + the bundle-priced lost-goodput estimate
            "elapsed_s": round(elapsed_s, 3),
            "bundles": bundles,
            "restarts_used": backoff.restarts_used,
        }
        cost = _lost_goodput_estimate(bundles)
        if cost is not None:
            record.update(cost)
        verdict = _fleet_verdict(bundles)
        if verdict is not None:
            record["fleet_verdict"] = verdict
        if classification == "ok":
            outcome = {"ok": True}
        elif classification == "fatal":
            outcome = {"ok": False, "fatal": True, "exit_code": code}
        else:
            delay = backoff.next_delay()
            if delay is None:
                outcome = {
                    "ok": False,
                    "exhausted": True,
                    "exit_code": code,
                    "max_restarts": max_restarts,
                }
            else:
                record["backoff_s"] = round(delay, 3)
        records.append(record)
        if record_path:
            with open(record_path, "a") as f:
                f.write(json.dumps(record) + "\n")
        sys.stderr.write(
            f"run_resilient: attempt {attempt} exited {code} "
            f"({classification})"
            + (f"; restarting in {record['backoff_s']}s" if "backoff_s" in record else "")
            + "\n"
        )
        if "backoff_s" not in record:
            break
        sleep(record["backoff_s"])
        attempt += 1
    outcome["attempts"] = attempt + 1
    outcome["restarts"] = attempt
    outcome["records"] = records
    return outcome


def main() -> int:
    ap = argparse.ArgumentParser(
        description="bounded restart supervisor (ISSUE 7): restarts "
        "resumable worker deaths (preemption 114 / watchdog 113 / signal "
        "kills) with exponential backoff; fatal exits stop immediately",
    )
    ap.add_argument("--max-restarts", type=int, default=8)
    ap.add_argument("--base-s", type=float, default=1.0,
                    help="first backoff delay (doubles per restart)")
    ap.add_argument("--max-s", type=float, default=60.0,
                    help="backoff ceiling")
    ap.add_argument("--jitter-frac", type=float, default=0.5,
                    help="additive-uniform jitter as a fraction of the "
                    "delay (de-synchronizes fleet restarts)")
    ap.add_argument("--extra-resumable", type=int, nargs="*", default=[],
                    help="additional exit codes to classify as resumable")
    ap.add_argument("--record", default=None,
                    help="append one JSONL restart record per attempt here")
    ap.add_argument("--seed", type=int, default=None,
                    help="pin the jitter rng (tests)")
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="worker command (prefix with --)")
    args = ap.parse_args()
    cmd = args.cmd
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        ap.error("no worker command given (append: -- python train.py ...)")
    outcome = run_resilient(
        cmd,
        max_restarts=args.max_restarts,
        base_s=args.base_s,
        max_s=args.max_s,
        jitter_frac=args.jitter_frac,
        extra_resumable=args.extra_resumable,
        record_path=args.record,
        seed=args.seed,
    )
    summary = {k: v for k, v in outcome.items() if k != "records"}
    print(json.dumps({"run_resilient": summary}))
    if outcome.get("ok"):
        return 0
    # surface the worker's own fatal code where there is one (a wrapper
    # swallowing exit codes makes outer supervisors blind); signal deaths
    # map to the shell convention 128+signum — a raw negative status
    # truncates mod 256 into a meaningless code
    code = int(outcome.get("exit_code") or 1)
    return 128 - code if code < 0 else code


if __name__ == "__main__":
    sys.exit(main())
