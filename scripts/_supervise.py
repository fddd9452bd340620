"""Shared watchdog supervisor for the measurement scripts.

The chip belongs to one process at a time: a parent that has touched JAX
holds it, and a child that needs it then fails or hangs.  ``supervise``
therefore never imports jax and starts no other client: it runs the real
measurement (``<script> --_worker ...``) as the only chip-owning process,
under a watchdog, so callers get an error line instead of a hang.

Two watchdogs (kill only on evidence of a hang — a total-wall-clock kill
alone rations healthy-but-slow sessions):

- ``idle_seconds``: no worker stdout for this long means a hang (every
  measurement phase prints a JSON line when it completes); this is the
  primary kill.
- ``watchdog_seconds``: absolute backstop.

The worker's environment carries ``STOKE_SESSION_DEADLINE`` (epoch seconds
of the absolute backstop) so long-running workers can budget optional extra
phases (e.g. accuracy_run's f32 retry) against the REAL remaining time.
"""

from __future__ import annotations

import codecs
import json
import os
import selectors
import subprocess
import sys
import tempfile
import time

#: exit code of a worker killed by the stoke health watchdog — kept in sync
#: with stoke_tpu/telemetry/health.py WATCHDOG_EXIT_CODE (duplicated here
#: because this module must never import jax-importing packages)
HEALTH_WATCHDOG_EXIT_CODE = 113

#: exit code of a worker that was preempted and drained cleanly (emergency
#: checkpoint written) — kept in sync with stoke_tpu/resilience.py
#: PREEMPTION_EXIT_CODE.  Distinct from 113: the supervisor can tell
#: "drained, resume from the emergency tag" from "hung and self-killed".
PREEMPTION_EXIT_CODE = 114

#: env var the flight recorder appends bundle paths to (kept in sync with
#: stoke_tpu/telemetry/recorder.py BUNDLE_FILE_ENV)
BUNDLE_FILE_ENV = "STOKE_HEALTH_BUNDLE_FILE"


def _read_bundles(path: str) -> list[str]:
    """Bundle paths the worker's flight recorder reported (empty when no
    bundle was written or the handshake file is unreadable)."""
    try:
        with open(path) as f:
            return [ln.strip() for ln in f if ln.strip()]
    except OSError:
        return []


def supervise(
    script_file: str,
    argv,
    watchdog_seconds: int = 2400,
    idle_seconds: int | None = None,
) -> int:
    deadline = time.time() + watchdog_seconds
    # health-bundle handshake: a worker running with HealthConfig appends
    # every post-mortem bundle path to this file, so a kill (ours or the
    # in-process hang watchdog's) still surfaces WHERE the corpse is
    bundle_fd, bundle_file = tempfile.mkstemp(prefix="stoke-bundles-")
    os.close(bundle_fd)
    env = {
        **os.environ,
        "STOKE_SESSION_DEADLINE": repr(deadline),
        BUNDLE_FILE_ENV: bundle_file,
    }
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(script_file), "--_worker", *argv],
        stdout=subprocess.PIPE,
        env=env,
    )
    # Non-blocking relay: a blocking readline() after select()
    # stalls until a full line arrives, so a worker wedging after a PARTIAL
    # line would disable both watchdogs.  os.read() on a non-blocking fd
    # always returns control to the watchdog loop.
    fd = proc.stdout.fileno()
    os.set_blocking(fd, False)
    sel = selectors.DefaultSelector()
    sel.register(proc.stdout, selectors.EVENT_READ)
    last_output = time.time()
    why = None
    eof = False
    # incremental decoder: a multi-byte UTF-8 char straddling a 64 KiB read
    # boundary must not decode to replacement chars mid-line
    decoder = codecs.getincrementaldecoder("utf-8")(errors="replace")

    def _relay() -> None:
        nonlocal last_output, eof
        while not eof:
            try:
                chunk = os.read(fd, 65536)
            except BlockingIOError:
                return
            except OSError:
                eof = True
                return
            if not chunk:
                # EOF with the worker possibly still alive (stdout closed/
                # redirected): unregister, or select() reports the dead fd
                # ready forever and this loop busy-spins until a watchdog
                eof = True
                sel.unregister(proc.stdout)
                sys.stdout.write(decoder.decode(b"", final=True))
                sys.stdout.flush()
                return
            sys.stdout.write(decoder.decode(chunk))
            sys.stdout.flush()
            last_output = time.time()

    try:
        while True:
            if eof:
                time.sleep(5)
            elif sel.select(timeout=5):
                _relay()
            if proc.poll() is not None:
                _relay()
                if proc.returncode == HEALTH_WATCHDOG_EXIT_CODE:
                    # the worker's in-process hang watchdog killed it: a
                    # distinct, diagnosable outcome (wedged collective),
                    # with the post-mortem bundle attached
                    print(json.dumps({
                        "error": (
                            "worker killed by stoke health watchdog "
                            f"(exit {HEALTH_WATCHDOG_EXIT_CODE}: no step "
                            "completed within its timeout)"
                        ),
                        "watchdog_exit_code": HEALTH_WATCHDOG_EXIT_CODE,
                        "bundles": _read_bundles(bundle_file),
                    }))
                elif proc.returncode == PREEMPTION_EXIT_CODE:
                    # preempted and drained cleanly (ISSUE 7): the worker
                    # wrote an emergency checkpoint and exited resumably —
                    # scripts/run_resilient.py restarts these; here we
                    # surface the outcome so a bare supervise caller knows
                    # the run is resumable, not broken
                    print(json.dumps({
                        "error": (
                            "worker preempted and drained cleanly "
                            f"(exit {PREEMPTION_EXIT_CODE}: emergency "
                            "checkpoint written; resumable via "
                            "Stoke.resume() / scripts/run_resilient.py)"
                        ),
                        "preemption_exit_code": PREEMPTION_EXIT_CODE,
                        "resumable": True,
                        "bundles": _read_bundles(bundle_file),
                    }))
                return proc.returncode
            now = time.time()
            if now > deadline:
                why = f"timed out after {watchdog_seconds}s (absolute backstop)"
                break
            if idle_seconds and now - last_output > idle_seconds:
                why = f"no output for {idle_seconds}s (worker hung)"
                break
    finally:
        sel.close()
        bundles = _read_bundles(bundle_file)
        try:
            os.remove(bundle_file)
        except OSError:
            pass
    proc.kill()
    proc.wait()
    err = {"error": why}
    if bundles:
        # a post-mortem bundle beats a bare "timed out": point at it
        err["bundles"] = bundles
    print(json.dumps(err))
    return 1
