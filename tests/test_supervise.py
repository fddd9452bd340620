"""Tests for scripts/_supervise.py — the measurement watchdogs.

A worker that wedges after writing a PARTIAL line (no trailing newline)
must still trip the idle watchdog; a blocking readline() after select()
would stall the supervisor inside the read and disable both watchdogs.
"""

import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.join(
    __file__.rsplit("/tests/", 1)[0], "scripts"))
import _supervise  # noqa: E402
from _supervise import supervise  # noqa: E402


def test_supervise_starts_no_second_client(tmp_path, monkeypatch):
    """The chip belongs to one process: the supervisor starts the worker
    and nothing else (no device probe ahead of it)."""
    started = []
    real_popen = _supervise.subprocess.Popen

    def popen(cmd, *a, **k):
        started.append(cmd)
        return real_popen(cmd, *a, **k)

    monkeypatch.setattr(_supervise.subprocess, "Popen", popen)
    monkeypatch.setattr(
        _supervise.subprocess, "run",
        lambda *a, **k: pytest.fail("supervise() ran a second process"),
    )
    worker = tmp_path / "ok.py"
    worker.write_text("print('done')\n")
    assert supervise(str(worker), [], watchdog_seconds=60) == 0
    assert len(started) == 1 and started[0][1] == str(worker)


def test_idle_watchdog_fires_on_partial_line_hang(tmp_path, capsys):
    worker = tmp_path / "wedge.py"
    worker.write_text(
        "import sys, time\n"
        "sys.stdout.write('partial-no-newline')\n"
        "sys.stdout.flush()\n"
        "time.sleep(300)\n"
    )
    t0 = time.time()
    rc = supervise(str(worker), [], watchdog_seconds=240, idle_seconds=5)
    elapsed = time.time() - t0
    assert rc == 1
    # the idle watchdog (5s), not the absolute backstop (240s), fired
    assert elapsed < 120, elapsed
    out = capsys.readouterr().out
    assert "partial-no-newline" in out
    assert "no output for 5s" in out


def test_idle_watchdog_fires_after_stdout_eof(tmp_path, capsys):
    """A worker that CLOSES stdout and keeps computing must not busy-spin
    the supervisor (select() reports an EOF fd ready forever); the idle
    watchdog still fires on schedule."""
    worker = tmp_path / "eof.py"
    worker.write_text(
        "import os, time\n"
        "print('about to close stdout', flush=True)\n"
        "os.close(1)\n"
        "time.sleep(300)\n"
    )
    t0 = time.time()
    rc = supervise(str(worker), [], watchdog_seconds=240, idle_seconds=5)
    elapsed = time.time() - t0
    assert rc == 1
    assert elapsed < 120, elapsed
    out = capsys.readouterr().out
    assert "about to close stdout" in out
    assert "no output for 5s" in out


def test_supervise_relays_output_and_exit_code(tmp_path, capsys):
    worker = tmp_path / "ok.py"
    worker.write_text(
        "import json\n"
        "print(json.dumps({'phase': 'done'}))\n"
    )
    rc = supervise(str(worker), [], watchdog_seconds=120, idle_seconds=60)
    assert rc == 0
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1]) == {"phase": "done"}


def test_watchdog_exit_code_surfaced_with_bundle(tmp_path, capsys):
    """A worker killed by the in-process stoke health watchdog (exit 113)
    produces a structured supervisor line carrying the exit code and the
    bundle paths the worker's flight recorder reported through the
    STOKE_HEALTH_BUNDLE_FILE handshake — not a bare nonzero exit."""
    worker = tmp_path / "wd.py"
    worker.write_text(
        "import json, os, sys\n"
        "print(json.dumps({'phase': 'running'}), flush=True)\n"
        "with open(os.environ['STOKE_HEALTH_BUNDLE_FILE'], 'a') as f:\n"
        "    f.write('/tmp/fake-postmortem-dir\\n')\n"
        "os._exit(113)\n"
    )
    rc = supervise(str(worker), [], watchdog_seconds=120, idle_seconds=60)
    assert rc == _supervise.HEALTH_WATCHDOG_EXIT_CODE == 113
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["watchdog_exit_code"] == 113
    assert "health watchdog" in line["error"]
    assert line["bundles"] == ["/tmp/fake-postmortem-dir"]


def test_timeout_attaches_bundle_paths(tmp_path, capsys):
    """The absolute-backstop kill attaches any bundles the worker wrote
    before wedging, instead of a bare 'timed out'."""
    worker = tmp_path / "hang.py"
    worker.write_text(
        "import json, os, time\n"
        "print(json.dumps({'phase': 'running'}), flush=True)\n"
        "with open(os.environ['STOKE_HEALTH_BUNDLE_FILE'], 'a') as f:\n"
        "    f.write('/tmp/pre-wedge-bundle\\n')\n"
        "time.sleep(300)\n"
    )
    rc = supervise(str(worker), [], watchdog_seconds=120, idle_seconds=5)
    assert rc == 1
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert "no output for 5s" in line["error"]
    assert line["bundles"] == ["/tmp/pre-wedge-bundle"]
