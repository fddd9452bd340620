"""Pod-scale resilience tests (ISSUE 7): preemption-aware emergency save,
manifest-verified resume with quarantine, supervised restarts with backoff,
and the deterministic chaos harness — including the end-to-end acceptance:
a run killed at an arbitrary step resumes under the supervisor and reaches
a bit-identical final-param state vs an uninterrupted run.

All CPU-only and deterministic on the 8-device simulated mesh (conftest).
"""

import json
import os
import random
import signal
import subprocess
import sys

import numpy as np
import optax
import pytest

from stoke_tpu import (
    PreemptedError,
    ResilienceConfig,
    Stoke,
    StokeOptimizer,
    StokeStatus,
    StokeValidationError,
    TelemetryConfig,
)
from stoke_tpu import io_ops, resilience
from stoke_tpu.resilience import (
    ChaosError,
    ChaosInjector,
    RestartBackoff,
    classify_exit,
    corrupt_checkpoint,
    find_latest_valid_checkpoint,
    parse_chaos,
    quarantine_checkpoint,
    verify_checkpoint,
    write_manifest,
)
from stoke_tpu.telemetry import read_step_events

pytestmark = pytest.mark.resilience

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "scripts"))
import run_resilient as run_resilient_mod  # noqa: E402

IN, OUT = 8, 4


def _make_stoke(tmp_path, *, resilience_over=None, telemetry=False,
                with_resilience=True, tag="run"):
    """Linear-regression overfit scenario on the 8-device CPU mesh."""
    configs = []
    if telemetry:
        configs.append(TelemetryConfig(
            output_dir=str(tmp_path / tag / "telemetry"),
            log_every_n_steps=1,
            sample_device_time=False,
            prometheus=False,
        ))
    if with_resilience:
        configs.append(ResilienceConfig(
            save_path=str(tmp_path / tag / "ckpts"),
            exit_on_preempt=False,
            **(resilience_over or {}),
        ))
    return Stoke(
        model=lambda p, x: x @ p["w"],
        optimizer=StokeOptimizer(
            optimizer=optax.sgd, optimizer_kwargs={"learning_rate": 0.05}
        ),
        loss=lambda o, y: ((o - y) ** 2).mean(),
        params={"w": np.ones((IN, OUT), np.float32) * 0.1},
        batch_size_per_device=4,
        configs=configs,
        verbose=False,
    )


def _batches(n, seed=7, batch=32):
    rng = np.random.default_rng(seed)
    W = rng.normal(size=(IN, OUT)).astype(np.float32)
    out = []
    for _ in range(n):
        x = rng.normal(size=(batch, IN)).astype(np.float32)
        out.append((x, (x @ W).astype(np.float32)))
    return out


def _fake_tag(root, step, name="emergency", payload=b"x" * 256):
    """A minimal on-disk checkpoint tag (meta.json + one payload file)."""
    tag_dir = os.path.join(root, f"stoke-{name}-backward-step-{step}")
    os.makedirs(tag_dir, exist_ok=True)
    with open(os.path.join(tag_dir, "meta.json"), "w") as f:
        json.dump({"format": "consolidated", "name": name}, f)
    with open(os.path.join(tag_dir, "state.bin"), "wb") as f:
        f.write(payload)
    return tag_dir


# --------------------------------------------------------------------------- #
# exit-code classification + restart backoff (jax-free supervisor primitives)
# --------------------------------------------------------------------------- #


def test_classify_exit():
    assert classify_exit(0) == "ok"
    assert classify_exit(113) == "resumable"   # health watchdog
    assert classify_exit(114) == "resumable"   # preemption drain
    assert classify_exit(-9) == "resumable"    # SIGKILL'd (preempted VM)
    assert classify_exit(-15) == "resumable"   # SIGTERM'd before handlers
    assert classify_exit(1) == "fatal"         # deterministic bug: stop
    assert classify_exit(2) == "fatal"
    assert classify_exit(7, extra_resumable=(7,)) == "resumable"
    # shell convention 128+signum: what wrapper launchers (including
    # run_resilient's own main()) report for a signal death
    assert classify_exit(137) == "resumable"   # 128+SIGKILL via a wrapper
    assert classify_exit(143) == "resumable"   # 128+SIGTERM via a wrapper
    assert classify_exit(128) == "fatal"       # not a signal death
    assert classify_exit(200) == "fatal"       # past the signal range


def test_backoff_schedule_and_budget():
    b = RestartBackoff(base_s=1.0, factor=2.0, max_s=5.0, jitter_frac=0.0,
                       max_restarts=4)
    assert [b.next_delay() for _ in range(4)] == [1.0, 2.0, 4.0, 5.0]
    assert b.exhausted
    assert b.next_delay() is None  # budget spent: no more restarts


def test_backoff_jitter_bounds_deterministic():
    b = RestartBackoff(base_s=2.0, factor=2.0, max_s=100.0, jitter_frac=0.5,
                       max_restarts=6, rng=random.Random(0))
    base = 2.0
    for _ in range(6):
        d = b.next_delay()
        # additive-uniform jitter in [0, 0.5 * delay]
        assert base <= d <= base * 1.5
        base = min(100.0, base * 2.0)
    # same seed -> same schedule (the determinism the tests rely on)
    b2 = RestartBackoff(base_s=2.0, factor=2.0, max_s=100.0, jitter_frac=0.5,
                        max_restarts=6, rng=random.Random(0))
    b3 = RestartBackoff(base_s=2.0, factor=2.0, max_s=100.0, jitter_frac=0.5,
                        max_restarts=6, rng=random.Random(0))
    assert [b2.next_delay() for _ in range(6)] == \
        [b3.next_delay() for _ in range(6)]


def test_backoff_rejects_bad_params():
    with pytest.raises(ValueError):
        RestartBackoff(base_s=-1.0)
    with pytest.raises(ValueError):
        RestartBackoff(factor=0.5)


def test_run_resilient_restarts_then_succeeds(tmp_path):
    """Injected clock + runner: 114 -> 113 -> 0 restarts twice with the
    exponential schedule, threads the attempt number through the env, and
    records one JSONL line per attempt — no subprocesses, no real sleeps."""
    codes = iter([114, 113, 0])
    envs = []

    def fake_run(argv, env):
        envs.append(dict(env))
        return next(codes)

    sleeps = []
    rec_path = str(tmp_path / "restarts.jsonl")
    out = run_resilient_mod.run_resilient(
        ["worker"], max_restarts=5, base_s=1.0, jitter_frac=0.0, seed=0,
        record_path=rec_path, run=fake_run, sleep=sleeps.append,
    )
    assert out["ok"] and out["attempts"] == 3 and out["restarts"] == 2
    assert sleeps == [1.0, 2.0]
    assert [e["STOKE_RESTART_ATTEMPT"] for e in envs] == ["0", "1", "2"]
    with open(rec_path) as f:
        records = [json.loads(ln) for ln in f]
    assert [r["exit_code"] for r in records] == [114, 113, 0]
    assert [r["class"] for r in records] == ["resumable", "resumable", "ok"]


def test_run_resilient_fatal_stops_immediately():
    calls = []

    def fake_run(argv, env):
        calls.append(1)
        return 1  # generic crash: a deterministic bug

    out = run_resilient_mod.run_resilient(
        ["worker"], max_restarts=5, run=fake_run,
        sleep=lambda s: pytest.fail("fatal exit must not back off"),
    )
    assert not out["ok"] and out["fatal"] and out["exit_code"] == 1
    assert len(calls) == 1  # restarting a deterministic bug burns budget


def test_run_resilient_budget_exhaustion():
    out = run_resilient_mod.run_resilient(
        ["worker"], max_restarts=2, base_s=0.0, jitter_frac=0.0,
        run=lambda argv, env: 114, sleep=lambda s: None,
    )
    assert not out["ok"] and out["exhausted"] and out["attempts"] == 3


def test_supervise_exit_codes_in_sync():
    """The jax-free supervisor side keeps copies of what the jax-importing
    side defines (the watchdog's exit code in resilience.py, the recorder's
    handshake variable in scripts/run_resilient.py); they must never drift.
    The supervisor keeps no copy of the resumable codes: it takes them from
    resilience.py, loaded by file."""
    from stoke_tpu.telemetry import health, recorder

    assert resilience._WATCHDOG_EXIT_CODE == health.WATCHDOG_EXIT_CODE
    assert run_resilient_mod.BUNDLE_FILE_ENV == recorder.BUNDLE_FILE_ENV


def test_tag_regex_in_sync_with_io_ops():
    # resilience duplicates the tag regex to stay importable without jax;
    # io_ops._TAG_RE is the authority
    assert resilience._TAG_RE.pattern == io_ops._TAG_RE.pattern


# --------------------------------------------------------------------------- #
# manifests, verification, quarantine, discovery
# --------------------------------------------------------------------------- #


def test_manifest_roundtrip_and_verify(tmp_path):
    tag = _fake_tag(str(tmp_path), 10)
    ok, reason = verify_checkpoint(tag)
    assert ok and "no manifest" in reason  # legacy tags stay loadable
    assert not verify_checkpoint(tag, require_manifest=True)[0]
    write_manifest(tag, extra={"backward_step": 10})
    ok, reason = verify_checkpoint(tag)
    assert ok and reason == "ok"
    with open(os.path.join(tag, resilience.MANIFEST_NAME)) as f:
        manifest = json.load(f)
    assert set(manifest["files"]) == {"meta.json", "state.bin"}
    assert manifest["backward_step"] == 10


def test_kill_during_metadata_write_leaves_unloadable_tag(
    tmp_path, monkeypatch
):
    """extras.pkl is written BEFORE meta.json (the tag's loadable marker):
    a hard kill landing between the two must leave a tag verify_checkpoint
    rejects as a partial write — the reverse order would let resume
    silently restore WITHOUT the rng/EMA/EF extras and break the
    bit-identical-resume guarantee."""
    from stoke_tpu.configs import CheckpointConfig

    def boom(*a, **kw):
        raise OSError("simulated hard kill mid-extras-write")

    monkeypatch.setattr(io_ops.pickle, "dump", boom)
    with pytest.raises(OSError, match="simulated hard kill"):
        io_ops.save_checkpoint(
            str(tmp_path),
            "emerg",
            variables={"w": np.zeros((2, 2), np.float32)},
            opt_state={},
            scaler_state={},
            counters={"optimizer_step": 3, "backward_step": 3},
            status={},
            extras={"resilience": {"optimizer_step": 3}},
            config=CheckpointConfig(),
            backward_step=3,
            manifest=True,
        )
    tags = [d for d in os.listdir(tmp_path) if "emerg" in d]
    assert len(tags) == 1
    ok, reason = verify_checkpoint(os.path.join(str(tmp_path), tags[0]))
    assert not ok and "meta.json" in reason


def test_verify_catches_corruption_truncation_and_loss(tmp_path):
    tag = _fake_tag(str(tmp_path), 4)
    write_manifest(tag)
    # bit rot: same size, different bytes
    assert corrupt_checkpoint(tag) is not None
    ok, reason = verify_checkpoint(tag)
    assert not ok and "digest mismatch" in reason
    # truncation
    tag2 = _fake_tag(str(tmp_path), 6)
    write_manifest(tag2)
    with open(os.path.join(tag2, "state.bin"), "wb") as f:
        f.write(b"x")
    assert "size mismatch" in verify_checkpoint(tag2)[1]
    # a listed file vanished
    tag3 = _fake_tag(str(tmp_path), 8)
    write_manifest(tag3)
    os.remove(os.path.join(tag3, "state.bin"))
    assert "missing file" in verify_checkpoint(tag3)[1]
    # meta-less dir = partial write by construction
    tag4 = os.path.join(str(tmp_path), "stoke-emergency-backward-step-9")
    os.makedirs(tag4)
    assert "partial" in verify_checkpoint(tag4)[1]


def test_quarantine_moves_never_deletes(tmp_path):
    tag = _fake_tag(str(tmp_path), 3, payload=b"evidence")
    dest = quarantine_checkpoint(tag, reason="digest mismatch")
    assert dest is not None and not os.path.exists(tag)
    assert os.path.dirname(dest) == str(tmp_path / "quarantine")
    # the bytes are evidence: payload preserved, reason recorded
    with open(os.path.join(dest, "state.bin"), "rb") as f:
        assert f.read() == b"evidence"
    with open(os.path.join(dest, "QUARANTINED.json")) as f:
        assert json.load(f)["reason"] == "digest mismatch"


def test_discovery_falls_back_past_corrupt_latest(tmp_path):
    root = str(tmp_path)
    for step in (2, 4, 6):
        write_manifest(_fake_tag(root, step))
    newest = os.path.join(root, "stoke-emergency-backward-step-6")
    corrupt_checkpoint(newest)
    seen = []
    cand = find_latest_valid_checkpoint(
        [(root, "emergency")],
        on_quarantine=lambda t, d, r: seen.append((t, d, r)),
    )
    assert cand is not None and cand["step"] == 4
    assert not os.path.exists(newest)  # quarantined, not deleted
    assert len(os.listdir(os.path.join(root, "quarantine"))) == 1
    assert len(seen) == 1 and "digest mismatch" in seen[0][2]
    # quarantine=False leaves the corrupt tag in place and still skips it
    corrupt_checkpoint(os.path.join(root, "stoke-emergency-backward-step-4"))
    cand2 = find_latest_valid_checkpoint(
        [(root, "emergency")], quarantine=False
    )
    assert cand2["step"] == 2
    assert os.path.exists(
        os.path.join(root, "stoke-emergency-backward-step-4")
    )


def test_discovery_scopes_by_name_and_handles_empty(tmp_path):
    root = str(tmp_path)
    write_manifest(_fake_tag(root, 5, name="other"))
    assert find_latest_valid_checkpoint([(root, "emergency")]) is None
    assert find_latest_valid_checkpoint([(root, None)])["step"] == 5
    assert find_latest_valid_checkpoint(
        [(str(tmp_path / "missing"), None)]
    ) is None


# --------------------------------------------------------------------------- #
# chaos harness
# --------------------------------------------------------------------------- #


def test_parse_chaos_grammar():
    assert parse_chaos(None) is None
    assert parse_chaos("  ") is None
    spec = parse_chaos("kill_at_step=5,kill_mode=sigkill")
    assert spec.kill_at_step == 5 and spec.kill_mode == "sigkill"
    spec = parse_chaos("corrupt_save=2, wedge_at_step=3, wedge_s=0.5")
    assert (spec.corrupt_save, spec.wedge_at_step, spec.wedge_s) == \
        (2, 3, 0.5)
    # a typo'd plan silently injecting nothing would fake a green test
    with pytest.raises(ValueError, match="unknown chaos key"):
        parse_chaos("kil_at_step=5")
    with pytest.raises(ValueError, match="kill_mode"):
        parse_chaos("kill_mode=nuke")
    with pytest.raises(ValueError, match="integer"):
        parse_chaos("kill_at_step=soon")
    with pytest.raises(ValueError, match="key=value"):
        parse_chaos("chaos!")
    # an armed injector that can never fire (corrupt_save is 1-based,
    # kill/wedge fire on steps >= 1) is the same fake-green hazard
    with pytest.raises(ValueError, match=">= 1"):
        parse_chaos("corrupt_save=0")
    with pytest.raises(ValueError, match=">= 1"):
        parse_chaos("kill_at_step=0")
    with pytest.raises(ValueError, match=">= 1"):
        parse_chaos("wedge_at_step=-3")
    with pytest.raises(ValueError, match="wedge_s"):
        parse_chaos("wedge_at_step=3,wedge_s=-1")
    # wedge_s=0 stays legal: fires without stalling (how these tests
    # exercise injector logic without real sleeps)
    assert parse_chaos("wedge_at_step=3,wedge_s=0").wedge_s == 0.0


def test_injector_kill_window_and_resume_anchor():
    inj = ChaosInjector(parse_chaos("kill_at_step=5,kill_mode=exception"))
    inj.on_step(3)  # before the window: nothing
    with pytest.raises(ChaosError):
        inj.on_step(6, window=4)  # 2 < 5 <= 6: K inside the window
    # a resumed process whose counter starts AT k never re-fires — the
    # supervised restart must make forward progress
    inj2 = ChaosInjector(parse_chaos("kill_at_step=5,kill_mode=exception"))
    inj2.note_resumed(5)
    inj2.on_step(6)
    inj2.on_step(7)


def test_injector_wedge_never_refires_after_resume():
    """A resumed process that restored step >= K must not re-arm the wedge
    (the per-process _wedged flag resets each restart) — otherwise every
    supervised attempt of a wedge-chaos run wedges again and the restart
    budget burns out without forward progress."""
    spec = "wedge_at_step=2,wedge_s=0"
    inj = ChaosInjector(parse_chaos(spec))
    inj.on_step(2)
    inj.on_dispatch("train_step")  # this process crossed K: wedges once
    assert inj._wedged
    inj2 = ChaosInjector(parse_chaos(spec))
    inj2.note_resumed(2)  # restored AT K: fired in a previous life
    inj2.on_dispatch("train_step")
    inj2.on_step(3)
    inj2.on_dispatch("train_step")
    assert not inj2._wedged


def test_injector_corrupt_save(tmp_path):
    inj = ChaosInjector(parse_chaos("corrupt_save=2"))
    t1 = _fake_tag(str(tmp_path), 1)
    t2 = _fake_tag(str(tmp_path), 2)
    write_manifest(t1)
    write_manifest(t2)
    inj.note_saved(t1)  # save #1: untouched
    assert verify_checkpoint(t1)[0]
    inj.note_saved(t2)  # save #2: corrupted
    assert not verify_checkpoint(t2)[0]
    assert inj.corrupted


# --------------------------------------------------------------------------- #
# satellite: wait_for_saves reports EVERY failed tag dir
# --------------------------------------------------------------------------- #


def test_wait_for_saves_reports_all_failures():
    first = OSError("disk full")
    io_ops._ASYNC_ERRORS.extend([
        ("/ckpts/tag-a", first),
        ("/ckpts/tag-b", ValueError("serialization failed")),
    ])
    try:
        with pytest.raises(RuntimeError) as ei:
            io_ops.wait_for_saves()
        msg = str(ei.value)
        # the full casualty list, not "first (+1 more)"
        assert "/ckpts/tag-a" in msg and "/ckpts/tag-b" in msg
        assert "disk full" in msg and "serialization failed" in msg
        assert ei.value.__cause__ is first
        assert not io_ops._ASYNC_ERRORS  # cleared: no double-raise later
    finally:
        io_ops._ASYNC_ERRORS.clear()


# --------------------------------------------------------------------------- #
# status rules
# --------------------------------------------------------------------------- #


def _status(configs, **kw):
    return StokeStatus(batch_size_per_device=4, configs=configs, **kw)


def test_status_validates_resilience(tmp_path):
    root = str(tmp_path / "ckpts")
    with pytest.raises(StokeValidationError, match="1..255"):
        _status([ResilienceConfig(save_path=root, exit_code=0)])
    with pytest.raises(StokeValidationError, match="collides"):
        _status([ResilienceConfig(save_path=root, exit_code=113)])
    with pytest.raises(StokeValidationError, match="preempt_signals"):
        _status([ResilienceConfig(save_path=root, preempt_signals=())])
    with pytest.raises(StokeValidationError, match="unknown"):
        _status([ResilienceConfig(save_path=root,
                                  preempt_signals=("SIGBOGUS",))])
    with pytest.raises(StokeValidationError, match="max_to_keep"):
        _status([ResilienceConfig(save_path=root, max_to_keep=0)])
    with pytest.raises(StokeValidationError, match="chaos"):
        _status([ResilienceConfig(save_path=root, chaos="kil_at=3")])
    # valid combination passes
    _status([ResilienceConfig(save_path=root)])


def test_status_rejects_typod_chaos_env(tmp_path, monkeypatch):
    monkeypatch.setenv(resilience.CHAOS_ENV, "kill_at=3")
    with pytest.raises(StokeValidationError, match="chaos"):
        _status([ResilienceConfig(save_path=str(tmp_path / "c"))])
    # the config field overrides (and validates instead of) the env
    monkeypatch.setenv(resilience.CHAOS_ENV, "also=bogus")
    _status([ResilienceConfig(save_path=str(tmp_path / "c"),
                              chaos="kill_at_step=3")])


def test_resilience_config_yaml_buildable(tmp_path):
    from stoke_tpu.utils import stoke_kwargs_from_config

    kwargs = stoke_kwargs_from_config({
        "batch_size_per_device": 4,
        "configs": {
            "ResilienceConfig": {
                "save_path": str(tmp_path / "ckpts"),
                "exit_code": 115,
                "max_to_keep": 5,
            },
        },
    })
    by_type = {type(c).__name__: c for c in kwargs["configs"]}
    cfg = by_type["ResilienceConfig"]
    assert cfg.exit_code == 115 and cfg.max_to_keep == 5


# --------------------------------------------------------------------------- #
# default-OFF identity (acceptance: bit-identical step programs)
# --------------------------------------------------------------------------- #


def test_resilience_off_is_bit_identical_and_on_adds_no_dispatches(
    tmp_path, devices
):
    """The whole subsystem is host-side: the engine dispatch count AND the
    lowered step-program HLO are identical with the config absent vs
    present (same technique as the PR 3/4/5 acceptance)."""
    import jax

    s_off = _make_stoke(tmp_path, with_resilience=False, tag="off")
    s_on = _make_stoke(tmp_path, tag="on")
    batches = _batches(4)
    for s in (s_off, s_on):
        for x, y in batches:
            s.train_step(x, (y,))
    assert s_on.dispatch_count == s_off.dispatch_count
    np.testing.assert_array_equal(
        np.asarray(s_on.params["w"]), np.asarray(s_off.params["w"])
    )
    x, y = batches[0]

    def fused_hlo(s):
        from stoke_tpu.engine import DeferredOutput, is_deferred

        margs = s._place_batch((x,))
        sentinel = DeferredOutput(None, -1)
        flat, treedef = jax.tree_util.tree_flatten(
            ((sentinel, y), {}), is_leaf=is_deferred
        )
        arrays = s._place_batch([l for l in flat if not is_deferred(l)])
        deferred = tuple(
            (i, l._path) for i, l in enumerate(flat) if is_deferred(l)
        )
        fn = s._engine._build_fused(treedef, deferred, True)
        return fn.lower(
            s._variables, s._opt_state, s._grad_buf, s._scaler_state,
            s._comm_state, s._rng, margs, {}, arrays,
        ).as_text()

    assert fused_hlo(s_on) == fused_hlo(s_off)
    s_on.close_telemetry()
    s_off.close_telemetry()


def test_signal_handlers_installed_and_restored(tmp_path):
    prev = signal.getsignal(signal.SIGTERM)
    s = _make_stoke(tmp_path)
    assert signal.getsignal(signal.SIGTERM) is not prev
    s.close_telemetry()
    assert signal.getsignal(signal.SIGTERM) is prev


def test_signal_handlers_overlapping_monitors(tmp_path):
    """Resume-while-old-run-open (telemetry_smoke's own pattern): closing
    the OLDER monitor must not strip the live one's handler, and the final
    close must restore the pre-Stoke handler, not a closed monitor's."""
    prev = signal.getsignal(signal.SIGTERM)
    a = _make_stoke(tmp_path, tag="ovl-a")
    b = _make_stoke(tmp_path, tag="ovl-b")
    assert signal.getsignal(signal.SIGTERM) == b.resilience._on_signal
    a.close_telemetry()
    # B installed over A, so A's close must leave B's handler in place
    assert signal.getsignal(signal.SIGTERM) == b.resilience._on_signal
    b.close_telemetry()
    assert signal.getsignal(signal.SIGTERM) is prev
    # reverse order: the newer monitor closing first hands SIGTERM back to
    # the still-open older one, whose close restores the original
    c = _make_stoke(tmp_path, tag="ovl-c")
    d = _make_stoke(tmp_path, tag="ovl-d")
    d.close_telemetry()
    assert signal.getsignal(signal.SIGTERM) == c.resilience._on_signal
    c.close_telemetry()
    assert signal.getsignal(signal.SIGTERM) is prev


# --------------------------------------------------------------------------- #
# preemption → emergency save → resume (the in-process cycle)
# --------------------------------------------------------------------------- #


def test_preemption_cycle_bit_identical_trajectory(tmp_path, devices):
    """A preempted-and-resumed run must reach a bit-identical final-param
    state vs an uninterrupted one: the emergency extras carry rng/EMA and
    the checkpoint the full optimizer state."""
    n = 6
    batches = _batches(n)
    ref = _make_stoke(tmp_path, tag="ref")
    for x, y in batches:
        ref.train_step(x, (y,))
    ref.close_telemetry()

    run = _make_stoke(tmp_path, tag="pre")
    for x, y in batches[:3]:
        run.train_step(x, (y,))
    run.resilience.request_preemption("test")
    with pytest.raises(PreemptedError) as ei:
        run.train_step(*_pair(batches[3]))
    # the in-flight step FINISHED before the drain: step 4 applied + saved
    assert ei.value.step == 4
    assert run.optimizer_steps == 4
    tag_dir = ei.value.tag_dir
    assert tag_dir and os.path.exists(
        os.path.join(tag_dir, resilience.MANIFEST_NAME)
    )
    assert verify_checkpoint(tag_dir, require_manifest=True)[0]
    summary = run.resilience_summary
    assert summary["preemptions"] == 1 and summary["emergency_saves"] == 1
    run.close_telemetry()

    resumed = _make_stoke(tmp_path, tag="pre")  # same save_path
    assert resumed.resume()
    assert resumed.optimizer_steps == 4
    rz = resumed.resilience_summary
    assert rz["resumed_step"] == 4 and rz["lost_steps"] == 0
    for x, y in batches[4:]:
        resumed.train_step(x, (y,))
    assert resumed.optimizer_steps == n
    np.testing.assert_array_equal(
        np.asarray(resumed.params["w"]), np.asarray(ref.params["w"])
    )
    resumed.close_telemetry()


def _pair(b):
    x, y = b
    return x, (y,)


def test_resume_without_checkpoint_starts_fresh(tmp_path):
    s = _make_stoke(tmp_path, tag="fresh")
    assert not s.resume()
    assert s.optimizer_steps == 0
    s.close_telemetry()


def test_corrupt_latest_quarantined_resume_falls_back(tmp_path, devices):
    """The corrupted-latest acceptance path: resume() quarantines the bad
    newest tag, restores the previous valid one, and charges the gap to
    lost_steps."""
    run = _make_stoke(tmp_path, tag="qr")
    batches = _batches(4)
    root = run.resilience.cfg.save_path
    for x, y in batches[:2]:
        run.train_step(x, (y,))
    run.save(root, name="emergency")          # valid tag at backward step 2
    for x, y in batches[2:]:
        run.train_step(x, (y,))
    newest = run.save(root, name="emergency")  # newest tag at step 4
    run.close_telemetry()
    assert corrupt_checkpoint(newest) is not None

    resumed = _make_stoke(tmp_path, tag="qr")
    assert resumed.resume()
    assert resumed.optimizer_steps == 2  # fell back past the corrupt tag
    rz = resumed.resilience_summary
    assert rz["quarantined_ckpts"] == 1
    assert rz["resumed_step"] == 2 and rz["lost_steps"] == 2
    assert not os.path.exists(newest)
    qdir = os.path.join(root, resilience.QUARANTINE_DIRNAME)
    assert len(os.listdir(qdir)) == 1
    resumed.close_telemetry()


def test_emergency_prune_skips_inflight_tags(tmp_path, devices):
    """Satellite regression: the emergency save's prune must never touch a
    tag an async save is still writing — a meta-less in-flight dir looks
    exactly like a crashed leftover, and deleting it mid-write would
    corrupt the concurrent checkpoint the drain is about to finish."""
    run = _make_stoke(tmp_path, resilience_over={"max_to_keep": 1},
                      tag="race")
    root = run.resilience.cfg.save_path
    os.makedirs(root, exist_ok=True)
    # simulate the race: an async save claimed its (still meta-less) tag
    # dir but has not finished when the preemption save prunes
    inflight = os.path.join(root, "stoke-emergency-backward-step-99")
    os.makedirs(inflight)
    io_ops._INFLIGHT_TAGS.add(inflight)
    # and a crashed leftover that is NOT in flight — prune must remove it
    leftover = os.path.join(root, "stoke-emergency-backward-step-98")
    os.makedirs(leftover)
    try:
        x, y = _batches(1)[0]
        run.train_step(x, (y,))
        run.resilience.request_preemption("test")
        with pytest.raises(PreemptedError):
            run.train_step(x, (y,))
        assert os.path.exists(inflight)       # guarded: still being written
        assert not os.path.exists(leftover)   # stale: pruned as always
        assert run.resilience_summary["emergency_saves"] == 1
    finally:
        io_ops._INFLIGHT_TAGS.discard(inflight)
        run.close_telemetry()


def test_chaos_exception_mode_via_facade(tmp_path, devices):
    run = _make_stoke(
        tmp_path, resilience_over={"chaos": "kill_at_step=2,"
                                   "kill_mode=exception"}, tag="chaos",
    )
    batches = _batches(3)
    run.train_step(*_pair(batches[0]))
    with pytest.raises(ChaosError):
        run.train_step(*_pair(batches[1]))
    run.close_telemetry()


def test_chaos_corrupt_save_via_facade(tmp_path, devices):
    run = _make_stoke(
        tmp_path, resilience_over={"chaos": "corrupt_save=1"}, tag="cor",
    )
    x, y = _batches(1)[0]
    run.train_step(x, (y,))
    tag = run.save(run.resilience.cfg.save_path, name="emergency")
    assert not verify_checkpoint(tag)[0]
    run.close_telemetry()


# --------------------------------------------------------------------------- #
# telemetry surface
# --------------------------------------------------------------------------- #


def test_resilience_jsonl_fields(tmp_path, devices):
    s = _make_stoke(tmp_path, telemetry=True, tag="tel")
    for x, y in _batches(2):
        s.train_step(x, (y,))
    s.close_telemetry()
    records = read_step_events(
        str(tmp_path / "tel" / "telemetry" / "steps.jsonl")
    )
    rec = records[-1]
    assert rec["resilience/preemptions"] == 0.0
    assert rec["resilience/emergency_saves"] == 0.0
    assert rec["resilience/quarantined"] == 0.0
    assert rec["resilience/restarts"] == 0.0
    assert rec["resilience/resumed_step"] is None
    assert rec["resilience/lost_steps"] is None
    # without the config the keys never appear (PR 1 registry contract)
    s_off = _make_stoke(tmp_path, telemetry=True, with_resilience=False,
                        tag="tel_off")
    for x, y in _batches(2):
        s_off.train_step(x, (y,))
    s_off.close_telemetry()
    rec_off = read_step_events(
        str(tmp_path / "tel_off" / "telemetry" / "steps.jsonl")
    )[-1]
    assert "resilience/preemptions" not in rec_off


def test_restart_attempt_env_surfaces(tmp_path, monkeypatch, devices):
    monkeypatch.setenv(resilience.RESTART_ATTEMPT_ENV, "3")
    s = _make_stoke(tmp_path, tag="att")
    assert s.resilience.restarts == 3
    assert s.resilience_summary["restarts"] == 3
    s.close_telemetry()


# --------------------------------------------------------------------------- #
# end-to-end acceptance: chaos kill + supervised restart, bit-identical
# --------------------------------------------------------------------------- #


def test_chaos_kill_supervised_restart_bit_identical(tmp_path):
    """The full detect→save→restart→resume loop as real processes: a
    worker SIGTERM'd at step 3 by the injector drains, saves, and exits
    114; run_resilient restarts it; the resumed attempt finishes and the
    final params + overlapping loss trajectory are bit-identical to an
    uninterrupted reference run."""
    worker = os.path.join(_REPO, "tests", "_resilience_worker.py")
    supervisor = os.path.join(_REPO, "scripts", "run_resilient.py")
    steps = 6

    def run_worker(root, chaos=None, supervised=False):
        env = {k: v for k, v in os.environ.items() if k != "STOKE_CHAOS"}
        env["PYTHONPATH"] = _REPO
        env.setdefault("JAX_PLATFORMS", "cpu")
        if chaos:
            env["STOKE_CHAOS"] = chaos
        worker_cmd = [sys.executable, worker, "--root", root,
                      "--steps", str(steps), "--resilience"]
        if supervised:
            cmd = [sys.executable, supervisor, "--max-restarts", "3",
                   "--base-s", "0.01", "--jitter-frac", "0",
                   "--record", os.path.join(root, "restarts.jsonl"),
                   "--"] + worker_cmd
        else:
            cmd = worker_cmd
        return subprocess.run(
            cmd, env=env, cwd=_REPO, timeout=240,
            capture_output=True, text=True,
        )

    ref_root = str(tmp_path / "ref")
    chaos_root = str(tmp_path / "chaos")
    os.makedirs(ref_root)
    os.makedirs(chaos_root)
    ref = run_worker(ref_root)
    assert ref.returncode == 0, ref.stderr
    out = run_worker(chaos_root, chaos="kill_at_step=3,kill_mode=sigterm",
                     supervised=True)
    assert out.returncode == 0, out.stderr

    # supervisor record: attempt 0 preempted (114, resumable), attempt 1 ok
    with open(os.path.join(chaos_root, "restarts.jsonl")) as f:
        records = [json.loads(ln) for ln in f]
    assert [r["exit_code"] for r in records] == [114, 0]
    assert records[0]["class"] == "resumable"
    summary = json.loads(
        [ln for ln in out.stdout.splitlines() if "run_resilient" in ln][-1]
    )["run_resilient"]
    assert summary["ok"] and summary["restarts"] == 1

    # the emergency checkpoint exists with its manifest
    ckpts = resilience.list_checkpoints(
        os.path.join(chaos_root, "ckpts"), "emergency"
    )
    assert ckpts and ckpts[0]["step"] == 3
    assert verify_checkpoint(ckpts[0]["tag_dir"], require_manifest=True)[0]

    # bit-identical final params vs the uninterrupted reference
    w_ref = np.load(os.path.join(ref_root, "final_w.npy"))
    w_chaos = np.load(os.path.join(chaos_root, "final_w.npy"))
    np.testing.assert_array_equal(w_chaos, w_ref)

    # and a bit-identical loss trajectory on every step both runs logged
    # (the killed step's line is missing by construction: the update was
    # applied and saved, but the worker exited before logging it)
    def traj(root):
        with open(os.path.join(root, "trajectory.jsonl")) as f:
            return {r["step"]: r["loss"] for r in map(json.loads, f)}

    t_ref, t_chaos = traj(ref_root), traj(chaos_root)
    assert set(t_chaos) == {1, 2, 4, 5, 6}
    for step, loss in t_chaos.items():
        assert loss == t_ref[step], f"step {step} diverged"
    # the resumed steps ran on attempt 1
    with open(os.path.join(chaos_root, "trajectory.jsonl")) as f:
        by_attempt = {}
        for r in map(json.loads, f):
            by_attempt.setdefault(r["attempt"], []).append(r["step"])
    assert by_attempt == {0: [1, 2], 1: [4, 5, 6]}
