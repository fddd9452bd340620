"""Persistent AOT compile-cache tests (ISSUE 6): environment-fingerprint
invalidation, cross-process cache-key stability, cold-miss/warm-hit with
reclaimed goodput_compile_s, default-OFF HLO bit-identity + dispatch-count
equality, status rules, YAML construction, and serialize-failure
degradation.

All CPU-only and deterministic on the 8-device simulated mesh (conftest).
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import optax
import pytest

from stoke_tpu import (
    AttributionConfig,
    CompileConfig,
    Stoke,
    StokeOptimizer,
    StokeStatus,
    StokeValidationError,
    TelemetryConfig,
)
from stoke_tpu.compile_cache import (
    CompileCache,
    environment_fingerprint,
    hlo_cache_key,
)
from stoke_tpu.telemetry import read_step_events

IN, OUT = 8, 4
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_process_cache():
    """Isolate the process-level program cache per test: several tests
    build identical tiny programs, and a leak across tests would turn an
    intended cold run into a warm hit."""
    import stoke_tpu.compile_cache as cc

    with cc._process_fn_lock:
        saved = dict(cc._process_fn_cache)
        cc._process_fn_cache.clear()
    yield
    with cc._process_fn_lock:
        cc._process_fn_cache.clear()
        cc._process_fn_cache.update(saved)


def _make_stoke(tmp_path, *, cache=True, telemetry=False, tag="run",
                cache_dir=None):
    configs = []
    if telemetry:
        configs.append(TelemetryConfig(
            output_dir=str(tmp_path / tag / "telemetry"),
            log_every_n_steps=1,
            sample_device_time=False,
            prometheus=False,
        ))
        configs.append(AttributionConfig(peak_tflops=1e-3))
    if cache:
        # the marker ledger lands under tmp_path; the persistent XLA
        # cache itself is refused on the CPU backend
        configs.append(CompileConfig(
            cache_dir=cache_dir or str(tmp_path / "compile_cache"),
        ))
    return Stoke(
        model=lambda p, x: x @ p["w"],
        optimizer=StokeOptimizer(
            optimizer=optax.sgd, optimizer_kwargs={"learning_rate": 0.05}
        ),
        loss=lambda o, y: ((o - y) ** 2).mean(),
        params={"w": np.ones((IN, OUT), np.float32) * 0.1},
        batch_size_per_device=4,
        distributed="dp",
        configs=configs or None,
        verbose=False,
    )


def _batches(n, seed=3, batch=32):
    rng = np.random.default_rng(seed)
    W = rng.normal(size=(IN, OUT)).astype(np.float32)
    return [
        (x, (x @ W).astype(np.float32))
        for x in (
            rng.normal(size=(batch, IN)).astype(np.float32)
            for _ in range(n)
        )
    ]


# --------------------------------------------------------------------------- #
# fingerprint + key
# --------------------------------------------------------------------------- #


def test_fingerprint_component_sensitivity():
    base = dict(
        xla_flags="--f=1", jax_version="0.4.37", jaxlib_version="0.4.36",
        backend="cpu", topology="8xcpu", n_processes=1,
    )
    fp = environment_fingerprint(**base)
    assert fp == environment_fingerprint(**base)  # deterministic
    for key, other in (
        ("xla_flags", "--f=2"),
        ("jax_version", "0.5.0"),
        ("jaxlib_version", "0.5.0"),
        ("backend", "tpu"),
        ("topology", "4xTPU v5e"),
        ("n_processes", 8),
    ):
        assert environment_fingerprint(**{**base, key: other}) != fp, key


def test_jaxlib_and_flag_fingerprint_invalidate_the_key():
    """The acceptance contract: an executable compiled under a different
    jaxlib or flag set must never be served — its key differs."""
    hlo = "HloModule jit_f, entry=main\nENTRY main { ROOT x = f32[] add }"
    base = dict(
        xla_flags="", jax_version="0.4.37", jaxlib_version="0.4.36",
        backend="cpu", topology="8xcpu", n_processes=1,
    )
    k0 = hlo_cache_key(hlo, environment_fingerprint(**base))
    assert k0 == hlo_cache_key(hlo, environment_fingerprint(**base))
    assert k0 != hlo_cache_key(
        hlo, environment_fingerprint(**{**base, "jaxlib_version": "0.9.0"})
    )
    assert k0 != hlo_cache_key(
        hlo, environment_fingerprint(**{**base, "xla_flags": "--new-flag"})
    )
    # different HLO body -> different key; renamed module -> same key
    assert k0 != hlo_cache_key(
        hlo.replace("add", "multiply"), environment_fingerprint(**base)
    )
    assert k0 == hlo_cache_key(
        hlo.replace("HloModule jit_f", "HloModule jit_f.7"),
        environment_fingerprint(**base),
    )


def test_key_normalizes_mlir_module_name():
    """``Lowered.as_text()`` emits StableHLO MLIR on current jax: the
    module header carries the jit wrapper's name plus any per-process
    uniquifying counter (``@jit__fused.1``), and a renamed module is
    still the same program — but the mhlo partition/replica attributes
    ARE semantic and must stay in the key."""
    fp = environment_fingerprint(
        xla_flags="", jax_version="0.4.37", jaxlib_version="0.4.36",
        backend="cpu", topology="8xcpu", n_processes=1,
    )
    a = ("module @jit__fused attributes "
         "{mhlo.num_partitions = 1 : i32} {\n  body\n}")
    b = ("module @jit__fused.1 attributes "
         "{mhlo.num_partitions = 1 : i32} {\n  body\n}")
    c = ("module @jit__fused attributes "
         "{mhlo.num_partitions = 2 : i32} {\n  body\n}")
    assert hlo_cache_key(a, fp) == hlo_cache_key(b, fp)
    assert hlo_cache_key(a, fp) != hlo_cache_key(c, fp)
    assert hlo_cache_key(a, fp) != hlo_cache_key(
        a.replace("body", "other"), fp
    )


_KEY_SNIPPET = r"""
import jax, jax.numpy as jnp
from stoke_tpu.compile_cache import environment_fingerprint, hlo_cache_key
f = jax.jit(lambda x: (x * 2 + 1).sum())
lowered = f.lower(jnp.ones((16, 8), jnp.float32))
print(hlo_cache_key(lowered.as_text(), environment_fingerprint()))
"""


def test_cache_key_stable_across_processes():
    """Two fresh interpreters lowering the same program must agree on the
    cache key (no PYTHONHASHSEED/object-id leakage) — the property that
    makes the second Stoke construction in a NEW process a warm start."""
    env = {
        **os.environ,
        "PYTHONPATH": REPO,
        "JAX_PLATFORMS": "cpu",
    }
    keys = []
    for seed in ("1", "2"):  # different hash seeds, same key expected
        out = subprocess.run(
            [sys.executable, "-c", _KEY_SNIPPET],
            capture_output=True, text=True, timeout=120,
            env={**env, "PYTHONHASHSEED": seed},
        )
        assert out.returncode == 0, out.stderr[-500:]
        keys.append(out.stdout.strip().splitlines()[-1])
    assert keys[0] == keys[1]
    assert keys[0].startswith("exe-")


# --------------------------------------------------------------------------- #
# where the persistent cache lives (ISSUE 21): one rule
# --------------------------------------------------------------------------- #

#: the rule refuses the CPU backend, so the probe process says "tpu" for
#: the one query the rule makes; nothing is compiled, nothing is written
_RULE_SNIPPET = r"""
import json, jax
jax.default_backend = lambda: "tpu"
from stoke_tpu import compile_cache as cc
seen = []
update = jax.config.update
jax.config.update = lambda k, v: (seen.append((k, v)), update(k, v))[1]
returned = cc.install_persistent_xla_cache()
print(json.dumps({
    "returned": returned,
    "knob": jax.config.jax_compilation_cache_dir,
    "set_in_code": [v for k, v in seen if k == "jax_compilation_cache_dir"],
}))
"""


def _run_rule(cwd, **env):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run(
        [sys.executable, "-c", _RULE_SNIPPET],
        capture_output=True, text=True, timeout=120, cwd=str(cwd),
        env={**base, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu", **env},
    )
    assert out.returncode == 0, out.stderr[-500:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_rule_env_var_wins_and_no_other_path_is_set(tmp_path):
    placed = str(tmp_path / "placed")
    got = _run_rule(tmp_path, JAX_COMPILATION_CACHE_DIR=placed)
    assert got["returned"] == got["knob"] == placed
    assert got["set_in_code"] == []  # jax read the variable itself


def test_cache_rule_unset_is_one_fixed_path_in_the_checkout(tmp_path):
    from stoke_tpu.compile_cache import DEFAULT_CACHE_DIR

    assert DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    got = [_run_rule(tmp_path / d) for d in ("a", "b")]  # two cwds
    for g in got:
        assert g["returned"] == g["knob"] == DEFAULT_CACHE_DIR
        assert g["set_in_code"] == [DEFAULT_CACHE_DIR]


def test_compile_config_default_ledger_sits_on_the_rule(monkeypatch, tmp_path):
    from stoke_tpu.compile_cache import DEFAULT_CACHE_DIR, ledger_dir

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert ledger_dir(CompileConfig()) == os.path.join(DEFAULT_CACHE_DIR, "aot")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert ledger_dir(CompileConfig()) == str(tmp_path / "aot")
    assert ledger_dir(CompileConfig(cache_dir="/x")) == "/x"


# --------------------------------------------------------------------------- #
# cold miss -> warm hit (the acceptance criterion)
# --------------------------------------------------------------------------- #


def test_second_construction_hits_and_reclaims_compile_seconds(
    tmp_path, devices
):
    """Acceptance (ISSUE 6): on the CPU mesh, a second Stoke construction
    with CompileConfig enabled reports >= 1 cache hit, a measurably
    smaller goodput_compile_s than the cold run, and step outputs
    bit-identical to the uncached path."""
    cache_dir = str(tmp_path / "cc")
    batches = _batches(3)

    def run(tag, *, cache):
        s = _make_stoke(
            tmp_path, cache=cache, telemetry=True, tag=tag,
            cache_dir=cache_dir,
        )
        for x, y in batches:
            s.train_step(x, (y,))
        s.close_telemetry()
        recs = read_step_events(
            str(tmp_path / tag / "telemetry" / "steps.jsonl")
        )
        compile_s = sum(r["goodput_compile_s"] or 0.0 for r in recs)
        return s, recs, compile_s

    cold, cold_recs, cold_compile = run("cold", cache=True)
    assert cold.compile_cache.misses >= 1
    assert cold.compile_cache.hits == 0
    assert cold_compile > 0
    cold_fresh = sum(
        r["goodput_compile_fresh_s"] or 0.0 for r in cold_recs
    )
    # the cold window's compile seconds were all FRESH
    assert cold_fresh == pytest.approx(cold_compile, rel=1e-6)
    assert sum(
        r["goodput_compile_cached_s"] or 0.0 for r in cold_recs
    ) == 0
    # ledger markers landed on disk (.bin artifacts additionally appear
    # when a live persistent XLA cache absorbs their extra compile —
    # not on the CPU backend, where that cache is disabled)
    files = os.listdir(cache_dir)
    assert any(f.startswith("exe-") and f.endswith(".json") for f in files)
    if cold.compile_cache.xla_available:
        assert any(
            f.startswith("exe-") and f.endswith(".bin") for f in files
        )

    warm, warm_recs, warm_compile = run("warm", cache=True)
    assert warm.compile_cache.hits >= 1
    assert warm.compile_cache.misses == 0
    assert warm.compile_cache.saved_compile_s > 0
    # measurably smaller: the persistent cache serves the warm backend
    # compile from disk instead of re-running XLA codegen
    assert warm_compile < cold_compile
    # the warm run's compile seconds are CACHED loads, not fresh codegen
    warm_fresh = sum(
        r["goodput_compile_fresh_s"] or 0.0 for r in warm_recs
    )
    warm_cached = sum(
        r["goodput_compile_cached_s"] or 0.0 for r in warm_recs
    )
    assert warm_cached > 0
    assert warm_fresh < cold_fresh
    assert warm_fresh + warm_cached == pytest.approx(
        warm_compile, rel=1e-6
    )
    # JSONL carries the cache counters
    assert warm_recs[-1]["compile_cache_hits"] >= 1
    assert warm_recs[-1]["compile_cache_saved_s"] > 0
    assert cold_recs[-1]["compile_cache_hits"] == 0

    plain, _, _ = run("plain", cache=False)
    np.testing.assert_array_equal(
        np.asarray(warm.params["w"]), np.asarray(plain.params["w"])
    )
    np.testing.assert_array_equal(
        np.asarray(cold.params["w"]), np.asarray(plain.params["w"])
    )


def test_all_step_apis_work_through_the_cache(tmp_path, devices):
    """The 4-call path (accum + apply) and the window/multi scans all
    dispatch through AOT executables with identical results."""
    cache_dir = str(tmp_path / "cc")
    batches = _batches(4, seed=7, batch=16)

    def run(cache):
        s = _make_stoke(tmp_path, cache=cache, cache_dir=cache_dir,
                        tag=f"api-{cache}")
        x0, y0 = batches[0]
        out = s.model(x0)
        loss = s.loss(out, y0)
        s.backward(loss)
        s.step()
        xs = np.stack([b[0] for b in batches[1:3]])
        ys = np.stack([b[1] for b in batches[1:3]])
        s.train_steps(xs, (ys,))
        s.train_step(*batches[3][:1], (batches[3][1],))
        return s

    cached = run(True)
    assert cached.compile_cache.misses >= 3  # accum, apply, multi, fused
    warm = run(True)
    assert warm.compile_cache.hits >= 3 and warm.compile_cache.misses == 0
    plain = run(False)
    np.testing.assert_array_equal(
        np.asarray(warm.params["w"]), np.asarray(plain.params["w"])
    )
    assert warm.dispatch_count == plain.dispatch_count
    assert warm.optimizer_steps == plain.optimizer_steps == 4


def test_warm_hit_serves_every_later_dispatch(tmp_path):
    """A process-cache hit must resolve LATER dispatches of the same
    signature to the shared already-compiled fn too — memoizing the warm
    run's own (never-compiled) fn instead would silently defer the full
    recompile to the second dispatch, turning the 'reclaimed' compile
    seconds into a one-step accounting fiction."""
    import jax.numpy as jnp

    cfg = CompileConfig(cache_dir=str(tmp_path / "cc"))
    x = jnp.arange(8, dtype=jnp.float32)
    fn_cold = jax.jit(lambda v: v * 2.0)
    cold = CompileCache(cfg)
    first = cold.executable("p", ("k", ()), fn_cold, (x,))
    np.testing.assert_array_equal(np.asarray(first(x)), np.asarray(x) * 2)
    assert cold.misses == 1
    # a second run's own fn for the identical program: never compiled
    fn_warm = jax.jit(lambda v: v * 2.0)
    warm = CompileCache(cfg)
    hit = warm.executable("p", ("k", ()), fn_warm, (x,))
    later = warm.executable("p", ("k", ()), fn_warm, (x,))
    assert warm.hits == 1 and warm.misses == 0
    assert hit is not fn_warm  # served the shared fn, not its own
    assert later is hit  # and every later dispatch resolves to it too
    np.testing.assert_array_equal(np.asarray(later(x)), np.asarray(x) * 2)


# --------------------------------------------------------------------------- #
# default-OFF identity
# --------------------------------------------------------------------------- #


def test_cache_off_is_bit_identical_and_on_adds_no_dispatches(
    tmp_path, devices
):
    """Default-OFF acceptance: the lowered step-program HLO and the
    dispatch count are identical with the config absent vs present (the
    cache swaps WHICH callable runs, never what it computes)."""
    s_off = _make_stoke(tmp_path, cache=False, tag="off")
    s_on = _make_stoke(tmp_path, cache=True, tag="on")
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

    off_text, on_text = fused_hlo(s_off), fused_hlo(s_on)
    strip = lambda t: "\n".join(
        ln for ln in t.splitlines() if not ln.startswith("HloModule")
    )
    assert strip(on_text) == strip(off_text)


# --------------------------------------------------------------------------- #
# degradation: serialization failures must never kill a step
# --------------------------------------------------------------------------- #


def test_serialize_failure_degrades_to_plain_compile(tmp_path, monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("serialization unsupported on this backend")

    import jax.experimental.serialize_executable as se

    monkeypatch.setattr(se, "serialize", boom)
    cache_dir = str(tmp_path / "cc")
    s = _make_stoke(tmp_path, cache=True, cache_dir=cache_dir)
    # force the artifact-serialization branch (on CPU it is skipped
    # because no live XLA cache would absorb the extra compile)
    s.compile_cache.xla_available = True
    x, y = _batches(1)[0]
    with pytest.warns(UserWarning, match="compile cache"):
        s.train_step(x, (y,))
    assert s.compile_cache.serialize_errors >= 1
    # the step still ran, and the marker (hit accounting) still landed —
    # only the offline .bin artifact is missing
    assert s.optimizer_steps == 1
    assert any(f.endswith(".json") for f in os.listdir(cache_dir))
    assert not any(
        f.endswith(".bin") for f in os.listdir(cache_dir)
    )


def test_corrupt_cache_marker_is_a_miss_not_a_crash(tmp_path, devices):
    import stoke_tpu.compile_cache as cc

    cache_dir = str(tmp_path / "cc")
    s1 = _make_stoke(tmp_path, cache=True, cache_dir=cache_dir, tag="a")
    x, y = _batches(1)[0]
    s1.train_step(x, (y,))
    markers = [f for f in os.listdir(cache_dir) if f.endswith(".json")]
    assert markers
    for m in markers:  # corrupt every marker
        with open(os.path.join(cache_dir, m), "w") as f:
            f.write("not json{")
    # simulate a FRESH process finding only the corrupt on-disk state
    # (in-process the program cache would mask the marker entirely)
    with cc._process_fn_lock:
        cc._process_fn_cache.clear()
    with pytest.warns(UserWarning, match="read"):
        s2 = _make_stoke(tmp_path, cache=True, cache_dir=cache_dir, tag="b")
        s2.train_step(x, (y,))
    assert s2.compile_cache.hits == 0
    assert s2.compile_cache.misses >= 1
    assert s2.optimizer_steps == 1
    # the miss path rewrote a valid marker AND republished the program,
    # so the next construction warm-starts again
    s3 = _make_stoke(tmp_path, cache=True, cache_dir=cache_dir, tag="c")
    s3.train_step(x, (y,))
    assert s3.compile_cache.hits >= 1


def test_artifact_roundtrip_offline(tmp_path):
    """The serialized ``exe-<key>.bin`` artifact deserializes and
    reproduces the jitted program's output on ready inputs (the
    supported OFFLINE use; training state never dispatches through
    it — see the module docstring's donation-bookkeeping evidence)."""
    import jax
    import jax.numpy as jnp

    from stoke_tpu.compile_cache import CompileCache, hlo_cache_key
    from stoke_tpu.configs import CompileConfig

    cfg = CompileConfig(cache_dir=str(tmp_path / "cc"))
    cache = CompileCache(cfg)
    if not cache.xla_available:
        pytest.skip("no live persistent XLA cache on this runtime")
    fn = jax.jit(lambda x: (x * 3.0 + 1.0).sum())
    x = jnp.arange(24.0, dtype=jnp.float32).reshape(4, 6)
    call = cache.executable("offline", ("k", ()), fn, (x,))
    expected = call(x)  # first dispatch writes marker + artifact
    key = hlo_cache_key(fn.lower(x).as_text(), cache.fingerprint)
    assert os.path.exists(os.path.join(cfg.cache_dir, key + ".bin"))
    try:
        exe = cache.deserialize(key)
        got = exe(x)
    except Exception as e:  # backend-dependent: see deserialize() docs
        pytest.skip(
            f"backend cannot reload its own serialized executable: {e!r}"
        )
    assert float(jax.block_until_ready(got)) == float(expected)


# --------------------------------------------------------------------------- #
# status rules + YAML construction
# --------------------------------------------------------------------------- #


def test_status_rejects_bad_compile_config(tmp_path):
    with pytest.raises(StokeValidationError, match="min_compile_time_s"):
        StokeStatus(
            batch_size_per_device=4,
            configs=[CompileConfig(
                cache_dir=str(tmp_path / "c"), min_compile_time_s=-1.0
            )],
        )
    with pytest.raises(StokeValidationError, match="caches nothing"):
        StokeStatus(
            batch_size_per_device=4,
            configs=[CompileConfig(
                cache_dir=str(tmp_path / "c"), aot=False, xla_cache=False
            )],
        )
    # unwritable cache dir: a FILE occupies the path
    blocker = tmp_path / "blocked"
    blocker.write_text("x")
    with pytest.raises(StokeValidationError, match="not writable"):
        StokeStatus(
            batch_size_per_device=4,
            configs=[CompileConfig(cache_dir=str(blocker))],
        )
    # valid combination passes and is accessible
    st = StokeStatus(
        batch_size_per_device=4,
        configs=[CompileConfig(cache_dir=str(tmp_path / "ok"))],
    )
    assert st.compile_config is not None
    assert st.compile_config.aot is True


def test_compile_config_yaml_buildable(tmp_path):
    from stoke_tpu.utils.yaml_config import stoke_kwargs_from_config

    kwargs = stoke_kwargs_from_config({
        "batch_size_per_device": 4,
        "configs": {
            "CompileConfig": {
                "cache_dir": str(tmp_path / "cc"),
                "min_compile_time_s": 0.5,
                "xla_cache": False,
            },
        },
    })
    (cfg,) = kwargs["configs"]
    assert isinstance(cfg, CompileConfig)
    assert cfg.min_compile_time_s == 0.5
    assert cfg.xla_cache is False


def test_cache_stats_surface(tmp_path, devices):
    s = _make_stoke(tmp_path, cache=True)
    x, y = _batches(1)[0]
    s.train_step(x, (y,))
    stats = s.compile_cache.stats()
    assert stats["misses"] == 1 and stats["hits"] == 0
    assert stats["serialize_errors"] == 0
    assert os.path.isdir(stats["cache_dir"])
    # no CompileConfig -> no cache surface
    s2 = _make_stoke(tmp_path, cache=False, tag="nocache")
    assert s2.compile_cache is None
