"""Static-analysis tests (ISSUE 15): the jax-free invariant linter's
rule families with seeded violations, waiver/manifest handling, the
program auditor over lowered step/serve programs, the Stoke.audit()
acceptance on the 8-device mesh (zero findings, zero added dispatches),
and the stoke_lint / gen_api_md --check CLIs."""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stoke_tpu.analysis.invariants import (
    check_banned_apis,
    check_config_coverage,
    check_jsonl_schema,
    check_wire_formats,
    run_invariant_lints,
)
from stoke_tpu.analysis.program import (
    ProgramSpec,
    abstractify_args,
    audit_program_specs,
)

pytestmark = pytest.mark.analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------- #
# wire-format append-only
# --------------------------------------------------------------------------- #


def _wire_fixture(tmp_path, body: str):
    (tmp_path / "mod.py").write_text(body)
    return [{"file": "mod.py", "name": "FMT", "fields": ["a", "b", "c"]}]


def test_wire_clean_tree():
    assert check_wire_formats(REPO) == []


def test_wire_reorder_flagged(tmp_path):
    manifest = _wire_fixture(tmp_path, 'FMT = ("a", "c", "b")\n')
    fs = check_wire_formats(str(tmp_path), manifest)
    assert len(fs) == 1 and fs[0].rule == "wire-append-only"
    assert fs[0].file == "mod.py" and fs[0].line == 1
    assert "slot 1" in fs[0].message and "'b'" in fs[0].message
    assert "never reorder" in fs[0].remedy


def test_wire_removal_flagged(tmp_path):
    manifest = _wire_fixture(tmp_path, 'FMT = ("a", "b")\n')
    fs = check_wire_formats(str(tmp_path), manifest)
    assert len(fs) == 1 and "<removed>" in fs[0].message


def test_wire_append_without_manifest_update_flagged(tmp_path):
    manifest = _wire_fixture(tmp_path, 'FMT = ("a", "b", "c", "d")\n')
    fs = check_wire_formats(str(tmp_path), manifest)
    assert len(fs) == 1
    assert "grew" in fs[0].message and "['d']" in fs[0].message
    assert "wire_formats.json" in fs[0].remedy


def test_wire_append_with_manifest_update_clean(tmp_path):
    manifest = _wire_fixture(tmp_path, 'FMT = ("a", "b", "c")\n')
    assert check_wire_formats(str(tmp_path), manifest) == []


def test_wire_missing_symbol_flagged(tmp_path):
    manifest = _wire_fixture(tmp_path, "OTHER = 1\n")
    fs = check_wire_formats(str(tmp_path), manifest)
    assert len(fs) == 1 and "not a top-level literal" in fs[0].message


# --------------------------------------------------------------------------- #
# config-field status-rule coverage
# --------------------------------------------------------------------------- #

_FIXTURE_CONFIGS = textwrap.dedent(
    """
    from dataclasses import dataclass

    @dataclass
    class FooConfig:
        guarded_knob: int = 1
        unguarded_knob: int = 2
        waived_knob: bool = True
    """
)

_FIXTURE_STATUS = textwrap.dedent(
    """
    def _foo_invalid(cfg):
        if cfg.guarded_knob < 1:
            return "FooConfig.guarded_knob must be >= 1"
        return False
    """
)


def _coverage(tmp_path, waivers):
    (tmp_path / "configs.py").write_text(_FIXTURE_CONFIGS)
    (tmp_path / "status.py").write_text(_FIXTURE_STATUS)
    return check_config_coverage(
        str(tmp_path),
        configs_path=str(tmp_path / "configs.py"),
        status_path=str(tmp_path / "status.py"),
        waivers=waivers,
    )


def test_config_coverage_clean_tree():
    assert check_config_coverage(REPO) == []


def test_config_unguarded_field_flagged(tmp_path):
    fs = _coverage(tmp_path, {"FooConfig.waived_knob": "boolean"})
    assert len(fs) == 1 and fs[0].rule == "config-guard"
    assert "FooConfig.unguarded_knob" in fs[0].message
    # file:line points at the field definition
    assert fs[0].file == "configs.py" and fs[0].line == 7
    assert "status.py rule" in fs[0].remedy and "waive" in fs[0].remedy


def test_config_waived_field_passes(tmp_path):
    fs = _coverage(
        tmp_path,
        {
            "FooConfig.waived_knob": "boolean",
            "FooConfig.unguarded_knob": "any int is legal",
        },
    )
    assert fs == []


def test_config_unknown_waiver_loud(tmp_path):
    fs = _coverage(
        tmp_path,
        {
            "FooConfig.waived_knob": "boolean",
            "FooConfig.unguarded_knob": "any int is legal",
            "FooConfig.typo_knob": "stale entry",
            "GoneConfig.x": "class no longer exists",
        },
    )
    rules = sorted(f.rule for f in fs)
    assert rules == ["config-waiver-unknown", "config-waiver-unknown"]
    assert any("FooConfig.typo_knob" in f.message for f in fs)
    assert any("GoneConfig.x" in f.message for f in fs)


def test_config_common_method_name_not_covered(tmp_path):
    """Review regression: ``"x".join(...)`` / ``d.get(...)`` method
    calls in status.py must NOT mark config fields named join/get as
    guarded — attribute collection is scoped to simple-name bases."""
    (tmp_path / "configs.py").write_text(textwrap.dedent(
        """
        from dataclasses import dataclass

        @dataclass
        class FooConfig:
            join: str = "x"
        """
    ))
    (tmp_path / "status.py").write_text(
        'MSG = ", ".join(["a", "b"])\n'
        "def rule(d):\n"
        "    return {}.get(MSG)\n"
    )
    fs = check_config_coverage(
        str(tmp_path),
        configs_path=str(tmp_path / "configs.py"),
        status_path=str(tmp_path / "status.py"),
        waivers={},
    )
    # the string constant "join"+... is not an identifier-only literal
    # here; the .join/.get METHOD accesses must not cover the field
    assert [f.rule for f in fs] == ["config-guard"]
    assert "FooConfig.join" in fs[0].message


def test_config_waiver_without_reason_loud(tmp_path):
    fs = _coverage(
        tmp_path,
        {
            "FooConfig.waived_knob": "",
            "FooConfig.unguarded_knob": "any int is legal",
        },
    )
    assert len(fs) == 1 and "no reason" in fs[0].message


# --------------------------------------------------------------------------- #
# nullable-JSONL discipline
# --------------------------------------------------------------------------- #

_FIXTURE_EVENTS = textwrap.dedent(
    """
    STEP_EVENT_FIELDS = {
        "step": (True, "int"),
        "serve/known": (False, "nullable_number"),
        "serve/required_oops": (True, "number"),
    }
    """
)


def _jsonl(tmp_path, emitter_body):
    (tmp_path / "events.py").write_text(_FIXTURE_EVENTS)
    (tmp_path / "emit.py").write_text(emitter_body)
    return check_jsonl_schema(
        str(tmp_path),
        emitters=["emit.py"],
        schema_path=str(tmp_path / "events.py"),
    )


def test_jsonl_clean_tree():
    assert check_jsonl_schema(REPO) == []


def test_jsonl_unknown_key_flagged(tmp_path):
    fs = _jsonl(
        tmp_path,
        "class M:\n"
        "    def event_fields(self):\n"
        '        return {"serve/known": 1, "serve/bogus": 2}\n',
    )
    assert len(fs) == 1 and fs[0].rule == "jsonl-schema"
    assert "serve/bogus" in fs[0].message and fs[0].line == 3
    assert "STEP_EVENT_FIELDS" in fs[0].remedy


def test_jsonl_required_key_flagged(tmp_path):
    fs = _jsonl(
        tmp_path,
        "class M:\n"
        "    def event_fields(self):\n"
        "        out = {}\n"
        '        out["serve/required_oops"] = 1\n'
        "        return out\n",
    )
    assert len(fs) == 1 and "required" in fs[0].message


def test_jsonl_non_emitter_function_ignored(tmp_path):
    fs = _jsonl(
        tmp_path,
        "def helper():\n"
        '    return {"serve/bogus": 1}\n',
    )
    assert fs == []


# --------------------------------------------------------------------------- #
# banned APIs
# --------------------------------------------------------------------------- #


def test_banned_clean_tree():
    assert check_banned_apis(REPO) == []


def test_banned_jax_import_flagged(tmp_path):
    (tmp_path / "driver.py").write_text(
        "import os\n"
        "try:\n"
        "    import jax\n"
        "except ImportError:\n"
        "    jax = None\n"
    )
    fs = check_banned_apis(
        str(tmp_path), jax_free=["driver.py"], no_device_get=[]
    )
    assert len(fs) == 1 and fs[0].rule == "banned-jax-import"
    assert fs[0].file == "driver.py" and fs[0].line == 3
    assert "subprocess" in fs[0].remedy


def test_banned_jax_import_function_local_ok(tmp_path):
    (tmp_path / "driver.py").write_text(
        "def go():\n"
        "    import jax\n"
        "    from jax import numpy\n"
        "    return jax, numpy\n"
    )
    fs = check_banned_apis(
        str(tmp_path), jax_free=["driver.py"], no_device_get=[]
    )
    assert fs == []


def test_banned_device_get_flagged(tmp_path):
    (tmp_path / "engine.py").write_text(
        "import jax\n"
        "def fetch(x):\n"
        "    return jax.device_get(x)\n"
    )
    fs = check_banned_apis(
        str(tmp_path), jax_free=[], no_device_get=["engine.py"]
    )
    assert len(fs) == 1 and fs[0].rule == "banned-device-get"
    assert fs[0].line == 3 and "sentinel" in fs[0].remedy


# --------------------------------------------------------------------------- #
# the full lint + CLI
# --------------------------------------------------------------------------- #


def test_full_lint_clean_on_current_tree():
    """THE merged-tree contract: make lint exits 0."""
    fs = run_invariant_lints(REPO)
    assert fs == [], "\n".join(f.format() for f in fs)


def test_cli_exit0_and_json():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "stoke_lint.py"),
         "--json"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    payload = json.loads(out.stdout)
    assert payload["findings"] == []
    assert payload["version"].startswith("stoke_tpu.analysis/")


def test_cli_never_imports_jax(tmp_path):
    """A poisoned jax package on
    PYTHONPATH proves the lint CLI never imports it (the banned-API rule
    enforces the same thing statically; this enforces it dynamically)."""
    poison = tmp_path / "jax"
    poison.mkdir()
    (poison / "__init__.py").write_text(
        'raise RuntimeError("stoke_lint must not import jax")\n'
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = str(tmp_path) + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "stoke_lint.py")],
        capture_output=True, text=True, cwd=REPO, env=env,
    )
    assert out.returncode == 0, out.stderr
    assert "0 finding(s)" in out.stdout


def test_cli_findings_exit1(tmp_path):
    """A doctored mini-tree (jax import in a jax-free module path) exits
    1 with the finding printed file:line + remedy."""
    driver = tmp_path / "stoke_tpu" / "resilience.py"
    driver.parent.mkdir(parents=True)
    driver.write_text("import jax\n")
    # satisfy the manifest-presence checks with empty-but-valid manifests
    man = tmp_path / "stoke_tpu" / "analysis" / "manifests"
    man.mkdir(parents=True)
    (man / "wire_formats.json").write_text('{"wire_formats": []}')
    (man / "config_waivers.json").write_text('{"waivers": {}}')
    (tmp_path / "stoke_tpu" / "configs.py").write_text("")
    (tmp_path / "stoke_tpu" / "status.py").write_text("")
    (tmp_path / "stoke_tpu" / "telemetry").mkdir()
    (tmp_path / "stoke_tpu" / "telemetry" / "events.py").write_text(
        'STEP_EVENT_FIELDS = {"step": (True, "int")}\n'
    )
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "stoke_lint.py"),
         "--repo-root", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert out.returncode == 1, out.stdout + out.stderr
    assert "banned-jax-import" in out.stdout
    assert "stoke_tpu/resilience.py:1" in out.stdout
    assert "remedy" in out.stdout


def test_gen_api_md_check_mode(tmp_path):
    """--check: exit 0 against the committed file, exit 2 against a
    doctored copy — regenerated-api.md stops being honor-system."""
    spec = importlib.util.spec_from_file_location(
        "_gen_api_md", os.path.join(REPO, "scripts", "gen_api_md.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    assert mod.main(["--check"]) == 0
    doctored = tmp_path / "api.md"
    doctored.write_text(mod.render() + "\n<!-- doctored -->\n")
    assert mod.main(["--check", "--out", str(doctored)]) == 2
    assert mod.main(["--check", "--out", str(tmp_path / "missing.md")]) == 2


def test_shared_hlo_normalizer():
    """ONE normalizer: the compile-cache key and the auditor consume the
    same module-name normalization (two would drift — the satellite)."""
    from stoke_tpu.analysis.hlo_text import normalize_module_name
    from stoke_tpu.compile_cache import hlo_cache_key

    a = "module @jit_step.1 attributes {x} {\n body \n}"
    b = "module @jit_other attributes {x} {\n body \n}"
    assert normalize_module_name(a) == normalize_module_name(b)
    assert hlo_cache_key(a, "fp") == hlo_cache_key(b, "fp")
    hlo_a = "HloModule jit_step.1, entry\nbody"
    hlo_b = "HloModule jit_other, entry\nbody"
    assert normalize_module_name(hlo_a) == normalize_module_name(hlo_b)


# --------------------------------------------------------------------------- #
# program auditor: seeded violations
# --------------------------------------------------------------------------- #


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def test_audit_donation_lost():
    """A declared donation with no matching output shape is silently
    dropped by jax — the auditor flags it with the remedy named."""
    fn = jax.jit(lambda x, y: y * 2.0, donate_argnums=(0,))
    rep = audit_program_specs(
        [ProgramSpec("apply", fn, (_f32(3, 7), _f32(4)),
                     donate_argnums=(0,))]
    )
    assert [f.rule for f in rep.findings] == ["audit-donation"]
    f = rep.findings[0]
    assert f.file == "<jit:apply>" and "argument 0" in f.message
    assert "donated" in f.remedy


def test_audit_donation_honored_clean():
    fn = jax.jit(lambda x, y: (x + 1.0, y), donate_argnums=(0,))
    rep = audit_program_specs(
        [ProgramSpec("apply", fn, (_f32(4, 4), _f32(4)),
                     donate_argnums=(0,))]
    )
    assert rep.findings == []


def test_audit_empty_donated_pytree_skipped():
    """A donated argnum whose subtree has no array leaves (the inactive
    comm state) cannot alias anything — never flagged."""
    fn = jax.jit(lambda x, c: (x + 1.0, c), donate_argnums=(0, 1))
    rep = audit_program_specs(
        [ProgramSpec("apply", fn, (_f32(4, 4), {}),
                     donate_argnums=(0, 1))]
    )
    assert rep.findings == []


def test_audit_hidden_transfer():
    def cb(x):
        return jax.pure_callback(
            lambda a: np.asarray(a) * 2,
            jax.ShapeDtypeStruct((4,), np.float32), x,
        )

    rep = audit_program_specs([ProgramSpec("fused", jax.jit(cb), (_f32(4),))])
    assert [f.rule for f in rep.findings] == ["audit-hidden-transfer"]
    assert "callback" in rep.findings[0].message
    assert "sentinel" in rep.findings[0].remedy


def test_audit_weak_type_scalar_arg():
    avals, weak = abstractify_args((np.zeros((4,), np.float32), 3.0))
    assert weak and "float" in weak[0]
    rep = audit_program_specs(
        [ProgramSpec("accum", jax.jit(lambda x, s: x * s), avals,
                     weak_leaves=weak)]
    )
    assert [f.rule for f in rep.findings] == ["audit-weak-type"]
    assert "recompile" in rep.findings[0].message


def test_audit_deserialized_executable():
    rep = audit_program_specs([ProgramSpec("apply", object(), ())])
    assert [f.rule for f in rep.findings] == ["audit-deserialized"]
    f = rep.findings[0]
    assert "donated-input bookkeeping" in f.message
    assert "persistent XLA cache" in f.remedy


def test_audit_replicated_bytes(devices):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices).reshape(8), ("data",))
    repl = NamedSharding(mesh, P())
    big = jax.ShapeDtypeStruct((1024, 1024), jnp.float32, sharding=repl)
    fn = jax.jit(lambda x: x + 1.0, out_shardings=repl)
    rep = audit_program_specs(
        [ProgramSpec("window", fn, (big,))],
        replicated_bytes_threshold=1 << 20,
    )
    assert [f.rule for f in rep.findings] == ["audit-replicated-bytes"]
    assert "replicated" in rep.findings[0].message
    # above the default 64 MiB threshold the same 4 MiB tensor is fine
    rep2 = audit_program_specs([ProgramSpec("window", fn, (big,))])
    assert rep2.findings == []
    # regression: a big SHARDED tensor alongside a tiny replicated arg
    # must NOT be flagged — the annotation belongs to the tiny arg, and
    # jax prints the whole @main signature on one line
    sharded = NamedSharding(mesh, P("data"))
    big_sharded = jax.ShapeDtypeStruct(
        (1024, 1024), jnp.float32, sharding=sharded
    )
    tiny_repl = jax.ShapeDtypeStruct((2,), jnp.float32, sharding=repl)
    fn2 = jax.jit(lambda x, s: x + s[0], out_shardings=sharded)
    rep3 = audit_program_specs(
        [ProgramSpec("window", fn2, (big_sharded, tiny_repl))],
        replicated_bytes_threshold=1 << 20,
    )
    assert rep3.findings == [], rep3.format()


def test_audit_comm_bytes_cross_check(devices):
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(devices).reshape(8), ("data",))
    plain = jax.jit(lambda x: x * 2.0)
    manual = jax.jit(
        shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                  in_specs=P("data"), out_specs=P())
    )
    # transport claims bytes but the apply program has no collective
    rep = audit_program_specs(
        [ProgramSpec("apply", plain, (_f32(8),))],
        transport_active=True, comm_bytes={"onwire": 4096},
    )
    assert [f.rule for f in rep.findings] == ["audit-comm-bytes"]
    assert "bytes_per_step" in rep.findings[0].message
    # manual collectives with NO transport: unaccounted traffic
    rep2 = audit_program_specs(
        [ProgramSpec("apply", manual, (_f32(8, 4),))],
        transport_active=False,
    )
    assert [f.rule for f in rep2.findings] == ["audit-comm-bytes"]
    assert "unaccounted" in rep2.findings[0].message.lower() or \
        "invisible" in rep2.findings[0].message
    # micro-step programs are exempt (no transport at their boundary)
    rep3 = audit_program_specs(
        [ProgramSpec("accum", manual, (_f32(8, 4),))],
        transport_active=False,
    )
    assert rep3.findings == []


def test_audit_recompile_churn():
    rep = audit_program_specs(
        [], shape_sig_counts={"accum": 40}, churn_threshold=32
    )
    assert [f.rule for f in rep.findings] == ["audit-recompile-churn"]
    assert "40 distinct" in rep.findings[0].message
    capped = audit_program_specs([], shape_sig_counts={"accum": 1024})
    assert "DISENGAGED" in capped.findings[0].message
    clean = audit_program_specs([], shape_sig_counts={"accum": 3})
    assert clean.findings == []


# --------------------------------------------------------------------------- #
# Stoke.audit() acceptance (8-device mesh; all four step APIs + serve)
# --------------------------------------------------------------------------- #


def _linear_stoke(**kw):
    import optax

    from stoke_tpu import Stoke

    kw.setdefault("batch_size_per_device", 2)
    kw.setdefault("verbose", False)
    return Stoke(
        model=lambda p, x: x @ p["w"],
        optimizer=optax.sgd(0.1),
        loss=lambda o, y: jnp.mean((o - y) ** 2),
        params={"w": np.zeros((8, 4), np.float32)},
        distributed="dp",
        **kw,
    )


@pytest.fixture(scope="module")
def serve_engine():
    from stoke_tpu.configs import ServeConfig
    from stoke_tpu.models.gpt import GPT
    from stoke_tpu.serving import ServingEngine
    from stoke_tpu.utils import init_module

    gpt = GPT(vocab_size=257, size_name="tiny", max_len=128,
              dropout_rate=0.0)
    variables = init_module(
        gpt, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32), train=False
    )
    eng = ServingEngine(
        gpt, variables["params"],
        ServeConfig(max_seqs=2, kv_block_size=8, max_seq_len=64,
                    max_new_tokens=4, prefill_pad_multiple=16),
    )
    eng.submit(np.array([5, 6, 7], np.int32))
    eng.run()
    return eng


def test_stoke_audit_acceptance(rng, serve_engine):
    """THE acceptance: all four step APIs + a serve engine audit with
    zero findings and ZERO added dispatches on the 8-device mesh."""
    s = _linear_stoke(grad_accum=2)
    x = rng.normal(size=(16, 8)).astype(np.float32)
    y = rng.normal(size=(16, 4)).astype(np.float32)
    s.train_step(x, y)
    s.train_step(x, y)  # boundary: fused_nb + fused
    s.backward(s.loss(s.model(x), y))
    s.backward(s.loss(s.model(x), y))
    s.step()  # accum + apply
    xs, ys = np.stack([x, x]), np.stack([y, y])
    s.train_step_window(xs, ys)  # window
    s.train_steps(np.stack([xs, xs]), np.stack([ys, ys]))  # multi
    before = s.dispatch_count
    report = s.audit(serve=serve_engine)
    # every step API's program family + both serve programs audited
    assert {"fused", "fused_nb", "accum", "apply", "window", "multi"} <= set(
        report.programs
    )
    assert {"serve_prefill", "serve_decode"} <= set(report.programs)
    assert report.findings == [], report.format()
    assert report.ok
    assert s.dispatch_count == before, "audit dispatched a program"
    # analysis/* counters on the PR-1 registry
    text = json.dumps(s._telemetry.registry.snapshot())
    assert "analysis/programs_audited_total" in text
    assert "analysis/audit_findings_total" in text


def test_engine_audit_specs_bounded_and_abstract(rng):
    """Specs record ShapeDtypeStructs (never live buffers — donation
    deletes those) and the ledger is capped."""
    s = _linear_stoke()
    x = rng.normal(size=(16, 8)).astype(np.float32)
    y = rng.normal(size=(16, 4)).astype(np.float32)
    s.train_step(x, y)
    specs = s._engine.audit_specs()
    assert specs and all(
        isinstance(l, jax.ShapeDtypeStruct)
        for sp in specs
        for l in jax.tree_util.tree_leaves(sp.abstract_args)
        if hasattr(l, "shape")
    )
    # repeat dispatches don't grow the ledger
    n = len(specs)
    s.train_step(x, y)
    assert len(s._engine.audit_specs()) == n
    assert s._engine._MAX_AUDIT_SPECS >= n
    # declared donations recorded at the jit sites (single source —
    # review regression: a hand-maintained mirror table would drift)
    assert s._engine._program_donations["fused"] == (0, 1, 2, 4)


def test_audit_notes_when_spec_cap_truncates(rng):
    """Review regression: a spec dropped at the audit cap must surface
    as a note — zero findings over an incomplete inventory is not a
    clean audit."""
    s = _linear_stoke()
    s._engine._MAX_AUDIT_SPECS = 0  # instance override: drop everything
    x = rng.normal(size=(16, 8)).astype(np.float32)
    y = rng.normal(size=(16, 4)).astype(np.float32)
    s.train_step(x, y)
    report = s.audit()
    assert report.programs == []
    assert any("truncated" in n for n in report.notes)


def test_audit_notes_when_churn_untracked(rng):
    """Review regression: without a TelemetryConfig the engine never
    tracks shape signatures — the audit must SAY the churn rule could
    not run instead of reporting it vacuously clean."""
    s = _linear_stoke()
    x = rng.normal(size=(16, 8)).astype(np.float32)
    y = rng.normal(size=(16, 4)).astype(np.float32)
    s.train_step(x, y)
    report = s.audit()
    assert report.ok
    assert any("audit-recompile-churn not checked" in n
               for n in report.notes)
    assert "not checked" in report.format()


def test_audit_warns_on_findings(rng):
    """An interactive audit is never silent: findings warn rank-0
    through the facade (the status remedy-naming machinery)."""
    s = _linear_stoke()
    x = rng.normal(size=(16, 8)).astype(np.float32)
    y = rng.normal(size=(16, 4)).astype(np.float32)
    s.train_step(x, y)
    # seed a bogus spec straight into the engine ledger
    s._engine._audit_specs.append(
        ProgramSpec("apply", object(), (), source="engine")
    )
    with pytest.warns(UserWarning, match="program audit found"):
        report = s.audit()
    assert not report.ok
