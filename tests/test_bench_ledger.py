"""Unit tests for what is left of bench.py's ledger (``BENCH_RESULTS.json``):
``persist_result``'s keep-best rule and ``check_regression``.  ROADMAP S1
removes both with the file; nothing reads a record back in place of a
measurement any more, and the new tests below pin that the full preset
refuses to run without a TPU.
"""

import os
import subprocess
import sys

import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
import bench


@pytest.fixture
def ledger(tmp_path, monkeypatch):
    path = tmp_path / "BENCH_RESULTS.json"
    monkeypatch.setattr(bench, "RESULTS_PATH", str(path))
    return path


def test_check_regression_flags_big_drop(ledger):
    bench.persist_result("m", {"value": 9257.0, "backend": "tpu"})
    reg = bench.check_regression("m", 8000.0)
    assert reg is not None
    assert reg["best"] == 9257.0
    assert reg["ratio"] == round(8000.0 / 9257.0, 4)


def test_check_regression_tolerates_noise_and_improvement(ledger):
    bench.persist_result("m", {"value": 9257.0, "backend": "tpu"})
    # within the 5% tolerance band: not a regression
    assert bench.check_regression("m", 9257.0 * 0.96) is None
    # faster than best: not a regression
    assert bench.check_regression("m", 10000.0) is None


def test_check_regression_no_prior_record(ledger):
    # a first-ever measurement can never regress
    assert bench.check_regression("never_measured", 1.0) is None


def test_persist_result_keep_best(ledger):
    bench.persist_result("m", {"value": 9000.0, "backend": "tpu"})
    # slower result with keep_best never clobbers the faster record
    bench.persist_result("m", {"value": 100.0, "backend": "tpu"},
                         keep_best=True)
    assert bench._load_results()["m"]["value"] == 9000.0
    # faster result replaces it
    bench.persist_result("m", {"value": 9500.0, "backend": "tpu"},
                         keep_best=True)
    assert bench._load_results()["m"]["value"] == 9500.0
    # without keep_best the write is unconditional (ranked callers like
    # accuracy_run order by backend/precision, not value alone)
    bench.persist_result("m", {"value": 42.0, "backend": "tpu"})
    assert bench._load_results()["m"]["value"] == 42.0


def test_memory_is_a_regression_config_key():
    """A --memory capture running slower than a differently-configured
    best is a cross-configuration comparison, never a like-for-like
    regression alarm."""
    assert "memory" in bench._REGRESSION_CONFIG_KEYS


@pytest.mark.parametrize("argv", [[], ["--serve"]])
def test_full_preset_refuses_cpu(argv):
    """No chip, no number: the full preset exits non-zero with a one-line
    reason and prints no value line."""
    repo = os.path.dirname(os.path.abspath(bench.__file__))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"), *argv],
        capture_output=True, text=True, timeout=300, cwd=repo,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode != 0
    assert out.stdout.strip() == "", out.stdout
    reason = out.stderr.strip().splitlines()[-1]
    assert "needs a TPU" in reason and "'cpu'" in reason, out.stderr[-500:]


def test_peaks_are_keyed_by_device_kind():
    """A device the table does not list has no peaks — the CPU included."""
    assert bench.DEVICE_PEAKS["TPU v5 lite"] == {
        "tflops_bf16": 197.0, "hbm_gbps": 819.0
    }
    assert "cpu" not in bench.DEVICE_PEAKS

