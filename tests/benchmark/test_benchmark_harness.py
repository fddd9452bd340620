"""CPU rehearsal of the benchmark harness (`benchmark/run.py`).

Every cell of `BENCHMARK.json` runs through `run.py`'s own entry at a tiny
width (2 layers x 128, interpreter kernels, a 2-second window): the cell's
real traffic file with its sizes overridden, a tiny configuration in place
of the real one, the real jobs, metric files and readers.  A CPU run gives
counts and plumbing, never a device number: the last line says
``"platform": "cpu"`` and carries no metric read from a device trace.
"""

import contextlib
import io
import json
import math
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

TINY_WIDTHS = {"n_layer": 2, "n_embd": 128, "n_head": 2, "vocab_size": 512,
               "n_positions": 128, "n_ctx": 128}
TINY_TRAFFIC = {
    "train_lm": {"seq_len": 64, "rows_per_device": 2, "grad_accum": 2,
                 "dataset_rows": 16, "warm_steps": 1, "trace_seconds": 2},
    "serve_lm": {
        "clients": 3, "pool": 12, "trace_seconds": 2, "checked_requests": 2,
        "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.8,
                       "min": 4, "max": 40},
        "output_len": {"dist": "uniform", "min": 2, "max": 4},
        "serve_config": {"max_seqs": 3, "kv_block_size": 16,
                         "max_seq_len": 64, "prefill_pad_multiple": 16,
                         "attention": "flash"},
    },
}


def _read(path):
    with open(path) as f:
        return json.load(f)


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def tiny_root(tmp_path):
    """A directory with the real `BENCHMARK.json` and metric files, every
    configuration cut to the tiny widths and every traffic file to tiny
    sizes.  No file of the benchmark is edited."""
    root = str(tmp_path)
    bench = _read(os.path.join(ROOT, "BENCHMARK.json"))
    for config in bench["configs"]:
        real = _read(os.path.join(ROOT, config["file"]))
        _write(os.path.join(root, config["file"]), {**real, **TINY_WIDTHS})
    for traffic in {w["traffic"] for w in bench["workloads"]}:
        rel = os.path.join("benchmark", "traffic", traffic + ".json")
        real = _read(os.path.join(ROOT, rel))
        _write(os.path.join(root, rel), {**real, **TINY_TRAFFIC[real["job"]]})
    shutil.copytree(os.path.join(ROOT, "benchmark", "metrics"),
                    os.path.join(root, "benchmark", "metrics"))
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root, bench


def run_cell(root, workload, trace, seconds=2, seed=2**31 + 12345):
    """`run.py`'s entry in this process; returns (exit code, stdout lines)."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main([
            "--root", root, "--workload", workload, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace), "--allow-cpu",
        ])
    return rc, out.getvalue().strip().splitlines()


def cell_metrics(bench, workload, group):
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


CELLS = [w["name"] for w in _read(os.path.join(ROOT, "BENCHMARK.json"))["workloads"]]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearsal(tmp_path, workload, trace):
    root, bench = tiny_root(tmp_path)
    rc, lines = run_cell(root, workload, trace)
    assert rc == 0, lines
    result = json.loads(lines[-1])
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True, lines
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(result["device"])
    group = "per_layer" if trace else "end_to_end"
    for metric in cell_metrics(bench, workload, group):
        if trace and (metric["source"] == "device_trace"
                      or "mfu" in metric["name"]):
            # a device number: a CPU run must not print one
            assert metric["name"] not in result["metrics"]
            continue
        got = result["metrics"][metric["name"]]
        assert got["unit"] == metric["unit"]
        assert math.isfinite(got["value"]) and got["value"] > 0, metric
    assert any("compiles:" in l and " 0 in the window" in l for l in lines), lines


def test_refuses_cpu_without_allow_cpu(tmp_path, capsys):
    root, _ = tiny_root(tmp_path)
    rc = bench_run.main(["--root", root, "--workload", CELLS[0],
                         "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_additions_are_data(tmp_path):
    """A later PR's cell: one more configuration file, one more traffic
    file, one more metric file on the `ratio` reader, and the entries that
    name them.  No file of the benchmark changes."""
    root, bench = tiny_root(tmp_path)
    first = bench["configs"][0]
    _write(os.path.join(root, "benchmark/configs/extra-model.json"),
           {**_read(os.path.join(root, first["file"])), "name": "extra-model",
            "n_layer": 3, "n_head": 4})
    base = _read(os.path.join(root, "benchmark/traffic/pretrain-1k-4call.json"))
    _write(os.path.join(root, "benchmark/traffic/extra-mix.json"),
           {**base, "seq_len": 32, "grad_accum": 1})
    _write(os.path.join(root, "benchmark/metrics/tokens_per_step.extra.json"),
           {"reader": "ratio",
            "args": {"num": "train.tokens", "den": "train.steps"}})
    bench["configs"].append({**first, "name": "extra-model",
                             "file": "benchmark/configs/extra-model.json"})
    bench["workloads"].append({"name": "extra-cell", "config": "extra-model",
                               "traffic": "extra-mix", "chips": 1, "why": "x"})
    for metric in bench["end_to_end"]:
        if metric["name"] == "train_tokens_per_s":
            metric["workloads"].append("extra-cell")
    bench["per_layer"].append({
        "name": "tokens_per_step.extra", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "facade",
        "moves": "train_tokens_per_s", "workloads": ["extra-cell"]})
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    rc, lines = run_cell(root, "extra-cell", trace=1, seconds=1)
    assert rc == 0, lines
    result = json.loads(lines[-1])
    assert result["correct"] is True
    assert result["metrics"] == {
        "tokens_per_step.extra": {"value": 2 * 32 * 1, "unit": "tokens"}}
    rc, lines = run_cell(root, "extra-cell", trace=0, seconds=1)
    assert rc == 0 and "train_tokens_per_s" in json.loads(lines[-1])["metrics"]


def test_every_named_file_exists():
    bench = _read(os.path.join(ROOT, "BENCHMARK.json"))
    for config in bench["configs"]:
        assert _read(os.path.join(ROOT, config["file"]))["name"] == config["name"]
    for cell in bench["workloads"]:
        traffic = _read(os.path.join(ROOT, "benchmark", "traffic",
                                     cell["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "jobs",
                                           traffic["job"] + ".py"))
    e2e = {m["name"] for m in bench["end_to_end"]}
    for metric in bench["per_layer"]:
        spec = _read(os.path.join(ROOT, "benchmark", "metrics",
                                  metric["name"] + ".json"))
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "readers",
                                           spec["reader"] + ".py"))
        assert metric["moves"] in e2e
