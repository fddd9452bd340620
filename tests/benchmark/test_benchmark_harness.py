"""CPU rehearsal of the benchmark harness (`benchmark/run.py`).

Every cell of `BENCHMARK.json` runs through `run.py`'s own entry at a tiny
size (interpreter kernels, a 2-second window): the cell's real configuration
and traffic files with the sizes of `tests/benchmark/rehearsal/configs/
<config>.json` and `.../traffic/<mix>.json` laid over them (data a PR adds
with its cell; for the accepted cells 2 layers x 128), the real jobs, family
modules, metric files and readers.  A CPU run gives
counts and plumbing, never a device number: the last line says
``"platform": "cpu"`` and carries no metric read from a device trace.
"""

import contextlib
import importlib.util
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

REHEARSAL = os.path.join(ROOT, "tests", "benchmark", "rehearsal")


def _read(path):
    with open(path) as f:
        return json.load(f)


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def tiny_root(tmp_path, source=ROOT, rehearsal=REHEARSAL):
    """A directory with `source`'s `BENCHMARK.json` and metric files, every
    configuration and every traffic file with the tiny sizes laid over it
    that `rehearsal`/configs/<config>.json and `rehearsal`/traffic/<mix>.json
    hold.  No file of the benchmark is edited.  A cell whose rehearsal file
    is missing is not written; `missing` maps it to the file to add, and
    only its own rehearsal fails."""
    root = str(tmp_path)
    bench = _read(os.path.join(source, "BENCHMARK.json"))
    files = {c["name"]: c["file"] for c in bench["configs"]}
    missing = {}
    for cell in bench["workloads"]:
        for kind, name, rel in (
            ("configs", cell["config"], files[cell["config"]]),
            ("traffic", cell["traffic"], os.path.join(
                "benchmark", "traffic", cell["traffic"] + ".json")),
        ):
            tiny = os.path.join(rehearsal, kind, name + ".json")
            if not os.path.isfile(tiny):
                missing.setdefault(cell["name"], os.path.join(
                    "tests", "benchmark", "rehearsal", kind, name + ".json"))
            elif not os.path.isfile(os.path.join(root, rel)):
                _write(os.path.join(root, rel),
                       {**_read(os.path.join(source, rel)), **_read(tiny)})
    shutil.copytree(os.path.join(source, "benchmark", "metrics"),
                    os.path.join(root, "benchmark", "metrics"))
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root, bench, missing


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
    root, bench, missing = tiny_root(tmp_path)
    check_rehearsal(root, bench, missing, workload, trace)


def check_rehearsal(root, bench, missing, workload, trace):
    assert workload not in missing, (
        f"{workload} has no rehearsal sizes: add {missing[workload]}")
    rc, lines = run_cell(root, workload, trace)
    assert rc == 0, lines
    result = json.loads(lines[-1])
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True, lines
    assert result["attempted"] > 0 and result["failed"] == 0
    # each number held against a limit, beside it, last in the line
    assert list(result)[-1] == "compared" and len(result["compared"]) >= 3
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())
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
    root, _, _ = tiny_root(tmp_path)
    rc = bench_run.main(["--root", root, "--workload", CELLS[0],
                         "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_additions_are_data(tmp_path):
    """A later PR's cell: one more configuration file, one more traffic
    file, one more metric file on the `ratio` reader, and the entries that
    name them.  No file of the benchmark changes."""
    root, bench, _ = tiny_root(tmp_path)
    first = bench["configs"][0]
    _write(os.path.join(root, "benchmark/configs/extra-model.json"),
           {**_read(os.path.join(root, first["file"])), "name": "extra-model",
            "vocab_size": 300})
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


def copy_source(source):
    """The benchmark's data files as a PR finds them, copied to `source` for
    the test to add to; returns `BENCHMARK.json`'s contents, not yet written
    there."""
    for kind in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmark", kind),
                        os.path.join(source, "benchmark", kind))
    return _read(os.path.join(ROOT, "BENCHMARK.json"))


def test_a_missing_rehearsal_file_fails_its_own_cell_only(tmp_path):
    """A cell that came without its rehearsal sizes: its rehearsal says
    which file to add, and every other cell's goes on as before."""
    source = str(tmp_path / "source")
    bench = copy_source(source)
    bench["workloads"].append({
        "name": "unrehearsed", "config": bench["configs"][0]["name"],
        "traffic": "no-such-mix", "chips": 1, "why": "x"})
    _write(os.path.join(source, "BENCHMARK.json"), bench)
    root, bench, missing = tiny_root(tmp_path / "tiny", source=source)
    assert missing == {"unrehearsed": os.path.join(
        "tests", "benchmark", "rehearsal", "traffic", "no-such-mix.json")}
    with pytest.raises(AssertionError, match="add tests/benchmark/rehearsal/"
                                             "traffic/no-such-mix.json"):
        check_rehearsal(root, bench, missing, "unrehearsed", trace=0)
    check_rehearsal(root, bench, missing, CELLS[0], trace=0)


@pytest.mark.parametrize("where,config,message", [
    ("benchmark/configs/x.json", {"name": "x", "vocab_size": 8},
     r'benchmark/configs/x.json: no "family" key'),
    (None, {"name": "x", "family": "no_such", "vocab_size": 8},
     r"configuration 'x': unknown family 'no_such'"),
    (None, {"name": "x", "family": "gpt2"}, r'no "vocab_size" key'),
])
def test_family_lookup_has_no_default(where, config, message):
    from benchmark.lib.model import family

    with pytest.raises(ValueError, match=message):
        family(config, where)


STUB_FAMILY = os.path.join(REHEARSAL, "families", "stub_hf.py")


def test_a_second_family_is_data(tmp_path, monkeypatch):
    """A later PR's model family, as that PR brings it: a family module that
    reads keys no file of the benchmark knows, a configuration of those
    keys, a traffic mix, their two rehearsal files, and the entries that
    name them.  No file of the benchmark changes; the new cell runs through
    the same jobs, and the accepted cells' rehearsals pass beside it."""
    spec = importlib.util.spec_from_file_location(
        "benchmark.lib.families.stub_hf", STUB_FAMILY)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    monkeypatch.setitem(sys.modules, spec.name, module)
    # the stub's configuration and rehearsal sizes sit with it, in its keys
    stub_config, stub_tiny = module.CONFIG, module.TINY

    source, rehearsal = str(tmp_path / "source"), str(tmp_path / "rehearsal")
    bench = copy_source(source)
    shutil.copytree(REHEARSAL, rehearsal)
    _write(os.path.join(source, "benchmark/configs/stub-model.json"),
           stub_config)
    mix = _read(os.path.join(ROOT, "benchmark/traffic/chat-closed12.json"))
    _write(os.path.join(source, "benchmark/traffic/stub-mix.json"),
           {**mix, "clients": 24, "why": "a second family's callers"})
    _write(os.path.join(rehearsal, "configs", "stub-model.json"), stub_tiny)
    _write(os.path.join(rehearsal, "traffic", "stub-mix.json"),
           {**_read(os.path.join(REHEARSAL, "traffic", "chat-closed12.json")),
            "clients": 2})
    bench["configs"].append({
        "name": "stub-model", "source": stub_config["source"],
        "file": "benchmark/configs/stub-model.json", "reduced": [],
        "why": "x"})
    bench["workloads"].append({
        "name": "serve-stub", "config": "stub-model", "traffic": "stub-mix",
        "chips": 1, "why": "x"})
    like = "serve-gpt2m-closed12"
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if like in metric.get("workloads", ()):
            metric["workloads"].append("serve-stub")
    _write(os.path.join(source, "BENCHMARK.json"), bench)

    root, bench, missing = tiny_root(tmp_path / "tiny", source, rehearsal)
    assert not missing
    for trace in (0, 1):
        check_rehearsal(root, bench, missing, "serve-stub", trace)
    assert len(cell_metrics(bench, "serve-stub", "per_layer")) == len(
        cell_metrics(bench, like, "per_layer")) > 6
    for cell in CELLS:
        check_rehearsal(root, bench, missing, cell, trace=0)


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
