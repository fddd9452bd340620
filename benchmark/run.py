#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: finds the cell in ``BENCHMARK.json``, loads its configuration
file and its traffic file, imports the job the traffic file names, and calls
that job's ``setup`` (build, warm), ``measure`` (one window), ``check``
(correctness, outside the window) and ``end_to_end``.  The job takes the
model's builder, reference and counts from the module the configuration's
``"family"`` names (``benchmark/lib/model.py``).  With ``--trace 1`` the
window runs under ``jax.profiler`` and the line carries the cell's per-layer
metrics, each read from the observations by the reader its
``metrics/<name>.json`` names.  Nothing about a cell, a model or a traffic
mix lives in this file.

Human-readable detail goes on ``bench:`` lines; the last line of stdout is the
result, one JSON object.  Without a TPU (and without ``--allow-cpu``, the
tests' rehearsal) it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)


METRICS_DIR = os.path.join("benchmark", "metrics")
TRAFFIC_DIR = os.path.join("benchmark", "traffic")


def _say(msg: str) -> None:
    print(f"bench: [{time.perf_counter() - _T_START:6.1f} s] {msg}", flush=True)


def _load(root: str, relative: str) -> dict:
    with open(os.path.join(root, relative)) as f:
        return json.load(f)


def _cache_entries(path) -> int:
    if not path or not os.path.isdir(path):
        return 0
    return sum(e.is_file() for e in os.scandir(path))


def _install_compile_cache(platform: str):
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    if set, else the fixed ``<checkout>/.jax_cache`` (the program's own
    rule, ``stoke_tpu/compile_cache.py``).  None on the CPU, where that
    module records that the cache corrupts the heap under donated programs."""
    import jax

    if platform == "cpu":
        return None
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(CHECKOUT, ".jax_cache")
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # every program, small and quick ones too: a second run must find all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def _memory_stats(devices, chips: int) -> list:
    return [d.memory_stats() or {} for d in devices[:chips]]


def _memory_peak(after_window: dict, at_end: dict) -> int:
    """Peak bytes of one chip.  The TPU runtime keeps a running program's
    temporaries in memory it *reserves*, outside ``bytes_in_use`` (seen on
    the v5e, PR 24: doubling a train cell's rows doubled
    ``peak_bytes_reserved`` and left ``peak_bytes_in_use`` where it was).
    So the peak is the larger of the allocator's own peak, which set-up or
    the check may set, and what was resident when the window closed plus
    the most the runtime ever reserved."""
    return max(
        int(at_end.get("peak_bytes_in_use", 0)),
        int(after_window.get("bytes_in_use", 0))
        + int(at_end.get("peak_bytes_reserved", 0)),
    )


def _compared_lines(correct: bool, compared: dict) -> list:
    """Each number held against a limit, beside it: the run's last words on
    standard error."""
    lines = [
        f"benchmark: compared {name}: {c['value']} (limit {c['limit']})"
        for name, c in compared.items()
    ]
    return lines + [f"benchmark: correct={str(correct).lower()}"]


def _cell(bench: dict, name: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, config


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def _per_layer(bench: dict, root: str, cell_name: str, obs: dict) -> dict:
    """Every per-layer metric of the cell, through its own reader.  A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for metric in bench["per_layer"]:
        if not _applies(metric, cell_name):
            continue
        spec = _load(root, os.path.join(METRICS_DIR, metric["name"] + ".json"))
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        value = reader.read(obs, spec.get("args", {}))
        if value is not None and math.isfinite(value):
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the window (default: run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal without a TPU: prints no device metric")
    ap.add_argument("--root", default=CHECKOUT,
                    help="directory holding BENCHMARK.json and the data "
                         "files it names (default: this checkout)")
    args = ap.parse_args(argv)

    bench = _load(args.root, "BENCHMARK.json")
    cell, config_entry = _cell(bench, args.workload)
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]

    # the system under test, before anything is printed: a directory that
    # holds only the benchmark has nothing to run, and says nothing
    import stoke_tpu  # noqa: F401

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.allow_cpu:
        print(f"benchmark: needs a TPU; JAX found {platform!r} "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})",
              file=sys.stderr)
        return 1
    if platform == "tpu" and len(devices) < cell["chips"]:
        print(f"benchmark: {cell['name']} needs {cell['chips']} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    kind = devices[0].device_kind
    peaks = _load(CHECKOUT, "benchmark/lib/peaks.json")["by_device_kind"]
    if platform == "tpu" and kind not in peaks:
        print(f"benchmark: no published peaks for device kind {kind!r} in "
              f"benchmark/lib/peaks.json", file=sys.stderr)
        return 1

    from benchmark.lib import compiles
    from benchmark.lib.model import family
    from benchmark.lib.trace_reduce import Tracer

    cache_dir = _install_compile_cache(platform)
    cache_before = _cache_entries(cache_dir)
    compiles.install()

    config = _load(args.root, config_entry["file"])
    family(config, config_entry["file"])  # a missing one stops the run here
    traffic = _load(args.root,
                    os.path.join(TRAFFIC_DIR, cell["traffic"] + ".json"))
    job = importlib.import_module(f"benchmark.jobs.{traffic['job']}")
    _say(f"{cell['name']}: config {config['name']}, traffic "
         f"{cell['traffic']} (job {traffic['job']}), seed {args.seed}, "
         f"{seconds:g} s, trace {args.trace}, {len(devices)} x {kind}")

    _say("imports done, devices found; set-up starts")
    state = job.setup(config, traffic, args.seed, cell["chips"])
    setup_s = time.perf_counter() - _T_START
    compiles_setup = compiles.count()

    tracer = None
    if args.trace:
        # one directory per cell, overwritten by the cell's next traced run
        trace_dir = os.path.join(CHECKOUT, ".bench_traces", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = Tracer(trace_dir)
    obs = job.measure(state, seconds, tracer)
    resident = _memory_stats(devices, cell["chips"])
    checked, compared = job.check(state)
    compared = {
        **compared,
        "failed": {"value": int(obs["failed"]), "limit": 0},
        "compiles_in_window": {"value": int(obs["compiles_in_window"]),
                               "limit": 0},
    }
    for c in compared.values():  # the line stays JSON whatever was read
        if not math.isfinite(c["value"]):
            c["value"] = None
    correct = bool(checked) and obs["failed"] == 0
    _say(f"compiles: {compiles_setup} in set-up, "
         f"{obs['compiles_in_window']} in the window; compile cache "
         f"{cache_dir}: {cache_before} entries before, "
         f"{_cache_entries(cache_dir)} after")
    if obs["compiles_in_window"]:
        _say("NOT PROVEN: something compiled inside the measured window")
        correct = False

    final = _memory_stats(devices, cell["chips"])
    _say(f"memory_stats of device 0 at the end: "
         f"{json.dumps(final[0], sort_keys=True)}")
    device = {
        "platform": platform,
        "kind": kind,
        "count": len(devices),
        "memory_peak_bytes": max(
            _memory_peak(after, end) for after, end in zip(resident, final)
        ),
    }
    obs["setup_s"] = setup_s
    if platform == "tpu":
        obs["device.peak_flops_per_s"] = (
            cell["chips"] * peaks[kind]["bf16_flops_per_s"]
        )
    result = {"correct": correct, "attempted": int(obs["attempted"]),
              "failed": int(obs["failed"])}
    if tracer is None:
        values = {**job.end_to_end(obs), "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"]
            if _applies(m, cell["name"])
        }
    else:
        reduced = tracer.reduce()
        if reduced is not None:
            obs.update({f"trace.{k}": reduced[k]
                        for k in ("busy_ns", "window_ns", "idle_ns")})
            device["busy_s"] = reduced["busy_ns"] / 1e9
            device["window_s"] = reduced["window_ns"] / 1e9
            result["breakdown"] = {
                "device_ops": [[n, ns / 1e9] for n, ns in reduced["ops"]],
                "idle_gaps": [[n, ns / 1e9] for n, ns in reduced["gaps"]],
            }
            _say(f"trace: {reduced['n_events']} device events on "
                 f"{reduced['devices']} device(s), busy "
                 f"{device['busy_s']:.4f} s of {device['window_s']:.4f} s")
            _say("trace: seconds by program " + json.dumps(
                [[n, ns / 1e9] for n, ns in reduced["modules"]]))
        else:
            _say("trace: no device plane in the trace (not a TPU run)")
        result["metrics"] = _per_layer(bench, args.root, cell["name"], obs)
    _say("observations " + json.dumps(obs, sort_keys=True))
    result["device"] = device
    result["compared"] = compared
    print(json.dumps(result), flush=True)
    print("\n".join(_compared_lines(correct, compared)), file=sys.stderr,
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
