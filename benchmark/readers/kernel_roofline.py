"""One kernel's share of its memory roofline in the traced window's
executions of a program: ``args["kernel"]`` (the kernel's name: the first
word of its events' labels on the ``XLA Ops`` line, ``lib/trace_reduce.py``
``op_label``) inside ``args["module"]`` (a program on the ``XLA Modules``
line, ``jit_serve_decode``), of the busiest chip.

    share = 100 x bytes a step x executions / (kernel seconds x HBM bytes/s)

The bytes a step come from ``kernel_bytes_per_step(config, kernel, step,
serve_config)`` of the family module of the cell's configuration
(``cell.config_file``), at the window's counters: the mean of each numeric
attribute of the ``serve/commit`` spans, and the ``serve_config`` of the
cell's traffic file.  None, never an error, where there is nothing to read:
no device plane (a CPU run), no such kernel or program in the trace, a
family without the count, a device kind without a published peak.
"""

import json
import os

from benchmark.lib import spans as S
from benchmark.lib.model import family
from benchmark.lib.trace_reduce import read_trace


def kernel_seconds(ops, modules, kernel: str, module: str):
    """``(seconds of the kernel's events inside the module's executions,
    executions)``: ``ops`` and ``modules`` are ``(name, start_ns, dur_ns)``
    lists of one device, operations under their labels."""
    runs = sorted((s, s + d) for n, s, d in modules if n == module)
    total, at = 0, 0
    for name, start, dur in sorted(
            (op for op in ops if op[0].split(" ", 1)[0] == kernel),
            key=lambda op: op[1]):
        while at < len(runs) and runs[at][1] < start + dur:
            at += 1
        if at < len(runs) and runs[at][0] <= start:
            total += dur
    return total / 1e9, len(runs)


def _step_counters(spans) -> dict:
    sums, counts = {}, {}
    for span in S.select(spans, ["serve/commit"]):
        for key, value in span[3].items():
            if isinstance(value, (int, float)):
                sums[key] = sums.get(key, 0.0) + value
                counts[key] = counts.get(key, 0) + 1
    return {key: sums[key] / counts[key] for key in sums}


def read(observations: dict, args: dict):
    path = observations["cell.config_file"]
    with open(path) as f:
        config = json.load(f)
    fam = family(config, path)
    if not hasattr(fam, "kernel_bytes_per_step"):
        return None
    xplane = S.newest_xplane()
    if xplane is None:
        return None
    devices = read_trace(xplane)["devices"]
    if not devices:
        return None
    busiest = max(devices.values(), key=lambda d: sum(x[2] for x in d["ops"]))
    seconds, runs = kernel_seconds(busiest["ops"], busiest.get("modules", []),
                                   args["kernel"], args["module"])
    if not seconds or not runs:
        return None
    root = os.path.dirname(os.path.dirname(os.path.dirname(path)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"]
                if w["name"] == observations["cell.name"])
    with open(os.path.join(root, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        serve_config = json.load(f)["serve_config"]
    step = _step_counters(S.load()["spans"])
    if not step:
        return None
    per_step = fam.kernel_bytes_per_step(config, args["kernel"], step,
                                         serve_config)
    if per_step is None:
        return None
    import jax

    with open(os.path.join(S.CHECKOUT, "benchmark", "lib", "peaks.json")) as f:
        peaks = json.load(f)["by_device_kind"]
    kind = jax.devices()[0].device_kind
    if kind not in peaks:
        return None
    return 100.0 * per_step * runs / (seconds
                                      * peaks[kind]["hbm_bytes_per_s"])
