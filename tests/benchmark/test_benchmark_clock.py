"""The device's clock set against the host's by causality
(`benchmark/lib/clock.py`), the reader over it (`readers/gap_part_ms.py`) and
the seven metrics of PR 37.

The arithmetic is checked on synthetic serve loops with a planted clock
offset, launch latency, read tail and turnaround: one true timeline, the
device's events written on a clock that runs `offset` behind (and, for one
case, at another rate).  The metrics are then read from one real CPU trace
of a tiny `ServingEngine`, made here under `jax.profiler` in a directory of
this test's own: the host-clock ones read above zero, the ones that need the
device's lines read nothing.
"""

import importlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import clock  # noqa: E402
from benchmark.lib import spans as S  # noqa: E402

PROGRAMS = {"jit_serve_decode": "serve/decode_step",
            "jit_serve_prefill": "serve/prefill"}
SERVE_CELLS = ["serve-gpt2m-closed12", "serve-axk1-closed192",
               "serve-solar2-closed256", "serve-longcat-closed160"]
# name: (source, reads on a CPU trace)
METRICS = {
    "read_tail_ms.serve": ("device_trace", False),
    "launch_latency_ms.serve": ("device_trace", False),
    "clock_slack_ms.serve": ("device_trace", False),
    "host_turnaround_ms.serve": ("program_counter", True),
    "read_extra_fetch_ms.serve": ("program_counter", True),
    "step_account_ms.serve": ("program_counter", True),
    "upload_bytes_per_step.serve": ("program_counter", True),
}
US = 1_000


def serve_loop(steps=40, offset=1_700 * US, seed=0, rate=1.0,
               least_launch=300 * US, least_tail=200 * US):
    """A closed serve loop on one true timeline, in ns: every step a decode
    execution, every third one a prefill before it.  Returns ``(spans,
    modules, planted)``: the host's spans on the true clock, the device's
    programs on a clock that reads ``rate * true - offset``, and the planted
    ``launch`` and ``tail`` of each execution and ``turnaround`` between
    consecutive ones, in time order, and the instants at which the runtime
    ``enqueued`` each program and saw it ``completed``.  One execution has the least launch and
    one the least tail, so the causal interval is ``[offset - least_launch,
    offset + least_tail]`` at ``rate`` 1."""
    rng = np.random.default_rng(seed)
    on_device = lambda t: int(round(rate * t)) - offset
    spans, modules = [], []
    planted = {"launch": [], "tail": [], "turnaround": [], "enqueued": [],
               "completed": []}
    t = 10_000 * US
    last_read = None
    n = 0

    def execute(site, program, device_ns):
        nonlocal t, last_read, n
        launch = least_launch + int(rng.integers(0, 900 * US))
        tail = least_tail + int(rng.integers(0, 700 * US))
        if n == 3:
            launch = least_launch
        if n == 5:
            tail = least_tail
        n += 1
        upload = t
        t += 400 * US
        spans.append((site + "/upload", upload, t - upload, {}))
        D = t
        if last_read is not None:
            planted["turnaround"].append(D - last_read)
        t += 150 * US  # the dispatch call returns before the program starts
        spans.append((site + "/dispatch", D, t - D, {}))
        started = D + launch
        modules.append((program, on_device(started),
                        on_device(started + device_ns) - on_device(started)))
        # the runtime's own marks: it enqueues the program 40-240 us before
        # the device starts it and learns of its end 30-130 us after
        planted["enqueued"].append(
            started - (40 * US if n == 8 else int(rng.integers(40, 240)) * US))
        planted["completed"].append(
            started + device_ns
            + (30 * US if n == 9 else int(rng.integers(30, 130)) * US))
        read = t
        t = last_read = started + device_ns + tail
        spans.append((site + "/read", read, t - read, {}))
        planted["launch"].append(launch)
        planted["tail"].append(tail)

    for k in range(steps):
        step = t
        spans.append(("serve/admit", t, 50 * US, {}))
        t += 50 * US
        if k % 3 == 0:
            prefill = t
            execute("serve/prefill", "jit_serve_prefill", 4_000 * US)
            spans.append(("serve/prefill", prefill, t - prefill, {}))
            t += 30 * US
        decode = t
        spans.append(("serve/decode_step/batch", t, 60 * US, {}))
        t += 60 * US
        execute("serve/decode_step", "jit_serve_decode", 6_000 * US)
        spans.append(("serve/decode_step", decode, t - decode, {}))
        t += int(rng.integers(100 * US, 300 * US))  # the step's accounting
        spans.append(("serve/commit", t, 250 * US, {}))
        t += 250 * US
        spans.append(("serve/gauges", t, 40 * US, {}))
        t += 40 * US
        spans.append(("serve/step", step, t - step, {"it": k}))
        t += int(rng.integers(150 * US, 400 * US))  # the caller
    return spans, modules, planted


def test_bounds_hold_the_planted_offset_and_split_gives_back_what_was_planted():
    offset = 1_700 * US
    spans, modules, planted = serve_loop(offset=offset)
    executed = clock.executions(modules, spans, PROGRAMS)
    assert len(executed) == len(modules) == 40 + 14
    lowest, highest = clock.offset_bounds(executed)
    # as wide as the least launch plus the least read tail, around the truth
    assert (lowest, highest) == (offset - 300 * US, offset + 200 * US)
    assert clock.split(executed, offset) == {
        k: planted[k] for k in ("launch", "tail", "turnaround")}
    # with the midpoint every execution is off by the same 50 us, under the
    # slack of 250, and neither part is negative
    parts = clock.split(executed, (lowest + highest) // 2)
    assert parts["turnaround"] == planted["turnaround"]
    assert [a - b for a, b in zip(parts["launch"], planted["launch"])] == (
        [-50 * US] * len(executed))
    assert [a - b for a, b in zip(parts["tail"], planted["tail"])] == (
        [50 * US] * len(executed))
    assert min(parts["launch"]) >= 0 and min(parts["tail"]) >= 0


def test_the_runtimes_marks_narrow_the_bounds_around_the_truth():
    """The instants at which the runtime enqueued a program and saw it
    complete lie between the dispatch span's start and the program's, and
    between its end and the read's return: the interval shrinks from the
    least launch plus the least tail (500 us) to the least of each mark's
    distances (70 us), still around the planted offset."""
    offset = -640 * US
    spans, modules, planted = serve_loop(offset=offset, seed=3)
    executed = clock.executions(modules, spans, PROGRAMS)
    marks = sorted(planted["enqueued"]), sorted(planted["completed"])
    assert clock.offset_bounds(executed, *marks) == (
        offset - 40 * US, offset + 30 * US)
    assert clock.offset_bounds(executed, marks[0]) == (
        offset - 40 * US, offset + 200 * US)
    assert clock.offset_bounds(executed, (), marks[1]) == (
        offset - 300 * US, offset + 30 * US)
    # a mark outside every execution's dispatch-to-read interval (a program
    # of another site, the trace's edge) constrains nothing
    stray = [executed[0][0] - 5 * US, executed[-1][3] + 5 * US]
    assert clock.offset_bounds(executed, stray, stray) == (
        clock.offset_bounds(executed))


@pytest.mark.parametrize("rate,fits", [(1.0, True), (1.0 + 50e-6, True),
                                       (1.0 + 2000e-6, False)])
def test_a_drifting_device_clock_makes_the_bounds_cross(monkeypatch, rate,
                                                        fits):
    """One constant has to fit the whole window.  Over the 0.5 s of this
    loop a clock 50 ppm off drifts 25 us, inside the interval's 500; one
    2000 ppm off drifts a millisecond: the bounds cross, the slack reads
    negative, the tail and the launch are left out, and the host's
    turnaround, which needs no device clock, still reads."""
    spans, modules, planted = serve_loop(rate=rate)
    executed = clock.executions(modules, spans, PROGRAMS)
    assert len(executed) == 54
    lowest, highest = clock.offset_bounds(executed)
    assert (lowest <= highest) == fits
    trace = {"spans": spans, "modules": modules}
    got = {name: read_metric(monkeypatch, name, trace) for name in METRICS}
    assert (got["clock_slack_ms.serve"] > 0) == fits
    assert (got["read_tail_ms.serve"] is not None) == fits
    assert (got["launch_latency_ms.serve"] is not None) == fits
    assert got["host_turnaround_ms.serve"] == pytest.approx(
        sum(planted["turnaround"]) / 40 / 1e6)


@pytest.mark.parametrize("where", ["lowest", "midpoint", "highest"])
def test_the_three_parts_add_up_to_the_idle_time_between_programs(where):
    spans, modules, _ = serve_loop(seed=1)
    executed = clock.executions(modules, spans, PROGRAMS)
    lowest, highest = clock.offset_bounds(executed)
    d = {"lowest": lowest, "highest": highest,
         "midpoint": (lowest + highest) // 2}[where]
    parts = clock.split(executed, d)
    between = (sum(parts["tail"][:-1]) + sum(parts["turnaround"])
               + sum(parts["launch"][1:]))
    idle = sum(b - a for a, b in S.idle_intervals(modules))
    assert between == idle > 0


def _cut(spans, modules, t0, t1, offset):
    """What a trace from ``t0`` to ``t1`` keeps, ``(modules, spans)``: the
    device's programs and the host's spans that began and ended inside it."""
    kept = [s for s in spans if s[1] >= t0 and s[1] + s[2] <= t1]
    ran = [m for m in modules
           if m[1] + offset >= t0 and m[1] + m[2] + offset <= t1]
    return ran, kept


@pytest.mark.parametrize("edge", [
    "before the dispatch", "inside the dispatch", "before the program",
    "inside the program", "inside the read's tail"])
@pytest.mark.parametrize("end", ["start", "stop"])
def test_a_trace_cut_in_the_middle_of_a_step_pairs_the_right_executions(
        edge, end):
    offset = -900 * US
    spans, modules, _ = serve_loop(steps=12, offset=offset, seed=2)
    whole = clock.executions(modules, spans, PROGRAMS)
    assert len(whole) == 12 + 4
    k = 4 if end == "start" else 11  # a decode execution of the middle
    D, started, E, _ = whole[k]
    at = {"before the dispatch": D - 20 * US,
          "inside the dispatch": D + 50 * US,
          "before the program": started + offset - 10 * US,
          "inside the program": (started + E) // 2 + offset,
          "inside the read's tail": E + offset + 30 * US}[edge]
    first, last = spans[0][1] - US, max(s[1] + s[2] for s in spans) + US
    if end == "start":
        got = clock.executions(
            *_cut(spans, modules, at, last, offset), PROGRAMS)
        # cut before its dispatch span the execution is whole; cut anywhere
        # later it has lost that span, and its program and read with it
        assert got == whole[k if edge == "before the dispatch" else k + 1:]
    else:
        got = clock.executions(
            *_cut(spans, modules, first, at, offset), PROGRAMS)
        assert got == whole[:k]  # its read never ended inside the trace
    assert clock.offset_bounds(got)[0] <= offset <= clock.offset_bounds(got)[1]


def test_no_execution_no_bounds():
    assert clock.executions([], [], PROGRAMS) == []
    assert clock.offset_bounds([]) is None
    assert clock.host_pairs([], PROGRAMS.values()) == []
    assert clock.turnarounds([]) == []


def metric_spec(name):
    with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
        spec = json.load(f)
    return importlib.import_module("benchmark.readers." + spec["reader"]), spec


def read_metric(monkeypatch, name, trace):
    reader, spec = metric_spec(name)
    trace = {"spans": [], "ops": [], "modules": [], **trace}
    monkeypatch.setattr(S, "load", lambda: trace)
    # and no trace file for the runtime's marks: another test's, left under
    # .bench_traces, is not this timeline's
    monkeypatch.setattr(clock, "newest_xplane", lambda root=None: None)
    return reader.read({}, spec["args"])


def test_the_four_gap_metrics_on_a_synthetic_trace(monkeypatch):
    """Per `serve/step` span, in ms: the planted parts, the tail and the
    launch off by the 50 us the midpoint lies from the truth, an execution."""
    spans, modules, planted = serve_loop()
    trace = {"spans": spans, "modules": modules}
    steps, executed = 40, 54
    got = {name: read_metric(monkeypatch, name, trace) for name in METRICS}
    assert got["clock_slack_ms.serve"] == pytest.approx(0.25)
    assert got["host_turnaround_ms.serve"] == pytest.approx(
        sum(planted["turnaround"]) / steps / 1e6)
    # between programs: without the last execution's tail and the first's
    # launch, so the three add up to the idle time between programs
    assert got["read_tail_ms.serve"] == pytest.approx(
        (sum(planted["tail"][:-1]) + (executed - 1) * 50 * US) / steps / 1e6)
    assert got["launch_latency_ms.serve"] == pytest.approx(
        (sum(planted["launch"][1:]) - (executed - 1) * 50 * US) / steps / 1e6)
    assert (got["read_tail_ms.serve"] + got["host_turnaround_ms.serve"]
            + got["launch_latency_ms.serve"]) == pytest.approx(
        sum(b - a for a, b in S.idle_intervals(modules)) / steps / 1e6)
    # with the runtime's marks (read from the trace file on a chip run) the
    # slack is half of 40 + 30 us and the midpoint 5 us from the truth
    monkeypatch.setattr(clock, "runtime_marks", lambda: (
        sorted(planted["enqueued"]), sorted(planted["completed"])))
    reader, spec = metric_spec("clock_slack_ms.serve")
    assert reader.read({}, spec["args"]) == pytest.approx(0.035)
    reader, spec = metric_spec("read_tail_ms.serve")
    assert reader.read({}, spec["args"]) == pytest.approx(
        (sum(planted["tail"][:-1]) + (executed - 1) * 5 * US) / steps / 1e6)
    monkeypatch.undo()
    # the attributes are PR 37's: a trace without them leaves these out
    for name in ("read_extra_fetch_ms.serve", "step_account_ms.serve",
                 "upload_bytes_per_step.serve"):
        assert got[name] is None
    commits = [(n, s, d, {"read_extra_us": 250.0, "account_us": 125.0,
                          "upload_bytes": 3216 + 8 * (i % 2)})
               for i, (n, s, d, _) in enumerate(spans) if n == "serve/commit"]
    trace = {"spans": [s for s in spans if s[0] != "serve/commit"] + commits}
    assert read_metric(monkeypatch, "read_extra_fetch_ms.serve",
                       trace) == pytest.approx(0.25)
    assert read_metric(monkeypatch, "step_account_ms.serve",
                       trace) == pytest.approx(0.125)
    assert read_metric(monkeypatch, "upload_bytes_per_step.serve",
                       trace) == pytest.approx(3220.0)


@pytest.mark.parametrize("name", list(METRICS))
def test_without_a_device_plane_only_the_host_clock_reads(monkeypatch, name):
    spans, _, planted = serve_loop()
    got = read_metric(monkeypatch, name, {"spans": spans})
    if name == "host_turnaround_ms.serve":
        assert got == pytest.approx(sum(planted["turnaround"]) / 40 / 1e6)
    else:
        assert got is None
    # and an older commit's trace, with no such span, reads nothing at all
    assert read_metric(monkeypatch, name, {}) is None


def test_gap_part_ms_refuses_a_part_it_does_not_know(monkeypatch):
    from benchmark.readers import gap_part_ms

    monkeypatch.setattr(S, "load", lambda: {"spans": [], "modules": []})
    with pytest.raises(ValueError, match="unknown part 'middle'"):
        gap_part_ms.read({}, {"programs": PROGRAMS, "part": "middle",
                              "per": ["serve/step"]})


@pytest.mark.parametrize("name", list(METRICS))
def test_the_entry_in_benchmark_json(name):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in bench["per_layer"]]
    # appended, in this order, after everything that was there
    assert names[-len(METRICS):] == list(METRICS)
    entry = bench["per_layer"][names.index(name)]
    assert entry == {
        "name": name, "unit": "bytes" if "bytes" in name else "ms",
        "better": "lower", "source": METRICS[name][0],
        "layer": "scheduler and host loop", "moves": "serve_tokens_per_s",
        "workloads": SERVE_CELLS}
    _, spec = metric_spec(name)
    if spec["reader"] == "gap_part_ms":
        assert spec["args"]["programs"] == PROGRAMS
        assert spec["args"]["per"] == ["serve/step"]
    else:
        assert spec["args"]["span"] == "serve/commit"


# --------------------------------------------------------------------------- #
# one real trace
# --------------------------------------------------------------------------- #

TINY = {"name": "clock-tiny", "family": "gpt2", "n_layer": 2,
        "n_embd": 128, "n_head": 2, "n_inner": None, "vocab_size": 300,
        "n_positions": 64}


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    from benchmark.lib.model import family
    from benchmark.lib.trace_reduce import Tracer, find_xplane
    from stoke_tpu import ServeConfig
    from stoke_tpu.serving.engine import ServingEngine

    fam = family(TINY)
    model = fam.build_model(TINY)
    engine = ServingEngine(
        model, fam.init_params(model, 0, 16)["params"],
        ServeConfig(max_seqs=2, kv_block_size=16, max_seq_len=64,
                    prefill_pad_multiple=16, attention="flash"),
    )
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 300, size=n, dtype=np.int32)
               for n in (5, 20, 9)]

    def serve():
        for p in prompts:
            engine.submit(p, 4)
        engine.run()

    serve()  # both programs compiled before the trace
    tracer = Tracer(str(tmp_path_factory.mktemp("clock_trace")))
    tracer.start()
    try:
        serve()
    finally:
        tracer.stop()
    return find_xplane(tracer.trace_dir)


@pytest.mark.parametrize("name", list(METRICS))
def test_metric_on_the_cpu_trace(trace, monkeypatch, name):
    """From a CPU trace of the program: the host-clock metrics read above
    zero (the extra fetches' too: with one fetch it is two clock readings
    apart), the three that need the device's lines read nothing, as the
    rehearsal asserts of every cell."""
    monkeypatch.setattr(S, "newest_xplane", lambda root=None: trace)
    monkeypatch.setattr(clock, "newest_xplane", lambda root=None: trace)
    assert clock.runtime_marks() == ([], [])  # no TPU runtime in a CPU trace
    reader, spec = metric_spec(name)
    got = reader.read({}, spec["args"])
    if not METRICS[name][1]:
        assert got is None
        return
    assert got > 0
    loaded = S.load(trace)["spans"]
    commits = S.select(loaded, ["serve/commit"])
    if name == "upload_bytes_per_step.serve":
        # two slots: tokens, positions, context lengths and 4 blocks a slot
        assert got == 3 * 2 * 4 + 2 * 4 * 4
        assert all(c[3]["upload_arrays"] == 4 and c[3]["read_fetches"] == 1
                   and c[3]["read_bytes"] == 2 * 4 for c in commits)
    elif name == "host_turnaround_ms.serve":
        pairs = clock.host_pairs(loaded, PROGRAMS.values())
        # three prefills and every decode step, none cut
        assert len(pairs) == 3 + len(commits)
        assert all(gap > 0 for gap in clock.turnarounds(pairs))
    elif name == "read_extra_fetch_ms.serve":
        assert got < 0.05  # one fetch: no round trip in it
