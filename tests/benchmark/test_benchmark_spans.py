"""The program's spans in a profiler trace (`benchmark/lib/spans.py`), the
readers over them, and the span trees the program emits.

The arithmetic is checked on hand-written timelines.  The trees are checked
on one real trace, made here under `jax.profiler` in a directory of this
test's own (never `.bench_traces`, which another worker's rehearsal
deletes): a tiny `ServingEngine` for a few steps and a tiny four-call
`Stoke` for two optimizer steps.  A CPU trace has no device plane: every
metric read from the device's lines must then read nothing.
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

from benchmark.lib import spans as S  # noqa: E402

NEW_METRICS = [
    "decode_device_ms.serve", "prefill_device_ms.serve", "launch_gap_ms.serve",
    "idle_bookkeeping_ms.serve", "first_token_hold_ms.serve",
    "queue_wait_ms.serve", "facade_host_ms.train", "dispatch_blocked_ms.train",
    "loader_fetch_ms.train",
]


def sp(name, start, end, **stats):
    return (name, start, end - start, stats)


# one engine step, 0..100: admit 0-10 | prefill 10-40 (upload 10-15,
# dispatch 15-20, read 20-40) | decode_step 40-90 (batch 40-45, upload 45-50,
# dispatch 50-55, read 55-90) | commit 90-95 | step's own tail 95-100
STEP = [
    sp("serve/step", 0, 100, it=0),
    sp("serve/admit", 0, 10),
    sp("serve/prefill", 10, 40, rid=3, queue_wait_us=250.0),
    sp("serve/prefill/upload", 10, 15),
    sp("serve/prefill/dispatch", 15, 20),
    sp("serve/prefill/read", 20, 40),
    sp("serve/decode_step", 40, 90, active=2),
    sp("serve/decode_step/batch", 40, 45),
    sp("serve/decode_step/upload", 45, 50),
    sp("serve/decode_step/dispatch", 50, 55),
    sp("serve/decode_step/read", 55, 90),
    sp("serve/commit", 90, 95),
    ("PjitFunction(serve_decode)", 50, 4, {}),  # not the program's: ignored
]


def test_nest_and_self_time():
    own = {s[0]: ns for s, ns in S.self_time(STEP)}
    assert own["serve/step"] == 100 - 10 - 30 - 50 - 5
    assert own["serve/prefill"] == 0 and own["serve/decode_step"] == 0
    assert own["serve/decode_step/read"] == 35
    # the runtime's own event nests under the dispatch span and charges it
    assert own["serve/decode_step/dispatch"] == 1
    parents = {s[0]: (S.nest(STEP)[p][0][0] if p is not None else None)
               for s, p in S.nest(STEP)}
    assert parents["serve/step"] is None
    assert parents["serve/prefill/read"] == "serve/prefill"
    assert parents["serve/commit"] == "serve/step"


@pytest.mark.parametrize("ops,want", [
    # a gap that runs from the read's tail through commit, the step's own
    # tail and the caller into nothing: split by overlap, rest outside
    ([("", 0, 85), ("", 112, 10)],
     {"serve/decode_step/read": 5, "serve/commit": 5, "serve/step": 5,
      "outside": 12}),
    # a gap wholly inside one span
    ([("", 0, 22), ("", 30, 70)], {"serve/prefill/read": 8}),
    # two owners, nothing unowned
    ([("", 0, 12), ("", 18, 80)],
     {"serve/prefill/upload": 3, "serve/prefill/dispatch": 3}),
    # busy all the way: nothing to own
    ([("", 0, 60), ("", 50, 50)], {}),
])
def test_idle_by_owner_splits_by_overlap_and_sums_to_idle(ops, want):
    owned = S.idle_by_owner(ops, STEP)
    assert owned == want
    assert sum(owned.values()) == sum(b - a for a, b in S.idle_intervals(ops))


def test_idle_intervals_agree_with_trace_reduce():
    from benchmark.lib.trace_reduce import reduce_events

    ops = [("a", 10, 20), ("b", 20, 20), ("loop", 50, 30), ("body", 55, 10),
           ("zero", 45, 0)]
    gaps = S.idle_intervals(ops)
    assert gaps == [(40, 50)]
    assert sum(b - a for a, b in gaps) == reduce_events(ops)["idle_ns"]
    assert S.idle_intervals([]) == []


def test_hold_per_execution_inside_and_select():
    assert S.hold(STEP, "serve/prefill", "serve/step") == [60]
    assert S.hold(STEP, "serve/prefill/read", "serve/step") == [60]
    assert S.hold(STEP, "serve/step", "serve/prefill") == []
    modules = [("jit_serve_decode", 0, 40), ("jit_serve_prefill", 50, 7),
               ("jit_serve_decode", 60, 44)]
    assert S.per_execution(modules, "jit_serve_decode") == (84, 2)
    assert S.per_execution(modules, "jit__decode_fn") is None
    under = S.inside(STEP, S.matcher(["serve/prefill", "serve/decode_step"]))
    assert len(under) == 8  # three + four children, and the runtime's event
    train = [sp("stoke/dispatch", 0, 5, program="fused_nb"),
             sp("stoke/dispatch", 5, 9, program="fused"), sp("stoke/apply", 9, 10)]
    steps = ["stoke/apply", {"name": "stoke/dispatch", "attr": "program",
                             "in": ["fused"]}]
    assert [s[1] for s in S.select(train, steps)] == [5, 9]


# two optimizer steps of two micro-steps through the four calls, 1 ms units
MS = 1_000_000
TRAIN = []
for k in range(4):
    t = 100 * k * MS
    TRAIN += [
        sp("stoke/io", t, t + 2 * MS),
        sp("stoke/model", t + 2 * MS, t + 3 * MS),
        sp("stoke/loss", t + 3 * MS, t + 60 * MS),
        sp("stoke/place", t + 3 * MS, t + 4 * MS),
        sp("stoke/accum", t + 5 * MS, t + 55 * MS),
        sp("stoke/track", t + 56 * MS, t + 59 * MS),
        sp("stoke/backward", t + 60 * MS, t + 61 * MS),
        sp("stoke/step", t + 61 * MS, t + (90 if k % 2 else 62) * MS),
    ]
    if k % 2:
        TRAIN.append(sp("stoke/apply", t + 62 * MS, t + 88 * MS))


def metric_spec(name):
    with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
        spec = json.load(f)
    return importlib.import_module("benchmark.readers." + spec["reader"]), spec


@pytest.mark.parametrize("name,trace,want", [
    ("loader_fetch_ms.train", {"spans": TRAIN}, 4.0),
    # (50 accum x 4 + 3 track x 4 + 26 apply x 2) / 2 steps
    ("dispatch_blocked_ms.train", {"spans": TRAIN}, 132.0),
    # per micro-step 1 + 57 + 1 in model/loss/backward, 1 or 29 in step,
    # less the accum, the track and the apply inside them
    ("facade_host_ms.train", {"spans": TRAIN},
     (4 * 59 + 2 * 1 + 2 * 29 - 4 * 50 - 4 * 3 - 2 * 26) / 2),
    ("first_token_hold_ms.serve", {"spans": STEP}, 60e-6),
    ("queue_wait_ms.serve", {"spans": STEP}, 0.25),
    ("launch_gap_ms.serve",
     {"spans": STEP, "ops": [("", 0, 85), ("", 112, 10)]}, 27e-6),
    ("idle_bookkeeping_ms.serve",
     {"spans": STEP, "ops": [("", 0, 85), ("", 112, 10)]}, 22e-6),
    ("decode_device_ms.serve",
     {"modules": [("jit_serve_decode", 0, 86 * MS),
                  ("jit_serve_decode", 90 * MS, 88 * MS)]}, 87.0),
    ("prefill_device_ms.serve",
     {"modules": [("jit_serve_prefill", 0, 27 * MS)]}, 27.0),
    # an older commit's trace: no such span, program or device plane
    *[(name, {}, None) for name in NEW_METRICS],
])
def test_reader_on_a_synthetic_trace(monkeypatch, name, trace, want):
    reader, spec = metric_spec(name)
    trace = {"spans": [], "ops": [], "modules": [], **trace}
    monkeypatch.setattr(S, "load", lambda: trace)
    got = reader.read({}, spec["args"])
    assert got == (pytest.approx(want) if want is not None else None)


def test_span_ms_self_time(monkeypatch):
    from benchmark.readers import span_ms

    monkeypatch.setattr(S, "load", lambda: {"spans": STEP})
    args = {"spans": ["serve/step"], "per": ["serve/step"], "self": True}
    assert span_ms.read({}, args) == pytest.approx(5e-6)
    assert span_ms.read({}, {**args, "self": False}) == pytest.approx(100e-6)


def test_bookkeeping_owners_lie_between_a_read_and_the_next_upload():
    """`idle_bookkeeping_ms.serve` owns only what cannot touch a program's
    edges (where the trace's two clocks decide): none of the upload,
    dispatch and read spans, nor the spans around them."""
    owners = set(metric_spec("idle_bookkeeping_ms.serve")[1]["args"]["owners"])
    emitted = {s[0] for s in STEP if S.is_program_span(s[0])}
    emitted |= {"serve/gauges", S.OUTSIDE}
    assert owners <= emitted
    assert owners == {n for n in emitted
                      if not n.startswith(("serve/prefill", "serve/decode_step"))}


def test_newest_xplane_skips_what_an_earlier_process_left(tmp_path, monkeypatch):
    made = []
    for age, cell in ((100, "old-cell"), (5, "this-cell")):
        d = tmp_path / ".bench_traces" / cell / "plugins" / "profile" / "x"
        d.mkdir(parents=True)
        f = d / "host.xplane.pb"
        f.write_bytes(b"")
        stamp = os.path.getmtime(f) - age
        os.utime(f, (stamp, stamp))
        made.append((str(f), stamp))
    assert S.newest_xplane(str(tmp_path)) == made[1][0]
    monkeypatch.setattr(S, "_process_started", lambda: made[0][1] + 1)
    assert S.newest_xplane(str(tmp_path)) == made[1][0]
    monkeypatch.setattr(S, "_process_started", lambda: made[1][1] + 1)
    assert S.newest_xplane(str(tmp_path)) is None


# --------------------------------------------------------------------------- #
# one real trace
# --------------------------------------------------------------------------- #

TINY = {"name": "spans-tiny", "family": "gpt2", "n_layer": 2,
        "n_embd": 128, "n_head": 2, "n_inner": None, "vocab_size": 300,
        "n_positions": 64}


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    import optax

    from benchmark.lib.model import family
    from benchmark.lib.trace_reduce import Tracer, find_xplane
    from stoke_tpu import ServeConfig, Stoke, StokeOptimizer
    from stoke_tpu.models import causal_lm_loss
    from stoke_tpu.serving.engine import ServingEngine

    fam = family(TINY)
    init_params = fam.init_params
    model = fam.build_model(TINY)
    engine = ServingEngine(
        model, init_params(model, 0, 16)["params"],
        ServeConfig(max_seqs=2, kv_block_size=16, max_seq_len=64,
                    prefill_pad_multiple=16, attention="flash"),
    )
    stoke = Stoke(
        model=model,
        optimizer=StokeOptimizer(optimizer=optax.sgd,
                                 optimizer_kwargs={"learning_rate": 0.1}),
        loss=causal_lm_loss, params=init_params(model, 0, 32),
        batch_size_per_device=2, grad_accum=2, verbose=False,
        model_train_kwargs={"train": True},
        model_eval_kwargs={"train": False},
    )
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 300, size=n, dtype=np.int32)
               for n in (5, 20, 9)]
    rows = rng.integers(0, 300, size=(2, 32), dtype=np.int32)

    def serve():
        for p in prompts:
            engine.submit(p, 3)
        engine.run()

    def train():
        for _ in range(4):  # two optimizer steps
            loss = stoke.loss(stoke.model(rows), rows)
            stoke.backward(loss)
            stoke.step()

    serve(), train()  # every program compiled before the trace
    tracer = Tracer(str(tmp_path_factory.mktemp("spans_trace")))
    tracer.start()
    try:
        serve(), train()
    finally:
        tracer.stop()
    path = find_xplane(tracer.trace_dir)
    return path, S.nest(S.load(path)["spans"])


def children(nested, index):
    return [s for s, p in nested if p == index]


def test_trace_serve_tree(trace):
    _, nested = trace
    names = [s[0] for s, _ in nested]
    steps = [i for i, n in enumerate(names) if n == "serve/step"]
    assert steps and all(nested[i][1] is None for i in steps)
    for i in steps:
        kids = [k[0] for k in children(nested, i)]
        assert kids[0] == "serve/admit" and kids[-1] == "serve/gauges"
        assert set(kids) <= {"serve/admit", "serve/prefill",
                             "serve/decode_step", "serve/commit",
                             "serve/gauges"}
        assert {"it", "queued", "active"} <= set(nested[i][0][3])
    assert [nested[i][0][3]["it"] for i in steps] == sorted(
        nested[i][0][3]["it"] for i in steps)
    decodes = [i for i, n in enumerate(names) if n == "serve/decode_step"]
    assert decodes
    for i in decodes:
        span, kids = nested[i][0], children(nested, i)
        # its four children, in order, one after the other, inside it
        assert [k[0] for k in kids] == [
            "serve/decode_step/" + c
            for c in ("batch", "upload", "dispatch", "read")]
        edges = [span[1]] + [e for k in kids for e in (k[1], k[1] + k[2])]
        assert edges == sorted(edges) and edges[-1] <= span[1] + span[2]
        assert span[3]["active"] >= 1
        assert names[nested[i][1]] == "serve/step"
    prefills = [i for i, n in enumerate(names) if n == "serve/prefill"]
    assert len(prefills) == 3
    for i in prefills:
        span = nested[i][0]
        assert [k[0] for k in children(nested, i)] == [
            "serve/prefill/" + c for c in ("upload", "dispatch", "read")]
        assert {"rid", "padded_len", "prompt_len", "queue_wait_us"} <= set(
            span[3])
        assert span[3]["padded_len"] % 16 == 0
        assert span[3]["padded_len"] >= span[3]["prompt_len"] > 0
        assert isinstance(span[3]["queue_wait_us"], float)
        assert span[3]["queue_wait_us"] > 0
    assert sorted(nested[i][0][3]["prompt_len"] for i in prefills) == [5, 9, 20]


def test_trace_train_tree(trace):
    _, nested = trace
    names = [s[0] for s, _ in nested]
    parent = lambda i: names[nested[i][1]] if nested[i][1] is not None else None
    count = lambda n: names.count(n)
    assert count("stoke/model") == count("stoke/loss") == 4
    assert count("stoke/backward") == count("stoke/step") == 4
    # the engine's micro-step runs inside loss(), the apply inside step(),
    # and only at the boundary of an optimizer step
    assert count("stoke/accum") == 4 and count("stoke/apply") == 2
    for i, n in enumerate(names):
        if n in ("stoke/accum", "stoke/track"):
            assert parent(i) == "stoke/loss"
        elif n == "stoke/apply":
            assert parent(i) == "stoke/step"
        elif n in ("stoke/model", "stoke/loss", "stoke/backward", "stoke/step"):
            assert parent(i) is None
    applied = [bool(children(nested, i)) for i, n in enumerate(names)
               if n == "stoke/step"]
    assert applied == [False, True, False, True]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_on_the_cpu_trace(trace, monkeypatch, name):
    """From a CPU trace the program's own spans read above zero and the
    device's lines read nothing, as the rehearsal asserts of every cell."""
    path, _ = trace
    monkeypatch.setattr(S, "newest_xplane", lambda root=None: path)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    reader, spec = metric_spec(name)
    got = reader.read({}, spec["args"])
    if entry["source"] == "device_trace":
        assert got is None
    elif name == "loader_fetch_ms.train":
        assert got is None  # no DataLoader in this trace: no stoke/io span
    else:
        assert got > 0
