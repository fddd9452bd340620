"""Structured-tracing tests (ISSUE 10): span-ring bounds, parent/child
nesting, Perfetto trace-event schema, per-request serve timelines,
cross-rank merge alignment, bundle trace.json, and the default-OFF
HLO-identity contract — all on the 8-device CPU mesh, no wall-clock
assertions (structural properties only)."""

import json
import os

import numpy as np
import pytest

import jax

from stoke_tpu.configs import TraceConfig
from stoke_tpu.status import StokeStatus, StokeValidationError
from stoke_tpu.telemetry.registry import MetricsRegistry
from stoke_tpu.telemetry.tracing import (
    TRACE_EVENT_KEYS,
    TraceRecorder,
    register_recorder,
    trace_point,
    trace_span,
    tracing_active,
    unregister_recorder,
)

pytestmark = pytest.mark.tracing


@pytest.fixture
def recorder(tmp_path):
    rec = TraceRecorder(ring_size=256, output_dir=str(tmp_path))
    register_recorder(rec)
    yield rec
    unregister_recorder(rec)


def _linear_stoke(tmp_path, with_trace: bool, **extra):
    import optax

    from stoke_tpu import Stoke, StokeOptimizer

    configs = list(extra.pop("configs", []))
    if with_trace:
        configs.append(TraceConfig(output_dir=str(tmp_path / "trace")))
    return Stoke(
        model=lambda p, x: x @ p["w"],
        optimizer=StokeOptimizer(
            optimizer=optax.sgd, optimizer_kwargs={"learning_rate": 0.1}
        ),
        loss=lambda o, y: ((o - y) ** 2).mean(),
        params={"w": np.ones((8, 4), np.float32)},
        batch_size_per_device=4,
        configs=configs or None,
        verbose=False,
        **extra,
    )


# --------------------------------------------------------------------------- #
# ring mechanics
# --------------------------------------------------------------------------- #


def test_ring_bounds_under_span_churn():
    """A full ring evicts oldest-first, counts every eviction, and never
    grows past its capacity — churning 10x the capacity through it."""
    registry = MetricsRegistry()
    rec = TraceRecorder(ring_size=16, registry=registry)
    for i in range(160):
        with rec.span(f"churn/{i % 4}"):
            pass
    assert len(rec) == 16
    assert rec.dropped == 160 - 16
    assert registry.get("trace/spans_total").value == 160
    assert registry.get("trace/dropped_total").value == 160 - 16
    # the ring holds the NEWEST spans (a post-mortem wants the recent
    # window), oldest first
    names = [s.name for s in rec.spans()]
    assert names[-1] == f"churn/{159 % 4}"
    assert all(n.startswith("churn/") for n in names)


def test_parent_child_nesting_and_self_time():
    rec = TraceRecorder(ring_size=64)
    with rec.span("outer"):
        with rec.span("mid"):
            with rec.span("inner"):
                pass
        with rec.span("mid2"):
            pass
    by_name = {s.name: s for s in rec.spans()}
    outer, mid, inner, mid2 = (
        by_name["outer"], by_name["mid"], by_name["inner"], by_name["mid2"]
    )
    assert outer.parent_id is None
    assert mid.parent_id == outer.span_id
    assert inner.parent_id == mid.span_id
    assert mid2.parent_id == outer.span_id
    # children close before parents: ids and the ring order agree
    assert [s.name for s in rec.spans()] == ["inner", "mid", "mid2", "outer"]
    # self-time discipline (structural, not wall-clock): a parent's self
    # time excludes its children's wall, and no span's self exceeds its
    # duration
    for s in rec.spans():
        assert 0.0 <= s.self_s <= s.dur_s + 1e-12
    assert outer.self_s <= outer.dur_s - (mid.dur_s + mid2.dur_s) + 1e-9


def test_explicit_intervals_and_points():
    rec = TraceRecorder(ring_size=64)
    rec.add("req/window", 10.0, 10.5, track="serve", request_id=7)
    rec.point("req/evict", track="serve", request_id=7)
    window, evict = rec.spans()
    assert window.dur_s == pytest.approx(0.5)
    assert window.request_id == 7 and evict.request_id == 7
    assert evict.dur_s == 0.0


def test_overlapping_slices_do_not_multiply_count_self_time():
    """Per-request timeline slices share one batch interval; with
    count_self=False they must not inflate the track's self-seconds or
    the critical-path partition (the owning span charges the wall)."""
    registry = MetricsRegistry()
    rec = TraceRecorder(ring_size=64, registry=registry)
    rec.add("serve/decode_step", 0.0, 1.0, track="serve")  # owns the wall
    for rid in range(8):  # 8 live requests riding the same interval
        rec.add("serve/decode", 0.0, 1.0, track="serve", request_id=rid,
                count_self=False)
    s = rec.summary()
    assert s["window_self_s"] == pytest.approx(1.0)
    assert registry.get("trace/serve_self_s").value == pytest.approx(1.0)
    # the slices still export with their full duration (the timeline)
    slices = [sp for sp in rec.spans() if sp.name == "serve/decode"]
    assert all(sp.dur_s == pytest.approx(1.0) for sp in slices)
    assert all(sp.self_s == 0.0 for sp in slices)


def test_summary_disambiguates_same_name_across_tracks():
    """'stoke/io' is both the loader's fetch (track ``data``) and a
    checkpoint's IO (track ``io``); the summary must keep the two apart
    instead of mislabeling one.  (The engine's apply dispatch no longer
    shares the facade phase's name: it is ``stoke/apply``.)"""
    rec = TraceRecorder(ring_size=64)
    rec.add("stoke/io", 0.0, 2.0, track="data")
    rec.add("stoke/io", 0.5, 1.5, track="io")
    rec.add("stoke/place", 2.0, 2.5, track="facade")
    s = rec.summary()
    assert "stoke/io [data]" in s["by_name"]
    assert "stoke/io [io]" in s["by_name"]
    assert s["by_name"]["stoke/io [data]"]["track"] == "data"
    assert s["by_name"]["stoke/io [io]"]["self_s"] == pytest.approx(1.0)
    # track-unique names keep their bare label
    assert "stoke/place" in s["by_name"]


def test_step_tagging():
    rec = TraceRecorder(ring_size=64)
    with rec.span("a"):
        pass
    rec.set_step(3)
    with rec.span("b"):
        pass
    steps = {s.name: s.step for s in rec.spans()}
    assert steps == {"a": 0, "b": 3}


# --------------------------------------------------------------------------- #
# the composed helper (the consolidation satellite)
# --------------------------------------------------------------------------- #


def test_trace_span_composes_timer_and_recorder(recorder):
    """One trace_span call must feed BOTH the registry timer and the span
    ring — the facade/telemetry layers no longer hand-roll the pairing."""
    registry = MetricsRegistry()
    timer = registry.timer("facade/work_s")
    with trace_span("stoke/work", track="facade", timer=timer):
        pass
    assert registry.get("facade/work_s").value > 0.0
    assert [s.name for s in recorder.spans()] == ["stoke/work"]


def test_trace_span_without_recorder_is_annotation_only():
    assert not tracing_active()
    cm = trace_span("stoke/bare")
    # no recorder, no timer: the composed helper degrades to the bare
    # xprof annotation (the pre-ISSUE-10 call-site behavior)
    with cm:
        pass
    trace_point("stoke/nothing")  # no-op, must not raise


@pytest.fixture
def annotations(monkeypatch):
    """Every ``(name, stats)`` handed to the profiler annotation, in place
    of the annotation itself (whose content only a profiler session
    shows; tests/benchmark/test_benchmark_spans.py reads one)."""
    import contextlib

    from stoke_tpu.telemetry import tracing

    seen = []

    def fake_xprof_span(name, **stats):
        seen.append((name, stats))
        return contextlib.nullcontext()

    monkeypatch.setattr(tracing, "xprof_span", fake_xprof_span)
    return seen


@pytest.mark.parametrize("with_ring", [False, True])
@pytest.mark.parametrize("attrs,request_id,stats", [
    (None, None, {}),
    ({"padded_len": 128, "queue_wait_us": 2.5}, None,
     {"padded_len": 128, "queue_wait_us": 2.5}),
    ({"program": "fused"}, 7, {"program": "fused", "rid": 7}),
    (None, 0, {"rid": 0}),
])
def test_trace_span_forwards_attrs_to_annotation_and_ring(
        annotations, tmp_path, with_ring, attrs, request_id, stats):
    rec = None
    if with_ring:
        rec = TraceRecorder(ring_size=8, output_dir=str(tmp_path))
        register_recorder(rec)
    try:
        with trace_span("serve/prefill", track="serve",
                        request_id=request_id, attrs=attrs):
            pass
    finally:
        if rec is not None:
            unregister_recorder(rec)
    assert annotations == [("serve/prefill", stats)]
    if rec is not None:
        (span,) = rec.spans()
        assert (span.attrs or {}) == (attrs or {})
        assert span.request_id == request_id


@pytest.mark.parametrize("api,expected", [
    ("4call", ["stoke/model", "stoke/loss", "stoke/accum", "stoke/track",
               "stoke/backward", "stoke/step", "stoke/apply"]),
    ("train_step", ["stoke/train_step", "stoke/dispatch", "stoke/track"]),
])
def test_facade_calls_annotate_with_wall_clock_breakdown_off(
        annotations, tmp_path, api, expected):
    """No TelemetryConfig, ProfilerConfig or TraceConfig: the four calls
    (and the fused one) are still in a profiler trace, and the engine's
    apply dispatch is ``stoke/apply``, not a second ``stoke/step``."""
    s = _linear_stoke(tmp_path, with_trace=False)
    assert not s._wall_clock_enabled
    x = np.ones((4, 8), np.float32)
    y = np.zeros((4, 4), np.float32)
    if api == "4call":
        loss = s.loss(s.model(x), y)
        s.backward(loss)
        s.step()
    else:
        s.train_step(x, y)
    names = [n for n, _ in annotations if n != "stoke/place"]
    assert names == expected
    programs = [st["program"] for n, st in annotations
                if n == "stoke/dispatch"]
    assert programs == (["fused"] if api == "train_step" else [])
    assert s.wall_clock_breakdown == {}


def test_telemetry_phase_records_span(recorder):
    from stoke_tpu.telemetry import Telemetry

    t = Telemetry(None)
    with t.phase("step"):
        pass
    assert [s.name for s in recorder.spans()] == ["stoke/step"]
    assert t.registry.get("facade/step_s").value > 0.0
    t.close()


# --------------------------------------------------------------------------- #
# export schema
# --------------------------------------------------------------------------- #


def test_trace_event_json_schema(tmp_path):
    rec = TraceRecorder(ring_size=64, rank=3, output_dir=str(tmp_path))
    rec.set_step(5)
    with rec.span("outer", track="step"):
        with rec.span("inner", track="step"):
            pass
    rec.add("req/decode", 1.0, 2.0, track="serve", request_id=11)
    path = rec.export()
    assert os.path.basename(path) == "trace.rank3.json"
    doc = json.load(open(path))
    events = doc["traceEvents"]
    durations = [e for e in events if e["ph"] == "X"]
    meta = [e for e in events if e["ph"] == "M"]
    assert len(durations) == 3
    for e in durations:
        # the Perfetto-required key set, on every duration event
        for key in TRACE_EVENT_KEYS:
            assert key in e, f"missing {key!r} in {e}"
        assert e["pid"] == 3
        assert e["dur"] >= 0
    # per-request spans get their own thread row; metadata names it
    req_events = [
        e for e in durations if e["args"].get("request_id") == 11
    ]
    assert len(req_events) == 1
    thread_names = {
        e["tid"]: e["args"]["name"] for e in meta
        if e["name"] == "thread_name"
    }
    assert thread_names[req_events[0]["tid"]] == "serve/req11"
    # nesting and steps survive the export
    inner = next(e for e in durations if e["name"] == "inner")
    outer = next(e for e in durations if e["name"] == "outer")
    assert inner["args"]["parent_id"] == outer["args"]["span_id"]
    assert outer["args"]["step"] == 5


def test_summary_critical_path():
    rec = TraceRecorder(ring_size=64)
    for _ in range(3):
        with rec.span("stoke/dispatch", track="step"):
            pass
    with rec.span("stoke/place", track="facade"):
        pass
    s = rec.summary(top=2)
    assert s["spans"] == 4
    assert s["by_name"]["stoke/dispatch"]["count"] == 3
    assert set(s["tracks"]) == {"step", "facade"}
    assert len(s["critical_path"]) == 2
    fracs = [c["frac"] for c in s["critical_path"]]
    assert all(0.0 <= f <= 1.0 for f in fracs)


# --------------------------------------------------------------------------- #
# serve request timelines
# --------------------------------------------------------------------------- #


def test_serve_request_id_correlation(recorder):
    """Every finished request's timeline must show admission, prefill,
    >= 1 decode slice, and the eviction marker, all sharing its
    request_id — TTFT/TPOT as visible span structure."""
    import optax

    from stoke_tpu.models.gpt import GPT
    from stoke_tpu.serving import ServingEngine
    from stoke_tpu.configs import ServeConfig
    from stoke_tpu.utils import init_module

    model = GPT(
        vocab_size=211, size_name="tiny", max_len=128, dropout_rate=0.0
    )
    variables = init_module(
        model, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32), train=False
    )
    eng = ServingEngine(
        model,
        variables["params"],
        ServeConfig(
            max_seqs=2, kv_block_size=8, max_seq_len=64, max_new_tokens=3,
            prefill_pad_multiple=16,
        ),
    )
    r = np.random.default_rng(0)
    rids = [
        eng.submit(r.integers(1, 211, size=5).astype(np.int32))
        for _ in range(3)  # 3 requests through 2 slots: one must queue
    ]
    eng.run()
    spans = recorder.spans()
    by_rid = {}
    for s in spans:
        if s.request_id is not None:
            by_rid.setdefault(s.request_id, []).append(s)
    assert set(by_rid) == set(rids)
    for rid in rids:
        names = [s.name for s in by_rid[rid]]
        assert names.count("serve/admission") == 1
        assert names.count("serve/prefill") == 1
        # max_new_tokens=3: prefill token + 2 decode slices
        assert names.count("serve/decode") == 2
        assert names.count("serve/evict") == 1
        # the timeline is ordered: admission before prefill before the
        # decode slices (t_start monotone along the request's row)
        ordered = sorted(by_rid[rid], key=lambda s: s.t_start)
        seq = [s.name for s in ordered]
        assert seq[0] == "serve/admission" and seq[1] == "serve/prefill"
    # batch-level decode spans carry no request id but exist
    assert any(
        s.name == "serve/decode_step" and s.request_id is None
        for s in spans
    )


# --------------------------------------------------------------------------- #
# config / status / facade integration
# --------------------------------------------------------------------------- #


def test_trace_config_status_validation(tmp_path):
    with pytest.raises(StokeValidationError, match="ring_size"):
        StokeStatus(
            batch_size_per_device=1, configs=[TraceConfig(ring_size=0)]
        )
    # legal config validates clean
    StokeStatus(
        batch_size_per_device=1,
        configs=[TraceConfig(output_dir=str(tmp_path))],
    )


def test_trace_config_yaml_buildable(tmp_path):
    from stoke_tpu.utils.yaml_config import stoke_kwargs_from_config

    kwargs = stoke_kwargs_from_config({
        "batch_size_per_device": 4,
        "configs": {
            "TraceConfig": {
                "output_dir": str(tmp_path), "ring_size": 8,
                "export_on_close": False,
            }
        },
    })
    (cfg,) = kwargs["configs"]
    assert isinstance(cfg, TraceConfig)
    assert cfg.ring_size == 8 and cfg.export_on_close is False


def test_trace_config_off_hlo_bit_identical(tmp_path):
    """Acceptance: with a TraceConfig present (tracing ON — it is purely
    host-side) the training step-program HLO and dispatch counts are
    bit-identical to a config-less run, and params march in lockstep."""
    s_off = _linear_stoke(tmp_path, with_trace=False)
    s_on = _linear_stoke(tmp_path, with_trace=True)
    x = np.ones((4, 8), np.float32)
    y = np.zeros((4, 4), np.float32)
    try:
        for s in (s_off, s_on):
            for _ in range(3):
                s.train_step(x, (y,))
        assert s_on.dispatch_count == s_off.dispatch_count
        np.testing.assert_array_equal(
            np.asarray(s_on.params["w"]), np.asarray(s_off.params["w"])
        )

        def fused_hlo(s):
            from stoke_tpu.engine import DeferredOutput, is_deferred

            margs = s._place_batch((x,))
            sentinel = DeferredOutput(None, -1)
            flat, treedef = jax.tree_util.tree_flatten(
                ((sentinel, y), {}), is_leaf=is_deferred
            )
            arrays = s._place_batch(
                [leaf for leaf in flat if not is_deferred(leaf)]
            )
            deferred = tuple(
                (i, leaf._path)
                for i, leaf in enumerate(flat)
                if is_deferred(leaf)
            )
            fn = s._engine._build_fused(treedef, deferred, True)
            return fn.lower(
                s._variables, s._opt_state, s._grad_buf, s._scaler_state,
                s._comm_state, s._rng, margs, {}, arrays,
            ).as_text()

        strip = lambda t: "\n".join(
            ln for ln in t.splitlines() if not ln.startswith("HloModule")
        )
        assert strip(fused_hlo(s_on)) == strip(fused_hlo(s_off))
    finally:
        s_on.close_telemetry()


def test_facade_trace_summary_and_export(tmp_path):
    s = _linear_stoke(tmp_path, with_trace=True)
    x = np.ones((4, 8), np.float32)
    y = np.zeros((4, 4), np.float32)
    s.train_step(x, (y,))
    summary = s.trace_summary
    assert summary["spans"] > 0
    # ISSUE 16 satellite: the eviction count rides the summary under the
    # same key the registry counter and merge tool use
    assert summary["trace/dropped_total"] == 0
    # the engine dispatch and the facade phase both landed as spans
    assert "stoke/dispatch" in summary["by_name"]
    assert "stoke/train_step" in summary["by_name"]
    # dispatch nests inside the train_step phase span
    dispatch = next(
        sp for sp in s.tracer.spans() if sp.name == "stoke/dispatch"
    )
    phase = next(
        sp for sp in s.tracer.spans() if sp.name == "stoke/train_step"
    )
    assert dispatch.parent_id == phase.span_id
    s.close_telemetry()
    path = tmp_path / "trace" / "trace.rank0.json"
    assert path.exists()
    doc = json.load(open(path))
    assert any(e["ph"] == "X" for e in doc["traceEvents"])
    # closed facade: the recorder is unregistered, later runs untraced
    assert not tracing_active()


def test_facade_without_config_has_no_tracer(tmp_path):
    s = _linear_stoke(tmp_path, with_trace=False)
    assert s.tracer is None
    assert s.trace_summary is None
    assert s.export_trace() is None


def test_bundle_contains_trace_json(tmp_path):
    from stoke_tpu import HealthConfig, TelemetryConfig

    s = _linear_stoke(
        tmp_path,
        with_trace=True,
        configs=[
            TelemetryConfig(
                output_dir=str(tmp_path / "t"), log_every_n_steps=1,
                prometheus=False, tensorboard=False,
                sample_device_time=False, track_hbm=False,
            ),
            HealthConfig(dump_signals=False),
        ],
    )
    x = np.ones((4, 8), np.float32)
    y = np.zeros((4, 4), np.float32)
    s.train_step(x, (y,))
    bundle = s.health.dump("tracing-test")
    try:
        doc = json.load(open(os.path.join(bundle, "trace.json")))
        events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert events, "bundle trace.json carries no spans"
        assert any(e["name"] == "stoke/dispatch" for e in events)
    finally:
        s.close_telemetry()


# --------------------------------------------------------------------------- #
# cross-rank merge
# --------------------------------------------------------------------------- #


def _fake_trace(path, rank, clock_offset_us, steps=(1, 2)):
    """A rank's trace whose perf-clock origin is shifted by
    ``clock_offset_us`` — step k's first span starts at
    ``offset + k * 1000``."""
    events = [{
        "name": "process_name", "ph": "M", "pid": rank, "tid": 0,
        "args": {"name": f"stoke rank{rank}"},
    }]
    for k in steps:
        events.append({
            "name": "stoke/dispatch", "ph": "X",
            "ts": clock_offset_us + k * 1000.0, "dur": 400.0,
            "pid": rank, "tid": 1, "args": {"step": k, "span_id": k},
        })
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)


def test_merge_rank_traces_alignment(tmp_path):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import merge_rank_traces as mrt

    _fake_trace(tmp_path / "trace.rank0.json", 0, clock_offset_us=0.0)
    _fake_trace(tmp_path / "trace.rank1.json", 1, clock_offset_us=5e6)
    out = tmp_path / "merged.json"
    rc = mrt.main([str(tmp_path), "--out", str(out)])
    assert rc == 0
    doc = json.load(open(out))
    by_rank_step = {}
    for e in doc["traceEvents"]:
        if e.get("ph") != "X":
            continue
        by_rank_step[(e["pid"], e["args"]["step"])] = e["ts"]
    # anchor step 1 aligned exactly; step 2 keeps each rank's own spacing
    assert by_rank_step[(0, 1)] == pytest.approx(by_rank_step[(1, 1)])
    assert by_rank_step[(0, 2)] == pytest.approx(by_rank_step[(1, 2)])


def test_merge_rank_traces_refuses_duplicate_ranks(tmp_path):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import merge_rank_traces as mrt

    a = tmp_path / "a"
    b = tmp_path / "b"
    a.mkdir()
    b.mkdir()
    _fake_trace(a / "trace.rank0.json", 0, 0.0)
    _fake_trace(b / "trace.rank0.json", 0, 1e6)
    with pytest.raises(ValueError, match="rank 0 already provided"):
        mrt.discover_traces([str(a), str(b)])
    # and the CLI reports it as the documented nonzero exit
    assert mrt.main([str(a), str(b), "--out",
                     str(tmp_path / "m.json")]) == 2


def test_merge_rank_traces_unnamed_file_takes_free_index(tmp_path):
    """An unnamed bundle trace listed BEFORE a dir containing
    trace.rank0.json must not squat on rank 0 and refuse the named
    file's legitimate claim — fallback indices assign after all named
    claims are collected."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import merge_rank_traces as mrt

    bundle = tmp_path / "trace.json"  # no rank claim in the name
    _fake_trace(bundle, 0, clock_offset_us=3e6)
    _fake_trace(tmp_path / "trace.rank0.json", 0, clock_offset_us=0.0)
    found = dict(mrt.discover_traces([str(bundle), str(tmp_path)]))
    assert found[0].endswith("trace.rank0.json")
    assert found[1] == str(bundle)
    out = tmp_path / "merged.json"
    assert mrt.main([str(bundle), str(tmp_path), "--out", str(out)]) == 0


def test_merge_rank_traces_no_common_step(tmp_path):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import merge_rank_traces as mrt

    _fake_trace(tmp_path / "trace.rank0.json", 0, 0.0, steps=(1,))
    _fake_trace(tmp_path / "trace.rank1.json", 1, 0.0, steps=(2,))
    assert mrt.main([str(tmp_path), "--out",
                     str(tmp_path / "m.json")]) == 2


# --------------------------------------------------------------------------- #
# ISSUE 16 satellite: dropped-span surfacing (summary, helpers, merge)
# --------------------------------------------------------------------------- #


def test_recorder_summary_and_module_helpers_surface_dropped():
    """``TraceRecorder.summary`` carries ``trace/dropped_total`` (the key
    the facade's trace_summary and the merge tool share), and the
    module-level ``dropped_total``/``request_spans`` helpers aggregate
    over every registered recorder — the surfaces the SLO attribution
    walks."""
    from stoke_tpu.telemetry.tracing import dropped_total, request_spans

    rec = TraceRecorder(ring_size=4)
    register_recorder(rec)
    try:
        for i in range(10):
            with rec.span("churn", request_id=i % 2):
                pass
        assert rec.summary()["trace/dropped_total"] == rec.dropped == 6
        assert dropped_total() == 6
        # request_spans filters the surviving window by request id
        rids = {s.request_id for s in request_spans(1)}
        assert rids == {1}
        assert request_spans(99) == []
    finally:
        unregister_recorder(rec)
    # no registered recorder: unknown coverage reads as zero spans, and
    # the dropped aggregate is 0 (nothing is recording)
    assert request_spans(1) == []
    assert dropped_total() == 0


def test_merge_rank_traces_surfaces_dropped_counts(tmp_path, capsys):
    """The merged report carries per-rank eviction counts and the pod
    total; a file without exporter metadata (bare chrome-trace) reports
    ``None`` — unknown is never shown as zero."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import merge_rank_traces as mrt

    _fake_trace(tmp_path / "trace.rank0.json", 0, 0.0)
    # rank 1 carries the exporter's metadata block with a nonzero count
    p1 = tmp_path / "trace.rank1.json"
    _fake_trace(p1, 1, 5e6)
    doc = json.load(open(p1))
    doc["stoke"] = {"rank": 1, "dropped": 7}
    json.dump(doc, open(p1, "w"))
    assert mrt.load_dropped(str(p1)) == 7
    assert mrt.load_dropped(str(tmp_path / "trace.rank0.json")) is None
    rc = mrt.main([str(tmp_path), "--out", str(tmp_path / "m.json"),
                   "--json"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["dropped_by_rank"] == {"0": None, "1": 7}
    assert report["trace/dropped_total"] == 7
    # human-read mode warns that the merged timeline is partial
    rc = mrt.main([str(tmp_path), "--out", str(tmp_path / "m2.json")])
    assert rc == 0
    out = capsys.readouterr().out
    assert "dropped unknown" in out and "dropped 7" in out
    assert "PARTIAL" in out
