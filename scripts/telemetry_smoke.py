"""Telemetry smoke: ONE CPU train step with the full pipeline enabled.

Proves the observability stack end-to-end in seconds (``make
telemetry-smoke``): a JSONL step record (schema-validated on read-back)
carrying the health-sentinel fields, a Prometheus exposition file, a TB
event stream readable by the native frame parser, and — since ISSUE 3 — a
forced post-mortem bundle with the flight-recorder ring, all-thread
stacks, and run config.  Since ISSUE 6, one compile-cache warm start;
since ISSUE 7, one preemption → emergency-save → resume cycle (manifest
written, counters restored); since ISSUE 8, one sharded-transport step
(int8 reduce-scatter under sddp: param-gather bytes + compression in the
JSONL); since ISSUE 9, one serve cycle (two concurrent requests through
the continuous-batching paged-KV engine with int8 weights: TTFT/TPOT
fields in the JSONL, >= 3.5x compression asserted, blocks drained back
to the pool); since ISSUE 10, one traced train window + one traced serve
request (the exported trace.rank0.json files must parse as chrome-trace
JSON and carry engine step spans AND a full per-request
admission->prefill->decode timeline); since ISSUE 12, a per-layer
numerics window (per-group JSONL block, a NaN injected into a known
layer attributed to that group's index in record + anomaly, and an
offline numerics_diff.py alignment of two smoke JSONLs); since ISSUE 13,
the serve cycle additionally runs one chunked-prefill + top-p request
(chunk/sampled counters in the JSONL, ``serve/prefill_chunk`` spans
asserted in the traced timeline; ``--serve-only`` runs just that leg —
the ``make serve-smoke`` entry); since ISSUE 16, one SLO-tagged request
(serve/slo_* JSONL fields, attainment in the summary block, and the
span-walked violation attribution whose buckets sum to the measured
end-to-end latency); since ISSUE 17, the serve cycle runs speculative
(``speculative_k=3``) with one repetitive-prompt request the
prompt-lookup drafter accelerates — accept-rate > 0 asserted on the
serve/spec_* counters, and the greedy streams asserted BIT-IDENTICAL to
a non-speculative reference engine; since ISSUE 19, the train window and
the serve cycle both run memory-armed (``MemoryConfig``) — the ``mem/*``
JSONL ledger block asserted to recombine exactly (components sum to the
resident total), the serve record carrying the KV headroom forecast and
the engine-side ledger (quantized weight store + KV block pool), the
ledger gauges in the Prometheus exposition, and every NON-armed run's
records asserted memory-free (the default-OFF contract); since ISSUE 20,
the train window and the serve cycle both run with a live ops plane
(``OpsPlaneConfig(port=0)``) — all six endpoints polled over real HTTP
(``/metrics``, ``/healthz``, ``/statusz`` asserted to be EXACTLY the
pinned ``STATUSZ_FIELDS`` tuple, ``/requests`` showing the serve
cycle's queued table, ``/trace``, and a bounded ``/profile`` capture
riding the attribution budget), plus a halting run proving the
``/healthz`` 200→503 drain flip on an injected-NaN halt.  Prints the
step record and a one-line verdict; exit 0 only when everything
round-trips.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _trace_events(path):
    with open(path) as f:
        doc = json.load(f)
    return [e for e in doc["traceEvents"] if e.get("ph") == "X"]


def _ops_get(base, path):
    """One real-HTTP GET against the live ops plane (ISSUE 20): returns
    ``(status, body_text)`` — error statuses are data here, not
    exceptions (a scraper reads 503 as the drain verdict)."""
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(base + path, timeout=10) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def run_serve_cycle(sv_dir: str) -> dict:
    """One traced serve cycle end-to-end (ISSUE 9, grown by 13 and 16):
    two concurrent greedy requests PLUS one long chunked-prefill + top-p
    request PLUS one SLO-tagged request through the continuous-batching
    engine (int8 weights), with the serve/* JSONL fields populated
    (compression >= 3.5x, prefill-chunk and sampled-token counters, the
    nullable serve/slo_* attainment fields), every KV block back in the
    pool after the drain, the per-request span timelines — including the
    ``serve/prefill_chunk`` chunk spans — asserted in the exported
    trace, and the SLO request's span-walked attribution summing to its
    end-to-end latency.  Since ISSUE 17 the engine is speculative
    (``speculative_k=3``): a repetitive-prompt request exercises the
    prompt-lookup drafter + k-token verify program (accept-rate > 0 on
    the serve/spec_* counters), and every greedy stream is asserted
    bit-identical to a non-speculative reference engine — the
    speculative default-correctness contract.  Callable standalone
    (``--serve-only``, the ``make serve-smoke`` leg) or as part of the
    full smoke."""
    import numpy as np
    import optax

    import jax as _jx

    from stoke_tpu import (
        AttributionConfig,
        MemoryConfig,
        OpsPlaneConfig,
        ServeConfig,
        Stoke,
        StokeOptimizer,
        TelemetryConfig,
        TraceConfig,
    )
    from stoke_tpu.models.gpt import GPT
    from stoke_tpu.serving import RequestSLO, SamplingParams, ServingEngine
    from stoke_tpu.telemetry import read_step_events
    from stoke_tpu.utils import init_module

    sv_model = GPT(
        vocab_size=211, size_name="tiny", max_len=128, dropout_rate=0.0
    )
    sv_vars = init_module(
        sv_model, _jx.random.PRNGKey(0), np.zeros((1, 8), np.int32),
        train=False,
    )
    sv = Stoke(
        model=sv_model,
        optimizer=StokeOptimizer(
            optimizer=optax.sgd, optimizer_kwargs={"learning_rate": 0.1}
        ),
        loss=lambda o, y: 0.0,
        params=sv_vars,
        batch_size_per_device=1,
        model_train_kwargs={"train": True},
        model_eval_kwargs={"train": False},
        configs=[
            TelemetryConfig(
                output_dir=sv_dir, log_every_n_steps=1, prometheus=True,
                tensorboard=False, sample_device_time=False, track_hbm=False,
            ),
            ServeConfig(
                max_seqs=2, kv_block_size=8, max_seq_len=64,
                max_new_tokens=4, prefill_pad_multiple=16,
                quant="int8", quant_min_size=256,
                # ISSUE 13: chunked prefill + sampling-aware programs
                # (the two short requests stay greedy — temperature 0)
                prefill_chunk_tokens=16, sampling=True,
                # ISSUE 17: self-drafting speculative decode — every
                # decode iteration is a k-token verify dispatch; greedy
                # streams stay bit-identical (asserted below)
                speculative_k=3,
                # ISSUE 18: the serve roofline observatory — cost cards
                # at the dispatch funnel, the serve/cost_* JSONL block,
                # and the verify-over-decode intensity uplift (asserted
                # below; the AttributionConfig supplies the v5e peaks)
                cost_cards=True,
            ),
            AttributionConfig(peak_tflops=197.0, peak_hbm_gbps=819.0),
            # ISSUE 19: the serve-side HBM ledger — the engine registers
            # its quantized weight store + KV block pool, the serve
            # records carry the mem/* block and the KV headroom
            # forecast, and the recombination identity is asserted below
            MemoryConfig(),
            # traced serve requests (ISSUE 10/13): the per-request
            # admission -> [chunks] -> prefill -> decode timelines are
            # parsed below
            TraceConfig(output_dir=os.path.join(sv_dir, "trace")),
            # live ops plane (ISSUE 20): the serve cycle is scrapeable
            # over real HTTP while it runs — ephemeral port, loopback
            OpsPlaneConfig(port=0),
        ],
        verbose=False,
    )
    sv_eng = sv.serve()
    sv_r = np.random.default_rng(0)
    sv_rids = [
        sv_eng.submit(sv_r.integers(1, 211, size=7).astype(np.int32), 4)
        for _ in range(2)
    ]
    # ISSUE 13: one long prompt (40 > 16 tokens -> 3 chunks) served with
    # top-p sampling from a pinned seed
    long_rid = sv_eng.submit(
        sv_r.integers(1, 211, size=40).astype(np.int32), 4,
        sampling=SamplingParams(temperature=0.7, top_p=0.9, seed=1),
    )
    # ISSUE 16: one SLO-tagged request — deadlines generous enough that a
    # CPU smoke attains them deterministically; the serve/slo_* JSONL
    # fields, the summary block, and the span-walked violation
    # attribution are asserted below
    slo_rid = sv_eng.submit(
        sv_r.integers(1, 211, size=9).astype(np.int32), 4,
        slo=RequestSLO(priority="interactive",
                       ttft_target_s=60.0, tpot_target_s=60.0),
    )
    # ISSUE 17: one repetitive-prompt greedy request — the workload
    # prompt-lookup drafting exists for (the tiled trigram repeats, so
    # the drafter proposes the continuation and the verify program
    # accepts it; accept-rate > 0 asserted below)
    spec_prompt = np.asarray([5, 9, 3] * 4, np.int32)
    spec_rid = sv_eng.submit(spec_prompt, 8)
    # live ops plane (ISSUE 20): five requests submitted, engine not yet
    # run — the /requests table shows every one QUEUED, the SLO-tagged
    # request carrying its remaining TTFT headroom (the drain/admission
    # signal an operator reads before deciding where to send load)
    op_base = f"http://127.0.0.1:{sv.opsplane.port}"
    _, op_body = _ops_get(op_base, "/requests")
    op_queued = json.loads(op_body)["requests"]
    sv_eng.run()
    # greedy-identity reference (ISSUE 17): the same greedy prompts
    # through a NON-speculative engine (same model / int8 weights — the
    # quantizer is seed-deterministic) must yield bit-identical streams;
    # exact-match verification makes speculation a pure dispatch-count
    # optimization
    ref_eng = ServingEngine(
        sv_model, sv_vars["params"],
        ServeConfig(
            max_seqs=2, kv_block_size=8, max_seq_len=64,
            max_new_tokens=4, prefill_pad_multiple=16,
            quant="int8", quant_min_size=256,
            prefill_chunk_tokens=16, sampling=True,
        ),
    )
    ref_r = np.random.default_rng(0)
    ref_prompts = [
        ref_r.integers(1, 211, size=7).astype(np.int32) for _ in range(2)
    ]
    ref_rids = [ref_eng.submit(p, 4) for p in ref_prompts]
    ref_spec_rid = ref_eng.submit(spec_prompt, 8)
    ref_eng.run()
    greedy_identity = all(
        list(sv_eng.scheduler.finished[a].tokens)
        == list(ref_eng.scheduler.finished[b].tokens)
        for a, b in list(zip(sv_rids, ref_rids))
        + [(spec_rid, ref_spec_rid)]
    )
    # live ops plane (ISSUE 20), post-drain: /statusz carries the full
    # engine summary block (completed counts, occupancy back to zero)
    _, op_body = _ops_get(op_base, "/statusz")
    op_statusz = json.loads(op_body)
    opsplane_ok = (
        len(op_queued) == 5
        and all(r["state"] == "queued" for r in op_queued)
        and any(
            r["rid"] == slo_rid
            and r["priority"] == "interactive"
            and (r["slo_headroom_s"] or 0) > 0
            for r in op_queued
        )
        and (op_statusz.get("serving") or {}).get("completed") == 5
        and (op_statusz.get("serving") or {}).get("kv_blocks_used") == 0
    )
    sv.close_telemetry()
    sv_rec = read_step_events(os.path.join(sv_dir, "steps.jsonl"))[-1]
    sv_prom = open(os.path.join(sv_dir, "metrics.prom")).read()
    serve_trace = _trace_events(
        os.path.join(sv_dir, "trace", "trace.rank0.json")
    )
    spans_by_rid = {}
    for e in serve_trace:
        rid = (e.get("args") or {}).get("request_id")
        if rid is not None:
            spans_by_rid.setdefault(rid, set()).add(e["name"])
    chunk_spans = [
        e for e in serve_trace if e["name"] == "serve/prefill_chunk"
    ]
    # ISSUE 16: the SLO-tagged request's attainment and span-walked
    # attribution — buckets must sum to the measured end-to-end latency,
    # with full span coverage (the cycle runs traced)
    slo_attr = sv_eng.slo.attributions.get(slo_rid, {})
    slo_summary = sv_eng.summary().get("slo", {})
    slo_bucket_sum = (
        slo_attr.get("queue_wait_s", 0.0)
        + slo_attr.get("prefill_blocked_s", 0.0)
        + slo_attr.get("decode_contention_s", 0.0)
    )
    spec_drafted = sv_rec.get("serve/spec_draft_tokens") or 0.0
    spec_accepted = sv_rec.get("serve/spec_accepted_tokens") or 0.0
    # ISSUE 18: the cost-card block and the roofline summary — analytic
    # FLOPs/bytes accumulated at the dispatch funnel, decode-family
    # classified memory-bound at the v5e peaks, and the verify program's
    # intensity uplift over plain decode > 1 (the reference engine runs
    # without cost_cards, so its summary block must stay inactive)
    cost_summary = sv_eng.summary().get("cost", {})
    cost_ok = (
        (sv_rec.get("serve/cost_flops") or 0.0) > 0
        and (sv_rec.get("serve/cost_bytes") or 0.0) > 0
        and sv_rec.get("serve/cost_decode_bound") == "memory"
        and (sv_rec.get("serve/cost_attainable_tpot_s") or 0.0) > 0
        and sv_rec.get("serve/cost_flops_per_token") is not None
        and cost_summary.get("active") is True
        and (cost_summary.get("verify_intensity_uplift") or 0.0) > 1.0
        and ref_eng.summary().get("cost", {}).get("active") is False
    )
    # ISSUE 19: the serve-side HBM ledger — the engine's two components
    # (quantized weight store + KV block pool) recombine EXACTLY into
    # the resident total on the JSONL record, the train-only components
    # stay None (absent, not zero), per-program memory_analysis peaks
    # were captured at the dispatch funnel, the KV headroom forecast
    # rides the serve block (every block back in the pool => the full
    # free-pool bytes), the ledger gauges reach the exposition, and the
    # memory-free reference engine's summary block stays inactive (the
    # default-OFF contract, engine-side)
    mem_summary = sv_eng.summary().get("memory", {})
    mem_ok = (
        (sv_rec.get("mem/params_bytes") or 0) > 0
        and (sv_rec.get("mem/kv_cache_bytes") or 0) > 0
        and sv_rec.get("mem/params_bytes")
        + sv_rec.get("mem/kv_cache_bytes")
        == sv_rec.get("mem/resident_bytes")
        and sv_rec.get("mem/opt_state_bytes") is None
        and sv_rec.get("mem/transport_bytes") is None
        and (sv_rec.get("mem/temp_peak_bytes") or 0) > 0
        and (sv_rec.get("serve/mem_headroom_bytes") or 0) > 0
        and "stoke_mem_resident_bytes" in sv_prom
        and "stoke_serve_mem_headroom_bytes" in sv_prom
        and mem_summary.get("active") is True
        and bool(mem_summary.get("programs"))
        and "serve" in mem_summary.get("preflights", {})
        and ref_eng.summary().get("memory", {}).get("active") is False
    )
    ok = (
        all(
            len(sv_eng.scheduler.finished[rid].tokens) == 4
            for rid in sv_rids + [long_rid, slo_rid]
        )
        and len(sv_eng.scheduler.finished[spec_rid].tokens) == 8
        and sv_rec.get("serve/completed") == 5.0
        and sv_rec.get("serve/ttft_p50_s") is not None
        and sv_rec.get("serve/tpot_p50_s") is not None
        and (sv_rec.get("serve/quant_compression") or 0) >= 3.5
        and sv_rec.get("serve/kv_block_occupancy") == 0.0
        and sv_eng.allocator.used_blocks == 0
        and "stoke_serve_ttft_s" in sv_prom
        and "stoke_serve_kv_block_occupancy" in sv_prom
        # ISSUE 13: the chunked + sampled request's wire evidence — the
        # counters in the JSONL record and the chunk spans in the traced
        # serve cycle (40 prompt tokens over 16-token chunks = 3)
        and sv_rec.get("serve/prefill_chunks") == 3.0
        and sv_rec.get("serve/sampled_tokens") == 4.0
        and len(chunk_spans) == 3
        and {"serve/prefill_chunk", "serve/decode"}
        <= spans_by_rid.get(long_rid, set())
        # ISSUE 16: SLO wire evidence — the nullable serve/slo_* fields
        # in the JSONL record, attainment in the summary block, and the
        # attribution identity queue+prefill+decode == e2e
        and sv_rec.get("serve/slo_requests") == 1.0
        and sv_rec.get("serve/slo_attainment") == 1.0
        and sv_rec.get("serve/slo_goodput_tokens_per_s") is not None
        and slo_attr.get("attained") is True
        and slo_attr.get("span_coverage") == "full"
        and slo_attr.get("partial") is False
        and abs(slo_bucket_sum - slo_attr.get("e2e_s", -1.0)) < 1e-9
        and slo_summary.get("by_class", {})
        .get("interactive", {}).get("attained") == 1
        # ISSUE 17: speculative wire evidence — drafts scored AND
        # accepted (accept-rate > 0), acceptance never exceeding the
        # drafted count, and the greedy streams bit-identical to the
        # non-speculative reference engine
        and spec_drafted > 0
        and 0 < spec_accepted <= spec_drafted
        and greedy_identity
        # ISSUE 18: cost-card / roofline wire evidence
        and cost_ok
        and "stoke_serve_cost_flops_total" in sv_prom
        # ISSUE 19: HBM-ledger wire evidence
        and mem_ok
        # ISSUE 20: the in-flight request table and the post-drain
        # engine summary, both read over real HTTP
        and opsplane_ok
    )
    return {
        "ok": ok,
        "opsplane_ok": opsplane_ok,
        "opsplane_queued": len(op_queued),
        "mem_ok": mem_ok,
        "mem_summary": mem_summary,
        "cost_summary": cost_summary,
        "spec_drafted": spec_drafted,
        "spec_accepted": spec_accepted,
        "spec_accept_rate": (
            spec_accepted / spec_drafted if spec_drafted else 0.0
        ),
        "greedy_identity": greedy_identity,
        "spec_rid": spec_rid,
        "spec_tokens": list(sv_eng.scheduler.finished[spec_rid].tokens),
        "record": sv_rec,
        "engine": sv_eng,
        "prom": sv_prom,
        "trace_events": serve_trace,
        "spans_by_rid": spans_by_rid,
        "chunk_spans": len(chunk_spans),
        "long_rid": long_rid,
        "long_tokens": list(sv_eng.scheduler.finished[long_rid].tokens),
        "slo_rid": slo_rid,
        "slo_attribution": slo_attr,
        "slo_summary": slo_summary,
    }


def main() -> int:
    import numpy as np
    import optax

    from stoke_tpu import (
        AttributionConfig,
        FleetConfig,
        HealthConfig,
        HealthHaltError,
        MemoryConfig,
        NumericsConfig,
        OpsPlaneConfig,
        ProfilerConfig,
        Stoke,
        StokeOptimizer,
        TelemetryConfig,
        TraceConfig,
    )
    from stoke_tpu.telemetry.opsplane import STATUSZ_FIELDS
    from stoke_tpu.telemetry import read_step_events
    from stoke_tpu.utils.tb_writer import read_scalar_events

    out_dir = os.environ.get(
        "STOKE_TELEMETRY_SMOKE_DIR",
        tempfile.mkdtemp(prefix="stoke-telemetry-smoke-"),
    )
    cfg = TelemetryConfig(
        output_dir=out_dir,
        log_every_n_steps=1,
        tensorboard=True,
        grad_norm=True,
    )
    hcfg = HealthConfig(dump_signals=False)
    # step-time attribution (ISSUE 4): one window through the CostCard /
    # MFU / goodput path on CPU — peak is arbitrary here, only the
    # plumbing is being proven
    acfg = AttributionConfig(peak_tflops=1.0, peak_hbm_gbps=100.0)
    # fleet view (ISSUE 5): one exchange window end-to-end — a fleet of
    # one host on CPU, proving the packed-vector/aggregation/JSONL path
    fcfg = FleetConfig(window_steps=1)
    # structured tracing (ISSUE 10): the span ring records the train
    # window below; the exported trace.rank0.json is parsed at the end
    tr_dir = os.path.join(out_dir, "trace")
    trcfg = TraceConfig(output_dir=tr_dir, ring_size=512)
    # per-layer numerics (ISSUE 12): the group-stats matrix rides the
    # same compiled step; the per-group block is asserted on the record
    nmcfg = NumericsConfig()
    # HBM capacity ledger (ISSUE 19): the analytic per-subsystem
    # observatory rides the same window — the mem/* JSONL block, the
    # recombination identity, and the ledger gauges are asserted below
    mmcfg = MemoryConfig()
    # live ops plane (ISSUE 20): the run is scrapeable WHILE it trains —
    # all six endpoints are polled over real HTTP below; port 0 binds an
    # ephemeral loopback port, and the ProfilerConfig trace_dir gives
    # /profile somewhere to land its bounded manual capture
    opcfg = OpsPlaneConfig(port=0)
    pfcfg = ProfilerConfig(trace_dir=os.path.join(out_dir, "xprof"))
    stoke = Stoke(
        model=lambda p, x: x @ p["w"],
        optimizer=StokeOptimizer(
            optimizer=optax.sgd, optimizer_kwargs={"learning_rate": 0.1}
        ),
        loss=lambda o, y: ((o - y) ** 2).mean(),
        params={"w": np.ones((8, 4), np.float32)},
        batch_size_per_device=16,
        configs=[cfg, hcfg, acfg, fcfg, trcfg, nmcfg, mmcfg, opcfg, pfcfg],
        verbose=False,
    )
    x = np.ones((16, 8), np.float32)
    y = np.zeros((16, 4), np.float32)
    stoke.train_step(x, (y,))
    # second step: the fleet view anchors its cadence on the first record
    # (warm-up discard) and closes its first exchange window on the next
    stoke.train_step(x, (y,))
    # live ops plane (ISSUE 20): all six endpoints polled over real HTTP
    # while the run is still alive — the exposition carries the same
    # families the sink file gets at close, /statusz is EXACTLY the
    # pinned field tuple (absent subsystems null, serving included: no
    # engine in this run), /trace serves the live span ring, and
    # /profile lands a bounded manual capture riding (and burning) the
    # attribution capture budget
    ops_base = f"http://127.0.0.1:{stoke.opsplane.port}"
    _, ops_metrics = _ops_get(ops_base, "/metrics")
    ops_hz_status, ops_hz_body = _ops_get(ops_base, "/healthz")
    _, ops_statusz_body = _ops_get(ops_base, "/statusz")
    _, ops_requests_body = _ops_get(ops_base, "/requests")
    _, ops_trace_body = _ops_get(ops_base, "/trace")
    ops_pf_status, ops_pf_body = _ops_get(ops_base, "/profile?seconds=0.05")
    ops_statusz = json.loads(ops_statusz_body)
    ops_profile = json.loads(ops_pf_body)
    opsplane_train_ok = (
        "stoke_jax_compiles_total" in ops_metrics
        and ops_hz_status == 200
        and json.loads(ops_hz_body)["ok"] is True
        and tuple(ops_statusz) == STATUSZ_FIELDS
        and ops_statusz["serving"] is None
        and (ops_statusz["training"] or {}).get("goodput") is not None
        and json.loads(ops_requests_body)["requests"] == []
        and any(
            e.get("name") == "stoke/dispatch"
            for e in json.loads(ops_trace_body)
        )
        and ops_pf_status == 200
        and os.path.isdir(ops_profile["trace_dir"])
    )
    # forced post-mortem dump: the bundle a human reads after a crash —
    # exercised end-to-end so the crash path is proven BEFORE the crash
    bundle = stoke.health.dump("smoke")
    stoke.close_telemetry()

    # the /healthz 200→503 flip (ISSUE 20): a second armed run halts on
    # an injected NaN — and the plane keeps serving AFTER the halt (the
    # socket is the load-balancer drain signal; it must not die with the
    # step loop)
    hz_dir = os.path.join(out_dir, "opsplane_halt")
    hz_stoke = Stoke(
        model=lambda p, x: x @ p["w"],
        optimizer=StokeOptimizer(
            optimizer=optax.sgd, optimizer_kwargs={"learning_rate": 0.1}
        ),
        loss=lambda o, y: ((o - y) ** 2).mean(),
        params={"w": np.ones((8, 4), np.float32)},
        batch_size_per_device=16,
        configs=[
            TelemetryConfig(
                output_dir=hz_dir, log_every_n_steps=1, prometheus=False,
                tensorboard=False, sample_device_time=False, track_hbm=False,
            ),
            HealthConfig(nonfinite_action="halt", dump_signals=False),
            OpsPlaneConfig(port=0),
        ],
        verbose=False,
    )
    hz_base = f"http://127.0.0.1:{hz_stoke.opsplane.port}"
    hz_stoke.train_step(x, (y,))
    hz_before, _ = _ops_get(hz_base, "/healthz")
    xn = x.copy()
    xn[:, 3] = np.nan
    hz_halted = False
    try:
        hz_stoke.train_step(xn, (y,))
    except HealthHaltError:
        hz_halted = True
    hz_after, hz_after_body = _ops_get(hz_base, "/healthz")
    hz_verdict = json.loads(hz_after_body)
    hz_stoke.close_telemetry()
    opsplane_flip_ok = (
        hz_before == 200
        and hz_halted
        and hz_after == 503
        and hz_verdict["ok"] is False
        and hz_verdict["halted"] == "nonfinite_grads"
        and (hz_verdict["anomalies"] or 0) >= 1
    )

    # persistent compile cache (ISSUE 6): one cached warm-start
    # end-to-end — a cold construction misses and persists, a second
    # construction hits the ledger, and the step outputs are
    # bit-identical between the two
    from stoke_tpu import CompileConfig

    cc_dir = os.path.join(out_dir, "compile_cache")

    def _cc_run():
        s = Stoke(
            model=lambda p, x: x @ p["w"],
            optimizer=StokeOptimizer(
                optimizer=optax.sgd, optimizer_kwargs={"learning_rate": 0.1}
            ),
            loss=lambda o, y: ((o - y) ** 2).mean(),
            params={"w": np.full((8, 4), 0.5, np.float32)},
            batch_size_per_device=16,
            configs=[CompileConfig(cache_dir=cc_dir)],
            verbose=False,
        )
        s.train_step(x, (y,))
        return s

    cc_cold = _cc_run()
    cc_warm = _cc_run()
    compile_cache_ok = (
        cc_cold.compile_cache.misses >= 1
        and cc_warm.compile_cache.hits >= 1
        and cc_warm.compile_cache.saved_compile_s > 0
        and np.array_equal(
            np.asarray(cc_cold.params["w"]), np.asarray(cc_warm.params["w"])
        )
    )

    # pod-scale resilience (ISSUE 7): one preemption -> emergency-save ->
    # resume cycle end-to-end — the in-process variant (exit_on_preempt
    # False raises PreemptedError instead of exiting), proving the
    # manifest-verified resume restores step counters AND the
    # out-of-payload state (rng/EMA) bit-identically
    from stoke_tpu import PreemptedError, ResilienceConfig

    rz_root = os.path.join(out_dir, "resilience")
    rz_cfg = ResilienceConfig(save_path=rz_root, exit_on_preempt=False)

    def _rz_run():
        return Stoke(
            model=lambda p, x: x @ p["w"],
            optimizer=StokeOptimizer(
                optimizer=optax.sgd, optimizer_kwargs={"learning_rate": 0.1}
            ),
            loss=lambda o, y: ((o - y) ** 2).mean(),
            params={"w": np.full((8, 4), 2.0, np.float32)},
            batch_size_per_device=16,
            configs=[rz_cfg],
            verbose=False,
        )

    rz_first = _rz_run()
    rz_first.train_step(x, (y,))
    rz_first.resilience.request_preemption("smoke")
    preempted = False
    try:
        rz_first.train_step(x, (y,))  # boundary honors the notice
    except PreemptedError:
        preempted = True
    rz_resumed = _rz_run()
    resumed_ok = rz_resumed.resume()
    rz_resumed.train_step(x, (y,))  # the step the preempted run never ran
    resilience_ok = (
        preempted
        and resumed_ok
        and rz_resumed.optimizer_steps == 3
        and (rz_resumed.resilience_summary or {}).get("resumed_step") == 2
        and os.path.exists(
            os.path.join(
                rz_root, "stoke-emergency-backward-step-2", "manifest.json"
            )
        )
    )
    rz_first.close_telemetry()
    rz_resumed.close_telemetry()

    # elastic resilience (ISSUE 14): one OFFLOAD-STAGED async save →
    # topology-elastic resume cycle — the save stages device→host off the
    # step path (no main-thread gather) onto the 8-device mesh, and a
    # 4-device run restores it bit-identically with the elastic counter
    # ticking
    import jax as _jax

    from stoke_tpu import CheckpointConfig, MeshConfig

    el_root = os.path.join(out_dir, "elastic")
    el_ckpt = CheckpointConfig(async_save=True, offload_staging=True)

    def _el_run(mesh_cfg=None):
        cfgs = [
            el_ckpt,
            ResilienceConfig(
                save_path=el_root, exit_on_preempt=False
            ),
        ]
        if mesh_cfg is not None:
            cfgs.append(mesh_cfg)
        return Stoke(
            model=lambda p, x: x @ p["w"],
            optimizer=StokeOptimizer(
                optimizer=optax.sgd, optimizer_kwargs={"learning_rate": 0.1}
            ),
            loss=lambda o, y: ((o - y) ** 2).mean(),
            params={"w": np.full((8, 4), 2.0, np.float32)},
            batch_size_per_device=2,
            distributed="dp",
            configs=cfgs,
            verbose=False,
        )

    el_first = _el_run()
    el_first.train_step(x, (y,))
    el_first._save_with_config(el_root, "emergency", el_ckpt, None)
    el_first.wait_for_checkpoint()
    el_params = np.asarray(el_first.params["w"])
    el_half = _el_run(
        MeshConfig(devices=np.array(_jax.devices("cpu")[:4]))
    )
    el_resumed = el_half.resume()
    el_sum = el_half.resilience_summary or {}
    elastic_ok = (
        el_resumed
        and int(el_first._mesh.size) == 8
        and int(el_half._mesh.size) == 4
        and np.array_equal(np.asarray(el_half.params["w"]), el_params)
        and el_sum.get("elastic_resumes") == 1
        and os.path.exists(
            os.path.join(
                el_root,
                "stoke-emergency-backward-step-1",
                "variables.staged.rank0.npz",
            )
        )
    )
    el_first.close_telemetry()
    el_half.close_telemetry()

    # sharded quantized transport (ISSUE 8): one optimizer step through
    # the weight-update-sharded path — int8 reduce-scatter + per-shard
    # error feedback under sddp — with the JSONL recording BOTH wire legs
    # (grad compression >= 3.5x analytic, param all-gather bytes) and the
    # residual carried as per-replica partitions
    from stoke_tpu import CommConfig, OSSConfig, SDDPConfig
    from stoke_tpu.parallel.zero import ShardedGradTransport

    import jax as _jax

    world = len(_jax.devices("cpu"))
    zr_dir = os.path.join(out_dir, "zero")
    zr = Stoke(
        model=lambda p, x: x @ p["w"],
        optimizer=StokeOptimizer(
            optimizer=optax.sgd, optimizer_kwargs={"learning_rate": 0.1}
        ),
        loss=lambda o, y: ((o - y) ** 2).mean(),
        params={"w": np.ones((8, 4), np.float32)},
        batch_size_per_device=2,
        distributed="dp",
        oss=True,
        sddp=True,
        configs=[
            CommConfig(dtype="int8", chunk_elems=32, bucket_mb=0.001),
            OSSConfig(min_shard_size=1),
            SDDPConfig(min_shard_size=1),
            TelemetryConfig(
                output_dir=zr_dir, log_every_n_steps=1, prometheus=False,
                tensorboard=False, sample_device_time=False, track_hbm=False,
            ),
        ],
        verbose=False,
    )
    zx = np.ones((2 * world, 8), np.float32)
    zy = np.zeros((zx.shape[0], 4), np.float32)
    zr.train_step(zx, (zy,))
    zr.close_telemetry()
    zero_rec = read_step_events(os.path.join(zr_dir, "steps.jsonl"))[-1]
    zero_sharded = isinstance(zr._engine.transport, ShardedGradTransport)
    zero_ok = (
        zero_sharded
        and (
            world == 1  # 1-wide mesh moves nothing on the wire
            or (
                (zero_rec.get("comm_compression") or 0) >= 3.5
                and (zero_rec.get("comm_bytes_param_gather") or 0) > 0
            )
        )
        and "residual" in zr._comm_state
    )

    # serving stack (ISSUE 9 + 13): one serve cycle end-to-end
    sv_dir = os.path.join(out_dir, "serve")
    sv_result = run_serve_cycle(sv_dir)
    serving_ok = sv_result["ok"]
    sv_rec = sv_result["record"]
    sv_eng = sv_result["engine"]

    # per-layer numerics observatory (ISSUE 12): two runs of a TWO-group
    # model — one clean, one with a NaN injected into the SECOND layer's
    # gradients only (the loss is separable, so lay_a's gradients stay
    # finite) — asserting the per-group JSONL block, a non-empty summary,
    # the NaN attributed to lay_b's group index in record AND anomaly,
    # and an offline numerics_diff.py alignment of the two JSONLs
    import subprocess

    nm_a_dir = os.path.join(out_dir, "numerics_a")
    nm_b_dir = os.path.join(out_dir, "numerics_b")

    def _nm_run(nm_dir, inject_nan):
        s = Stoke(
            model=lambda p, x: (p["lay_a"]["w"] * x[:, :4, None]).sum()
            + (p["lay_b"]["w"] * x[:, 4:, None]).sum(),
            optimizer=StokeOptimizer(
                optimizer=optax.sgd, optimizer_kwargs={"learning_rate": 0.0}
            ),
            loss=lambda o: o,
            params={
                "lay_a": {"w": np.ones((4, 3), np.float32)},
                "lay_b": {"w": np.ones((4, 3), np.float32)},
            },
            batch_size_per_device=8,
            configs=[
                TelemetryConfig(
                    output_dir=nm_dir, log_every_n_steps=1,
                    prometheus=False, tensorboard=False,
                    sample_device_time=False, track_hbm=False,
                ),
                HealthConfig(dump_signals=False),
                NumericsConfig(),
            ],
            verbose=False,
        )
        nx = np.ones((8, 8), np.float32)
        s.train_step(nx, ())
        nx2 = nx.copy()
        if inject_nan:
            nx2[:, 5] = np.nan  # only lay_b's gradient sees it
        s.train_step(nx2, ())
        s.close_telemetry()
        return s

    nm_clean = _nm_run(nm_a_dir, inject_nan=False)
    nm_nan = _nm_run(nm_b_dir, inject_nan=True)
    nm_rec = read_step_events(os.path.join(nm_b_dir, "steps.jsonl"))[-1]
    nm_clean_rec = read_step_events(
        os.path.join(nm_a_dir, "steps.jsonl")
    )[-1]
    nm_summary = nm_nan.numerics_summary or {}
    nm_anomalies = {
        a.detector for a in (nm_nan.health.anomalies if nm_nan.health else [])
    }
    diff_proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "numerics_diff.py"),
         nm_a_dir, nm_b_dir, "--json", "--stat", "update_rms"],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    try:
        diff_report = json.loads(diff_proc.stdout)
    except ValueError:
        diff_report = {}
    numerics_ok = (
        (nm_rec.get("numerics/per_group") or {}).keys()
        == {"lay_a", "lay_b"}
        and nm_rec.get("numerics/provenance_group") == 1
        and nm_rec.get("numerics/provenance_name") == "lay_b"
        and nm_rec.get("numerics/provenance_field") == "grad"
        and nm_clean_rec.get("numerics/provenance_group") is None
        and "numerics_provenance" in nm_anomalies
        and bool(nm_summary.get("top_grad_noise"))
        and diff_proc.returncode == 0
        and diff_report.get("aligned_steps", 0) >= 2
        and set(diff_report.get("groups") or []) == {"lay_a", "lay_b"}
    )

    # structured tracing (ISSUE 10): both exported traces must parse as
    # chrome-trace JSON; the train trace must carry engine step spans,
    # the serve trace at least one full request timeline — admission,
    # prefill, and decode spans sharing one request_id (the serve cycle
    # already parsed its own trace, chunk spans included)
    train_trace = _trace_events(os.path.join(tr_dir, "trace.rank0.json"))
    serve_trace = sv_result["trace_events"]
    step_span_names = {e["name"] for e in train_trace}
    spans_by_rid = sv_result["spans_by_rid"]
    tracing_ok = (
        bool(step_span_names & {"stoke/dispatch", "stoke/accum", "stoke/apply"})
        and "stoke/place" in step_span_names
        and sum(
            1
            for names in spans_by_rid.values()
            if {"serve/admission", "serve/prefill", "serve/decode"} <= names
        ) >= 2
        and (stoke.trace_summary or {}).get("spans", 0) > 0
    )

    records = read_step_events(os.path.join(out_dir, "steps.jsonl"))
    print(json.dumps(records[-1], sort_keys=True))
    rec = records[-1]
    # the read_step_events round-trip already schema-validated the record;
    # additionally require the ISSUE 3 sentinel fields to be POPULATED
    health_fields_ok = (
        rec.get("grad_norm") is not None
        and rec.get("param_norm") is not None
        and rec.get("update_ratio") is not None
        and rec.get("nonfinite_leaves") == 0.0
        and rec.get("health_anomalies") == 0.0
    )
    # ISSUE 4: the attribution window populated MFU + bound + a goodput
    # partition, and the end-of-run goodput summary is coherent
    goodput = stoke.goodput or {}
    attribution_ok = (
        rec.get("mfu") is not None
        and rec.get("achieved_tflops") is not None
        and rec.get("bound") in ("compute", "memory", "comm", "host")
        and rec.get("goodput_productive_s") is not None
        and goodput.get("windows", 0) >= 1
        and goodput.get("goodput_fraction") is not None
    )
    # ISSUE 5: the fleet window populated the per-host view (a fleet of
    # one here: skew zero, class "none") and the end-of-run summary
    fleet = stoke.fleet_summary or {}
    fleet_ok = (
        rec.get("fleet/hosts") == 1
        and rec.get("fleet/window", 0) >= 1
        and rec.get("fleet/skew_class") == "none"
        and fleet.get("windows", 0) >= 1
    )
    bundle_files = set(os.listdir(bundle)) if os.path.isdir(bundle) else set()
    bundle_ok = {
        "manifest.json", "ring.jsonl", "config.json", "mesh.json",
        "environment.json", "stacks.txt",
        # ISSUE 4: utilization at time of death rides every bundle
        "goodput.json", "cost_cards.json",
        # ISSUE 5: which host was slow at time of death
        "fleet.json",
        # ISSUE 10: what the host was doing at time of death
        "trace.json",
        # ISSUE 12: which layer was bad at time of death
        "numerics.json",
    } <= bundle_files
    ring_kinds = set()
    if bundle_ok:
        with open(os.path.join(bundle, "ring.jsonl")) as f:
            ring_kinds = {json.loads(ln)["kind"] for ln in f if ln.strip()}
    prom = open(os.path.join(out_dir, "metrics.prom")).read()
    tb_dir = os.path.join(out_dir, "tb")
    tb_files = [
        os.path.join(tb_dir, f) for f in os.listdir(tb_dir)
        if f.startswith("events.out.tfevents.")
    ]
    tb_events = read_scalar_events(tb_files[0]) if tb_files else []
    # ISSUE 19: the train-side HBM ledger — the four train components
    # recombine EXACTLY into the resident total (kv_cache stays None:
    # no serving subsystem in this run), the worst program transient
    # was captured at the dispatch funnel, predicted peak = resident +
    # transient, the build pre-flight was recorded (silent — the CPU
    # simulator reports no capacity to squeeze against), and the
    # resident gauge reached the exposition
    mem_preflights = stoke.memory.preflights if stoke.memory else {}
    memory_ok = (
        rec.get("mem/params_bytes") == 128
        and None not in (
            rec.get("mem/opt_state_bytes"),
            rec.get("mem/transport_bytes"),
            rec.get("mem/snapshot_bytes"),
        )
        and rec.get("mem/params_bytes")
        + rec.get("mem/opt_state_bytes")
        + rec.get("mem/transport_bytes")
        + rec.get("mem/snapshot_bytes")
        == rec.get("mem/resident_bytes")
        and rec.get("mem/kv_cache_bytes") is None
        and (rec.get("mem/temp_peak_bytes") or 0) > 0
        and rec.get("mem/predicted_peak_bytes")
        == rec.get("mem/resident_bytes") + rec.get("mem/temp_peak_bytes")
        and "build" in mem_preflights
        and mem_preflights["build"]["fired"] is False
        and "stoke_mem_resident_bytes" in prom
    )
    ok = (
        len(records) == 2
        and records[0]["step"] == 1
        and health_fields_ok
        and attribution_ok
        and fleet_ok
        and "stoke_jax_compiles_total" in prom
        and "stoke_health_anomalies_total" in prom
        and "stoke_goodput_productive_s_total" in prom
        and "stoke_attr_mfu" in prom
        and "stoke_fleet_windows_total" in prom
        and "stoke_sync_barriers_total" in prom
        and 'host="' in prom  # multi-host scrape-collision labels
        and any(t.startswith("telemetry/") for t, _, _ in tb_events)
        and bundle_ok
        and {"sentinels", "step_event"} <= ring_kinds
        and compile_cache_ok
        and resilience_ok
        and elastic_ok
        and zero_ok
        and serving_ok
        and tracing_ok
        and numerics_ok
        and memory_ok
        # ISSUE 12: the main run's record carries the per-group block
        # (one group: the single "w" param)
        and (rec.get("numerics/per_group") or {}).keys() == {"w"}
        # default-OFF discipline (ISSUE 9): training records never carry
        # serve fields — and (ISSUE 12) a run without a NumericsConfig
        # (the serve cycle's) never carries numerics fields; the
        # serve/cost_* block (ISSUE 18) rides the same contract, so a
        # non-serve record is cost-free by construction
        and not any(k.startswith("serve/") for k in rec)
        and not any(k.startswith("serve/cost_") for r in records for k in r)
        and not any(k.startswith("numerics/") for k in sv_rec)
        # ISSUE 19 default-OFF discipline: runs WITHOUT a MemoryConfig
        # (the sharded-transport and numerics legs) emit records with
        # zero mem/* fields — absent, never null
        and not any(k.startswith("mem/") for k in zero_rec)
        and not any(k.startswith("mem/") for k in nm_rec)
        and not any(k.startswith("mem/") for k in nm_clean_rec)
        # ISSUE 20: the live ops plane — six endpoints over real HTTP on
        # the training run, the /healthz 200→503 drain flip on the
        # injected-NaN halt, and the serve cycle's request table
        and opsplane_train_ok
        and opsplane_flip_ok
        and sv_result["opsplane_ok"]
    )
    print(json.dumps({
        "telemetry_smoke": "ok" if ok else "FAILED",
        "output_dir": out_dir,
        "jsonl_records": len(records),
        "prom_bytes": len(prom),
        "tb_scalars": len(tb_events),
        "bundle": bundle,
        "bundle_files": sorted(bundle_files),
        "ring_kinds": sorted(ring_kinds),
        "mfu": rec.get("mfu"),
        "bound": rec.get("bound"),
        "goodput_fraction": goodput.get("goodput_fraction"),
        "fleet_hosts": rec.get("fleet/hosts"),
        "fleet_windows": fleet.get("windows"),
        "fleet_skew_class": rec.get("fleet/skew_class"),
        "compile_cache_cold": cc_cold.compile_cache.stats(),
        "compile_cache_warm": cc_warm.compile_cache.stats(),
        "resilience_cycle": "ok" if resilience_ok else "FAILED",
        "resilience_resumed": rz_resumed.resilience_summary,
        "elastic_cycle": "ok" if elastic_ok else "FAILED",
        "elastic_resumed": el_sum.get("elastic_resumes"),
        "zero_sharded_step": "ok" if zero_ok else "FAILED",
        "zero_comm_compression": zero_rec.get("comm_compression"),
        "zero_param_gather_bytes": zero_rec.get("comm_bytes_param_gather"),
        "serve_cycle": "ok" if serving_ok else "FAILED",
        "serve_ttft_p50_s": sv_rec.get("serve/ttft_p50_s"),
        "serve_tpot_p50_s": sv_rec.get("serve/tpot_p50_s"),
        "serve_quant_compression": sv_rec.get("serve/quant_compression"),
        "serve_prefill_chunks": sv_rec.get("serve/prefill_chunks"),
        "serve_sampled_tokens": sv_rec.get("serve/sampled_tokens"),
        "serve_slo_attainment": sv_rec.get("serve/slo_attainment"),
        "serve_slo_coverage": sv_result["slo_attribution"].get(
            "span_coverage"
        ),
        "serve_cost_decode_bound": sv_rec.get("serve/cost_decode_bound"),
        "serve_cost_mfu": sv_rec.get("serve/cost_mfu"),
        "serve_verify_intensity_uplift": sv_result["cost_summary"].get(
            "verify_intensity_uplift"
        ),
        "numerics": "ok" if numerics_ok else "FAILED",
        "numerics_provenance": nm_rec.get("numerics/provenance_name"),
        "numerics_diff_aligned": diff_report.get("aligned_steps"),
        "memory": "ok" if memory_ok else "FAILED",
        "mem_resident_bytes": rec.get("mem/resident_bytes"),
        "mem_temp_peak_bytes": rec.get("mem/temp_peak_bytes"),
        "mem_preflight_fired": (
            mem_preflights.get("build") or {}
        ).get("fired"),
        "serve_memory": "ok" if sv_result["mem_ok"] else "FAILED",
        "serve_mem_resident_bytes": sv_rec.get("mem/resident_bytes"),
        "serve_mem_headroom_bytes": sv_rec.get("serve/mem_headroom_bytes"),
        "tracing": "ok" if tracing_ok else "FAILED",
        "trace_train_spans": len(train_trace),
        "trace_serve_spans": len(serve_trace),
        "trace_requests": sorted(spans_by_rid),
        "opsplane": (
            "ok"
            if opsplane_train_ok
            and opsplane_flip_ok
            and sv_result["opsplane_ok"]
            else "FAILED"
        ),
        "opsplane_healthz_flip": [hz_before, hz_after],
        "opsplane_halted": hz_verdict.get("halted"),
        "opsplane_profile_dir": ops_profile.get("trace_dir"),
        "opsplane_serve_queued": sv_result["opsplane_queued"],
    }))
    return 0 if ok else 1


def serve_only() -> int:
    """The ``make serve-smoke`` leg: just the traced serve cycle — one
    chunked-prefill + top-p request (plus two greedy ones and the
    ISSUE 17 speculative repetitive-prompt request) end-to-end, chunk
    spans asserted in the exported timeline and the speculative
    accept-rate / greedy-identity contract asserted on the counters."""
    out_dir = os.environ.get(
        "STOKE_TELEMETRY_SMOKE_DIR",
        tempfile.mkdtemp(prefix="stoke-serve-smoke-"),
    )
    res = run_serve_cycle(os.path.join(out_dir, "serve"))
    print(json.dumps({
        "serve_smoke": "ok" if res["ok"] else "FAILED",
        "output_dir": out_dir,
        "serve_prefill_chunks": res["record"].get("serve/prefill_chunks"),
        "serve_sampled_tokens": res["record"].get("serve/sampled_tokens"),
        "serve_quant_compression": res["record"].get(
            "serve/quant_compression"
        ),
        "chunk_spans": res["chunk_spans"],
        "long_request_tokens": res["long_tokens"],
        "serve_slo_attainment": res["record"].get("serve/slo_attainment"),
        "serve_slo_attribution": {
            k: res["slo_attribution"].get(k)
            for k in ("queue_wait_s", "prefill_blocked_s",
                      "decode_contention_s", "e2e_s", "span_coverage")
        },
        "spec_accept_rate": res["spec_accept_rate"],
        "spec_drafted": res["spec_drafted"],
        "spec_accepted": res["spec_accepted"],
        "spec_greedy_identity": res["greedy_identity"],
        "serve_memory": "ok" if res["mem_ok"] else "FAILED",
        "serve_mem_resident_bytes": res["record"].get("mem/resident_bytes"),
        "serve_mem_headroom_bytes": res["record"].get(
            "serve/mem_headroom_bytes"
        ),
        "serve_cost_decode_bound": res["record"].get(
            "serve/cost_decode_bound"
        ),
        "serve_cost_attainable_tpot_s": res["record"].get(
            "serve/cost_attainable_tpot_s"
        ),
        "serve_verify_intensity_uplift": res["cost_summary"].get(
            "verify_intensity_uplift"
        ),
        "trace_requests": sorted(res["spans_by_rid"]),
    }))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(serve_only() if "--serve-only" in sys.argv[1:] else main())
