"""Unified telemetry subsystem (ISSUE 1 tentpole).

One pipeline replaces the facade's disconnected one-off probes
(``profile_trace`` / ``estimate_step_flops`` / the wall-clock dict):

    registry (counters/gauges/histograms)
        <- facade phase timers, data-loader wait/starvation, compile
           tracking, HBM watermarks, user scalars
    -> sinks at the logging cadence:
         JSONL structured step events (events.py schema, one line/window)
         Prometheus text exposition (atomic scrape file)
         native TensorBoard writer (utils/tb_writer.py format)

Enable by passing ``TelemetryConfig`` to ``Stoke(configs=[...])``; the
:class:`Telemetry` object is also usable standalone (scripts, tests):

    from stoke_tpu.telemetry import Telemetry
    from stoke_tpu import TelemetryConfig

    t = Telemetry(TelemetryConfig(output_dir="/tmp/run1"), rank=0)
    with t.phase("step"):
        ...
    t.record_step(step=1, window_steps=1, ema_loss=2.3)

See docs/observability.md for the full tour.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

from stoke_tpu.telemetry.collectors import (
    CompileTracker,
    hbm_stats,
    update_hbm_gauges,
    xprof_span,
)
from stoke_tpu.telemetry.events import (
    STEP_EVENT_SCHEMA,
    build_step_event,
    read_step_events,
    validate_step_event,
)
from stoke_tpu.telemetry.health import (
    SENTINEL_FIELDS,
    WATCHDOG_EXIT_CODE,
    Anomaly,
    HangWatchdog,
    HealthHaltError,
    HealthMonitor,
    compute_sentinels,
    unpack_sentinels,
)
from stoke_tpu.telemetry.attribution import (
    BOUND_CLASSES,
    GOODPUT_BUCKETS,
    AttributionMonitor,
    AutoCaptureDetector,
    CostCard,
    CostCardCache,
    classify_bound,
    cost_analysis_of,
    roofline_summary,
    roofline_time_s,
)
from stoke_tpu.telemetry.fleet import (
    FLEET_EVENT_FIELDS,
    FLEET_SIGNALS,
    FleetMonitor,
    FleetStragglerDetector,
    fleet_aggregates,
    observe_sync_wait,
    pack_fleet_vector,
    register_sync_registry,
    straggler_verdict,
    timed_sync,
    unpack_fleet_vector,
    unregister_sync_registry,
)
from stoke_tpu.telemetry.numerics import (
    GROUP_REPORT_FIELDS,
    N_NUMERICS_STATS,
    NUMERICS_STATS,
    ModuleGroup,
    NumericsMonitor,
    NumericsProvenanceDetector,
    compute_group_stats,
    leaf_path_names,
    max_quant_error,
    module_groups,
    provenance_of,
    quant_error_by_group,
    unpack_group_stats,
    wire_residual_group_norms,
)
from stoke_tpu.telemetry.memory import (
    MEM_FIELDS,
    MemoryObservatory,
    transport_resident_bytes,
    tree_resident_bytes,
)
from stoke_tpu.telemetry.recorder import FlightRecorder
from stoke_tpu.telemetry.tracing import (
    TRACE_EVENT_KEYS,
    ComposedContext,
    Span,
    TraceRecorder,
    register_recorder,
    trace_add,
    trace_point,
    trace_span,
    tracing_active,
    unregister_recorder,
)
from stoke_tpu.telemetry.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from stoke_tpu.telemetry.sinks import (
    JsonlSink,
    PrometheusSink,
    Sink,
    TensorBoardSink,
    render_prometheus,
)

__all__ = [
    "Telemetry",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "Sink",
    "JsonlSink",
    "PrometheusSink",
    "TensorBoardSink",
    "render_prometheus",
    "CompileTracker",
    "hbm_stats",
    "update_hbm_gauges",
    "xprof_span",
    "STEP_EVENT_SCHEMA",
    "build_step_event",
    "validate_step_event",
    "read_step_events",
    # health monitor (ISSUE 3)
    "SENTINEL_FIELDS",
    "WATCHDOG_EXIT_CODE",
    "Anomaly",
    "HangWatchdog",
    "HealthHaltError",
    "HealthMonitor",
    "FlightRecorder",
    "compute_sentinels",
    "unpack_sentinels",
    # step-time attribution & goodput (ISSUE 4)
    "AttributionMonitor",
    "AutoCaptureDetector",
    "CostCard",
    "CostCardCache",
    "BOUND_CLASSES",
    "GOODPUT_BUCKETS",
    "classify_bound",
    "cost_analysis_of",
    "roofline_summary",
    "roofline_time_s",
    # fleet observability (ISSUE 5)
    "FLEET_SIGNALS",
    "FLEET_EVENT_FIELDS",
    "FleetMonitor",
    "FleetStragglerDetector",
    "fleet_aggregates",
    "straggler_verdict",
    "pack_fleet_vector",
    "unpack_fleet_vector",
    "register_sync_registry",
    "unregister_sync_registry",
    "observe_sync_wait",
    "timed_sync",
    # per-layer numerics observatory (ISSUE 12)
    "NUMERICS_STATS",
    "N_NUMERICS_STATS",
    "GROUP_REPORT_FIELDS",
    "ModuleGroup",
    "NumericsMonitor",
    "NumericsProvenanceDetector",
    "compute_group_stats",
    "leaf_path_names",
    "max_quant_error",
    "module_groups",
    "provenance_of",
    "quant_error_by_group",
    "unpack_group_stats",
    "wire_residual_group_norms",
    # HBM capacity observatory (ISSUE 19)
    "MEM_FIELDS",
    "MemoryObservatory",
    "transport_resident_bytes",
    "tree_resident_bytes",
    # structured tracing (ISSUE 10)
    "TRACE_EVENT_KEYS",
    "ComposedContext",
    "Span",
    "TraceRecorder",
    "register_recorder",
    "unregister_recorder",
    "trace_span",
    "trace_point",
    "trace_add",
    "tracing_active",
]


class Telemetry:
    """Orchestrator: owns the registry, collectors, and sinks.

    Constructed with ``config=None`` it is a *disabled* pipeline: the
    registry still works (the facade's wall-clock breakdown and xprof spans
    ride on it unconditionally) but no collectors attach and ``record_step``
    is a no-op — zero IO, zero listeners, zero device touches.

    Multi-host: sinks default to rank 0 only; ``jsonl_all_ranks=True`` adds
    a per-rank JSONL stream (``steps.rank<N>.jsonl``).
    """

    def __init__(
        self,
        config=None,
        rank: int = 0,
        extra_sinks: Optional[List[Sink]] = None,
    ):
        self.config = config
        self.rank = int(rank)
        self.registry = MetricsRegistry()
        self.sinks: List[Sink] = list(extra_sinks or [])
        self.compile_tracker: Optional[CompileTracker] = None
        # step-time attribution monitor (ISSUE 4) — assigned by the
        # facade when an AttributionConfig is supplied; None keeps
        # record_step free of MFU/goodput computation entirely
        self.attribution = None
        # fleet-view monitor (ISSUE 5) — assigned by the facade when a
        # FleetConfig is supplied; None keeps record_step free of any
        # cross-host exchange entirely
        self.fleet = None
        # resilience monitor (ISSUE 7) — assigned by the facade when a
        # ResilienceConfig is supplied; None keeps the resilience/* keys
        # out of every step event entirely
        self.resilience = None
        # per-layer numerics monitor (ISSUE 12) — assigned by the facade
        # when a NumericsConfig is supplied; None keeps the numerics/*
        # keys out of every step event entirely
        self.numerics = None
        # HBM capacity observatory (ISSUE 19) — assigned by the facade
        # when a MemoryConfig is supplied; None keeps the mem/* keys out
        # of every step event entirely
        self.memory = None
        # cross-process sync timings (Stoke.barrier / checkpoint
        # sync_global_devices) land in this registry even when no
        # TelemetryConfig drives sinks — the wall-clock breakdown and
        # the fleet barrier-wait attribution both read them
        register_sync_registry(self.registry)
        self._last_record: Dict[str, float] = {}
        # seeded now so the FIRST record's rates cover init->record wall
        # time (includes warm-up compiles — honest, if conservative)
        self._last_record_ts: Optional[float] = time.time()
        self._last_loss_scale = None
        self._closed = False
        if config is None:
            return
        import os

        if config.track_compiles:
            self.compile_tracker = CompileTracker(self.registry)
        is_rank0 = self.rank == 0
        out = config.output_dir
        if config.jsonl and (is_rank0 or config.jsonl_all_ranks):
            name = (
                "steps.jsonl"
                if is_rank0 and not config.jsonl_all_ranks
                else f"steps.rank{self.rank}.jsonl"
            )
            self.sinks.append(JsonlSink(os.path.join(out, name)))
        if config.prometheus and (is_rank0 or config.prometheus_all_ranks):
            from stoke_tpu.telemetry.sinks import host_labels

            prom_name = (
                "metrics.prom"
                if is_rank0 and not config.prometheus_all_ranks
                else f"metrics.rank{self.rank}.prom"
            )
            self.sinks.append(
                PrometheusSink(
                    os.path.join(out, prom_name),
                    # host/process_index labels (ISSUE 5 satellite): a
                    # multi-host job's per-host expositions scraped into
                    # one Prometheus must not collide into one series
                    labels={
                        "rank": str(self.rank),
                        "run": config.run_name,
                        **host_labels(self.rank),
                    },
                )
            )
        if config.tensorboard and is_rank0:
            self.sinks.append(TensorBoardSink(os.path.join(out, "tb")))

    # ------------------------------------------------------------------ #
    # emit surface (facade / data / user)
    # ------------------------------------------------------------------ #

    @property
    def enabled(self) -> bool:
        """True when a ``TelemetryConfig`` drives sinks (the registry works
        regardless)."""
        return self.config is not None

    def phase(self, name: str, annotate: bool = True):
        """Timer for a facade/engine phase: seconds accumulate into
        ``facade/<name>_s`` (the wall-clock breakdown), the span is
        labeled in xprof timelines, AND — with a trace recorder
        registered (ISSUE 10) — the same section lands in the host span
        ring, so every timed section is also a trace span (one composed
        helper instead of the hand-rolled span+timer pairing)."""
        timer = self.registry.timer(f"facade/{name}_s")
        if not annotate:
            return timer
        return trace_span(f"stoke/{name}", track="facade", timer=timer)

    def log_scalar(self, tag: str, value: float) -> None:
        """User scalar -> gauge ``user/<tag>`` (mirrored to sinks at the
        next cadence; the facade additionally writes it to its TB stream
        immediately for parity with the legacy ``log_scalar``)."""
        self.registry.gauge(f"user/{tag}").set(float(value))

    def add_samples(self, n: int) -> None:
        self.registry.counter("data/samples_total").inc(n)

    def add_tokens(self, n: int) -> None:
        self.registry.counter("data/tokens_total").inc(n)

    def observe_device_step(self, seconds: float) -> None:
        """Record one sampled device-step time (block_until_ready bracketed
        dispatch, see facade)."""
        self.registry.histogram("device/step_s").observe(seconds)

    def will_sample_device(self) -> bool:
        return self.enabled and self.config.sample_device_time

    def wall_clock_breakdown(self) -> Dict[str, float]:
        """``{phase: cumulative host seconds}`` from the registry-backed
        facade timers (the legacy ``Stoke.wall_clock_breakdown`` surface).
        With attribution on (ISSUE 4), the cumulative goodput buckets are
        merged in as ``goodput/<bucket>`` entries — one call answers both
        "where did host dispatch go" and "where did wall clock go"."""
        out = {}
        for name in self.registry.names():
            if name.startswith("facade/") and name.endswith("_s"):
                out[name[len("facade/"):-2]] = self.registry.get(name).value
        # cross-process sync time (ISSUE 5 satellite): barrier waits are
        # host wall clock like the facade phases, and invisible anywhere
        # else a wall-clock reader looks — surface once any accrued
        sync = self.registry.get("sync/barrier_wait_s")
        if sync is not None and sync.value > 0:
            out["sync/barrier_wait"] = sync.value
        if self.attribution is not None:
            summary = self.attribution.goodput_summary()
            for b in GOODPUT_BUCKETS:
                out[f"goodput/{b}"] = summary[f"{b}_s"]
        return out

    def goodput_summary(self) -> Optional[dict]:
        """End-of-run goodput accounting (cumulative bucket seconds,
        goodput fraction, aggregate achieved TFLOP/s + MFU, capture
        paths); None without an ``AttributionConfig``."""
        if self.attribution is None:
            return None
        return self.attribution.goodput_summary()

    def fleet_summary(self) -> Optional[dict]:
        """End-of-run fleet accounting (windows, latest per-host matrix +
        aggregates + straggler verdict, straggler counts); None without a
        ``FleetConfig``."""
        if self.fleet is None:
            return None
        return self.fleet.summary()

    # ------------------------------------------------------------------ #
    # step records
    # ------------------------------------------------------------------ #

    def _counter_value(self, name: str) -> float:
        inst = self.registry.get(name)
        return inst.value if inst is not None else 0.0

    def _counter_or_none(self, name: str) -> Optional[float]:
        """Counter value, or None when nothing ever registered it (the
        "feature absent -> field null" contract)."""
        inst = self.registry.get(name)
        return inst.value if inst is not None else None

    def _delta(self, name: str) -> float:
        """Per-window delta of a cumulative counter (vs the last record)."""
        now = self._counter_value(name)
        prev = self._last_record.get(name, 0.0)
        self._last_record[name] = now
        return max(0.0, now - prev)

    def note_loss_scale(self, scale) -> int:
        """Track dynamic-loss-scale transitions; returns the cumulative
        transition (backoff+growth) count."""
        events = self.registry.counter("precision/loss_scale_events_total")
        if scale is not None and self._last_loss_scale is not None:
            prev, cur = self._last_loss_scale, scale
            prev_l = prev if isinstance(prev, list) else [prev]
            cur_l = cur if isinstance(cur, list) else [cur]
            changed = len(prev_l) != len(cur_l) or any(
                a != b for a, b in zip(prev_l, cur_l)
            )
            if changed:
                events.inc()
        if scale is not None:
            self._last_loss_scale = scale
        return int(events.value)

    def record_step(
        self,
        step: int,
        window_steps: int = 1,
        *,
        ema_loss: Optional[float] = None,
        step_loss: Optional[float] = None,
        grad_norm: Optional[float] = None,
        loss_scale=None,
        skipped_steps: float = 0.0,
        comm_residual_norm: Optional[float] = None,
        param_norm: Optional[float] = None,
        update_ratio: Optional[float] = None,
        nonfinite_leaves: Optional[float] = None,
        health_anomalies: Optional[float] = None,
        tokens_hint: Optional[float] = None,
        ts: Optional[float] = None,
        serve: Optional[Dict[str, Any]] = None,
        memory=None,
    ) -> Optional[dict]:
        """Assemble one structured step event from the registry state and
        fan it to every sink.  Called by the facade at the logging cadence;
        safe to call directly from scripts.  Returns the record (None when
        telemetry is disabled)."""
        if not self.enabled or self._closed:
            return None
        now = time.time() if ts is None else ts
        wall_dt = (
            None
            if self._last_record_ts is None
            else max(now - self._last_record_ts, 1e-9)
        )
        self._last_record_ts = now

        if self.config.track_hbm:
            update_hbm_gauges(self.registry)

        # host dispatch seconds this window: sum of facade phase deltas
        # (checkpoint IO tracked separately — it feeds the goodput ledger)
        host_dispatch = 0.0
        ckpt_io = 0.0
        for name in self.registry.names():
            if name.startswith("facade/") and name.endswith("_s"):
                d = self._delta(name)
                host_dispatch += d
                if name in ("facade/save_s", "facade/load_s"):
                    ckpt_io += d
        loader_wait = self._delta("data/loader_wait_s")
        samples_delta = self._delta("data/samples_total")
        tokens_delta = self._delta("data/tokens_total")
        samples_total = self._counter_value("data/samples_total")

        samples_per_s = (
            samples_delta / wall_dt if wall_dt and samples_delta else None
        )
        tokens = tokens_delta if tokens_delta else (tokens_hint or 0.0)
        tokens_per_s = tokens / wall_dt if wall_dt and tokens else None

        dev_hist = self.registry.get("device/step_s")
        device_step_s = (
            dev_hist.ema if isinstance(dev_hist, Histogram) else None
        )

        # gradient-transport bytes (ISSUE 2): per-window deltas of the
        # analytic bytes-on-wire counters the facade increments per
        # optimizer step; null when no transport is configured
        if self.registry.get("comm/grad_bytes_prequant_total") is not None:
            comm_pre = self._delta("comm/grad_bytes_prequant_total")
            comm_wire = self._delta("comm/grad_bytes_onwire_total")
            comm_ratio = comm_pre / comm_wire if comm_wire else None
        else:
            comm_pre = comm_wire = comm_ratio = None
        # second wire leg (ISSUE 8): the updated-parameter all-gather of
        # the weight-update-sharded path; the counter exists only when the
        # facade runs a sharded transport — absent, the field rides null
        if self.registry.get("comm/param_gather_bytes_total") is not None:
            comm_gather = self._delta("comm/param_gather_bytes_total")
        else:
            comm_gather = None

        if self.compile_tracker is not None:
            compiles = self.compile_tracker.compiles
            recompiles = self.compile_tracker.recompiles
            compile_time = self.compile_tracker.compile_time_s
        else:
            compiles = recompiles = 0
            compile_time = 0.0

        # persistent compile cache (ISSUE 6): cumulative AOT hit/miss
        # counts + reclaimed compile seconds.  The counters exist only
        # when a CompileCache registered them (a CompileConfig run) —
        # absent, the fields ride as nulls.
        cc_hits = self._counter_or_none("compile_cache/hits_total")
        cc_misses = self._counter_or_none("compile_cache/misses_total")
        cc_saved = self._counter_or_none("compile_cache/saved_s_total")

        # step-time attribution (ISSUE 4): per-window MFU/roofline gauges
        # + goodput buckets, derived from the deltas computed above — one
        # code path for all four facade step APIs
        attr_fields: dict = {}
        if self.attribution is not None:
            attr_fields = self.attribution.window_stats(
                step=step,
                wall_s=wall_dt,
                host_dispatch_s=host_dispatch,
                loader_wait_s=loader_wait,
                ckpt_io_s=ckpt_io,
                comm_bytes_onwire=comm_wire,
            )

        # fleet view (ISSUE 5): accumulate this record's deltas into the
        # current fleet window; at a window boundary ONE in-band
        # process_allgather yields the per-host matrix and the fleet/*
        # fields below — between boundaries the fields ride as nulls
        fleet_fields: Optional[dict] = None
        if self.fleet is not None:
            fleet_fields = self.fleet.window_stats(
                step=step,
                wall_s=wall_dt,
                loader_wait_s=loader_wait,
                comm_bytes_onwire=comm_wire,
            )

        # resilience counters (ISSUE 7): cumulative preemption/restart/
        # quarantine accounting rides every record when a monitor is
        # attached — pure registry reads, no device or IO work
        resilience_fields: Optional[dict] = None
        if self.resilience is not None:
            resilience_fields = self.resilience.event_fields()

        # per-layer numerics (ISSUE 12): the latest per-group block +
        # provenance / quant-error attribution rides every record when a
        # monitor is attached — pure host reads of already-fetched state
        numerics_fields: Optional[dict] = None
        if self.numerics is not None:
            numerics_fields = self.numerics.event_fields()

        # HBM capacity ledger (ISSUE 19): the analytic per-subsystem
        # resident ledger + OOM forecast rides every record when an
        # observatory is attached — pure host arithmetic over
        # shape/dtype trees, no device touches
        # (a ServingEngine passes its OWN observatory via ``memory=`` so
        # serve records ledger the serving subsystems, not the train ones)
        memory_obs = memory if memory is not None else self.memory
        memory_fields: Optional[dict] = None
        if memory_obs is not None:
            memory_obs.refresh_gauges()
            memory_fields = memory_obs.event_fields()

        hbm = hbm_stats() if self.config.track_hbm else None
        record = build_step_event(
            ts=now,
            step=step,
            rank=self.rank,
            window_steps=window_steps,
            host_dispatch_s=host_dispatch,
            device_step_s=device_step_s,
            loader_wait_s=loader_wait,
            samples_per_s=samples_per_s,
            tokens_per_s=tokens_per_s,
            samples_total=samples_total,
            ema_loss=ema_loss,
            step_loss=step_loss,
            grad_norm=grad_norm,
            loss_scale=loss_scale,
            loss_scale_events=self.note_loss_scale(loss_scale),
            skipped_steps=skipped_steps,
            comm_bytes_prequant=comm_pre,
            comm_bytes_onwire=comm_wire,
            comm_bytes_param_gather=comm_gather,
            comm_compression=comm_ratio,
            comm_residual_norm=comm_residual_norm,
            param_norm=param_norm,
            update_ratio=update_ratio,
            nonfinite_leaves=nonfinite_leaves,
            health_anomalies=health_anomalies,
            compiles_total=compiles,
            recompiles=recompiles,
            compile_time_s=compile_time,
            compile_cache_hits=cc_hits,
            compile_cache_misses=cc_misses,
            compile_cache_saved_s=cc_saved,
            hbm_bytes_in_use=(hbm or {}).get("bytes_in_use"),
            hbm_peak_bytes=(hbm or {}).get("peak_bytes_in_use"),
            hbm_bytes_limit=(hbm or {}).get("bytes_limit"),
            fleet=fleet_fields,
            resilience=resilience_fields,
            # serving fields (ISSUE 9): only a ServingEngine emit passes
            # them — training records stay free of every serve/* key
            serve=serve,
            numerics=numerics_fields,
            memory=memory_fields,
            **attr_fields,
        )
        snapshot = self.registry.snapshot()
        for sink in self.sinks:
            sink.emit(record, snapshot)
        return record

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        # stop receiving other runs' barrier waits: a closed pipeline's
        # counters are a finished run's record, not a live subscriber
        unregister_sync_registry(self.registry)
        if self.attribution is not None:
            try:
                self.attribution.close()  # stop an in-flight auto-capture
            except Exception:
                pass
        for sink in self.sinks:
            try:
                sink.close()
            except Exception:
                pass
