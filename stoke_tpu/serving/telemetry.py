"""Per-request serving telemetry riding the PR-1 metrics registry.

TTFT (time-to-first-token: arrival -> first generated token, queue time
included) and TPOT (time-per-output-token over the decode tokens) are THE
serving SLOs; alongside them ride the capacity gauges (queue depth,
KV-block occupancy, batch fill) and a goodput split of the serve
wall-clock into queue/idle vs prefill vs decode — same sums-to-wall
contract as the PR-4 training goodput ledger.

Everything lands in the shared :class:`~stoke_tpu.telemetry.registry
.MetricsRegistry` (so the Prometheus exposition and flight-recorder
snapshots pick it up for free) under ``serve/*`` names; the JSONL step
events gain the nullable ``serve/*`` field block (events.py), populated
only when a serving engine emits — training records never carry them.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional

from stoke_tpu.telemetry.registry import MetricsRegistry

#: speculative-decoding JSONL fields (ISSUE 17) — emitted only by engines
#: with ``speculative_k`` set (the default-OFF contract: non-speculative
#: records carry zero new fields).  Pinned append-only by the
#: ``analysis/manifests/wire_formats.json`` manifest.
SPEC_FIELDS = (
    "serve/spec_draft_tokens",
    "serve/spec_accepted_tokens",
)

#: sample cap for the exact-percentile reservoirs (beyond it the oldest
#: samples age out; p50/p99 then describe the trailing window)
_MAX_SAMPLES = 8192

#: sub-second latency buckets for the TTFT/TPOT histograms (the default
#: registry ladder starts at 1ms and tops out at 60s — fine here too, but
#: serving wants finer sub-100ms resolution)
LATENCY_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)


class _Reservoir:
    """Sorted trailing-window sample store for exact percentiles (the
    registry Histogram keeps cumulative buckets for Prometheus; the p50/p99
    gauges want exact order statistics)."""

    def __init__(self, cap: int = _MAX_SAMPLES):
        self._sorted: List[float] = []
        self._fifo: List[float] = []
        self._cap = cap

    def add(self, v: float) -> None:
        v = float(v)
        if len(self._fifo) >= self._cap:
            old = self._fifo.pop(0)
            idx = bisect.bisect_left(self._sorted, old)
            self._sorted.pop(idx)
        self._fifo.append(v)
        bisect.insort(self._sorted, v)

    def percentile(self, p: float) -> Optional[float]:
        if not self._sorted:
            return None
        idx = min(
            len(self._sorted) - 1, int(round(p * (len(self._sorted) - 1)))
        )
        return self._sorted[idx]


class ServeMetrics:
    """Serving-side instrument bundle over one registry."""

    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        self.ttft = registry.histogram(
            "serve/ttft_s",
            help="time to first token (arrival -> prefill token)",
            buckets=LATENCY_BUCKETS,
        )
        self.tpot = registry.histogram(
            "serve/tpot_s",
            help="time per output token (decode tokens)",
            buckets=LATENCY_BUCKETS,
        )
        self._ttft_samples = _Reservoir()
        self._tpot_samples = _Reservoir()
        self.requests = registry.counter(
            "serve/requests_total", help="requests submitted"
        )
        self.completed = registry.counter(
            "serve/completed_total", help="requests completed"
        )
        self.tokens_out = registry.counter(
            "serve/tokens_out_total", help="generated tokens"
        )
        self.prefills = registry.counter(
            "serve/prefills_total", help="prefill program dispatches"
        )
        self.prefill_chunks = registry.counter(
            "serve/prefill_chunks_total",
            help="chunked-prefill program dispatches (ISSUE 13)",
        )
        self.decode_steps = registry.counter(
            "serve/decode_steps_total", help="decode program dispatches"
        )
        self.decode_steps_ahead = registry.counter(
            "serve/decode_steps_ahead_total",
            help="decode program dispatches made while the step before "
            "was unread (one step in flight: the read, the commit and the "
            "next admission run under the device's work)",
        )
        self.sampled_tokens = registry.counter(
            "serve/sampled_tokens_total",
            help="tokens drawn through the sampling path "
            "(temperature > 0; greedy tokens excluded)",
        )
        # goodput buckets (sums-to-wall: queue = wall - prefill - decode)
        self.prefill_s = registry.counter(
            "serve/goodput_prefill_s_total",
            help="serve wall seconds spent in prefill dispatch",
        )
        self.decode_s = registry.counter(
            "serve/goodput_decode_s_total",
            help="serve wall seconds spent in decode dispatch",
        )
        self.queue_s = registry.counter(
            "serve/goodput_queue_s_total",
            help="serve wall seconds spent queued/idle (wall - prefill - decode)",
        )
        self.queue_depth = registry.gauge(
            "serve/queue_depth", help="requests waiting for a slot"
        )
        self.active_seqs = registry.gauge(
            "serve/active_seqs", help="occupied decode slots"
        )
        self.batch_fill = registry.gauge(
            "serve/batch_fill", help="active_seqs / max_seqs"
        )
        self.kv_blocks_used = registry.gauge(
            "serve/kv_blocks_used", help="KV blocks owned by live requests"
        )
        self.kv_occupancy = registry.gauge(
            "serve/kv_block_occupancy",
            help="owned / allocatable KV blocks",
        )
        self.quant_compression = registry.gauge(
            "serve/quant_compression",
            help="param bytes fp / param bytes as-served",
        )
        self.cache_bytes_per_token = registry.gauge(
            "serve/cache_bytes_per_token",
            help="cached bytes a token over all layers and planes, as the "
            "model describes its cache",
        )
        self._p = {
            "ttft_p50": registry.gauge("serve/ttft_p50_s"),
            "ttft_p99": registry.gauge("serve/ttft_p99_s"),
            "tpot_p50": registry.gauge("serve/tpot_p50_s"),
            "tpot_p99": registry.gauge("serve/tpot_p99_s"),
        }
        # speculative counters (ISSUE 17): created by enable_speculative()
        # so a non-speculative engine's registry (and JSONL records) carry
        # zero speculative series
        self.spec_active = False
        self.spec_draft_tokens = None
        self.spec_accepted_tokens = None
        # cost-card counters (ISSUE 18): created by enable_cost() so an
        # engine without ServeConfig.cost_cards registers zero serve/cost
        # series (same default-OFF contract as the speculative block)
        self.cost_active = False
        self.cost_flops = None
        self.cost_bytes = None
        # expert-layer load: created by enable_experts() for a model that
        # holds routed experts, so no other engine registers the series
        self.expert_assignments = None
        self.expert_load_max_over_mean = None
        self.expert_weight_passes = None
        self.zero_expert_share = None
        # per-slot recurrent state: created by enable_state() for a model
        # whose cache keeps one, so no other engine registers the series
        self.state_passes = None

    def enable_experts(self, zero_compute: bool = False) -> None:
        """Arm the expert-layer instruments, called at engine construction
        for a model whose ``experts_held`` is above 0:
        assignments the held experts computed, over all expert layers and
        decode steps, the last decode step's busiest held expert over
        the mean (1.0: even), and the bytes of held weight its grouped
        products read over the bytes held (1.0: each streamed once); with
        ``zero_compute`` (a router with zero-compute outputs) also the
        share of the live tokens' assignments that went to those."""
        if self.expert_assignments is not None:
            return
        if zero_compute:
            self.zero_expert_share = self.registry.gauge(
                "serve/zero_expert_share",
                help="last decode step: the live tokens' assignments to "
                "zero-compute experts over all their assignments (all "
                "expert layers)",
            )
        self.expert_assignments = self.registry.counter(
            "serve/expert_assignments_total",
            help="token-to-expert assignments computed by the held experts "
            "in decode steps (all expert layers)",
        )
        self.expert_load_max_over_mean = self.registry.gauge(
            "serve/expert_load_max_over_mean",
            help="last decode step: busiest held expert's assignments over "
            "the mean of the held experts",
        )
        self.expert_weight_passes = self.registry.gauge(
            "serve/expert_weight_passes",
            help="last decode step: bytes of held expert weight the grouped "
            "products read over the bytes they hold",
        )

    def enable_state(self) -> None:
        """Arm the per-slot-state instrument, called at engine construction
        for a model whose cache keeps a recurrent state a slot: the bytes
        of state the last decode step's kernels moved over the bytes its
        live slots' state holds once each way (1.0: each read once and
        written once; ``ops/delta_rule.py`` ``state_passes``)."""
        if self.state_passes is not None:
            return
        self.state_passes = self.registry.gauge(
            "serve/state_passes",
            help="last decode step: bytes of per-slot state the state "
            "layers' kernels moved over the bytes the live slots' state "
            "holds, once each way",
        )

    def enable_speculative(self) -> None:
        """Arm the speculative-decoding instruments (ISSUE 17) — called at
        engine construction when ``ServeConfig.speculative_k`` is set.
        ``accepted / drafted`` is the acceptance rate;
        ``tokens_out / decode_steps`` the accepted tokens per dispatch."""
        if self.spec_active:
            return
        self.spec_active = True
        self.spec_draft_tokens = self.registry.counter(
            "serve/spec_draft_tokens_total",
            help="draft tokens scored by verify dispatches (ISSUE 17)",
        )
        self.spec_accepted_tokens = self.registry.counter(
            "serve/spec_accepted_tokens_total",
            help="draft tokens accepted into the output stream (ISSUE 17)",
        )

    def enable_cost(self) -> None:
        """Arm the per-dispatch cost counters (ISSUE 18) — called by the
        :class:`~stoke_tpu.serving.roofline.ServeCostObservatory` an
        engine with ``ServeConfig.cost_cards`` constructs.  The counters
        are the SAME registry series the observatory's ``CostCardCache``
        (``counter_prefix="serve/cost"``) accumulates into — registry
        instruments are cached by name — so ``cost_flops.value`` is the
        analytic-FLOPs-dispatched total the recombination tests pin."""
        if self.cost_active:
            return
        self.cost_active = True
        self.cost_flops = self.registry.counter(
            "serve/cost/flops_total",
            help="analytic FLOPs dispatched",
        )
        self.cost_bytes = self.registry.counter(
            "serve/cost/bytes_total",
            help="analytic bytes accessed by dispatches",
        )

    # ------------------------------ feeds ------------------------------ #

    def observe_ttft(self, seconds: float) -> None:
        self.ttft.observe(seconds)
        self._ttft_samples.add(seconds)

    def observe_tpot(self, seconds: float) -> None:
        self.tpot.observe(seconds)
        self._tpot_samples.add(seconds)

    def latency_percentiles(self) -> Dict[str, Optional[float]]:
        """Exact order statistics of the trailing reservoirs — the public
        accessor the engine summary reads (the
        reservoirs themselves are an implementation detail)."""
        return {
            "ttft_p50_s": self._ttft_samples.percentile(0.50),
            "ttft_p99_s": self._ttft_samples.percentile(0.99),
            "tpot_p50_s": self._tpot_samples.percentile(0.50),
            "tpot_p99_s": self._tpot_samples.percentile(0.99),
        }

    def refresh_percentiles(self) -> None:
        for name, v in self.latency_percentiles().items():
            if v is not None:
                self._p[name[: -len("_s")]].set(v)

    # --------------------------- JSONL fields --------------------------- #

    def event_fields(self) -> Dict[str, object]:
        """The ``serve/*`` block of one JSONL step event.  The goodput
        counters already sum to the serve wall clock — the engine derives
        the queue bucket as ``wall - prefill - decode`` when it refreshes
        gauges (``ServingEngine._refresh_gauges``), so this is a pure
        registry read."""
        self.refresh_percentiles()
        pct = self.latency_percentiles()
        out = {
            "serve/requests": self.requests.value,
            "serve/completed": self.completed.value,
            "serve/tokens_out": self.tokens_out.value,
            "serve/queue_depth": self.queue_depth.value,
            "serve/active_seqs": self.active_seqs.value,
            "serve/batch_fill": self.batch_fill.value,
            "serve/kv_blocks_used": self.kv_blocks_used.value,
            "serve/kv_block_occupancy": self.kv_occupancy.value,
            "serve/ttft_p50_s": pct["ttft_p50_s"],
            "serve/ttft_p99_s": pct["ttft_p99_s"],
            "serve/tpot_p50_s": pct["tpot_p50_s"],
            "serve/tpot_p99_s": pct["tpot_p99_s"],
            "serve/goodput_queue_s": self.queue_s.value,
            "serve/goodput_prefill_s": self.prefill_s.value,
            "serve/goodput_decode_s": self.decode_s.value,
            "serve/prefill_chunks": self.prefill_chunks.value,
            "serve/sampled_tokens": self.sampled_tokens.value,
            "serve/quant_compression": (
                self.quant_compression.value
                if self.quant_compression.has_value
                else None
            ),
        }
        if self.spec_active:
            # speculative block (ISSUE 17): absent — not null — without a
            # speculative config, like the serve/slo_* block
            out["serve/spec_draft_tokens"] = self.spec_draft_tokens.value
            out["serve/spec_accepted_tokens"] = (
                self.spec_accepted_tokens.value
            )
        return out
