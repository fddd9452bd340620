"""Step-time attribution & goodput accounting (ISSUE 4 tentpole).

The telemetry layer (ISSUE 1) answers "how fast is the step" and the
health monitor (ISSUE 3) answers "is the run still healthy"; this module
answers **"where is the time going, and how much of the hardware are we
actually using"** — live, per window, while training:

- :class:`CostCard` / :class:`CostCardCache` — one XLA cost-analysis per
  compiled step program (keyed by the engine's existing program+shape
  signature): analytic FLOPs, bytes accessed, and the roofline-optimal
  step time against a configured peak.  The cards generalize the old
  offline ``Stoke.estimate_step_flops`` probe (now a thin wrapper) and
  feed per-dispatch FLOP/byte counters, so achieved TFLOP/s works across
  all four step paths (apply / fused / window / multi) and any mix of
  them.
- :class:`AttributionMonitor` — per-window gauges derived from the
  registry deltas the telemetry pipeline already collects: achieved
  TFLOP/s, **MFU** against ``AttributionConfig.peak_tflops``, HBM
  bandwidth utilization, and a **bound classification** (compute /
  memory / comm / host) from step wall time + comm bytes-on-wire
  (ISSUE 2) + loader wait (ISSUE 1).
- **Goodput ledger** — buckets total wall clock into productive-compute
  vs compile vs recompile vs loader-stall vs checkpoint-IO vs halt time
  (MLPerf-scale TPU practice, arXiv:1909.09756: utilization and goodput
  are the primary scaling lens).  Emitted per window in the JSONL step
  events and Prometheus, summarized at end of run
  (:meth:`AttributionMonitor.goodput_summary`), and included in
  flight-recorder post-mortem bundles.
- **Anomaly-triggered profiler capture** — when MFU drops below a
  threshold or the step wall time z-score spikes, capture a bounded
  number of xprof trace windows into ``ProfilerConfig.trace_dir`` so the
  device timeline of the bad window is on disk before anyone asks.
  Registered as a health detector (:class:`AutoCaptureDetector`) when a
  ``HealthConfig`` is present, so captures surface in the anomaly stream
  and post-mortem ring.

Everything is host-side bookkeeping over programs the engine compiles
anyway: with ``AttributionConfig`` absent nothing here runs and the
compiled step programs are bit-identical to a build without the feature;
with it enabled the only extra device-adjacent work is one
``cost_analysis`` per program signature (on the already-traced lowering —
no second compile on runtimes that support unoptimized-HLO cost
analysis).
"""

from __future__ import annotations

import threading
import time
import warnings
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from stoke_tpu.telemetry.health import Anomaly, Detector, _RunningStats

#: goodput bucket names, in emission order.  ``productive`` is the
#: remainder after the measured overheads — Google-goodput convention:
#: productive time = total wall clock minus accounted losses.
GOODPUT_BUCKETS: Tuple[str, ...] = (
    "productive", "compile", "recompile", "loader", "checkpoint", "halt",
)

#: bound classifications the per-window attribution can emit
BOUND_CLASSES: Tuple[str, ...] = ("compute", "memory", "comm", "host")

#: backends that reported "no cost analysis" — warn once per backend and
#: remember the negative result so every later probe/estimate call is a
#: silent no-op instead of a fresh lower + warning (ISSUE 4 satellite:
#: estimate_step_flops used to warn on every call)
_COST_UNAVAILABLE_BACKENDS: set = set()
_cost_warn_lock = threading.Lock()


def _cost_dict(obj) -> Optional[Dict[str, float]]:
    """Normalize a jax cost-analysis return (dict, or a 1-list of dicts on
    older versions) to a plain dict, or None when empty."""
    if isinstance(obj, (list, tuple)):
        obj = obj[0] if obj else None
    if not obj:
        return None
    return dict(obj)


def cost_analysis_of(fn, *args, backend: Optional[str] = None):
    """XLA cost analysis of jitted ``fn`` at ``args``: the one shared
    funnel behind CostCards and ``Stoke.estimate_step_flops``.

    Prefers ``Lowered.cost_analysis()`` (no second compile); falls back
    to compiling when the lowering cannot answer.  Returns the raw cost
    dict (``flops`` / ``bytes accessed`` keys) or None when the backend
    reports no cost analysis — in which case it warns ONCE per backend
    and caches the negative result.
    """
    if backend is None:
        try:
            import jax

            backend = jax.default_backend()
        except Exception:  # pragma: no cover - jax-free analysis callers
            backend = "unknown"
    if backend in _COST_UNAVAILABLE_BACKENDS:
        return None
    # tracing errors are USER errors (bad loss structure, shape mismatch)
    # and propagate — only a backend declining to report cost analysis
    # lands in the warn-once negative cache
    lowered = fn.lower(*args)
    cost = None
    try:
        cost = _cost_dict(lowered.cost_analysis())
    except Exception:
        cost = None
    if cost is None:
        # unoptimized-HLO analysis unavailable: pay the compile once.
        # Real compile failures (bad shardings, OOM) raise — same
        # contract the pre-refactor estimate_step_flops documented.
        compiled = lowered.compile()
        try:
            cost = _cost_dict(compiled.cost_analysis())
        except Exception as e:
            _note_cost_unavailable(backend, e)
            return None
    if not cost:
        _note_cost_unavailable(backend, "empty cost analysis")
        return None
    # NOTE: a dict WITHOUT a "flops" key is a program property (XLA omits
    # zero-valued properties, so a zero-FLOP program reports none), not a
    # backend one — return it (callers treat missing flops as 0) instead
    # of blacklisting the whole backend for every later program
    return cost


def memory_analysis_stats(fn, *args) -> Optional[Dict[str, float]]:
    """Component breakdown of jitted ``fn``'s compiled
    ``memory_analysis()`` at ``args``: argument / output / temp /
    generated-code / alias bytes plus the derived ``peak_bytes``
    (argument + output + temp - aliased) — the ISSUE 19 per-program
    memory card.  REQUIRES a compile, so callers pay it only on explicit
    opt-in (``CostCardCache(memory_analysis=True)``); ``None`` whenever
    the backend or jax version cannot answer."""
    try:
        stats = fn.lower(*args).compile().memory_analysis()
        if stats is None:
            return None
        out = {
            "argument_bytes": float(stats.argument_size_in_bytes),
            "output_bytes": float(stats.output_size_in_bytes),
            "temp_bytes": float(stats.temp_size_in_bytes),
            "alias_bytes": float(stats.alias_size_in_bytes),
            "generated_code_bytes": float(
                getattr(stats, "generated_code_size_in_bytes", 0.0)
            ),
        }
        out["peak_bytes"] = (
            out["argument_bytes"]
            + out["output_bytes"]
            + out["temp_bytes"]
            - out["alias_bytes"]
        )
        return out
    except Exception:
        return None


def memory_analysis_bytes(fn, *args) -> Optional[float]:
    """Best-effort peak-HBM estimate of jitted ``fn`` at ``args`` from
    the compiled executable's ``memory_analysis()`` (argument + output +
    temp, minus donated aliases).  Unlike :func:`cost_analysis_of` this
    REQUIRES a compile, so callers pay it only on explicit opt-in (the
    serve roofline observatory's per-program cards); ``None`` whenever
    the backend or jax version cannot answer."""
    stats = memory_analysis_stats(fn, *args)
    if stats is None:
        return None
    total = stats["peak_bytes"]
    return total if total > 0 else None


def _note_cost_unavailable(backend: str, reason) -> None:
    with _cost_warn_lock:
        if backend in _COST_UNAVAILABLE_BACKENDS:
            return
        _COST_UNAVAILABLE_BACKENDS.add(backend)
    warnings.warn(
        f"Stoke -- cost_analysis unavailable on backend {backend!r}: "
        f"{reason!r}; FLOPs/MFU attribution disabled for this backend"
    )


@dataclass
class CostCard:
    """Analytic cost of ONE compiled step program (one dispatch).

    ``steps`` is how many optimizer steps a single dispatch of this
    program advances (n for a ``train_steps`` segment, 1 for apply /
    boundary ``train_step``, 0 for non-boundary micro-steps — their
    FLOPs still count toward achieved-TFLOP/s, they just do not complete
    a step on their own).
    """

    program: str                    # "apply" | "fused" | "accum" | ...
    flops: float                    # per dispatch
    bytes_accessed: Optional[float] # per dispatch (None when unreported)
    steps: int                      # optimizer steps per dispatch
    optimal_time_s: Optional[float] = None  # roofline bound per dispatch
    #: compiled peak-HBM estimate (memory_analysis; None unless a caller
    #: opted into the extra AOT compile — see memory_analysis_bytes)
    peak_hbm_bytes: Optional[float] = None
    #: memory_analysis component breakdown (argument/output/temp/alias/
    #: generated-code/peak bytes; same opt-in — the ISSUE 19 memory
    #: observatory's per-program card)
    mem_stats: Optional[Dict[str, float]] = None

    @property
    def intensity(self) -> Optional[float]:
        """Arithmetic intensity (FLOPs per byte accessed) — the roofline
        x-axis; None when XLA did not report bytes."""
        if not self.bytes_accessed or self.flops <= 0:
            return None
        return self.flops / self.bytes_accessed

    def to_dict(self) -> Dict[str, Any]:
        return {
            "program": self.program,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "steps_per_dispatch": self.steps,
            "optimal_time_s": self.optimal_time_s,
            "intensity": self.intensity,
            "peak_hbm_bytes": self.peak_hbm_bytes,
        }

    @classmethod
    def from_cost(cls, cost: Dict[str, Any], program: str, steps: int,
                  peak_tflops: float = 0.0,
                  peak_hbm_gbps: float = 0.0) -> "CostCard":
        """The one cost-dict → CostCard conversion (XLA omits zero-valued
        properties, so a missing "flops" key means 0) — shared by the
        live cache and ``Stoke.estimate_step_cost`` so the offline
        estimate can never diverge from the live gauges."""
        flops = float(cost.get("flops") or 0.0)
        bytes_acc = cost.get("bytes accessed")
        bytes_acc = float(bytes_acc) if bytes_acc else None
        return cls(
            program,
            flops,
            bytes_acc,
            steps,
            optimal_time_s=roofline_time_s(
                flops, bytes_acc, peak_tflops, peak_hbm_gbps
            ),
        )


def roofline_time_s(
    flops: float,
    bytes_accessed: Optional[float],
    peak_tflops: float,
    peak_hbm_gbps: float = 0.0,
) -> Optional[float]:
    """Roofline-optimal execution time: max of the compute-limited and
    (when a bandwidth peak is configured) the memory-limited bound."""
    if peak_tflops <= 0:
        return None
    t = flops / (peak_tflops * 1e12)
    if bytes_accessed and peak_hbm_gbps > 0:
        t = max(t, bytes_accessed / (peak_hbm_gbps * 1e9))
    return t


def roofline_summary(
    flops: Optional[float], step_seconds: float, peak_tflops: float
) -> Dict[str, Optional[float]]:
    """Achieved TFLOP/s + fraction-of-peak from a per-step FLOPs count
    and a measured step time — the arithmetic behind the live MFU
    gauge."""
    if not flops or step_seconds <= 0:
        return {"achieved_tflops": None, "mfu": None}
    achieved = flops / step_seconds / 1e12
    return {
        "achieved_tflops": achieved,
        "mfu": achieved / peak_tflops if peak_tflops > 0 else None,
    }


def classify_bound(
    *,
    wall_s: float,
    compute_optimal_s: Optional[float],
    memory_optimal_s: Optional[float],
    comm_s: Optional[float],
    host_s: float,
    host_fraction: float = 0.5,
    dominant_fraction: float = 0.4,
) -> Optional[str]:
    """Classify one window as compute/memory/comm/host-bound from its
    wall time and the per-resource time estimates (pure function —
    unit-tested on synthetic timings).

    Host time (loader wait + non-overlapped dispatch) wins when it alone
    covers ``host_fraction`` of the wall clock — the device is starving,
    nothing else matters.  Otherwise the resource whose optimal/estimated
    time is largest wins, provided it explains at least
    ``dominant_fraction`` of the wall clock; below that nothing dominates
    and the window is host/overhead-bound by elimination.
    """
    if wall_s <= 0:
        return None
    if host_s / wall_s >= host_fraction:
        return "host"
    candidates = {
        "compute": compute_optimal_s or 0.0,
        "memory": memory_optimal_s or 0.0,
        "comm": comm_s or 0.0,
    }
    bound = max(candidates, key=lambda k: candidates[k])
    if candidates[bound] <= 0 or candidates[bound] / wall_s < dominant_fraction:
        return "host"
    return bound


class CostCardCache:
    """One cost-analysis per (program, shape-signature): the engine calls
    :meth:`note_dispatch` on every compiled-program invocation; the first
    call per key runs the analysis (on the engine's own jitted function
    with the live args) and every call adds the card's analytic FLOPs /
    bytes to the registry counters the per-window attribution deltas.
    """

    #: cap on cached cards, mirroring the engine's _MAX_SHAPE_SIGS bound:
    #: pathological shape churn must not retrace/cost-analyze per new
    #: signature forever nor grow host memory without bound.  Beyond the
    #: cap, unseen signatures reuse the program's most recent card (shape
    #: churn rarely changes per-dispatch cost much) without analysis.
    _MAX_CARDS = 1024

    def __init__(self, registry, peak_tflops: float = 0.0,
                 peak_hbm_gbps: float = 0.0, counter_prefix: str = "attr",
                 memory_analysis: bool = False):
        self.registry = registry
        self.peak_tflops = float(peak_tflops)
        self.peak_hbm_gbps = float(peak_hbm_gbps)
        #: registry namespace for the per-dispatch counters — "attr" for
        #: the training monitor (wire-stable names), "serve/cost" for the
        #: ISSUE 18 serve roofline observatory riding the same machinery
        self.counter_prefix = counter_prefix
        #: opt-in compiled peak-HBM attachment (one extra AOT compile per
        #: distinct program signature — never on by default: training
        #: attribution stays lowering-only)
        self.memory_analysis = bool(memory_analysis)
        self.cards: Dict[Any, CostCard] = {}
        self.cost_analysis_runs = 0  # test hook: one per distinct key
        self._program_fallback: Dict[str, CostCard] = {}
        self._lock = threading.Lock()
        registry.counter(
            f"{counter_prefix}/flops_total",
            help="analytic FLOPs dispatched",
        )
        registry.counter(
            f"{counter_prefix}/bytes_total",
            help="analytic bytes accessed by dispatches",
        )
        registry.counter(
            f"{counter_prefix}/optimal_s_total",
            help="roofline-optimal seconds of dispatched programs",
        )
        registry.counter(
            f"{counter_prefix}/cost_cards_total",
            help="distinct programs analyzed",
        )

    def note_dispatch(self, key, program: str, fn, args: tuple,
                      steps: int) -> Optional[CostCard]:
        """Called by the engine per dispatch.  ``key`` is the engine's
        program cache key + input-shape signature; ``fn`` the jitted
        function about to run; ``args`` its positional arguments."""
        card = self.cards.get(key)
        if card is None:
            if (
                len(self.cards) >= self._MAX_CARDS
                and program in self._program_fallback
            ):
                # bounded under shape churn: no retrace, no new entry —
                # account the program's last known cost instead.  A
                # program kind never analyzed before the cap filled still
                # gets its one analysis (a handful of kinds exist), so
                # its FLOPs are never silently dropped.
                card = self._program_fallback[program]
            else:
                card = self._analyze(key, program, fn, args, steps)
        if card is None:
            return None
        prefix = self.counter_prefix
        self.registry.counter(f"{prefix}/flops_total").inc(card.flops)
        if card.bytes_accessed:
            self.registry.counter(f"{prefix}/bytes_total").inc(
                card.bytes_accessed
            )
        if card.optimal_time_s:
            self.registry.counter(f"{prefix}/optimal_s_total").inc(
                card.optimal_time_s
            )
        return card

    def _analyze(self, key, program, fn, args, steps) -> Optional[CostCard]:
        with self._lock:
            card = self.cards.get(key)
            if card is not None:
                return card
            self.cost_analysis_runs += 1
            try:
                cost = cost_analysis_of(fn, *args)
            except Exception as e:
                # the REAL dispatch of the same program/args is about to
                # run and will surface any genuine error; attribution
                # bookkeeping must never be what kills a training step
                warnings.warn(
                    f"Stoke -- cost analysis of program {program!r} "
                    f"failed: {e!r}; attribution skips it"
                )
                cost = None
            if cost is None:
                # negative result IS the cached result: a backend without
                # cost analysis must not re-lower on every dispatch
                card = CostCard(program, 0.0, None, steps)
                # the zero card is also the program's fallback — without
                # one, the _MAX_CARDS bound would never engage for this
                # program and shape churn would grow the dict forever
                self._program_fallback.setdefault(program, card)
            else:
                card = CostCard.from_cost(
                    cost, program, steps, self.peak_tflops,
                    self.peak_hbm_gbps,
                )
                if self.memory_analysis:
                    card.mem_stats = memory_analysis_stats(fn, *args)
                    if card.mem_stats is not None:
                        peak = card.mem_stats["peak_bytes"]
                        card.peak_hbm_bytes = peak if peak > 0 else None
                self.registry.counter(
                    f"{self.counter_prefix}/cost_cards_total"
                ).inc()
                self._program_fallback[program] = card
            self.cards[key] = card
            return card

    def last_cards(self, n: int = 8) -> List[Dict[str, Any]]:
        """Most recently analyzed cards (insertion-ordered dict), for the
        post-mortem bundle: utilization context at time of death."""
        return [c.to_dict() for c in list(self.cards.values())[-n:] if c.flops]


class AutoCaptureDetector(Detector):
    """Health-registry adapter for the profiler auto-capture (ISSUE 4):
    when the attribution monitor triggered a capture since the last
    health observation, surface it as an anomaly (action from
    ``AttributionConfig.capture_action``) so captures land in the anomaly
    counters, the flight-recorder ring, and post-mortem bundles."""

    name = "attribution_capture"

    def __init__(self, monitor: "AttributionMonitor", action: str = "record"):
        super().__init__(action)
        self.monitor = monitor

    def check(self, step, sentinels, ctx) -> Optional[Anomaly]:
        trigger = self.monitor.consume_trigger()
        if trigger is None:
            return None
        return self._fire(
            step,
            f"profiler auto-capture #{trigger['capture']} triggered "
            f"({trigger['reason']}) -> {trigger['trace_dir']}",
            value=trigger.get("value"),
        )


class AttributionMonitor:
    """Owns the cost-card cache, the per-window gauges, the goodput
    ledger, and the auto-capture state.  The facade constructs one per
    run when an ``AttributionConfig`` is supplied, attaches the cache to
    the engine and itself to the telemetry pipeline; ``record_step``
    calls :meth:`window_stats` with the window wall time and the already-
    collected registry deltas."""

    def __init__(self, cfg, registry, *, compile_tracker=None,
                 trace_dir: Optional[str] = None):
        self.cfg = cfg
        self.registry = registry
        self.compile_tracker = compile_tracker
        self.trace_dir = trace_dir
        self.cost_cards = CostCardCache(
            registry, cfg.peak_tflops, cfg.peak_hbm_gbps
        )
        self._last: Dict[str, float] = {}
        self._goodput_totals: Dict[str, float] = {
            b: 0.0 for b in GOODPUT_BUCKETS
        }
        # compile split (ISSUE 6 satellite): fresh backend compiles vs
        # AOT-cache warm-start loads, summing to the compile+recompile
        # bucket totals
        self._compile_fresh_total = 0.0
        self._compile_cached_total = 0.0
        self._wall_total = 0.0
        # FLOPs covered by RECORDED windows only — the aggregate-MFU
        # numerator.  The raw attr/flops_total counter also carries
        # dispatches after the last record, whose wall time is not in
        # _wall_total; dividing it by recorded wall would inflate MFU.
        self._flops_recorded = 0.0
        self._windows = 0
        self._step_stats = _RunningStats(cfg.ema_alpha)
        # auto-capture state
        self.captures = 0
        self._capturing = False
        self._capture_stop_at: Optional[int] = None
        self._pending_trigger: Optional[Dict[str, Any]] = None
        self._capture_dirs: List[str] = []
        # manual (ops-plane) captures run on scraper threads while the
        # step path runs on_step: the lock orders start/stop transitions
        # and the flag keeps on_step from closing a wall-clock-bounded
        # manual window at its step-count boundary
        self._capture_lock = threading.Lock()
        self._manual_capture = False
        for b in GOODPUT_BUCKETS:
            registry.counter(
                f"goodput/{b}_s_total", help=f"wall seconds: {b}"
            )
        registry.counter(
            "goodput/compile_fresh_s_total",
            help="compile-bucket seconds from fresh XLA backend compiles",
        )
        registry.counter(
            "goodput/compile_cached_s_total",
            help="compile-bucket seconds from AOT-cache warm-start loads",
        )

    # ------------------------------------------------------------------ #
    # per-window attribution
    # ------------------------------------------------------------------ #

    def _delta(self, name: str) -> float:
        inst = self.registry.get(name)
        now = inst.value if inst is not None else 0.0
        prev = self._last.get(name, 0.0)
        self._last[name] = now
        return max(0.0, now - prev)

    def window_stats(
        self,
        *,
        step: int,
        wall_s: Optional[float],
        host_dispatch_s: float,
        loader_wait_s: float,
        ckpt_io_s: float,
        comm_bytes_onwire: Optional[float],
    ) -> Dict[str, Any]:
        """Compute one window's attribution record from the registry
        deltas.  Returns the JSONL-field dict (achieved_tflops / mfu /
        hbm_bw_util / bound / goodput_* — all nullable)."""
        flops = self._delta("attr/flops_total")
        bytes_acc = self._delta("attr/bytes_total")
        # compile split (ISSUE 6 satellite): the compile bucket carries
        # fresh backend-compile seconds (jax/compile_time_s — full XLA
        # codegen; on non-CPU backends a cache-served load also lands
        # here as a small "fresh" duration, a documented imprecision)
        # plus the warm-start overhead cache hits actually paid
        # (compile_cache/hit_s_total: lowering + ledger lookup, measured
        # strictly before dispatch so step execution can never inflate
        # the bucket).  A warm start therefore shows a small cached
        # share where the cold run showed seconds of fresh codegen.
        compile_fresh_dt = self._delta("jax/compile_time_s")
        compile_cached_dt = self._delta("compile_cache/hit_s_total")
        compile_dt = compile_fresh_dt + compile_cached_dt
        recompiles_dt = self._delta("jax/recompiles_total")
        halt_dt = self._delta("health/halt_s")
        out: Dict[str, Any] = {
            "achieved_tflops": None, "mfu": None, "hbm_bw_util": None,
            "bound": None,
            "goodput_compile_fresh_s": None,
            "goodput_compile_cached_s": None,
        }
        for b in GOODPUT_BUCKETS:
            out[f"goodput_{b}_s"] = None
        if wall_s is None or wall_s <= 0:
            return out

        # --- utilization gauges ---
        rl = roofline_summary(flops, wall_s, self.cfg.peak_tflops)
        out["achieved_tflops"] = rl["achieved_tflops"]
        out["mfu"] = rl["mfu"]
        if bytes_acc and self.cfg.peak_hbm_gbps > 0:
            out["hbm_bw_util"] = (
                bytes_acc / wall_s / (self.cfg.peak_hbm_gbps * 1e9)
            )

        # --- bound classification ---
        comm_s = None
        if comm_bytes_onwire and self.cfg.ici_gbps > 0:
            comm_s = comm_bytes_onwire / (self.cfg.ici_gbps * 1e9)
        compute_s = (
            flops / (self.cfg.peak_tflops * 1e12)
            if self.cfg.peak_tflops > 0 else None
        )
        memory_s = (
            bytes_acc / (self.cfg.peak_hbm_gbps * 1e9)
            if bytes_acc and self.cfg.peak_hbm_gbps > 0 else None
        )
        # host leg = loader wait + host dispatch time (classify_bound's
        # documented contract).  NOTE: on synchronous backends (the CPU
        # simulator) the facade phase timers contain the device execution
        # itself, so host_s ~ wall and the classification reads "host" —
        # honest there; on TPU, dispatch is async and host_s only grows
        # when the host genuinely cannot keep the device fed.
        out["bound"] = classify_bound(
            wall_s=wall_s,
            compute_optimal_s=compute_s,
            memory_optimal_s=memory_s,
            comm_s=comm_s,
            host_s=loader_wait_s + host_dispatch_s,
        )

        # --- goodput ledger ---
        overheads = {
            "compile": compile_dt if recompiles_dt == 0 else 0.0,
            "recompile": compile_dt if recompiles_dt > 0 else 0.0,
            "loader": loader_wait_s,
            "checkpoint": ckpt_io_s,
            "halt": halt_dt,
        }
        total_over = sum(overheads.values())
        if total_over > wall_s > 0:
            # concurrent losses (e.g. a compile overlapping a loader
            # stall) cannot exceed the window: scale proportionally so
            # the buckets remain a partition of wall clock
            scale = wall_s / total_over
            overheads = {k: v * scale for k, v in overheads.items()}
            total_over = wall_s
        buckets = {"productive": max(0.0, wall_s - total_over), **overheads}
        for b, v in buckets.items():
            out[f"goodput_{b}_s"] = v
            self._goodput_totals[b] += v
            self.registry.counter(f"goodput/{b}_s_total").inc(v)
        # fresh/cached split of the compile seconds this window accounted
        # (whether they landed in the compile or the recompile bucket):
        # scaled by the same factor the buckets were, so the split sums to
        # the bucketed compile time
        accounted = overheads["compile"] + overheads["recompile"]
        frac = accounted / compile_dt if compile_dt > 0 else 0.0
        fresh = compile_fresh_dt * frac
        cached = compile_cached_dt * frac
        out["goodput_compile_fresh_s"] = fresh
        out["goodput_compile_cached_s"] = cached
        self._compile_fresh_total += fresh
        self._compile_cached_total += cached
        self.registry.counter("goodput/compile_fresh_s_total").inc(fresh)
        self.registry.counter("goodput/compile_cached_s_total").inc(cached)
        self._wall_total += wall_s
        self._flops_recorded += flops
        self._windows += 1
        self.registry.gauge("attr/mfu").set(out["mfu"] or 0.0)
        self.registry.gauge("attr/achieved_tflops").set(
            out["achieved_tflops"] or 0.0
        )

        # --- capture triggers ---
        self._maybe_trigger_capture(step, out["mfu"], wall_s)
        return out

    def goodput_summary(self) -> Dict[str, Any]:
        """End-of-run (or any-time) cumulative goodput accounting:
        seconds and fraction per bucket, plus the utilization aggregate.
        ``Stoke.wall_clock_breakdown`` merges this in as ``goodput/*``
        entries when attribution is on."""
        wall = self._wall_total
        out: Dict[str, Any] = {
            "wall_s": wall,
            "windows": self._windows,
            "goodput_fraction": (
                self._goodput_totals["productive"] / wall if wall > 0 else None
            ),
        }
        for b in GOODPUT_BUCKETS:
            out[f"{b}_s"] = self._goodput_totals[b]
        # compile split + reclaimed seconds (ISSUE 6): cached warm-start
        # loads vs fresh compiles, and the original compile seconds the
        # AOT cache's hits avoided paying at all
        out["compile_fresh_s"] = self._compile_fresh_total
        out["compile_cached_s"] = self._compile_cached_total
        saved = self.registry.get("compile_cache/saved_s_total")
        out["compile_saved_s"] = saved.value if saved is not None else 0.0
        if wall > 0:
            out.update(roofline_summary(
                self._flops_recorded, wall, self.cfg.peak_tflops
            ))
        out["captures"] = self.captures
        out["capture_dirs"] = list(self._capture_dirs)
        return out

    # ------------------------------------------------------------------ #
    # anomaly-triggered profiler capture
    # ------------------------------------------------------------------ #

    def _maybe_trigger_capture(self, step: int, mfu: Optional[float],
                               wall_s: float) -> None:
        cfg = self.cfg
        z = self._step_stats.zscore(wall_s)
        warm = self._step_stats.count >= cfg.capture_warmup_windows
        self._step_stats.update(wall_s)
        if not cfg.auto_capture or self._capturing:
            return
        if self.captures >= cfg.max_captures:
            return
        reason = value = None
        if (
            warm
            and cfg.capture_step_zscore > 0
            and z is not None
            and z > cfg.capture_step_zscore
        ):
            reason, value = f"step-time z={z:.1f}", wall_s
        elif (
            warm
            and cfg.capture_mfu_below > 0
            and mfu is not None
            and mfu < cfg.capture_mfu_below
        ):
            reason, value = f"mfu {mfu:.4f} < {cfg.capture_mfu_below}", mfu
        if reason is None:
            return
        self._start_capture(step, reason, value)

    def _start_capture(self, step: int, reason: str, value) -> None:
        import os

        if self.trace_dir is None:  # status-validated, but stay safe
            return
        safe = "".join(
            c if (c.isalnum() or c in "-_=.") else "-" for c in reason
        )[:48]
        with self._capture_lock:
            if self._capturing:  # a manual capture raced in; defer
                return
            target = os.path.join(
                self.trace_dir,
                f"auto-capture-{self.captures + 1}-step{step}-{safe}",
            )
            try:
                import jax

                jax.profiler.start_trace(target)
            except Exception as e:  # unavailable profiler can't kill a run
                warnings.warn(
                    f"Stoke -- attribution auto-capture failed to start: "
                    f"{e!r}"
                )
                return
            # count only traces that actually started: a failing profiler
            # must neither burn the max_captures budget nor report
            # phantom captures
            self.captures += 1
            self._capturing = True
            self._capture_stop_at = step + max(1, self.cfg.capture_steps)
            self._capture_dirs.append(target)
        self.registry.counter(
            "attr/captures_total", help="anomaly-triggered xprof captures"
        ).inc()
        self._pending_trigger = {
            "capture": self.captures,
            "reason": reason,
            "value": None if value is None else float(value),
            "trace_dir": target,
            "step": step,
        }

    def on_step(self, optimizer_steps: int) -> None:
        """Per-optimizer-step hook (the facade calls this from every step
        boundary): closes an in-flight capture window once it covered
        ``capture_steps`` steps.  A MANUAL capture (ops-plane /profile)
        is wall-clock-bounded by its own thread, never by step count —
        the flag keeps this hook's step boundary from truncating it."""
        with self._capture_lock:
            if self._manual_capture:
                return
            if self._capturing and (
                self._capture_stop_at is None
                or optimizer_steps >= self._capture_stop_at
            ):
                self._stop_capture()

    def manual_capture(
        self, seconds: float, reason: str = "manual"
    ) -> Dict[str, Any]:
        """One bounded on-demand xprof capture (the ops plane's
        ``/profile`` executor, ISSUE 20): starts the profiler, sleeps
        ``seconds`` on the CALLER's thread (the step path keeps running
        — the capture observes it), then stops.  Shares the
        ``max_captures`` budget and the in-flight exclusivity with the
        anomaly-triggered captures, so a scraper can never DoS the run
        with profiler sessions.  Returns ``{"ok": True, "trace_dir",
        "seconds", "captures"}`` or ``{"ok": False, "error"}``."""
        import os

        if self.trace_dir is None:
            return {
                "ok": False,
                "error": "no trace_dir — set ProfilerConfig.trace_dir "
                "to enable on-demand capture",
            }
        safe = "".join(
            c if (c.isalnum() or c in "-_=.") else "-" for c in reason
        )[:48]
        with self._capture_lock:
            if self._capturing:
                return {"ok": False, "error": "capture already in flight"}
            if self.captures >= self.cfg.max_captures:
                return {
                    "ok": False,
                    "error": f"capture budget exhausted "
                    f"({self.captures}/{self.cfg.max_captures})",
                }
            target = os.path.join(
                self.trace_dir,
                f"manual-capture-{self.captures + 1}-{safe}",
            )
            try:
                import jax

                jax.profiler.start_trace(target)
            except Exception as e:
                return {
                    "ok": False,
                    "error": f"profiler failed to start: {e!r}",
                }
            # same budget discipline as _start_capture: only a trace
            # that actually started burns a capture slot
            self.captures += 1
            self._capturing = True
            self._manual_capture = True
            self._capture_stop_at = None
            self._capture_dirs.append(target)
            self.registry.counter(
                "attr/captures_total",
                help="anomaly-triggered xprof captures",
            ).inc()
        time.sleep(max(0.0, float(seconds)))
        with self._capture_lock:
            self._stop_capture()
            self._manual_capture = False
        return {
            "ok": True,
            "trace_dir": target,
            "seconds": float(seconds),
            "captures": self.captures,
            "max_captures": self.cfg.max_captures,
        }

    def _stop_capture(self) -> None:
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception:
            pass
        self._capturing = False
        self._capture_stop_at = None

    def consume_trigger(self) -> Optional[Dict[str, Any]]:
        """One-shot read of the latest capture trigger (the health
        detector adapter drains this)."""
        t, self._pending_trigger = self._pending_trigger, None
        return t

    def close(self) -> None:
        with self._capture_lock:
            if self._capturing:
                self._stop_capture()
                self._manual_capture = False
