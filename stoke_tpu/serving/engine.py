"""Continuous-batching serving engine: prefill/decode split over paged KV.

ISSUE 9 pillar 3 and the piece that wires the other three together.  Two
compiled programs, deliberately split (the Gemma-on-TPU comparison's
serving shape, arXiv:2605.25645):

- **prefill** — one request at a time, prompt padded up to the
  ``prefill_pad_multiple`` bucket (each bucket is ONE compiled program, so
  program count is bounded), causal attention through the configured
  kernel (dense or the Pallas flash kernel), every prompt K/V written into
  the request's blocks, and the first generated token sampled from the
  last prompt position — the TTFT point.
- **decode** — ALL ``max_seqs`` slots every step, single fresh token per
  slot, cache-read attention over gathered blocks
  (``ops.flash_attention.paged_decode_attention``).  Inactive slots run
  against the scratch block and their outputs are discarded, so the
  program shape never changes and XLA compiles it exactly once.

Both programs register with the PR-6 compile-cache program ledger when a
``CompileConfig`` is attached (``compile_cache.executable`` — warm starts
load from the persistent XLA cache and book reclaimed seconds), dispatch
through plain ``jax.jit`` (page buffers donated off-CPU, so cache updates
are in-place in HBM), and read weights through the ISSUE 9 quantized
store (``serving/quant.py``; dequant fused matmul-side by XLA).

Sampling defaults to greedy argmax — deterministic by design: the
continuous-batching acceptance (staggered admission produces token
streams identical to sequential generation) is only testable under a
deterministic sampler, and the decode program's fixed batch shape makes
per-slot results independent of co-batched requests.  Since ISSUE 13
``ServeConfig(sampling=True)`` compiles sampling-aware program variants
instead (temperature / top-k / top-p drawn in-program from per-request
seeded key streams — ``serving/sampling.py``); the greedy engine's
programs stay bit-identical to pre-fast-path.  The same ISSUE adds the
serve fast path's other two pieces: ``decode_kernel="pallas"`` routes
decode attention through the streaming Pallas kernel
(``ops.flash_attention.paged_decode_attention_pallas``), and
``prefill_chunk_tokens`` bounds per-iteration prefill work so one long
prompt cannot stall the in-flight decode batch (chunks interleave with
decode steps; ``serve/prefill_chunk`` spans on the request timeline).

Speculative decoding (ISSUE 17, ``ServeConfig.speculative_k``): decode at
low batch is dispatch-bound — one query token per request per dispatch —
so the engine grows a **verify** program: the host-side prompt-lookup
drafter (``serving/speculative.py``) proposes up to k tokens per request
from history it already owns, the verify dispatch scores all k+1
positions in one forward (chunk-attention semantics over the paged
cache), the accept rule keeps the leading exact-match run, and rejected
positions' K/V roll back out of the pool before the dispatch returns.
Exact-match acceptance makes emitted streams BIT-identical to the
non-speculative engine in every sampling mode (each emitted token is the
true model draw with the correct sequential subkey — the draft only
decides how many draws one dispatch keeps).  The same multi-token-query
shape packs all prefilling slots' chunks into one dispatch
(``serve_prefill_chunk_packed``).  ``speculative_k=None`` engines compile
the PR-13 programs verbatim.

One decode step in flight (ISSUE 38): a greedy engine dispatches decode
step n+1 BEFORE it reads step n, so the runtime always holds the next
program when the running one ends and the host's read, commit, admission
and upload run under the device's work.  The next token never leaves the
device on its way to the next program (the decode program takes the rows
the host has not read from the vector the last step left there; a prefill's
first token is written into that vector on the device), every output the
commit reads is started home at dispatch, and a request is finished only
when its last token is on the host.  The lag is a number the engine derives
at construction, ``ServingEngine._lag``: 1 for greedy engines; 0, today's
read-then-dispatch order of the same code, where the host must see a step's
result to build the next (sampling: the key streams live on the host;
speculative verification: how many drafts were accepted decides the next
positions).
"""

from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from stoke_tpu.configs import ServeConfig
from stoke_tpu.ops.delta_rule import state_passes
from stoke_tpu.ops.flash_attention import partition_kernels_over
from stoke_tpu.ops.grouped_matmul import expert_weight_passes
from stoke_tpu.serving.kv_cache import (
    BlockAllocator,
    HybridCacheHook,
    LatentAttentionHook,
    SparseLatentHook,
    PagedAttentionHook,
    PagedKVCache,
)
from stoke_tpu.serving.quant import (
    compression_stats,
    dequantize_params,
    quantize_params,
)
from stoke_tpu.serving.sampling import (
    SamplingParams,
    accept_drafts,
    initial_key_data,
    sample_tokens,
    select_key_data,
    speculative_sample_tokens,
    split_key_data,
    validate_sampling_params,
)
from stoke_tpu.serving.scheduler import FROM_DEVICE, Request, Scheduler
from stoke_tpu.serving.slo import (
    RequestSLO,
    SLOTracker,
    resolve_request_slo,
)
from stoke_tpu.serving.telemetry import ServeMetrics
from stoke_tpu.telemetry.registry import MetricsRegistry
from stoke_tpu.telemetry.tracing import (
    dropped_total,
    request_spans,
    trace_add,
    trace_point,
    trace_span,
    tracing_active,
)

_KV_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def _nbytes(arrays) -> int:
    """Bytes of the host arrays one upload or one read moves."""
    return sum(a.nbytes for a in arrays)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@dataclass
class _DecodeInFlight:
    """One decode step between its dispatch and its commit."""

    out: list  # what the commit reads of it, on the device
    host_args: tuple  # the batch as uploaded
    rows: list  # the (slot, rid) pairs that rode it
    t0: float  # when the host began to build it


class ServingEngine:
    """Continuous-batching inference engine over one decoder-only model.

    Built by :meth:`stoke_tpu.facade.Stoke.serve` (which supplies the
    trained params, telemetry pipeline, and compile cache) or standalone
    in tests/scripts.

    Args:
        model: a module that carries the serving contract:
            ``__call__(input_ids, train, positions, decode, kv_cache)`` and
            ``cache_spec()``, the description of its cache
            (:class:`~stoke_tpu.models.bert.CacheSpec`: layers, planes and
            their widths) the pool is built from; a model with expert
            layers also says how many routed experts it holds
            (``experts_held``).
            :class:`~stoke_tpu.models.gpt.GPT` (dense FFN,
            ``chunked_head=False``; two planes of ``heads * head_dim``) and
            :class:`~stoke_tpu.models.decoder.Decoder` (one latent plane,
            or, for a hybrid of grouped-query and delta-rule layers, one
            plane of keys and values for the layers that cache rows and
            the per-slot state arrays for the others; its weights are
            served as given, in its own ``param_dtype``, and live on the
            device once) do.
        params: the model's ``params`` pytree (NOT the variables dict).
        cfg: :class:`~stoke_tpu.configs.ServeConfig`.
        registry: metrics registry for the ``serve/*`` instruments
            (defaults to ``telemetry.registry`` or a private one).
        telemetry: optional :class:`~stoke_tpu.telemetry.Telemetry` —
            when enabled, serve records land in its JSONL/Prometheus
            sinks with the ``serve/*`` field block.
        compile_cache: optional PR-6 :class:`~stoke_tpu.compile_cache
            .CompileCache` — prefill/decode programs register with its
            HLO-keyed ledger for warm starts.
        kv_sharding: optional sharding for the page pool (mesh-placed
            serving; default = wherever ``jnp.zeros`` lands).
        attribution: :class:`~stoke_tpu.configs.AttributionConfig`
            supplying the hardware peaks (``peak_tflops`` /
            ``peak_hbm_gbps``) the ISSUE 18 cost observatory rooflines
            against — required when ``cfg.cost_cards`` is on (the facade
            passes the run's config; standalone engines construct one).
        memory: optional :class:`~stoke_tpu.configs.MemoryConfig`
            (ISSUE 19) — arms the HBM capacity observatory: the engine
            registers its own subsystems (quantized weights, KV page
            pool), runs the serve-side OOM pre-flight at construction,
            and forecasts ``serve/mem_headroom_bytes`` (free-pool bytes
            minus the queue's worst-case block demand) every gauge
            refresh.  None (the default) constructs nothing.
    """

    def __init__(
        self,
        model: Any,
        params: Any,
        cfg: ServeConfig,
        *,
        registry: Optional[MetricsRegistry] = None,
        telemetry=None,
        compile_cache=None,
        kv_sharding=None,
        attribution=None,
        memory=None,
    ):
        self._kv_sharding = kv_sharding
        if not callable(getattr(model, "cache_spec", None)):
            raise TypeError(
                f"ServingEngine serves models that carry the serving "
                f"contract (GPT, Decoder: a paged-cache decode forward and "
                f"cache_spec()); got {type(model).__name__}"
            )
        spec = model.cache_spec()
        #: routed experts the model's expert layers compute here (the
        #: model's ``experts_held``; 0 for a model with none): the decode
        #: program then hands their assignment counts back
        self._experts_held = int(getattr(model, "experts_held", 0))
        #: zero-compute outputs of its routers (the model's
        #: ``zero_experts``; 0 for none): the decode program then hands
        #: back each row's count of picks among them too
        self._zero_experts = int(getattr(model, "zero_experts", 0))
        # a latent or hybrid cache or an expert layer runs through the
        # greedy serve_prefill and serve_decode programs only, and states
        # its own compute dtype: its weights are served as given
        self._native = spec.kind != "mha" or self._experts_held > 0
        if self._native:
            missing = {
                "sampling": (cfg.sampling, "sampling serve_prefill / "
                             "serve_decode"),
                "prefill_chunk_tokens": (
                    cfg.prefill_chunk_tokens is not None,
                    "serve_prefill_chunk"),
                "speculative_k": (cfg.speculative_k is not None,
                                  "serve_verify"),
                f"quant={cfg.quant!r}": (
                    cfg.quant != "none",
                    "quantized weight store for a model served in its "
                    "own param_dtype"),
            }
            for option, (asked, program) in missing.items():
                if asked:
                    raise NotImplementedError(
                        f"ServeConfig {option}: a {spec.kind}-cache model"
                        f"{' with experts' if self._experts_held else ''} "
                        f"has no {program} program yet (greedy "
                        f"serve_prefill and serve_decode only)"
                    )
        if cfg.max_seq_len > model.max_len:
            raise ValueError(
                f"ServeConfig.max_seq_len={cfg.max_seq_len} exceeds the "
                f"model's max_len={model.max_len}"
            )
        if (
            cfg.prefill_chunk_tokens is not None
            and cfg.prefill_chunk_tokens % cfg.prefill_pad_multiple
        ):
            raise ValueError(
                f"prefill_chunk_tokens={cfg.prefill_chunk_tokens} must be "
                f"a multiple of prefill_pad_multiple="
                f"{cfg.prefill_pad_multiple} (the bucket discipline that "
                f"bounds compiled-program count; same rule the status "
                f"layer enforces)"
            )
        if cfg.speculative_k is not None and not cfg.sampling:
            raise ValueError(
                "ServeConfig.speculative_k needs sampling=True — the "
                "verify program rides the key-threaded sampling programs "
                "(temperature=0.0 keeps exact greedy streams); set "
                "sampling=True or drop speculative_k"
            )
        if cfg.cost_cards and attribution is None:
            raise ValueError(
                "ServeConfig.cost_cards needs the hardware peaks an "
                "AttributionConfig carries (peak_tflops / peak_hbm_gbps) "
                "to roofline against — pass attribution= to the engine "
                "(Stoke.serve() supplies the run's AttributionConfig)"
            )
        if _round_up(cfg.max_seq_len, cfg.prefill_pad_multiple) > model.max_len:
            raise ValueError(
                f"prefill padding bucket round_up(max_seq_len="
                f"{cfg.max_seq_len}, {cfg.prefill_pad_multiple}) exceeds the "
                f"model's max_len={model.max_len} — a full-length prompt "
                f"would pad past the position table; shrink max_seq_len or "
                f"prefill_pad_multiple"
            )
        self.model = model
        self.cfg = cfg
        self._telemetry = telemetry
        self._compile_cache = compile_cache
        self.metrics = ServeMetrics(
            registry
            if registry is not None
            else (
                telemetry.registry
                if telemetry is not None
                else MetricsRegistry()
            )
        )
        # SLO observatory (ISSUE 16): purely host-side — never enters a
        # dispatch argument list, so the compiled programs are identical
        # with and without it; inert (zero instruments, zero JSONL
        # fields) until the first SLO-tagged request arrives
        self.slo = SLOTracker(self.metrics.registry)

        self._spec = spec
        self._heads = spec.heads
        self._head_dim = spec.head_dim

        # --- weight store (pillar 4): quantize once at load time ---
        self.qparams = quantize_params(
            params,
            cfg.quant,
            chunk_elems=cfg.quant_chunk_elems,
            stochastic=cfg.quant_stochastic,
            min_size=cfg.quant_min_size,
        )
        self.quant_stats = compression_stats(params, self.qparams)
        self.metrics.quant_compression.set(self.quant_stats["compression"])
        # per-layer dequant-error attribution (ISSUE 12): computed ONCE at
        # quantize time — which module int8 hurt most bounds the serving
        # quality story, so it rides the registry (numerics/* gauges), the
        # engine surface, and
        # stats()["quant_errors"]
        self.quant_errors: Dict[str, Dict[str, float]] = {}
        self.quant_errors_by_group: Dict[str, Dict[str, float]] = {}
        self.quant_err_layer: Optional[str] = None
        self.quant_err_max: Optional[float] = None
        if cfg.quant == "int8":
            from stoke_tpu.serving.quant import quantization_error
            from stoke_tpu.telemetry.numerics import (
                leaf_path_names,
                max_quant_error,
                module_groups,
                quant_error_by_group,
            )

            self.quant_errors = quantization_error(params, self.qparams)
            self.quant_errors_by_group = quant_error_by_group(
                self.quant_errors,
                module_groups(params),
                leaf_path_names(params),
            )
            self.quant_err_layer, self.quant_err_max = max_quant_error(
                self.quant_errors_by_group
            )
            # gauge publication respects the ISSUE 12 default-OFF
            # contract: on a SHARED telemetry pipeline the numerics/*
            # series exist only when a NumericsConfig attached a monitor
            # (Stoke.serve() installs the table on it, which publishes);
            # a standalone engine's own registry publishes directly
            if telemetry is None:
                reg = self.metrics.registry
                for group, err in self.quant_errors_by_group.items():
                    reg.gauge(f"numerics/{group}/quant_err_rel_rms").set(
                        err["rel_rms"]
                    )

        # --- paged KV pool (pillar 1) ---
        max_blocks_per_seq = -(-cfg.max_seq_len // cfg.kv_block_size)
        self._max_blocks_per_seq = max_blocks_per_seq
        num_blocks = (
            cfg.kv_blocks
            if cfg.kv_blocks is not None
            else cfg.max_seqs * max_blocks_per_seq + 1  # +1 scratch
        )
        # planes for the layers that cache rows only; the others' state a
        # slot in the second store
        self.cache = PagedKVCache(
            len(spec.layers_of("rows")),
            num_blocks,
            cfg.kv_block_size,
            dtype=_KV_DTYPES[cfg.kv_dtype],
            sharding=kv_sharding,
            planes=spec.planes,
            state=spec.state,
            state_layers=len(spec.layers_of("state")),
            max_seqs=cfg.max_seqs,
        )
        #: bytes of recurrent state a live slot's decode step reads and
        #: writes: every state layer's, once each way (the few convolution
        #: inputs beside it, kept in the pool's dtype, are not counted)
        self._state_bytes_per_slot = 2 * len(spec.layers_of("state")) * sum(
            int(np.prod(shape)) * jnp.dtype(kind).itemsize
            for _, shape, kind in spec.state if kind != "cache"
        )
        self.metrics.cache_bytes_per_token.set(self.cache.bytes_per_token)
        if self._experts_held:
            self.metrics.enable_experts(zero_compute=self._zero_experts > 0)
        if self.cache.state:
            self.metrics.enable_state()
        self.allocator = BlockAllocator(num_blocks, cfg.kv_block_size)

        # --- continuous-batching scheduler (pillar 2) ---
        self.scheduler = Scheduler(
            cfg.max_seqs,
            self.allocator,
            max_blocks_per_seq,
            max_seq_len=cfg.max_seq_len,
            default_max_new_tokens=cfg.max_new_tokens,
            eos_id=cfg.eos_id,
            pad_multiple=cfg.prefill_pad_multiple,
            prefill_chunk_tokens=cfg.prefill_chunk_tokens,
            sampling_seed_base=cfg.sampling_seed,
        )

        # --- serve fast path (ISSUE 13): decode kernel + sampling state ---
        # pallas decode off-TPU auto-falls-back to the interpreter (the
        # CPU parity mode the tests pin); a REAL serve config declaring a
        # CPU device is rejected upstream by the status layer instead
        self._decode_interpret = (
            jax.default_backend() != "tpu"
            if cfg.decode_kernel == "pallas"
            else None
        )
        self._sampling = bool(cfg.sampling)
        #: decode steps the dispatch runs ahead of the read: 1 unless the
        #: host must see a step's result to build the next.  A sampling
        #: engine advances its key streams on the host from what the step
        #: hands back, and a speculative one (sampling too) learns from the
        #: verify step how many drafts were accepted, which sets the next
        #: positions.  No configuration sets it.
        self._lag = 0 if self._sampling else 1
        #: the last decode step's tokens, on the device, with each fresh
        #: prefill's first token written in (``_first_token_jit``): what
        #: the next step feeds the rows whose token the host has not read
        self._prev_tokens = jnp.zeros((cfg.max_seqs,), jnp.int32)
        self._inflight: Deque[_DecodeInFlight] = deque()
        # (slot, request, read) of the prefill programs dispatched in this
        # step and not yet read
        self._awaited: List[tuple] = []
        # config-level default knobs (requests may override per-submit);
        # greedy when sampling is off — those engines never consult them
        self._default_sampling = (
            SamplingParams(
                temperature=cfg.temperature,
                top_k=cfg.top_k,
                top_p=cfg.top_p,
            )
            if self._sampling
            else SamplingParams()
        )
        if self._sampling:
            validate_sampling_params(self._default_sampling)
        # per-slot PRNG key state, threaded through the sampling-mode
        # dispatches like the KV pages (wrapped to TYPED keys in-program,
        # split once per emitted token, advanced data written back) —
        # maintained whenever any program consumes it
        kd = initial_key_data(0)
        self._key_data = np.zeros(
            (cfg.max_seqs,) + kd.shape, kd.dtype
        )
        # counterfactual-parity hook (tests): when True, every sampling-
        # mode dispatch's PRE-sampling logits are fetched and recorded
        # per request id — the bit-match check staggered-vs-sequential
        # sampling leans on (greedy streams can no longer assert it)
        self.capture_logits = False
        self.captured_logits: Dict[int, List[np.ndarray]] = {}

        # --- compiled programs (pillar 3) ---
        # donation keeps the page pool in-place in HBM; the CPU backend
        # has no donation (jax warns and copies), so only donate off-CPU
        donate = (
            tuple(range(1, 1 + len(self.cache.arrays)))
            if jax.default_backend() != "cpu"
            else ()
        )

        def program(name, method):
            # the module carries the program's name (``jit_serve_decode``):
            # what a profiler trace's ``XLA Modules`` line calls it
            def fn(*args):
                return method(*args)

            fn.__name__ = fn.__qualname__ = name
            return jax.jit(fn, donate_argnums=donate)

        if self._sampling:
            self._prefill_jit = program(
                "serve_prefill", self._prefill_sampling_fn
            )
            self._decode_jit = program(
                "serve_decode", self._decode_sampling_fn
            )
        else:
            # greedy programs are the PRE-ISSUE-13 ones verbatim: with
            # decode_kernel="reference" their HLO and token streams are
            # bit-identical to the pre-fast-path engine
            self._prefill_jit = program("serve_prefill", self._prefill_fn)
            self._decode_jit = program("serve_decode", self._decode_fn)
        self._chunk_jit = (
            program("serve_prefill_chunk", self._chunk_fn)
            if cfg.prefill_chunk_tokens is not None
            else None
        )

        def serve_first_token(tokens, token, slot):
            return jax.lax.dynamic_update_slice(tokens, token, (slot,))

        # a prefill's first token [1] into the slot's row of the token
        # vector, without a host read between the prefill and the decode
        # step behind it
        self._first_token_jit = jax.jit(serve_first_token)
        # speculative decoding (ISSUE 17): the verify program replaces the
        # per-token decode program, and chunk packing replaces the
        # one-chunk-per-iteration schedule with the same multi-token-query
        # program shape.  Both are construction-time choices gated on
        # speculative_k — a speculative_k=None engine compiles the PR-13
        # programs verbatim (HLO bit-identical, the default-OFF contract
        # audit_specs lowering asserts).
        self._speculative_k = cfg.speculative_k
        self._verify_jit = (
            program("serve_verify", self._verify_fn)
            if cfg.speculative_k is not None
            else None
        )
        self._packed_chunk_jit = (
            program("serve_prefill_chunk_packed", self._packed_chunk_fn)
            if (
                cfg.speculative_k is not None
                and cfg.prefill_chunk_tokens is not None
            )
            else None
        )
        if cfg.speculative_k is not None:
            self.metrics.enable_speculative()

        # program-audit ledger (ISSUE 15): one abstract spec per
        # (program, shape signature), recorded at the dispatch funnel so
        # Stoke.audit() can statically check the serve programs exactly
        # like the step programs — donation per the tuple jit actually
        # received (empty on CPU, where pages are copied, not donated)
        self._donate = donate
        self._audit_specs: list = []
        self._audit_seen: set = set()

        # serve roofline observatory (ISSUE 18): host-side cost cards
        # over the dispatch funnel — never enters an argument list, so
        # the compiled serve programs are HLO bit-identical with and
        # without it (the audit_specs lowering test pins this); absent
        # (None) entirely when cost_cards is off, so an unconfigured
        # engine registers zero serve/cost series and its JSONL records
        # carry zero new fields
        self._cost = None
        if cfg.cost_cards:
            from stoke_tpu.serving.roofline import ServeCostObservatory

            self._cost = ServeCostObservatory(
                self.metrics,
                attribution.peak_tflops,
                attribution.peak_hbm_gbps,
            )
            if self._verify_jit is not None:
                # a speculative engine never dispatches plain decode:
                # lower it at the decode-batch shapes (abstract args
                # only) so the verify program's intensity uplift has its
                # counterfactual leg
                self._cost.set_decode_baseline(
                    self._decode_jit, self._decode_baseline_args()
                )

        # HBM capacity observatory (ISSUE 19): same host-side discipline
        # as the cost cards — never enters an argument list, so the
        # compiled serve programs stay HLO bit-identical with and without
        # it.  The engine registers the two subsystems it owns (the
        # quantized weight store and the KV page pool) and runs the
        # serve-side OOM pre-flight HERE, before the first request can
        # allocate a block.
        self._memory = None
        if memory is not None:
            from stoke_tpu.telemetry.memory import (
                MemoryObservatory,
                tree_resident_bytes,
            )

            self._memory = MemoryObservatory(memory, self.metrics.registry)
            self._memory.set_component(
                "params", lambda: tree_resident_bytes(self.qparams)
            )
            self._memory.set_component(
                "kv_cache",
                lambda: self.cache.nbytes + self.cache.state_nbytes,
            )
            self._memory.preflight("serve")

        self._iterations = 0
        self._last_emit_iter = 0
        self._t_start = time.perf_counter()

    # ------------------------------------------------------------------ #
    # compiled program bodies
    # ------------------------------------------------------------------ #

    def _apply(self, params, tokens, positions, hook, decode: bool,
               expert_counts: bool = False):
        # with the pool placed on a mesh the serve programs are multi-device
        # programs, where the Pallas kernels must shard_map themselves; every
        # replica serves the whole slot batch, so no axis splits the rows
        mesh = getattr(self._kv_sharding, "mesh", None)
        scope = (
            partition_kernels_over(mesh, ())
            if mesh is not None
            else contextlib.nullcontext()
        )
        with scope:
            out = self.model.apply(
                {"params": params},
                tokens,
                train=False,
                positions=positions,
                decode=decode,
                kv_cache=hook,
                mutable=["intermediates"] if expert_counts else False,
            )
        if not expert_counts:
            return out
        # what the expert layers sowed, a row a layer: ``expert_counts``
        # int32[held] and, of a router with zero-compute outputs,
        # ``zero_expert_count`` int32[rows]
        logits, sown = out
        by_name = {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(
                sown["intermediates"]):
            by_name.setdefault(path[-2].key, []).append(leaf)
        names = ("expert_counts",) + (
            ("zero_expert_count",) if self._zero_experts else ())
        return logits, tuple(jnp.stack(by_name[name]) for name in names)

    def _split(self, args: tuple):
        """A serve program's arguments after the weights: the cache's
        arrays as the model describes them (the pool's planes, then the
        state arrays), then the rest."""
        spec = self._spec
        n = len(spec.planes) + len(spec.state) * len(spec.layers_of("state"))
        return args[:n], args[n:]

    def _weights(self, qparams):
        """The dense tree the forward reads: dequantized from the store, or
        as given for a model that states its own compute dtype."""
        return qparams if self._native else dequantize_params(qparams)

    def _make_hook(self, pages, tables, positions, mode, lengths,
                   slot=None):
        """The per-trace cache hook with this engine's kernel selection —
        with the default ``decode_kernel="reference"`` the constructed
        graph is op-for-op the pre-ISSUE-13 one."""
        if self._spec.kind == "hybrid":
            n = len(self._spec.planes)
            return HybridCacheHook(
                pages[:n], pages[n:], tables, positions, mode=mode,
                lengths=lengths, layer_kinds=self._spec.layer_kinds,
                slot=slot, attention_impl=self.cfg.attention,
            )
        if self._spec.kind == "sparse_latent":
            return SparseLatentHook(
                pages, tables, positions, mode=mode, lengths=lengths,
                index_topk=self._spec.index_topk,
                index_layers=self._spec.plane_layers("index"),
            )
        if self._spec.kind == "latent":
            return LatentAttentionHook(
                *pages, tables, positions, mode=mode, lengths=lengths,
                attention_impl=self.cfg.attention,
            )
        return PagedAttentionHook(
            *pages, tables, positions,
            mode=mode, lengths=lengths,
            attention_impl=self.cfg.attention,
            decode_impl=self.cfg.decode_kernel,
            decode_interpret=self._decode_interpret,
        )

    def _prefill_fn(self, qparams, *args):
        """After the weights and the cache's arrays: tokens [1, P] padded
        prompt; block_row [1, MB]; prompt_len [1]; for a model with
        per-slot state also slot [1], whose state rows the prompt's end
        state overwrites.
        Returns (first generated token [1], updated arrays)."""
        pages, (tokens, block_row, prompt_len, *slot) = self._split(args)
        params = self._weights(qparams)
        P = tokens.shape[1]
        positions = jnp.arange(P, dtype=jnp.int32)[None, :]
        hook = self._make_hook(
            pages, block_row, positions, "prefill", prompt_len, *slot
        )
        logits = self._apply(params, tokens, positions, hook, decode=False)
        last = logits[0, prompt_len[0] - 1]
        return (
            jnp.argmax(last, axis=-1).astype(jnp.int32)[None],
            *hook.pages,
            *getattr(hook, "state", ()),
        )

    def _decode_fn(self, qparams, *args):
        """After the weights and the cache's arrays: tokens/positions [B];
        block_tables [B, MB]; context_lens [B]; then, where a step is
        dispatched before the one before it is read, that step's tokens
        [B], still on the device: a row whose token the host has not seen
        (``scheduler.FROM_DEVICE``) takes its token from there.
        Returns (next tokens [B], updated arrays); a model with expert
        layers hands back their assignment counts (int32[expert layers,
        held]) beside the tokens, one whose routers have zero-compute
        outputs each row's picks among them (int32[expert layers, B]), and
        one with a sparse latent cache the words of latent plane its
        kernels' row DMAs fetched, a slot over the layers (int32[B])."""
        pages, (tokens, positions, block_tables, context_lens, *prev) = (
            self._split(args)
        )
        if prev:
            tokens = jnp.where(tokens == FROM_DEVICE, prev[0], tokens)
        params = self._weights(qparams)
        hook = self._make_hook(
            pages, block_tables, positions[:, None], "decode", context_lens
        )
        counting = self._experts_held > 0
        logits = self._apply(
            params, tokens[:, None], positions[:, None], hook, decode=True,
            expert_counts=counting,
        )
        counts = ()
        if counting:
            logits, counts = logits
        if self._spec.kind == "sparse_latent":
            counts += (hook.words_fetched,)
        return (
            jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32),
            *counts,
            *hook.pages,
            *getattr(hook, "state", ()),
        )

    # --- sampling-mode programs (ISSUE 13): same forward, the draw added
    # in-program on the pre-sampling logits; key state threaded like the
    # pages.  Compiled INSTEAD of the greedy bodies only when
    # ``ServeConfig.sampling`` is set, so the default engine's programs
    # stay bit-identical to pre-fast-path. ---

    def _prefill_sampling_fn(self, qparams, k_pages, v_pages, tokens,
                             block_row, prompt_len, key_data, temp, top_k,
                             top_p):
        """Sampling prefill: returns (token [1], advanced key data,
        pre-sampling logits row [1, V], updated pages)."""
        params = dequantize_params(qparams)
        P = tokens.shape[1]
        positions = jnp.arange(P, dtype=jnp.int32)[None, :]
        hook = self._make_hook(
            (k_pages, v_pages), block_row, positions, "prefill", prompt_len
        )
        logits = self._apply(params, tokens, positions, hook, decode=False)
        row = logits[0, prompt_len[0] - 1][None, :]
        key_out, sub = split_key_data(key_data)
        tok = sample_tokens(row, sub, temp, top_k, top_p)
        return tok, key_out, row, hook.k_pages, hook.v_pages

    def _decode_sampling_fn(self, qparams, k_pages, v_pages, tokens,
                            positions, block_tables, context_lens, key_data,
                            temps, top_ks, top_ps):
        """Sampling decode: returns (tokens [B], advanced key data,
        pre-sampling logits [B, V], updated pages)."""
        params = dequantize_params(qparams)
        hook = self._make_hook(
            (k_pages, v_pages), block_tables, positions[:, None], "decode",
            context_lens,
        )
        logits = self._apply(
            params, tokens[:, None], positions[:, None], hook, decode=True
        )[:, -1, :]
        key_out, sub = split_key_data(key_data)
        tok = sample_tokens(logits, sub, temps, top_ks, top_ps)
        return tok, key_out, logits, hook.k_pages, hook.v_pages

    def _chunk_fn(self, qparams, k_pages, v_pages, tokens, positions,
                  block_row, prompt_len, logit_idx, key_data, temp, top_k,
                  top_p):
        """ONE chunked-prefill step (ISSUE 13): tokens [1, C] at GLOBAL
        positions [1, C]; writes the chunk's K/V into the request's
        blocks and attends over everything cached so far (causal by
        global position).  Samples from the ``logit_idx`` row — the last
        prompt token's — which only the FINAL chunk's caller consumes
        (greedy encodes as temperature 0, so one program serves both
        modes; the chunk shape is fixed, so the compile-cache ledger
        registers it once)."""
        params = dequantize_params(qparams)
        hook = self._make_hook(
            (k_pages, v_pages), block_row, positions, "chunk", prompt_len
        )
        logits = self._apply(params, tokens, positions, hook, decode=False)
        row = logits[0, logit_idx[0]][None, :]
        key_out, sub = split_key_data(key_data)
        tok = sample_tokens(row, sub, temp, top_k, top_p)
        return tok, key_out, row, hook.k_pages, hook.v_pages

    # --- speculative programs (ISSUE 17): fixed-shape k-token verify and
    # packed chunked prefill — both the multi-token-query shape the chunk
    # program pinned, compiled only when ``speculative_k`` is set. ---

    def _verify_fn(self, qparams, k_pages, v_pages, tokens, positions,
                   block_tables, lengths, draft_lens, key_data, temps,
                   top_ks, top_ps):
        """ONE speculative verify step (ISSUE 17): tokens ``[B, S]`` =
        each slot's pending token + up to k drafts at GLOBAL positions
        ``[B, S]``; scores all S positions in one forward, draws the S
        sequential target tokens from each slot's key stream, accepts
        the leading exact-match run, rolls rejected positions' K/V back
        out of the cache (scratch-steered restore — rejected drafts
        never dirty the pool across dispatches), and rewinds each slot's
        key state to one split per EMITTED token.  Returns ``(targets
        [B, S], n_emit [B], key data [B, ...], pre-sampling logits
        [B, S, V], updated pages)``."""
        params = dequantize_params(qparams)
        hook = self._make_hook(
            (k_pages, v_pages), block_tables, positions, "verify", lengths
        )
        logits = self._apply(params, tokens, positions, hook, decode=False)
        targets, key_stack = speculative_sample_tokens(
            logits, key_data, temps, top_ks, top_ps
        )
        n_emit = accept_drafts(tokens[:, 1:], draft_lens, targets)
        hook.rollback(n_emit)
        key_out = select_key_data(key_stack, n_emit)
        return targets, n_emit, key_out, logits, hook.k_pages, hook.v_pages

    def _packed_chunk_fn(self, qparams, k_pages, v_pages, tokens, positions,
                         block_tables, lengths, logit_idx, key_data, temps,
                         top_ks, top_ps):
        """Packed chunked prefill (ISSUE 17): every prefilling slot's next
        chunk rides ONE dispatch — tokens ``[B, C]`` at global positions
        ``[B, C]`` against the full slot batch's tables (idle rows
        scratch-steered, outputs discarded), the same multi-token-query
        shape as :meth:`_verify_fn`.  Samples every row at its own
        ``logit_idx`` (only final-chunk rows' draws are consumed; their
        callers also take the key writeback, preserving one split per
        emitted token).  Returns ``(tokens [B], advanced key data,
        pre-sampling logit rows [B, V], updated pages)``."""
        params = dequantize_params(qparams)
        hook = self._make_hook(
            (k_pages, v_pages), block_tables, positions, "chunk", lengths
        )
        logits = self._apply(params, tokens, positions, hook, decode=False)
        rows = jnp.take_along_axis(
            logits, logit_idx[:, None, None], axis=1
        )[:, 0]  # [B, V]
        key_out, sub = split_key_data(key_data)
        tok = sample_tokens(rows, sub, temps, top_ks, top_ps)
        return tok, key_out, rows, hook.k_pages, hook.v_pages

    # ------------------------------------------------------------------ #
    # program-signature dispatch (PR-6 AOT ledger registration)
    # ------------------------------------------------------------------ #

    @staticmethod
    def _sig(args) -> tuple:
        return tuple(
            (tuple(l.shape), str(getattr(l, "dtype", "")))
            for l in jax.tree_util.tree_leaves(args)
            if hasattr(l, "shape")
        )

    def _note_audit(self, program: str, fn, args: tuple) -> None:
        """Record one abstract ProgramSpec per PROGRAM for the ISSUE 15
        auditor (the StepEngine._note_audit contract: shapes/dtypes/
        shardings only, pre-donation).  Keyed by program NAME alone —
        pad buckets share one program body, and auditing one
        representative keeps the steady-state decode loop's cost at a
        single set lookup (no per-token tree walk)."""
        if program in self._audit_seen:
            return
        self._audit_seen.add(program)
        from stoke_tpu.analysis.program import ProgramSpec, abstractify_args

        avals, weak = abstractify_args(args)
        self._audit_specs.append(
            ProgramSpec(
                program=program,
                fn=fn,
                abstract_args=avals,
                donate_argnums=self._donate,
                weak_leaves=weak,
                source="serve",
            )
        )

    def audit_specs(self) -> list:
        """The recorded serve-program specs (ISSUE 15; consumed by
        ``Stoke.audit(serve=engine)`` or a standalone
        ``audit_program_specs`` call)."""
        return list(self._audit_specs)

    def _decode_baseline_args(self) -> tuple:
        """Abstract (ShapeDtypeStruct) argument tuple for ONE plain-decode
        dispatch at this engine's fixed batch shapes — what the roofline
        observatory lowers on a speculative engine (which never dispatches
        plain decode) so the verify program's arithmetic-intensity uplift
        keeps its counterfactual leg.  Lowering-only: no arrays are
        materialized and nothing executes."""
        abstract = lambda leaf: jax.ShapeDtypeStruct(  # noqa: E731
            leaf.shape, leaf.dtype
        )
        B = self.cfg.max_seqs
        i32 = jnp.int32
        args = (
            jax.tree_util.tree_map(abstract, self.qparams),
            *map(abstract, self.cache.arrays),
            jax.ShapeDtypeStruct((B,), i32),  # tokens
            jax.ShapeDtypeStruct((B,), i32),  # positions
            jax.ShapeDtypeStruct((B, self._max_blocks_per_seq), i32),
            jax.ShapeDtypeStruct((B,), i32),  # context_lens
        )
        if self._sampling:
            args += (
                abstract(jnp.asarray(self._key_data)),
                jax.ShapeDtypeStruct((B,), jnp.float32),  # temps
                jax.ShapeDtypeStruct((B,), i32),  # top_ks
                jax.ShapeDtypeStruct((B,), jnp.float32),  # top_ps
            )
        else:
            args += (abstract(self._prev_tokens),)
        return args

    def _dispatch(self, program: str, fn, args: tuple):
        """Route one dispatch through the compile cache's program ledger
        (same contract as ``StepEngine._aot_call``): first dispatch per
        (program, shape signature) checks the HLO-keyed ledger — warm
        starts resolve to an already-built fn and book reclaimed compile
        seconds — and every dispatch runs plain ``jax.jit`` semantics."""
        self._note_audit(program, fn, args)
        if self._cost is not None:
            self._cost.note_dispatch(program, fn, args, self._sig(args))
        if self._memory is not None:
            self._memory.note_program(program, fn, args, self._sig(args))
        cc = self._compile_cache
        if cc is not None:
            fn = cc.executable(program, (program, self._sig(args)), fn, args)
        return fn(*args)

    def _upload(self, host_args: tuple, *on_device) -> tuple:
        """A serve program's arguments: the weights, the cache's arrays,
        the numpy ``host_args`` put on the device, and what is
        ``on_device`` already."""
        return (
            self.qparams,
            *self.cache.pages,
            *self.cache.state,
            *map(jnp.asarray, host_args),
            *on_device,
        )

    def _run(self, program: str, fn, args: tuple) -> list:
        """Dispatch one serve program; the cache arrays it returns last
        replace the cache's.  Returns its other outputs, still on the
        device."""
        out = self._dispatch(program, fn, args)
        cache = self.cache
        n = len(cache.pages)
        first = len(out) - n - len(cache.state)
        cache.pages = tuple(out[first:first + n])
        cache.state = tuple(out[first + n:])
        return list(out[:first])

    def _launch(self, span: str, program: str, fn, host_args: tuple,
                *on_device) -> list:
        """:meth:`_upload` and :meth:`_run`, each under its own child of
        ``span`` (``<span>/upload``, ``<span>/dispatch``).  The caller
        fetches what comes back under ``<span>/read``, so a profiler trace
        tells the blocking read from the upload and from the enqueue.  For
        the two sites the benchmark's serve cell runs (prefill, decode)."""
        with trace_span(f"{span}/upload", track="serve"):
            args = self._upload(host_args, *on_device)
        with trace_span(f"{span}/dispatch", track="serve"):
            return self._run(program, fn, args)

    # ------------------------------------------------------------------ #
    # request intake
    # ------------------------------------------------------------------ #

    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: Optional[int] = None,
        eos_id: Optional[int] = None,
        sampling: Optional[SamplingParams] = None,
        slo: Optional[RequestSLO] = None,
    ) -> int:
        """Enqueue one request (mid-flight is the point); returns its id.

        ``sampling`` (ISSUE 13) carries per-request temperature / top-k /
        top-p / seed — validated here, never mid-decode — and requires
        ``ServeConfig.sampling=True`` (the sampling-aware programs are a
        construction-time choice; the default greedy engine's programs
        are bit-identical to pre-fast-path).  Without it the request uses
        the config's default knobs; a request without an explicit seed
        gets the deterministic per-request default
        ``sampling_seed + rid``, so whole runs replay from the config.

        ``slo`` (ISSUE 16) carries the request's priority class and
        TTFT/TPOT deadlines — same contract: validated here, never
        mid-decode, unset targets resolved from the
        ``ServeConfig.slo_ttft_target_s`` / ``slo_tpot_target_s``
        defaults.  Purely host-side accounting; the compiled programs
        never see it.
        """
        if sampling is not None:
            if not self._sampling:
                raise ValueError(
                    "per-request SamplingParams need ServeConfig."
                    "sampling=True (the sampling-aware decode programs "
                    "are compiled at engine construction; docs/serving.md)"
                )
            validate_sampling_params(sampling)
            params = sampling
        else:
            params = self._default_sampling
        if slo is not None:
            slo = resolve_request_slo(
                slo, self.cfg.slo_ttft_target_s, self.cfg.slo_tpot_target_s
            )
        # the scheduler resolves the seed beside the rid it assigns
        # (explicit params.seed wins, else sampling_seed + rid)
        rid = self.scheduler.submit(
            prompt, max_new_tokens, eos_id, params=params, slo=slo
        )
        self.metrics.requests.inc()
        if slo is not None:
            # the queue tail IS the request just enqueued (single-threaded
            # intake; the scheduler appends before returning the rid)
            self.slo.on_submit(self.scheduler.queue[-1])
        return rid

    def result(self, rid: int) -> Optional[Request]:
        return self.scheduler.finished.get(rid)

    # ------------------------------------------------------------------ #
    # the engine loop
    # ------------------------------------------------------------------ #

    def _sampling_scalar_args(self, params: SamplingParams, slot: int):
        """The per-request sampling tail of a prefill/chunk dispatch:
        (key_data [1, ...], temperature [1], top_k [1], top_p [1])."""
        t, k, p = params.as_arrays()
        return (
            self._key_data[slot : slot + 1],
            np.array([t], np.float32),
            np.array([k], np.int32),
            np.array([p], np.float32),
        )

    def _emit_first_token(self, slot, req, tok_host, now):
        """Shared bookkeeping for the TTFT token, whether it came from the
        one-shot prefill program or the final prefill chunk."""
        m = self.metrics
        self.scheduler.note_prefill_token(slot, tok_host, now)
        m.tokens_out.inc()
        if not req.params.is_greedy:
            m.sampled_tokens.inc()
        m.observe_ttft(req.ttft_s)
        if req.finished:
            self._finish(req)

    def _first_token_dispatched(self, slot: int, token) -> None:
        """The program that makes the slot's first token (``token`` [1], on
        the device) is on its way: the slot rides the next decode step on
        that count, and where that step is dispatched before the token is
        read, the token goes into the slot's row of the vector the step
        feeds from, on the device."""
        self.scheduler.note_prefill_dispatched(slot)
        if self._lag:
            self._prev_tokens = self._first_token_jit(
                self._prev_tokens, token, np.int32(slot)
            )

    def _read_or_await(self, slot, req, token,
                       read: Callable[[], Optional[int]]) -> Optional[int]:
        """A prefill program is dispatched; ``read()`` blocks for it and
        returns the first token it made (None for a chunk that makes
        none).  At lag 0 read now; at lag 1 start ``token`` home and leave
        the read to :meth:`_settle`, behind the decode step's dispatch."""
        if not self._lag:
            return read()
        token.copy_to_host_async()
        self._awaited.append((slot, req, read))
        return None

    def _settle(self) -> None:
        """Read what this step's prefill programs made, in dispatch order:
        each first token is emitted when the host has it.  Runs after the
        decode step behind them is dispatched and the one before is
        committed, so the device has D(k-1), the prefills and D(k) back to
        back while the host waits here.  The wait is a span of the ring
        only (``serve/prefill_wait`` on the request's row): the profiler's
        ``serve/step`` keeps the children its readers know."""
        m = self.metrics
        for slot, req, read in self._awaited:
            t0 = time.perf_counter()
            with trace_span("serve/prefill_wait", track="serve",
                            request_id=req.rid, annotate=False):
                tok_host = read()
            now = time.perf_counter()
            m.prefill_s.inc(now - t0)
            if tok_host is not None:
                self._emit_first_token(slot, req, tok_host, now)
        self._awaited.clear()

    def _prefill_one(self, slot, req, padded, plen) -> None:
        """Unchunked prefill: one program over the bucket-padded prompt
        (the pre-ISSUE-13 path, sampling-aware when enabled).  At lag 0
        its read waits for the first token and emits it; at lag 1 the read
        only starts the token home, and :meth:`_settle` emits it once the
        decode step behind the prefill is dispatched."""
        sched, m = self.scheduler, self.metrics
        t0 = time.perf_counter()
        host_args = (
            padded,
            sched.block_tables[slot : slot + 1],
            np.array([plen], np.int32),
        )
        if self._sampling:
            host_args += self._sampling_scalar_args(req.params, slot)
        if self.cache.state:
            # a state row is addressed by slot, not through the table
            host_args += (np.array([slot], np.int32),)

        def read() -> int:
            if self._sampling:
                self._key_data[slot] = np.asarray(out[1])[0]
                if self.capture_logits:
                    self.captured_logits.setdefault(req.rid, []).append(
                        np.asarray(out[2])[0].copy()
                    )
            return int(np.asarray(out[0])[0])  # sync: the TTFT point

        with trace_span(
            "serve/prefill", track="serve", request_id=req.rid,
            attrs={
                "padded_len": int(padded.shape[1]),
                "prompt_len": int(plen),
                "queue_wait_us": 1e6 * (req.admit_ts - req.arrival_ts),
                # what is known at the opening: the bytes the upload will
                # put on the device and the fetches the read will make
                "upload_bytes": _nbytes(host_args),
                "read_fetches": 1 + (
                    1 + bool(self.capture_logits) if self._sampling else 0
                ),
            },
        ):
            out = self._launch(
                "serve/prefill", "serve_prefill", self._prefill_jit, host_args
            )
            self._first_token_dispatched(slot, out[0])
            with trace_span("serve/prefill/read", track="serve"):
                tok_host = self._read_or_await(slot, req, out[0], read)
        now = time.perf_counter()
        m.prefills.inc()
        m.prefill_s.inc(now - t0)
        if tok_host is not None:
            self._emit_first_token(slot, req, tok_host, now)

    def _run_chunk(self, slot, req, toks, positions, is_final,
                   logit_idx) -> None:
        """One chunked-prefill step (ISSUE 13): dispatch the fixed-shape
        chunk program for ``slot``; the final chunk produces the TTFT
        token.  Every chunk is waited for, at lag 1 in :meth:`_settle`;
        only the final chunk advances the request's key stream, one split
        per emitted token, the same recurrence as unchunked prefill."""
        sched, m = self.scheduler, self.metrics
        t0 = time.perf_counter()

        def read() -> Optional[int]:
            # EVERY chunk syncs (one [1] token fetch): dispatch is async,
            # and without the sync the chunk's compute would be charged to
            # a later decode step's fetch — the serve/prefill_chunk spans
            # and the prefill goodput bucket must own their real wall
            tok_host = int(np.asarray(tok)[0])
            if not is_final:
                return None
            self._key_data[slot] = np.asarray(key_out)[0]
            if self.capture_logits:
                self.captured_logits.setdefault(req.rid, []).append(
                    np.asarray(row)[0].copy()
                )
            return tok_host

        with trace_span(
            "serve/prefill_chunk", track="serve", request_id=req.rid,
            attrs={
                "start": int(positions[0]),
                "chunk": int(toks.shape[0]),
                "final": bool(is_final),
            },
        ):
            tok, key_out, row = self._run(
                "serve_prefill_chunk", self._chunk_jit,
                self._upload((
                    toks[None, :],
                    positions[None, :],
                    sched.block_tables[slot : slot + 1],
                    np.array([int(req.prompt.size)], np.int32),
                    np.array([logit_idx], np.int32),
                ) + self._sampling_scalar_args(req.params, slot)),
            )
            # the cursor moves at dispatch: a final chunk's slot rides the
            # decode step built next
            sched.note_chunk(slot)
            if is_final:
                self._first_token_dispatched(slot, tok)
            tok_host = self._read_or_await(slot, req, tok, read)
        now = time.perf_counter()
        m.prefill_chunks.inc()
        m.prefill_s.inc(now - t0)
        if tok_host is not None:
            self._emit_first_token(slot, req, tok_host, now)

    def _run_packed_chunks(self, tokens, positions, tables, lengths,
                           logit_idx, rows) -> None:
        """One PACKED chunked-prefill step (ISSUE 17): every prefilling
        slot's next chunk rides one fixed-shape ``[B, C]`` dispatch.
        Final-chunk rows produce their TTFT tokens and take the key
        writeback; every serviced row advances its prefill cursor."""
        sched, m = self.scheduler, self.metrics
        B = self.cfg.max_seqs
        temps = np.zeros(B, np.float32)
        ks = np.zeros(B, np.int32)
        ps = np.ones(B, np.float32)
        for i, req, _is_final in rows:
            temps[i], ks[i], ps[i] = req.params.as_arrays()
        t0 = time.perf_counter()
        with trace_span(
            "serve/prefill_chunk_packed", track="serve",
            attrs={"packed": len(rows), "chunk": int(tokens.shape[1])},
        ):
            tok, key_out, logit_rows = self._run(
                "serve_prefill_chunk_packed", self._packed_chunk_jit,
                self._upload((tokens, positions, tables, lengths, logit_idx,
                              self._key_data, temps, ks, ps)),
            )
            # sync for the same reason the single-chunk path does: the
            # chunk compute must be charged to the prefill bucket, not
            # the next dispatch's fetch
            tok_host = np.asarray(tok)
        now = time.perf_counter()
        m.prefill_chunks.inc()  # dispatches, not serviced rows
        m.prefill_s.inc(now - t0)
        kd = np.asarray(key_out)
        larr = np.asarray(logit_rows) if self.capture_logits else None
        for i, req, is_final in rows:
            if tracing_active():
                # per-request slice of the shared packed interval — the
                # SLO attribution walk keys on the serve/prefill_chunk
                # span name; count_self=False since the packed span above
                # owns the wall once
                trace_add(
                    "serve/prefill_chunk", t0, now, track="serve",
                    request_id=req.rid, count_self=False,
                )
            sched.note_chunk(i)
            if is_final:
                sched.note_prefill_dispatched(i)
                self._key_data[i] = kd[i]
                if larr is not None:
                    self.captured_logits.setdefault(req.rid, []).append(
                        larr[i].copy()
                    )
                self._emit_first_token(i, req, int(tok_host[i]), now)

    def _step_verify(self) -> None:
        """One speculative decode step (ISSUE 17): draft host-side,
        verify all draft positions in one dispatch, commit the accepted
        run + the correction/bonus token.  Replaces the per-token decode
        dispatch — ``decode_steps`` still counts dispatches, so
        tokens_out / decode_steps IS accepted-tokens-per-dispatch."""
        sched, m = self.scheduler, self.metrics
        k = self._speculative_k
        decode_rows = [
            i
            for i, s in enumerate(sched.slots)
            if s.request is not None and s.prefill_pos is None
        ]
        live_rids = (
            [sched.slots[i].request.rid for i in decode_rows]
            if tracing_active()
            else None
        )
        t0 = time.perf_counter()
        with trace_span("serve/verify_step", track="serve",
                        attrs={"active": sched.decoding, "k": k}):
            batch = sched.verify_batch(
                k,
                ngram_max=self.cfg.speculative_ngram_max,
                ngram_min=self.cfg.speculative_ngram_min,
            )
            draft_lens = batch[-1]
            targets, n_emit, key_out, logits = self._run(
                "serve_verify", self._verify_jit,
                self._upload(
                    batch + (self._key_data,) + sched.sampling_batch()
                ),
            )
            targets_host = np.asarray(targets)  # sync: tokens stream out
            n_emit_host = np.asarray(n_emit)
            kd = np.asarray(key_out)
            for i in decode_rows:
                self._key_data[i] = kd[i]
            if self.capture_logits:
                larr = np.asarray(logits)
                for i in decode_rows:
                    rid = sched.slots[i].request.rid
                    # one pre-sampling logits row per EMITTED token, so
                    # speculative captures align 1:1 with the
                    # non-speculative engine's per-step captures
                    for j in range(int(n_emit_host[i])):
                        self.captured_logits.setdefault(rid, []).append(
                            larr[i, j].copy()
                        )
        now = time.perf_counter()
        if live_rids:
            for rid in live_rids:
                trace_add("serve/decode", t0, now, track="serve",
                          request_id=rid, count_self=False)
        m.decode_steps.inc()
        m.decode_s.inc(now - t0)
        # greedy-ness per row, read BEFORE commit evicts finished slots
        greedy_row = {
            i: sched.slots[i].request.params.is_greedy for i in decode_rows
        }
        was_finished = set(sched.finished)
        committed, accepted = sched.commit_verify(
            targets_host, n_emit_host, now
        )
        m.tokens_out.inc(int(committed.sum()))
        m.spec_draft_tokens.inc(int(draft_lens.sum()))
        m.spec_accepted_tokens.inc(accepted)
        n_sampled = sum(
            int(committed[i]) for i in decode_rows if not greedy_row[i]
        )
        if n_sampled:
            m.sampled_tokens.inc(n_sampled)
        for rid in set(sched.finished) - was_finished:
            self._finish(sched.finished[rid])

    def step(self) -> bool:
        """One engine iteration: admit arrivals (short prompts prefill
        whole; long ones enter the chunked-prefill state), run at most ONE
        prefill chunk, then one decode step over the fully-prefilled slot
        batch.  Bounding per-iteration prefill work by the chunk size is
        what keeps in-flight TPOT flat while a long prompt admits.
        Returns True while work remains, a decode step in flight included.

        At lag 1 (greedy engines) the decode step this call dispatches is
        read by the NEXT call: a call hands the caller the tokens of the
        decode step dispatched one call earlier and the first token of
        every prefill dispatched in this one, and a request is ``finished``
        when its last token is on the host.  Order inside the call: admit;
        upload and dispatch each prefill (behind the decode step in
        flight); build, upload and dispatch this call's decode step; read
        the step before and commit it; read each prefill's first token.

        One span tree per iteration (docs/observability.md): ``serve/step``
        holds ``serve/admit``, each ``serve/prefill``, the decode step with
        its batch / upload / dispatch / read children, ``serve/commit`` and
        ``serve/gauges``, so a profiler trace splits the device's idle time
        between the blocking read, the launch path and the bookkeeping."""
        sched = self.scheduler
        with trace_span(
            "serve/step", track="serve",
            attrs={"it": self._iterations, "queued": sched.queued,
                   "active": sched.active},
        ):
            with trace_span("serve/admit", track="serve"):
                admitted = sched.admit()
            for slot, req, padded, plen in admitted:
                if tracing_active():
                    # the request timeline's first span: arrival → admission
                    # (the queue wait) on the request's own track row
                    # count_self=False: the queue wait overlaps other
                    # requests' prefill/decode spans, which own that wall
                    trace_add(
                        "serve/admission", req.arrival_ts, req.admit_ts,
                        track="serve", request_id=req.rid,
                        attrs={"prompt_len": plen}, count_self=False,
                    )
                if req.slo is not None:
                    self.slo.on_admit(req)
                if self._sampling or self._chunk_jit is not None:
                    self._key_data[slot] = initial_key_data(req.seed)
                if padded is None:
                    continue  # chunked admission: chunks run below
                self._prefill_one(slot, req, padded, plen)

            if self._packed_chunk_jit is not None:
                nxt = sched.next_chunks()
                if nxt is not None:
                    self._run_packed_chunks(*nxt)
            else:
                nxt = sched.next_chunk()
                if nxt is not None:
                    self._run_chunk(*nxt)

            if self._verify_jit is None:
                self._step_decode()
                self._settle()
            elif sched.decoding > 0:
                self._step_verify()

            with trace_span("serve/gauges", track="serve"):
                self._iterations += 1
                self._refresh_gauges()
                if (
                    self._iterations - self._last_emit_iter
                    >= self.cfg.log_every_n_steps
                ):
                    self.emit_record()
        return sched.has_work

    def _step_decode(self) -> None:
        """One decode step over the slots that ride it: build the batch,
        upload, dispatch; then read and commit the step dispatched
        ``_lag`` steps ago.  At lag 0 that is the step just dispatched;
        at lag 1 the one before it, whose read the device's work on this
        one hides: the first step after an empty engine then has nothing
        to read, and the step after the last nothing to dispatch."""
        sched, m = self.scheduler, self.metrics
        inflight = self._inflight
        t0 = time.perf_counter()
        riding = sched.riding
        read = None
        if riding:
            # dispatched while the step before is unread
            ahead = int(bool(inflight))
            with trace_span("serve/decode_step", track="serve",
                            attrs={"active": riding, "ahead": ahead}):
                with trace_span("serve/decode_step/batch", track="serve"):
                    host_args = sched.decode_batch()
                    if self._sampling:
                        host_args += (
                            (self._key_data,) + sched.sampling_batch()
                        )
                out = self._launch(
                    "serve/decode_step", "serve_decode", self._decode_jit,
                    host_args,
                    *(() if self._sampling else (self._prev_tokens,)),
                )
                if self._sampling:
                    # tokens, key streams and, asked for, the logits
                    out = out[:2 + bool(self.capture_logits)]
                else:
                    self._prev_tokens = out[0]
                # everything the commit reads starts home now, so the
                # read finds it on the host
                for a in out:
                    a.copy_to_host_async()
                inflight.append(_DecodeInFlight(
                    out, host_args, sched.note_decode_dispatched(), t0
                ))
                with trace_span("serve/decode_step/read", track="serve"):
                    if len(inflight) > self._lag:
                        read = self._read_decode(inflight.popleft())
            m.decode_steps.inc()
            m.decode_steps_ahead.inc(ahead)
        elif inflight:
            # the step after the last: nothing to dispatch
            read = self._read_decode(inflight.popleft())
        else:
            return
        m.decode_s.inc(time.perf_counter() - t0)
        if read is not None:
            self._commit_decode(*read)

    def _read_decode(self, step: _DecodeInFlight):
        """Fetch what the commit reads of one decode step.  Returns the
        step, the host arrays and the seconds the fetches after the first
        took."""
        next_host = np.asarray(step.out[0])  # sync: tokens stream out
        # the fetch above waited for the device (at lag 0; at lag 1 for
        # the step's copy home); each one below is one more
        t_first = time.perf_counter()
        fetched = [next_host]
        if self._experts_held or self._spec.kind == "sparse_latent":
            # the held experts' assignment counts ride beside, the rows'
            # zero-compute picks where the routers have those, and the
            # words the sparse kernels fetched where the cache is read by row
            fetched += [np.asarray(a) for a in step.out[1:]]
        if self._sampling:
            # advance ONLY the decoding slots' key streams: a
            # request's draw sequence depends on its own seed and
            # token count, never on who else rode the batch
            kd = np.asarray(step.out[1])
            fetched.append(kd)
            for i, _ in step.rows:
                self._key_data[i] = kd[i]
            if self.capture_logits:
                larr = np.asarray(step.out[2])
                fetched.append(larr)
                for i, rid in step.rows:
                    self.captured_logits.setdefault(rid, []).append(
                        larr[i].copy()
                    )
        return step, fetched, time.perf_counter() - t_first

    def _commit_decode(self, step: _DecodeInFlight, fetched: list,
                       read_extra_s: float) -> None:
        """Fold one decode step that was read into the slots, under
        ``serve/commit`` with the step's counts as its attributes."""
        sched, m = self.scheduler, self.metrics
        now = time.perf_counter()
        host_args = step.host_args
        decode_rows = [i for i, _ in step.rows]
        if tracing_active():
            # per-request decode slices: every live request's timeline
            # row shows the batch decode interval it rode, dispatch to
            # read (the TPOT structure the histograms only summarize).
            # count_self=False: all slices share ONE interval the
            # serve/decode_step spans already own — charging
            # each would multiply-count the window by batch depth
            for _, rid in step.rows:
                trace_add("serve/decode", step.t0, now, track="serve",
                          request_id=rid, count_self=False)
        # what the step read, on the span that closes after the read:
        # the live rows' context lengths (fresh token included), the blocks
        # of the pool one layer's attention read for them (the paged kernel
        # of a latent or hybrid cache reads to each slot's own length, the
        # MHA gather takes every slot's whole table), the bytes of per-slot
        # state the live slots' layers read and wrote and how often the
        # state layers' kernel moved them (its grid walks every slot) and,
        # of an expert model, its held experts' load, how often their
        # products streamed the weights and, where its routers have
        # zero-compute outputs, the share of the live rows' assignments
        # that went to those (an idle slot's row is routed too, and left
        # out here)
        tables, context = host_args[2], host_args[3][decode_rows]
        step_attrs = {
            "context_tokens": int(context.sum()),
            "window_blocks": (
                tables.size if self._spec.kind == "mha"
                else int((-(-context // self.cfg.kv_block_size)).sum())
            ),
            # the step's round trip, counted where it happened: what the
            # read fetched and what its fetches after the first cost, and
            # what the upload put on the device
            "read_fetches": len(fetched),
            "read_bytes": _nbytes(fetched),
            "read_extra_us": 1e6 * read_extra_s,
            "upload_arrays": len(host_args),
            "upload_bytes": _nbytes(host_args),
        }
        if self._spec.kind == "sparse_latent":
            step_attrs.update(self._sparse_counts(
                host_args[3], decode_rows, fetched[-1]))
        if self.cache.state:
            passes = state_passes(len(decode_rows), self.cfg.max_seqs)
            m.state_passes.set(passes)
            step_attrs.update(
                state_bytes=len(decode_rows) * self._state_bytes_per_slot,
                state_passes=passes,
            )
        if self._experts_held:
            held_counts = fetched[1]
            per_expert = held_counts.sum(axis=0)  # over the expert layers
            total = int(per_expert.sum())
            imbalance = (
                float(per_expert.max()) * per_expert.size / total
                if total else 0.0
            )
            passes = expert_weight_passes(held_counts)
            m.expert_assignments.inc(total)
            m.expert_load_max_over_mean.set(imbalance)
            m.expert_weight_passes.set(passes)
            step_attrs.update(
                expert_assignments=total,
                expert_load_max_over_mean=imbalance,
                expert_weight_passes=passes,
            )
            if self._zero_experts:
                share = float(fetched[2][:, decode_rows].sum()) / max(
                    len(decode_rows) * held_counts.shape[0]
                    * self.model.experts_per_token, 1)
                m.zero_expert_share.set(share)
                step_attrs["zero_expert_share"] = share
        # everything since the read returned, this dictionary included, is
        # the step's own accounting, paid every step
        step_attrs["account_us"] = 1e6 * (time.perf_counter() - now)
        with trace_span("serve/commit", track="serve", attrs=step_attrs):
            # a sampling engine commits the step it just dispatched, so
            # the slots still hold the requests that rode it
            n_sampled = sum(
                1
                for i in decode_rows
                if not sched.slots[i].request.params.is_greedy
            ) if self._sampling else 0
            was_finished = set(sched.finished)
            live = sched.commit_decode(fetched[0], now)
            m.tokens_out.inc(live)
            if n_sampled:
                m.sampled_tokens.inc(n_sampled)
            for rid in set(sched.finished) - was_finished:
                self._finish(sched.finished[rid])

    def _sparse_counts(self, context, live, issued) -> dict:
        """What a decode step of a sparse-attention model read: the keys
        its indexers scored (every live slot to its length, in each layer
        with an indexer), the rows its selections chose (``min(context,
        index_topk)`` a live slot and layer), and the bytes of latent plane
        the row kernels fetched over the chosen rows' bytes: ``issued`` is
        the kernels' own count of the plane's words their DMAs fetched, a
        slot over the layers."""
        spec = self._spec
        chosen = np.minimum(context, spec.index_topk)
        selected = spec.layers * int(chosen[live].sum())
        fetched = int(np.sum(issued)) * self.cache.pages[0].dtype.itemsize
        row_bytes = spec.values_per_token * self.cache.dtype.itemsize
        return {
            "indexer_keys": len(spec.plane_layers("index"))
            * int(context[live].sum()),
            "selected_rows": selected,
            "sparse_row_passes": fetched / max(selected * row_bytes, 1),
        }

    def run(self, max_steps: Optional[int] = None) -> int:
        """Drive :meth:`step` until drained (or ``max_steps``); emits a
        final telemetry record.  Returns iterations run."""
        n = 0
        while self.scheduler.has_work:
            self.step()
            n += 1
            if max_steps is not None and n >= max_steps:
                break
        if self._iterations != self._last_emit_iter:
            # final record on drain — unless the last step() just emitted
            # at the cadence (a duplicate step key would confuse readers)
            self.emit_record()
        return n

    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        max_new_tokens: Optional[int] = None,
    ) -> List[List[int]]:
        """Convenience batch API: submit all, drain, return token lists in
        prompt order (the continuous batcher still interleaves them)."""
        rids = [self.submit(p, max_new_tokens) for p in prompts]
        self.run()
        return [list(self.scheduler.finished[r].tokens) for r in rids]

    # ------------------------------------------------------------------ #
    # accounting
    # ------------------------------------------------------------------ #

    def _finish(self, req: Request) -> None:
        # eviction marker closes the request's trace timeline (its blocks
        # are already back in the pool — scheduler._finish freed them)
        trace_point(
            "serve/evict", track="serve", request_id=req.rid,
            attrs={"tokens": len(req.tokens)},
        )
        m = self.metrics
        m.completed.inc()
        tpot = req.tpot_s
        if tpot is not None:
            m.observe_tpot(tpot)
        if req.slo is not None:
            # finalize attainment + re-walk the request's span timeline
            # into the violation-attribution buckets (ISSUE 16); a ring
            # that dropped spans marks the attribution partial
            self.slo.on_finish(
                req, request_spans(req.rid), dropped_total()
            )
        if self._telemetry is not None:
            self._telemetry.add_tokens(len(req.tokens))

    def _refresh_gauges(self) -> None:
        m, sched = self.metrics, self.scheduler
        m.queue_depth.set(sched.queued)
        m.active_seqs.set(sched.active)
        m.batch_fill.set(sched.batch_fill)
        m.kv_blocks_used.set(self.allocator.used_blocks)
        m.kv_occupancy.set(self.allocator.occupancy)
        # sums-to-wall: queue/idle is the wall clock neither program used
        wall = time.perf_counter() - self._t_start
        target = max(
            0.0, wall - m.prefill_s.value - m.decode_s.value
        )
        if target > m.queue_s.value:
            m.queue_s.inc(target - m.queue_s.value)
        if self._cost is not None:
            # roofline gauges first, then hand the SLO tracker the current
            # model-FLOPs-per-token so its TFLOP-goodput column tracks the
            # same analytic cost the cards carry
            self._cost.refresh_gauges()
            self.slo.set_flops_per_token(self._cost.flops_per_token())
        self.slo.refresh_gauges()
        if self._memory is not None:
            self._memory.note_serve_headroom(self._mem_headroom_bytes())
            self._memory.refresh_gauges()

    def _mem_headroom_bytes(self) -> float:
        """KV-pool headroom forecast (ISSUE 19): free-pool bytes minus
        the worst-case blocks-to-completion still owed to in-flight work.
        Admission reserves every ACTIVE request's full worst-case budget
        up front (the allocator contract), so the outstanding demand is
        the QUEUE's: each queued request will claim
        ``blocks_for(prompt + max_new_tokens)`` at admission.  Negative
        headroom forecasts that the queue cannot be admitted against the
        current pool — the bursty-admission signal."""
        alloc = self.allocator
        queued_blocks = sum(
            alloc.blocks_for(req.prompt.size + req.max_new_tokens)
            for req in self.scheduler.queue
        )
        bytes_per_block = self.cache.nbytes / max(alloc.num_blocks, 1)
        return (alloc.free_blocks - queued_blocks) * bytes_per_block

    def emit_record(self) -> Optional[dict]:
        """Write one JSONL serve record through the telemetry pipeline
        (None when no enabled Telemetry is attached; the registry gauges
        update regardless)."""
        self._refresh_gauges()
        window = max(1, self._iterations - self._last_emit_iter)
        self._last_emit_iter = self._iterations
        if self._telemetry is None or not self._telemetry.enabled:
            return None
        # the serve/slo_* and serve/cost_* blocks are conditional: {} /
        # absent until armed, so an engine without SLO-tagged requests or
        # cost cards emits records with zero new fields (build_step_event
        # honors the omission)
        return self._telemetry.record_step(
            step=self._iterations,
            window_steps=window,
            serve={
                **self.metrics.event_fields(),
                **self.slo.event_fields(),
                **(
                    self._cost.event_fields()
                    if self._cost is not None
                    else {}
                ),
                **(
                    self._memory.serve_event_fields()
                    if self._memory is not None
                    else {}
                ),
            },
            # the serve record's mem/* ledger is THIS engine's (quantized
            # weights + KV pool), not the train facade's — record_step
            # falls back to the pipeline's observatory only when None
            memory=self._memory,
        )

    # ------------------------------------------------------------------ #
    # introspection
    # ------------------------------------------------------------------ #

    def summary(self) -> Dict[str, Any]:
        m = self.metrics
        m.refresh_percentiles()
        return {
            "iterations": self._iterations,
            "requests": m.requests.value,
            "completed": m.completed.value,
            "tokens_out": m.tokens_out.value,
            "prefills": m.prefills.value,
            "decode_steps": m.decode_steps.value,
            "kv_blocks_used": self.allocator.used_blocks,
            "kv_block_occupancy": self.allocator.occupancy,
            "quant": dict(self.quant_stats),
            # per-layer dequant-error attribution (ISSUE 12): which module
            # bounds int8 quality, and by how much
            "quant_errors_by_group": {
                g: dict(e) for g, e in self.quant_errors_by_group.items()
            },
            "quant_err_layer": self.quant_err_layer,
            "quant_err_max": self.quant_err_max,
            "kv_cache_bytes": self.cache.nbytes,
            **m.latency_percentiles(),
            "goodput_s": {
                "queue": m.queue_s.value,
                "prefill": m.prefill_s.value,
                "decode": m.decode_s.value,
            },
            # SLO observatory (ISSUE 16): {"active": False} until an
            # SLO-tagged request arrives, else per-class attainment,
            # goodput-under-SLO, and queue-ETA forecasts
            "slo": self.slo.summary(),
            # roofline observatory (ISSUE 18): {"active": False} without
            # ServeConfig.cost_cards, else per-program cost cards, the
            # decode roofline (attainable vs achieved TPOT, bound class),
            # MFU / HBM-bandwidth utilization, and the verify-over-decode
            # intensity uplift
            "cost": (
                self._cost.summary()
                if self._cost is not None
                else {"active": False}
            ),
            # HBM capacity observatory (ISSUE 19): {"active": False}
            # without a MemoryConfig, else the subsystem ledger, the
            # serve OOM pre-flight verdict, per-program memory cards,
            # and the KV headroom forecast
            "memory": (
                self._memory.summary()
                if self._memory is not None
                else {"active": False}
            ),
        }
