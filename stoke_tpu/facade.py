"""The `Stoke` facade: declarative flags → validated status → one SPMD engine.

TPU-native re-design of the reference facade (stoke/stoke.py:49-1466).  The
public contract is preserved — construct with flags, then drive your own
training loop through four wrapped calls plus a DataLoader factory and
unified save/load (reference README.md:13-43):

    stoke = Stoke(model, optimizer, loss, batch_size_per_device=32,
                  device="tpu", distributed="dp", precision="bf16", fsdp=True)
    loader = stoke.DataLoader(dataset, sampler=...)
    for x, y in loader:
        out = stoke.model(x)          # lazy handle (train) / eager (eval)
        loss = stoke.loss(out, y)     # ONE compiled fused micro-step
        stoke.backward(loss)          # commit accumulated grads
        stoke.step()                  # compiled apply at accum boundary

What changed under the hood (SURVEY.md §7): the reference's dynamically
composed mixin runner (``type("StokeRunner", (dist, fp16, opt, io))``,
stoke.py:599-657) becomes explicit strategy *data* — a device mesh, sharding
rules, a precision policy, and compiled step functions.  There is no wrap
ordering dance (stoke.py:306-324): placement is declared once and XLA derives
the collective schedule.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Any, Callable, Dict, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from stoke_tpu.configs import (
    ClipGradConfig,
    ClipGradNormConfig,
    DeviceOptions,
    DistributedOptions,
    ParamNormalize,
    PrecisionOptions,
    LossReduction,
)
from stoke_tpu.engine import (
    DeferredOutput,
    PrecisionPolicy,
    StepEngine,
    as_adapter,
    build_optimizer,
    init_scaler_state,
    is_deferred,
)
from stoke_tpu.parallel.mesh import (
    backend_devices,
    build_mesh,
    initialize_distributed,
)
from stoke_tpu.parallel.sharding import make_sharding_rules, place_global_tree
from stoke_tpu.status import StokeStatus
from stoke_tpu.telemetry import Telemetry
from stoke_tpu.telemetry.tracing import trace_span
from stoke_tpu.telemetry.health import (
    SENTINEL_INDEX,
    HealthHaltError,
    HealthMonitor,
    unpack_sentinels,
)
from stoke_tpu.telemetry.recorder import FlightRecorder
from stoke_tpu.utils.printing import unrolled_print
from stoke_tpu.utils.trees import tree_count_params

from jax.sharding import NamedSharding, PartitionSpec as P


def _on_accelerator(leaf) -> bool:
    """True when ``leaf`` is a jax Array resident on a non-CPU device (its
    bytes are already in the accelerator's ``bytes_in_use``)."""
    if not isinstance(leaf, jax.Array):
        return False
    try:
        return all(d.platform != "cpu" for d in leaf.sharding.device_set)
    except Exception:
        return False


def _device_memory_stats() -> Optional[dict]:
    """Memory stats of the first local device, or None where the backend
    doesn't report them (CPU simulator).  Delegates to the shared
    None-tolerant reader in ``telemetry/collectors.py`` (the PR-15
    shared-normalizer discipline: one ``device.memory_stats()`` probe,
    not two drifting copies)."""
    from stoke_tpu.telemetry.collectors import hbm_stats

    return hbm_stats() or None


def _check_segment_memory(seg_bytes: int, stats: Optional[dict]) -> None:
    """Raise an actionable error when a ``train_steps`` segment obviously
    cannot fit in device memory (pure function — unit-tested with synthetic
    stats).  A conservative pre-flight: only the stacked-input bytes are
    counted (activations/params need room too), and the guard fires only
    when those alone exceed 90% of free memory — the point is a clear error
    *before* the runtime OOMs mid-compile, not an exact accounting."""
    if not stats:
        return
    limit = stats.get("bytes_limit")
    if not limit:
        return
    free = limit - stats.get("bytes_in_use", 0)
    if seg_bytes > 0.9 * free:
        raise ValueError(
            f"Stoke -- train_steps() segment stacks {seg_bytes / 1e9:.2f} GB "
            f"of inputs but the device has only {free / 1e9:.2f} GB free "
            f"(limit {limit / 1e9:.2f} GB). Pass segment_size=<c> to stream "
            f"the segment host->device in chunks of c optimizer steps, or "
            f"stack fewer steps per call. (docs/performance.md)"
        )


def _timed(phase: str):
    """Method decorator: the call runs under its ``stoke/<phase>`` span,
    which also feeds the wall-clock breakdown when that is enabled."""
    import functools

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, *args, **kwargs):
            with self._clock(phase):
                return fn(self, *args, **kwargs)

        return wrapper

    return deco


def _health_guarded(fn):
    """Method decorator for the dispatching step paths (ISSUE 3): arms the
    hang watchdog across the call (a wedged collective hangs the training
    thread inside the dispatch or its result fetch — only the watchdog's
    daemon thread can report it) and writes a post-mortem bundle when the
    call dies on an uncaught exception.  Zero overhead without a
    ``HealthConfig``."""
    import functools

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        h = self._health
        if h is None:
            return fn(self, *args, **kwargs)
        # deadline scaled by compile grace until the first step completes;
        # train_steps re-arms with its per-segment step count once known
        h.arm_watchdog()
        try:
            return fn(self, *args, **kwargs)
        except HealthHaltError:
            raise  # the halt path already dumped its bundle
        except Exception as e:
            # one bundle per exception (nested guarded calls — e.g. the
            # chunked train_steps recursion — re-raise through multiple
            # wrappers) and at most max_dumps exception bundles per run
            # (a caller retrying a failing call must not fill the disk)
            if (
                h.cfg.dump_on_exception
                and not getattr(e, "_stoke_health_dumped", False)
                and h.note_exception_dump()
            ):
                try:
                    e._stoke_health_dumped = True
                except Exception:
                    pass
                h.dump(
                    "exception",
                    extra={"method": fn.__name__, "error": repr(e)[:500]},
                )
            raise
        finally:
            h.disarm_watchdog()

    return wrapper


class Stoke:
    """Declarative training-context facade (reference stoke/stoke.py:49-1466).

    Args:
        model: flax ``linen.Module``, plain callable ``fn(params, *args)``,
            or a :class:`~stoke_tpu.engine.ModelAdapter`.
        optimizer: ``StokeOptimizer`` TypedDict (ctor + kwargs, reference
            configs.py:754-770) or an ``optax.GradientTransformation``.
        loss: callable ``loss(out, *targets) -> scalar | tuple | dict``
            (multi-loss supported, reference stoke.py:872-912).
        params: initial model variables — either a flax variables dict
            (``{"params": ..., "batch_stats": ...}``) or a bare params pytree.
            (The reference receives an initialized ``nn.Module``; JAX splits
            module and state, so state is passed explicitly.)  The facade
            TAKES OWNERSHIP of these arrays: compiled steps donate their
            buffers (in-place updates), and placement may alias the passed
            tree, so do not reuse it elsewhere (e.g. to build a second
            ``Stoke``) — read live values via ``stoke.params`` instead, or
            pass a copy.
        batch_size_per_device: micro-batch size per device.
        grad_accum: gradient accumulation steps (reference stoke.py:137).
        grad_clip: ``ClipGradConfig`` / ``ClipGradNormConfig`` / None.
        device: "cpu" | "tpu" (reference ``gpu`` flag).
        distributed: None | "dp" (reference {ddp,horovod,deepspeed} collapse).
        precision: None/"full" | "bf16" | "fp16" (reference FP16Options).
        oss / sddp / fsdp: ZeRO-1/2/3-equivalent sharding tiers (reference
            fairscale flags, stoke.py:147-152).
        configs: list of config-class instances (deduped by class).
        model_train_kwargs / model_eval_kwargs: extra kwargs for flax apply
            in train/eval mode (e.g. ``{"train": True}``), replacing torch's
            implicit module mode bit.
        loss_weights: optional pytree of floats matching the structure of
            ``loss()``'s return; the training objective becomes the weighted
            sum ``Σ wᵢ·lossᵢ``.  Gradient-equivalent to the reference's
            per-loss backward passes with weights (fp16.py:545-579,
            stoke.py:891-902); reported per-loss values stay unweighted.
            ``None`` (default) sums all losses with weight 1 — the
            "summed objective" contract.
        aux_loss_weight: weight for MODEL-internal auxiliary losses sown
            into the flax "losses" collection (e.g. the MoE router's
            load-balancing term, models/moe.py) — they join the training
            objective as ``aux_loss_weight · Σ aux`` (0 disables; default
            0.01, the Switch-Transformer α).  The user's loss report stays
            untouched; latest values are readable via ``aux_losses``.
        seed: PRNG seed for dropout etc.
        ema_weight: EMA coefficient for the rolling loss (reference
            stoke.py:155 ``ema_weight``).
        verbose: rank-0 status printing (reference stoke.py:154).
    """

    def __init__(
        self,
        model: Any,
        optimizer: Any,
        loss: Callable,
        params: Any,
        batch_size_per_device: int,
        grad_accum: Optional[int] = None,
        grad_clip: Optional[Union[ClipGradConfig, ClipGradNormConfig]] = None,
        device: Union[str, DeviceOptions] = "cpu",
        distributed: Optional[Union[str, DistributedOptions]] = None,
        precision: Optional[Union[str, PrecisionOptions]] = None,
        oss: bool = False,
        sddp: bool = False,
        fsdp: bool = False,
        configs: Optional[Sequence[Any]] = None,
        model_train_kwargs: Optional[dict] = None,
        model_eval_kwargs: Optional[dict] = None,
        model_rng_keys: Sequence[str] = ("dropout",),
        loss_weights: Optional[Any] = None,
        aux_loss_weight: float = 0.01,
        seed: int = 0,
        ema_weight: float = 0.1,
        verbose: bool = True,
    ):
        # ----- L3: validated status (reference stoke.py:201) -----
        self._status_obj = StokeStatus(
            batch_size_per_device=batch_size_per_device,
            grad_accum=grad_accum,
            grad_clip=grad_clip,
            device=device,
            distributed=distributed,
            precision=precision,
            oss=oss,
            sddp=sddp,
            fsdp=fsdp,
            configs=configs,
        )
        st = self._status_obj
        self._verbose = verbose

        # ----- multi-host rendezvous + mesh (reference setup_distributed,
        #       stoke.py:220 → distributed.py:491-538) -----
        if st.is_distributed and st.dist_init_config.auto_initialize:
            initialize_distributed(st.dist_init_config)
        self._mesh = build_mesh(st.mesh_config, st.device, st.is_distributed)
        self._rules = make_sharding_rules(
            st.sharding_tier,
            self._mesh,
            st.dp_config.axis_name,
            st.oss_config,
            st.sddp_config,
            st.fsdp_config,
            partition_rules=(
                st.partition_rules_config.rules
                if st.partition_rules_config is not None
                else None
            ),
        )
        if self._mesh is None:
            self._device = backend_devices(st.device, local=True)[0]
        else:
            self._device = None

        # ----- model / loss / optimizer checks (reference stoke.py:214-216) -----
        self._adapter = as_adapter(
            model,
            **(
                dict(
                    train_kwargs=model_train_kwargs,
                    eval_kwargs=model_eval_kwargs,
                    rng_keys=model_rng_keys,
                )
                if hasattr(model, "apply") and not isinstance(model, StepEngine)
                else {}
            ),
        )
        if not callable(loss):
            raise TypeError("Stoke -- loss must be callable")
        self._loss_fn = loss
        self._optimizer = build_optimizer(optimizer)

        # ----- state -----
        variables = params
        if not (isinstance(variables, dict) and "params" in variables):
            variables = {"params": variables}
        self._precision = PrecisionPolicy.make(st.precision, st.precision_config)
        self._engine = StepEngine(
            self._adapter,
            self._loss_fn,
            self._optimizer,
            precision=self._precision,
            precision_config=st.precision_config,
            grad_accum=st.grad_accum,
            grad_clip=st.grad_clip,
            rules=self._rules,
            remat=st.activation_checkpointing_config,
            offload_optimizer=st.offload_optimizer_config,
            offload_params=st.offload_params_config,
            loss_weights=loss_weights,
            aux_loss_weight=aux_loss_weight,
            comm=st.comm_config,
            health=st.health_config,
            numerics=st.numerics_config,
        )
        if self._rules is not None:
            opt_shapes = jax.eval_shape(self._optimizer.init, variables["params"])
            variables = self._engine.resolve_placement_abstract(variables, opt_shapes)
            self._variables = variables
            self._opt_state = self._engine.init_opt_state(variables)
        else:
            self._variables = jax.device_put(variables, self._device)
            opt_target = self._device
            if st.offload_optimizer_config is not None:
                opt_target = self._single_device_offload_target()
            # optimizer init creates fresh scalars (e.g. the adam count) on
            # the DEFAULT backend; pin it to this run's device
            with jax.default_device(self._device):
                opt_state = self._optimizer.init(self._variables["params"])
            self._opt_state = jax.device_put(opt_state, opt_target)
        # disk tier (NVMe-offload equivalent): spill the freshly initialized
        # optimizer state immediately — it is only needed again at the first
        # accumulation boundary
        self._disk_store = None
        if st.offload_disk_config is not None:
            import tempfile

            from stoke_tpu.offload import DiskOptimizerStore

            if st.offload_disk_config.path is not None:
                # unique per process AND per instance/run: concurrent runs
                # pointing at the same NVMe mount must not clobber each other
                base = os.path.join(
                    st.offload_disk_config.path, f"proc{jax.process_index()}"
                )
                os.makedirs(base, exist_ok=True)
                # a killed run cannot clean its spill — reclaim siblings
                # whose recorded pid is dead before adding ours
                from stoke_tpu.offload import reclaim_stale_spills

                reclaim_stale_spills(base)
                spill_dir = tempfile.mkdtemp(prefix="run-", dir=base)
            else:
                spill_dir = tempfile.mkdtemp(prefix="stoke-optspill-")
            with open(os.path.join(spill_dir, "pid"), "w") as f:
                f.write(str(os.getpid()))
            self._disk_store = DiskOptimizerStore(
                os.path.join(spill_dir, "opt"), cleanup_root=spill_dir
            )
            # protect the model variables: some optax transforms alias params
            # inside their init state, and deleting those buffers would kill
            # the live model
            self._disk_store.store(self._opt_state, protect=self._variables)
            self._opt_state = None
        self._grad_buf = self._engine.init_grad_buffer(self._variables)
        self._scaler_state = self._place_scalar_tree(
            init_scaler_state(st.precision_config)
        )
        # gradient-transport state (ISSUE 2): error-feedback residual +
        # stochastic-rounding rng, threaded through every apply path like
        # the scaler state.  Empty dict when no CommConfig (or fp32
        # pass-through) — structurally free.  Transient like the sown
        # "losses" collection: not checkpointed (worst case a restart
        # loses one step's quantization residual).
        self._comm_state = self._engine.init_comm_state(self._variables)
        # analytic per-step bytes-on-wire of the gradient exchange
        # (telemetry counters; None without a CommConfig)
        self._comm_bytes = self._engine.comm_bytes_per_step(self._variables)
        # PRNGKey dispatches on the default backend, which need not be
        # this run's; build it on a device the run owns.  LOCAL device: in
        # multi-process runs the mesh leads with other processes'
        # (non-addressable) devices.
        with jax.default_device(self._device or self._mesh.local_devices[0]):
            key = jax.random.PRNGKey(seed)
        self._rng = self._place_scalar_tree(key)

        # ----- counters (reference stoke.py:237-243) -----
        self._grad_accum_counter = 0
        self._optimizer_steps = 0
        self._backward_steps = 0
        self._agg_loss = self._zero_scalar()
        self._agg_count = 0
        self._rolling_mean_loss = self._zero_scalar()
        self._ema_initialized = False
        self._ema_weight = float(ema_weight)
        self._skipped_steps = self._zero_scalar()
        self._last_step_loss = None
        # restart-cost accounting (ISSUE 14 satellite): the step of the
        # last durable save and a host-wall EMA of one optimizer step —
        # the preemption bundle carries both so the supervisor can price
        # an attempt's lost goodput without replaying JSONL
        self._last_save_step = 0
        self._step_wall_ema: Optional[float] = None
        self._last_boundary_t: Optional[float] = None

        # ----- lazy-step bookkeeping -----
        self._training = True
        self._token = 0
        self._stashed_model_call: Optional[tuple] = None
        self._pending: Optional[tuple] = None  # (new_grad_buf, new_scaler, token)

        self._replication_warned: set = set()
        self._materialize_warned = False
        self._tb_writer_obj = None

        # ----- telemetry (ISSUE 1: unified pipeline — registry + sinks +
        #       collectors; a None TelemetryConfig keeps the registry alive
        #       for the wall-clock aliases but attaches no sinks) -----
        self._telemetry = Telemetry(
            st.telemetry_config, rank=jax.process_index()
        )
        # instance-scoped recompile attribution: this engine reports shape-
        # driven recompiles to this run's tracker only (another facade's
        # shape churn in the same process is not this run's problem)
        self._engine._compile_tracker = self._telemetry.compile_tracker
        self._last_grad_norm: Optional[float] = None

        # ----- structured tracing (ISSUE 10: bounded host-span ring +
        #       Perfetto export + per-request serve timelines; default OFF
        #       — without a TraceConfig no recorder is registered and the
        #       composed span helper degrades to the bare xprof
        #       annotation.  Purely host-side either way: step-program
        #       HLO and dispatch counts are bit-identical with the config
        #       absent OR present) -----
        self._tracer = None
        tcfg = st.trace_config
        if tcfg is not None:
            from stoke_tpu.telemetry.tracing import (
                TraceRecorder,
                register_recorder,
            )

            self._tracer = TraceRecorder(
                tcfg,
                rank=jax.process_index(),
                registry=self._telemetry.registry,
            )
            register_recorder(self._tracer)

        # ----- persistent AOT compile cache (ISSUE 6: warm starts load
        #       backend compiles from the persistent XLA disk cache and
        #       the HLO-keyed program ledger books the reclaimed seconds;
        #       step programs ALWAYS dispatch through plain jax.jit —
        #       never through deserialized executables, which lose
        #       donated-input bookkeeping.  Default OFF — without a
        #       CompileConfig the engine dispatches exactly as before)
        # -----
        self._compile_cache = None
        ccfg = st.compile_config
        if ccfg is not None:
            from stoke_tpu.compile_cache import CompileCache

            self._compile_cache = CompileCache(
                ccfg, self._telemetry.registry
            )
            self._engine._compile_cache = self._compile_cache

        # ----- step-time attribution & goodput (ISSUE 4: CostCards, live
        #       MFU/roofline gauges, goodput ledger, anomaly-triggered
        #       xprof capture; default OFF — without an AttributionConfig
        #       the engine runs no cost analysis and the step programs
        #       are untouched) -----
        self._attribution = None
        acfg = st.attribution_config
        if acfg is not None:
            from stoke_tpu.telemetry.attribution import AttributionMonitor

            self._attribution = AttributionMonitor(
                acfg,
                self._telemetry.registry,
                compile_tracker=self._telemetry.compile_tracker,
                trace_dir=st.profiler_config.trace_dir,
            )
            self._telemetry.attribution = self._attribution
            self._engine._attribution = self._attribution.cost_cards

        # ----- health monitor (ISSUE 3: sentinels + detectors + flight
        #       recorder + watchdog; default OFF — without a HealthConfig
        #       the step paths are untouched) -----
        self._health: Optional[HealthMonitor] = None
        self._fleet = None  # assigned below; the recorder's fleet_fn
        self._numerics = None  # assigned below; the recorder's numerics_fn
        self._wire_error_warned = False
        self._last_sentinels = None  # closure may fire before then
        hcfg = st.health_config
        if hcfg is not None:
            bundle_dir = hcfg.bundle_dir
            if bundle_dir is None:
                base = (
                    st.telemetry_config.output_dir
                    if st.telemetry_config is not None
                    else "health"
                )
                bundle_dir = os.path.join(base, "postmortem")
            recorder = FlightRecorder(
                bundle_dir,
                ring_size=hcfg.ring_size,
                status_dict=st.to_dict(),
                mesh_info=self._mesh_info(),
                snapshot_fn=self._telemetry.registry.snapshot,
                install_signal_handlers=hcfg.dump_signals,
                # ISSUE 4 satellite: a post-mortem shows utilization at
                # time of death — the goodput summary and the last
                # analyzed CostCards join every bundle
                goodput_fn=(
                    self._telemetry.goodput_summary
                    if self._attribution is not None
                    else None
                ),
                cost_cards_fn=(
                    self._attribution.cost_cards.last_cards
                    if self._attribution is not None
                    else None
                ),
                # ISSUE 5: late-bound — the fleet monitor is constructed
                # after the health block so it can see the full registry;
                # bundles written before the first exchange carry no
                # fleet.json (snapshot() of a monitor-less run is None)
                fleet_fn=lambda: (
                    self._fleet.snapshot()
                    if self._fleet is not None
                    else None
                ),
                # ISSUE 10: the span ring at time of death — every bundle
                # gains a Perfetto-loadable trace.json when tracing is on
                trace_fn=(
                    self._tracer.to_trace_events
                    if self._tracer is not None
                    else None
                ),
                # ISSUE 12: late-bound like the fleet view — which LAYER
                # was bad at time of death (numerics.json); bundles
                # written before the monitor exists carry none
                numerics_fn=lambda: (
                    self._numerics.snapshot()
                    if self._numerics is not None
                    else None
                ),
            )
            self._health = HealthMonitor(
                hcfg,
                self._telemetry.registry,
                recorder,
                compile_tracker=self._telemetry.compile_tracker,
            )
            # leaf-level NaN provenance (ISSUE 12 satellite): the sentinel
            # row carries the first offending leaf INDEX; this table lets
            # the NonFiniteDetector name its path even without a
            # NumericsConfig
            from stoke_tpu.telemetry.numerics import leaf_path_names

            self._health.leaf_paths = leaf_path_names(
                self._variables["params"]
            )
            if self._attribution is not None:
                # the profiler auto-capture registers as a health
                # detector (PR 3 registry): captures surface in the
                # anomaly counters, ring, and post-mortem bundles
                from stoke_tpu.telemetry.attribution import (
                    AutoCaptureDetector,
                )

                self._health.detectors.append(
                    AutoCaptureDetector(
                        self._attribution, acfg.capture_action
                    )
                )

        # ----- fleet observability (ISSUE 5: cross-host skew aggregation,
        #       straggler detection, barrier-wait attribution; default OFF
        #       — without a FleetConfig no cross-host exchange ever runs
        #       and the step paths are untouched) -----
        fcfg = st.fleet_config
        if fcfg is not None:
            from stoke_tpu.telemetry.fleet import (
                FleetMonitor,
                FleetStragglerDetector,
            )

            self._fleet = FleetMonitor(
                fcfg,
                self._telemetry.registry,
                rank=jax.process_index(),
                n_processes=jax.process_count(),
                dispatch_count_fn=lambda: self._engine.dispatch_count,
            )
            self._telemetry.fleet = self._fleet
            if self._health is not None:
                # the straggler streak surfaces as a health anomaly
                # (PR 3 registry): counted, ringed, and bundled like any
                # other detector firing
                self._health.detectors.append(
                    FleetStragglerDetector(
                        self._fleet, fcfg.straggler_action
                    )
                )

        # ----- per-layer numerics observatory (ISSUE 12: module
        #       sentinels, NaN provenance, quantization-error attribution;
        #       default OFF — without a NumericsConfig the compiled step
        #       programs are bit-identical and no numerics/* field or
        #       gauge exists anywhere) -----
        ncfg = st.numerics_config
        if ncfg is not None:
            from stoke_tpu.telemetry.numerics import (
                NumericsMonitor,
                NumericsProvenanceDetector,
                leaf_path_names as _leaf_paths,
                module_groups,
            )

            self._numerics = NumericsMonitor(
                ncfg,
                self._telemetry.registry,
                module_groups(self._variables["params"]),
                leaf_paths=_leaf_paths(self._variables["params"]),
                rank=jax.process_index(),
            )
            self._telemetry.numerics = self._numerics
            if self._health is not None:
                # NaN provenance surfaces as a health anomaly (PR 3
                # registry): counted, ringed, bundled — and a halt action
                # stops the run at the facade boundary with the layer
                # named
                self._health.detectors.append(
                    NumericsProvenanceDetector(
                        self._numerics, ncfg.provenance_action
                    )
                )

        # ----- pod-scale resilience (ISSUE 7: preemption-aware emergency
        #       save, integrity-verified auto-resume with quarantine, and
        #       the deterministic fault injector; default OFF — without a
        #       ResilienceConfig no signal handler is installed, no
        #       manifest is written, and the step paths are untouched:
        #       bit-identical HLO, dispatch-count equal) -----
        self._resilience = None
        rcfg = st.resilience_config
        if rcfg is not None:
            from stoke_tpu.resilience import ResilienceMonitor

            # constructed AFTER the health block on purpose: with
            # resilience on, the preemption signals mean "drain and save",
            # so this monitor's handlers supersede the flight recorder's
            # dump-and-die disposition for those signals (the emergency
            # path writes a better corpse — a loadable checkpoint, plus a
            # post-mortem bundle when a HealthConfig is present)
            self._resilience = ResilienceMonitor(
                rcfg,
                self._telemetry.registry,
                recorder=(
                    self._health.recorder
                    if self._health is not None
                    else None
                ),
            )
            self._telemetry.resilience = self._resilience
            if self._resilience.chaos.active:
                # engine pre-dispatch hook only when a chaos spec is armed
                self._engine._chaos = self._resilience.chaos

        # ----- HBM capacity observatory (ISSUE 19: per-subsystem memory
        #       ledger, per-program memory_analysis peaks, OOM pre-flight;
        #       default OFF — without a MemoryConfig no observatory is
        #       constructed, no mem/* field or gauge exists anywhere, and
        #       the compiled programs are bit-identical) -----
        self._memory_obs = None
        mcfg = st.memory_config
        if mcfg is not None:
            from stoke_tpu import offload as _offload
            from stoke_tpu.telemetry.memory import (
                MemoryObservatory,
                transport_resident_bytes,
                tree_resident_bytes,
            )

            obs = MemoryObservatory(mcfg, self._telemetry.registry)
            obs.set_component(
                "params", lambda: tree_resident_bytes(self._variables)
            )
            # the disk store spills the optimizer state between steps
            # (self._opt_state is None then) — resident bytes are 0, the
            # transient reload is the step program's temp, not the ledger
            obs.set_component(
                "opt_state",
                lambda: (
                    0
                    if self._opt_state is None
                    else tree_resident_bytes(self._opt_state)
                ),
            )
            # per-shard via the transport's layout descriptor: the PR-8
            # sharded transport ledgers 1/world of the buckets + residual,
            # the PR-2 replicated one a full copy (None when inactive -> 0)
            obs.set_component(
                "transport",
                lambda: transport_resident_bytes(
                    self._engine.transport.layout_descriptor(
                        self._variables["params"]
                    )
                ),
            )
            obs.set_component("snapshot", _offload.staged_nbytes)
            self._memory_obs = obs
            self._telemetry.memory = obs
            # engine dispatch-funnel hook: one memory_analysis per
            # distinct (program, signature) at _aot_call
            self._engine._memory = obs
            # OOM pre-flight at build: resident-only (no program has
            # dispatched yet); warns BEFORE the first step can allocate
            obs.preflight("build")

        # ----- live ops plane (ISSUE 20: scrapeable HTTP observatory —
        #       /metrics via the sink's own renderer, /healthz drain
        #       signal, pinned /statusz, /requests, /trace, bounded
        #       /profile; default OFF — without an OpsPlaneConfig no
        #       thread starts and no socket binds, and with one the
        #       plane adds zero JSONL fields and zero dispatches) -----
        self._opsplane = None
        ocfg = st.opsplane_config
        if ocfg is not None:
            from stoke_tpu.telemetry.opsplane import OpsPlane

            plane = OpsPlane(
                ocfg, self._telemetry, rank=jax.process_index()
            )
            if self._health is not None:
                plane.attach_health(self._health)
            if self._tracer is not None:
                plane.attach_tracer(self._tracer)
            if self._attribution is not None:
                plane.attach_attribution(self._attribution)
            plane.attach_training(
                goodput=(
                    self._telemetry.goodput_summary
                    if self._attribution is not None
                    else None
                ),
                memory=(
                    self._memory_obs.summary
                    if self._memory_obs is not None
                    else None
                ),
                trace_summary=(
                    self._tracer.summary
                    if self._tracer is not None
                    else None
                ),
            )
            plane.start()
            self._opsplane = plane

        # ----- wall-clock breakdown (reference wall_clock_breakdown,
        #       configs.py:540; host-side dispatch times — device work is
        #       async, use profile_trace() for device timelines).  Backed by
        #       the telemetry registry; enabling telemetry implies it -----
        self._wall_clock_enabled = (
            st.profiler_config.wall_clock_breakdown
            or self._telemetry.enabled
            # tracing needs the facade phase sections live: each timed
            # phase is also a trace span (ISSUE 10 consolidation)
            or self._tracer is not None
        )

        # ----- post-init status (reference stoke.py:245) -----
        world = self._mesh.size if self._mesh is not None else 1
        st.set_post_init_values(world, n_processes=jax.process_count())
        if self._verbose and self.is_rank_0:
            unrolled_print(repr(st).splitlines())

    # ------------------------------------------------------------------ #
    # placement helpers
    # ------------------------------------------------------------------ #

    def _single_device_offload_target(self):
        """Host-memory placement for single-device optimizer offload, with
        the same probe/fallback policy as the mesh path."""
        import warnings

        from jax.sharding import SingleDeviceSharding

        try:
            # construction itself validates memory kinds on newer jax
            # (ValueError for backends without pinned_host) — it belongs
            # inside the probe, not before it
            target = SingleDeviceSharding(
                self._device, memory_kind="pinned_host"
            )
            with jax.default_device(self._device):
                jax.device_put(jnp.zeros((1,), jnp.float32), target)
            return target
        except Exception:
            cfg = self._status_obj.offload_optimizer_config
            if cfg is not None and cfg.fallback_to_device:
                warnings.warn(
                    "Stoke -- optimizer-state host offload unsupported on "
                    "this runtime; keeping state on device"
                )
                return self._device
            raise

    def _mesh_info(self) -> dict:
        """Topology description for post-mortem bundles (host-side only)."""
        try:
            if self._mesh is None:
                return {
                    "mesh": None,
                    "device": str(self._device),
                    "n_processes": jax.process_count(),
                }
            return {
                "axes": list(self._mesh.axis_names),
                "shape": {k: int(v) for k, v in self._mesh.shape.items()},
                "n_devices": int(self._mesh.size),
                "device_kinds": sorted(
                    {d.device_kind for d in self._mesh.devices.flat}
                ),
                "n_processes": jax.process_count(),
            }
        except Exception:
            return {"mesh": "unavailable"}

    def _opt_materialize(self):
        """Optimizer state as device arrays (reads the disk tier if the
        state is spilled; otherwise the live tree)."""
        if self._disk_store is not None and self._disk_store.spilled:
            return self._disk_store.load()
        return self._opt_state

    def _opt_commit(self, new_opt) -> None:
        """Hand updated optimizer state back to its tier (disk spill or the
        live facade slot)."""
        if self._disk_store is not None:
            self._disk_store.store(new_opt, protect=self._variables)
            self._opt_state = None
        else:
            self._opt_state = new_opt

    def _zero_scalar(self):
        # np scalar: creation must not touch the default accelerator backend
        return self._place_scalar_tree(np.float32(0.0))

    def _place_scalar_tree(self, tree):
        if self._rules is not None:
            repl = self._rules.replicated()
            return place_global_tree(tree, repl)
        return jax.device_put(tree, self._device)

    def _batch_sharding_for(self, shape, batch_dim: int = 0):
        if self._mesh is None:
            return self._device
        axis = self._rules.axis_name
        if axis not in self._mesh.axis_names:
            # mesh without a dp axis (pure pipeline/TP): batch replicated
            return NamedSharding(self._mesh, P())
        axis_size = self._mesh.shape[axis]
        nproc = jax.process_count()
        if nproc > 1:
            # multi-process: ``shape`` is the process-LOCAL slab; it must
            # divide evenly into this process's shards along the data axis
            # (axis_size/nproc of them).  Indivisible local batches are an
            # ERROR, not a replication fallback — each process holds
            # DIFFERENT local data, so a "replicated" global array would
            # silently mix batches.
            if len(shape) <= batch_dim:
                # batch-dim-less leaf (per-batch scalar/constant): replicate
                # under the same contract as the pure-TP mesh case — the user
                # feeds identical values on every process
                return NamedSharding(self._mesh, P())
            if axis_size % nproc != 0:
                raise ValueError(
                    f"Stoke -- the '{axis}' mesh axis (size {axis_size}) "
                    f"does not divide evenly across {nproc} processes; "
                    f"per-process batch feeding needs each process to own a "
                    f"whole number of data-axis shards. Reshape the mesh so "
                    f"the data axis is a multiple of the process count."
                )
            local_shards = axis_size // nproc
            if shape[batch_dim] % local_shards != 0:
                raise ValueError(
                    f"Stoke -- per-process batch leaf shape {shape} is not "
                    f"divisible by this process's {local_shards} shards of "
                    f"the '{axis}' mesh axis (size {axis_size}, "
                    f"{nproc} processes); in a multi-process run batches "
                    f"cannot be replicated consistently (each process holds "
                    f"different local data). Pad or drop-last so the "
                    f"per-process batch divides its shard count."
                )
        elif len(shape) <= batch_dim or shape[batch_dim] % axis_size != 0:
            # batch not divisible by the data axis: replicate, but tell the
            # user once per shape — they're paying full-batch compute on
            # every device without realizing it
            if len(shape) > batch_dim and shape not in self._replication_warned:
                self._replication_warned.add(shape)
                self.warn(
                    f"batch leaf shape {shape} is not divisible by the "
                    f"'{axis}' mesh axis ({self._mesh.shape[axis]}); "
                    f"replicating it on every device"
                )
            return NamedSharding(self._mesh, P())
        spec = [None] * (batch_dim + 1)
        spec[batch_dim] = axis
        # opt-in sequence-dim sharding (DataParallelConfig.shard_seq_dim):
        # pre-place inputs for sequence-parallel attention
        cfg = self._status_obj.dp_config
        sd = cfg.shard_seq_dim
        if (
            sd is not None
            and cfg.seq_axis_name in self._mesh.axis_names
            and len(shape) > sd
            and sd != batch_dim
            and shape[sd] % self._mesh.shape[cfg.seq_axis_name] == 0
        ):
            spec += [None] * (sd + 1 - len(spec))
            spec[sd] = cfg.seq_axis_name
        return NamedSharding(self._mesh, P(*spec))

    def _place_batch(self, tree, batch_dim: int = 0):
        """Host batch → device, sharded over the data axis (the TPU
        equivalent of ``place_data_on_gpu``, reference utils.py:39-80; for
        multi-host, each process contributes its local slice of the
        logically-global batch).  ``batch_dim=1`` serves stacked
        [grad_accum, micro_batch, ...] windows."""

        def _leaf(x):
            if isinstance(x, jax.Array):
                return x
            if hasattr(x, "detach"):  # torch tensor
                x = x.detach().cpu().numpy()
            x = np.asarray(x)
            sh = self._batch_sharding_for(x.shape, batch_dim)
            if self._mesh is not None and jax.process_count() > 1:
                return jax.make_array_from_process_local_data(sh, x)
            return jax.device_put(x, sh)

        with trace_span("stoke/place", track="facade"):
            return jax.tree_util.tree_map(_leaf, tree)

    # ------------------------------------------------------------------ #
    # mode toggles (torch module.train()/eval() equivalent)
    # ------------------------------------------------------------------ #

    def train(self) -> "Stoke":
        self._training = True
        return self

    def eval(self) -> "Stoke":
        self._training = False
        return self

    @property
    def training(self) -> bool:
        return self._training

    # ------------------------------------------------------------------ #
    # the 4-call contract
    # ------------------------------------------------------------------ #

    @_timed("model")
    def model(self, *args, **kwargs):
        """Wrapped forward (reference stoke.py:853-869).

        Train mode: returns a lazy :class:`DeferredOutput`; the actual
        forward runs fused with loss+grad inside ``loss()`` (one dispatch per
        micro-batch).  Eval mode: runs the compiled eval forward eagerly and
        returns real arrays.
        """
        placed_args = self._place_batch(args)
        placed_kwargs = self._place_batch(kwargs)
        if self._training:
            self._token += 1
            # stash the CURRENT rng: loss() will consume exactly this key for
            # the fused step, so a later .value read reproduces the same
            # dropout masks even after self._rng has advanced
            self._stashed_model_call = (
                placed_args, placed_kwargs, self._token, self._rng
            )
            return DeferredOutput(self._materialize, self._token)
        return self._engine.eval_fwd(self._variables, placed_args, placed_kwargs)

    def _materialize(self, token: int):
        if self._stashed_model_call is None or self._stashed_model_call[2] != token:
            raise RuntimeError(
                "Stoke -- stale DeferredOutput: materialize before the next "
                "model() call"
            )
        margs, mkwargs, _, rng = self._stashed_model_call
        if not self._materialize_warned:
            self._materialize_warned = True
            self.warn(
                "DeferredOutput.value runs a SECOND compiled forward (the "
                "fused step computes its own); reading .value every step "
                "doubles forward compute. Use it for debugging/metrics only."
            )
        return self._engine.train_fwd(self._variables, rng, margs, mkwargs)

    @_health_guarded
    @_timed("loss")
    def loss(self, *args, **kwargs):
        """Wrapped loss (reference stoke.py:872-912).

        Train mode: runs the compiled fused micro-step (forward + loss +
        grad + buffer-accumulate) and returns device-scalar losses already
        divided by ``grad_accum`` (reference stoke.py:901-911).  The
        cross-replica loss sync of the reference (.item() + allreduce every
        micro-batch, distributed.py:619-646) is free here: the loss is
        computed over the logically-global batch.
        """
        flat, treedef = jax.tree_util.tree_flatten(
            (args, kwargs), is_leaf=is_deferred
        )
        deferred_info = []
        arrays = []
        for i, leaf in enumerate(flat):
            if is_deferred(leaf):
                if (
                    self._stashed_model_call is None
                    or leaf._token != self._stashed_model_call[2]
                ):
                    raise RuntimeError(
                        "Stoke -- loss() received a DeferredOutput from a "
                        "previous model() call; call model() then loss() in "
                        "order"
                    )
                deferred_info.append((i, leaf._path))
            else:
                arrays.append(leaf)
        if self._training and deferred_info:
            # consume the rng stashed at model() time — the SAME key a
            # .value materialization uses, so dropout masks always agree
            margs, mkwargs, token, rng = self._stashed_model_call
            arrays = self._place_batch(arrays)
            report, updated, new_buf, new_scaler, new_rng = (
                self._engine.accum_step(
                    self._variables,
                    self._grad_buf,
                    self._scaler_state,
                    rng,
                    margs,
                    mkwargs,
                    arrays,
                    treedef,
                    tuple(deferred_info),
                    True,
                )
            )
            self._rng = new_rng
            if updated:
                self._variables = {**self._variables, **updated}
            # new_scaler (carrying per-loss overflow flags in num_losses>1
            # mode) commits at backward() time together with the buffer —
            # a dropped pending loss must not skip steps or back off scales
            self._pending = (new_buf, new_scaler, token)
            self._update_loss_tracking(report)
            return report
        # eval path (or no deferred handle): materialize + loss-only
        full = [leaf.value if is_deferred(leaf) else leaf for leaf in flat]
        placed = self._place_batch(full)
        report = self._engine.loss_eval(placed, treedef)
        if self._training:
            # this loss produced NO gradients; drop any stale pending buffer
            # so a following backward() errors instead of committing grads
            # from an earlier, unrelated loss() call
            self._pending = None
            # keep the fused-path convention: training losses are returned
            # divided by grad_accum (reference stoke.py:901-911)
            inv = 1.0 / self._status_obj.grad_accum
            report = jax.tree_util.tree_map(lambda l: l * inv, report)
            self._update_loss_tracking(report)
        return report

    @_timed("backward")
    def backward(self, loss: Any = None) -> None:
        """Wrapped backward (reference stoke.py:960-988): commits the grads
        of the last ``loss()`` into the accumulation buffer and advances the
        micro-step counters.  The gradients were already computed inside the
        fused step; an uncommitted pending buffer is simply dropped, so
        "no backward → no gradient contribution" holds."""
        if not self._training:
            raise RuntimeError("Stoke -- backward() called in eval mode")
        if self._pending is None:
            raise RuntimeError(
                "Stoke -- backward() called without a preceding loss() on a "
                "model() output"
            )
        new_buf, new_scaler, _ = self._pending
        self._grad_buf = new_buf
        # per-loss fp16 mode: overflow flags observed in the micro-step
        # join the scaler state only now that its grads are committed
        self._scaler_state = new_scaler
        self._pending = None
        self._grad_accum_counter += 1
        self._backward_steps += 1

    @_health_guarded
    @_timed("step")
    def step(self) -> None:
        """Wrapped optimizer step (reference stoke.py:990-1040): at the
        accumulation boundary runs the compiled apply (unscale → finite-check
        → clip → update → zero buffer → scaler update); otherwise a no-op.
        """
        if self._grad_accum_counter < self._status_obj.grad_accum:
            return
        will_record = self._telemetry_will_record()
        if will_record:
            self._sample_grad_norm()
        t0 = time.perf_counter() if (
            will_record and self._telemetry.will_sample_device()
        ) else None
        (
            self._variables,
            new_opt,
            self._grad_buf,
            self._scaler_state,
            self._comm_state,
            sentinels,
            numerics,
            finite,
        ) = self._engine.apply_step(
            self._variables,
            self._opt_materialize(),
            self._grad_buf,
            self._scaler_state,
            self._comm_state,
            self._health_loss_input(),
        )
        self._opt_commit(new_opt)
        if t0 is not None:
            # periodic true-device-time sample: one host sync per logging
            # window (async dispatch hides device time otherwise)
            jax.block_until_ready(self._variables)
            self._telemetry.observe_device_step(time.perf_counter() - t0)
        if self._precision.scaled:
            self._skipped_steps = self._skipped_steps + (
                1.0 - finite.astype(jnp.float32)
            )
        self._optimizer_steps += 1
        self._grad_accum_counter = 0
        self._reset_tracking_window()
        self._observe_numerics(numerics)
        self._observe_health(sentinels)
        self._maybe_log_metrics()
        self._maybe_emit_telemetry()
        self._maybe_auto_save()
        self._resilience_boundary()

    @_health_guarded
    @_timed("train_step")
    def train_step(
        self,
        model_args: Any,
        loss_args: Any = (),
        model_kwargs: Optional[dict] = None,
    ):
        """Fused fast path: one compiled dispatch per micro-step, with the
        optimizer apply fused in at the accumulation boundary.

        Semantically identical to ``model → loss → backward → step`` (same
        compiled math, same counters/EMA/scaler behavior) but with half the
        dispatches — with ``grad_accum == 1`` a full optimizer step is ONE
        XLA program.  The 4-call API remains for reference-contract parity;
        use this in throughput-critical loops.

        Args:
            model_args: positional args for the model (a single array or a
                tuple of arrays).
            loss_args: extra args for the loss after the model output (a
                single array or tuple): ``loss_fn(out, *loss_args)``.
            model_kwargs: optional keyword args for the model.

        Returns the loss report (divided by grad_accum, like ``loss()``).
        """
        if not self._training:
            raise RuntimeError("Stoke -- train_step() called in eval mode")
        if not isinstance(model_args, tuple):
            model_args = (model_args,)
        if not isinstance(loss_args, tuple):
            loss_args = (loss_args,)
        margs = self._place_batch(model_args)
        mkwargs = self._place_batch(model_kwargs or {})
        # loss call structure: loss_fn(out, *loss_args) — the model output
        # slot is a deferred leaf at flat index 0 with an empty path
        sentinel = DeferredOutput(None, -1)
        flat, treedef = jax.tree_util.tree_flatten(
            ((sentinel, *loss_args), {}), is_leaf=is_deferred
        )
        arrays = self._place_batch([l for l in flat if not is_deferred(l)])
        deferred_info = tuple(
            (i, l._path) for i, l in enumerate(flat) if is_deferred(l)
        )
        do_apply = self._grad_accum_counter + 1 >= self._status_obj.grad_accum
        will_record = do_apply and self._telemetry_will_record()
        t0 = time.perf_counter() if (
            will_record and self._telemetry.will_sample_device()
        ) else None
        (
            report,
            _updated,
            self._variables,
            new_opt,
            self._grad_buf,
            self._scaler_state,
            self._comm_state,
            self._rng,
            sentinels,
            numerics,
            finite,
        ) = self._engine.fused_step(
            self._variables,
            self._opt_materialize() if do_apply else self._opt_state,
            self._grad_buf,
            self._scaler_state,
            self._comm_state,
            self._rng,
            margs,
            mkwargs,
            arrays,
            treedef,
            deferred_info,
            do_apply,
        )
        if do_apply:
            self._opt_commit(new_opt)
        else:
            self._opt_state = new_opt
        if t0 is not None:
            jax.block_until_ready(self._variables)
            self._telemetry.observe_device_step(time.perf_counter() - t0)
        self._pending = None
        self._backward_steps += 1
        self._update_loss_tracking(report)
        if do_apply:
            if self._precision.scaled:
                self._skipped_steps = self._skipped_steps + (
                    1.0 - finite.astype(jnp.float32)
                )
            self._optimizer_steps += 1
            self._grad_accum_counter = 0
            self._reset_tracking_window()
            self._observe_numerics(numerics)
            self._observe_health(sentinels)
            self._maybe_log_metrics()
            self._maybe_emit_telemetry()
            self._maybe_auto_save()
            self._resilience_boundary()
        else:
            self._grad_accum_counter += 1
        return report

    # ------------------------------------------------------------------ #
    # TensorBoard metrics (reference DeepspeedTensorboardConfig,
    # configs.py:392-405 — passthrough there, first-class here)
    # ------------------------------------------------------------------ #

    @property
    def _tb_writer(self):
        cfg = self._status_obj.tensorboard_config
        if cfg is None or not self.is_rank_0:
            return None
        if self._tb_writer_obj is None:
            import os

            from stoke_tpu.utils.tb_writer import TBEventWriter

            # native event writer (utils/tb_writer.py) — same file format,
            # no torch import on the metrics path
            self._tb_writer_obj = TBEventWriter(
                os.path.join(cfg.output_path, cfg.job_name)
            )
        return self._tb_writer_obj

    def log_scalar(self, tag: str, value, step: Optional[int] = None) -> None:
        """Log a user scalar: lands in the telemetry registry (gauge
        ``user/<tag>``, mirrored to sinks at the next cadence) AND — for
        parity with the legacy contract — immediately in TensorBoard when a
        ``TensorboardConfig`` is supplied on rank 0."""
        self._telemetry.log_scalar(tag, float(value))
        w = self._tb_writer
        if w is not None:
            w.add_scalar(tag, float(value), step if step is not None
                         else self._optimizer_steps)

    @staticmethod
    def _crossed_boundary(steps: int, every: int, window: int) -> bool:
        """True if any multiple of ``every`` falls in ``(steps-window,
        steps]`` — the cadence check for step paths that advance the counter
        by more than one (train_steps segments)."""
        return steps > 0 and steps // every > (steps - window) // every

    def _maybe_log_metrics(self, window: int = 1) -> None:
        cfg = self._status_obj.tensorboard_config
        if (
            cfg is None
            or self._optimizer_steps == 0
            or not self._crossed_boundary(
                self._optimizer_steps, cfg.log_every_n_steps, window
            )
        ):
            return
        w = self._tb_writer
        if w is None:
            return
        step = self._optimizer_steps
        w.add_scalar("loss/ema", self.ema_loss, step)
        if self._last_step_loss is not None:
            w.add_scalar("loss/micro", self.step_loss, step)
        if self._precision.scaled:
            ls = self.loss_scale
            if isinstance(ls, list):  # per-loss scalers: one curve each
                for i, v in enumerate(ls):
                    w.add_scalar(f"scaler/loss_scale_{i}", v, step)
            else:
                w.add_scalar("scaler/loss_scale", ls, step)
            w.add_scalar("scaler/skipped_steps", self.skipped_optimizer_steps, step)
        w.add_scalar("counters/backward_steps", self._backward_steps, step)
        w.flush()

    # ------------------------------------------------------------------ #
    # telemetry step records (ISSUE 1: structured per-window events)
    # ------------------------------------------------------------------ #

    def _telemetry_will_record(self, window: int = 1) -> bool:
        """True when the optimizer step(s) about to complete cross the
        telemetry logging cadence (decides whether to pay for the optional
        device-side samples: grad-norm reduction, block_until_ready)."""
        t = self._telemetry
        return t.enabled and self._crossed_boundary(
            self._optimizer_steps + window,
            t.config.log_every_n_steps,
            window,
        )

    def _sample_grad_norm(self) -> None:
        """Global norm of the accumulated gradient buffer (one device
        reduction + fetch; only at the logging cadence and only when
        ``TelemetryConfig.grad_norm``).  In fp16 single-loss mode the
        buffer holds scale-multiplied grads (the apply unscales them,
        engine._apply_core); the norm is divided by the current scale here
        so the logged value is in true-gradient units.  Per-loss mode
        (num_losses > 1) unscales into the buffer immediately, so no
        adjustment applies.

        With health sentinels on this whole extra reduction is skipped:
        the sentinel vector already carries the same norm computed inside
        the compiled apply (``_observe_health`` installs it — ISSUE 3
        satellite: no second reduction/dispatch)."""
        t = self._telemetry
        if not (t.enabled and t.config.grad_norm):
            return
        if self._engine.sentinels_enabled:
            return
        try:
            import optax

            norm = float(jax.device_get(optax.global_norm(self._grad_buf)))
            if (
                self._precision.scaled
                and self._status_obj.precision_config.num_losses == 1
            ):
                scale = float(jax.device_get(self._scaler_state["scale"]))
                if scale > 0:
                    norm /= scale
            self._last_grad_norm = norm
            t.registry.gauge("train/grad_norm").set(norm)
        except Exception:
            self._last_grad_norm = None

    def _sample_comm_residual_norm(self) -> Optional[float]:
        """Global norm of the error-feedback residual (one device
        reduction + fetch, only at the logging cadence) — the
        "quantization error being carried" gauge; near-constant norm over
        training is the error-feedback-working signal."""
        residual = (self._comm_state or {}).get("residual")
        if residual is None:
            return None
        try:
            import optax

            norm = float(jax.device_get(optax.global_norm(residual)))
            self._telemetry.registry.gauge("comm/residual_norm").set(norm)
            return norm
        except Exception:
            return None

    # ------------------------------------------------------------------ #
    # health monitor (ISSUE 3: sentinels / detectors / recorder / watchdog)
    # ------------------------------------------------------------------ #

    def _health_loss_input(self):
        """Boundary loss scalar for the 4-call apply's sentinel vector
        (None — an empty jit input — when sentinels are off, keeping the
        compiled program bit-identical to a health-free build)."""
        if not self._engine.sentinels_enabled:
            return None
        if self._last_step_loss is not None:
            return self._last_step_loss
        return self._zero_scalar()

    def _observe_health(self, sentinels, window: int = 1) -> None:
        """Feed the just-completed optimizer step(s) to the health monitor:
        fetch the on-device sentinel rows (one tiny host transfer — the
        values were computed inside the step's existing dispatch), run the
        detector registry, and cache the latest row for the telemetry step
        event.  A ``halt``-action detector raises
        :class:`~stoke_tpu.telemetry.health.HealthHaltError` from inside
        ``HealthMonitor.observe`` — i.e. at this facade boundary."""
        h = self._health
        if h is None:
            return
        rows = None
        if sentinels is not None:
            rows = np.asarray(jax.device_get(sentinels), np.float32)
            if rows.ndim == 1:
                rows = rows[None]
            self._last_sentinels = rows[-1]
            t = self._telemetry
            if t.enabled and t.config.grad_norm:
                # sentinel delegation (ISSUE 3 satellite): the in-step
                # grad norm replaces _sample_grad_norm's host-side extra
                # reduction — same true-gradient units (the apply core
                # unscales before the norm)
                gn = float(rows[-1][SENTINEL_INDEX["grad_norm"]])
                self._last_grad_norm = gn
                t.registry.gauge("train/grad_norm").set(gn)
        first = self._optimizer_steps - window + 1
        for i in range(window):
            h.observe(first + i, rows[i] if rows is not None else None)

    # ------------------------------------------------------------------ #
    # per-layer numerics (ISSUE 12: module sentinels / provenance / quant)
    # ------------------------------------------------------------------ #

    def _observe_numerics(self, numerics, window: int = 1) -> None:
        """Feed the just-completed optimizer step(s)' per-group stats
        matrices to the numerics monitor (one tiny host transfer — the
        values were computed inside the step's existing dispatch).  NaN
        provenance derived here is drained into the health anomaly
        pipeline by the ``numerics_provenance`` detector at the
        ``_observe_health`` call that immediately follows."""
        m = self._numerics
        if m is None or numerics is None:
            return
        rows = np.asarray(jax.device_get(numerics), np.float32)
        m.observe_window(self._optimizer_steps - window + 1, rows)

    def _sample_wire_error(self) -> None:
        """Per-group error-feedback residual norms at the logging cadence
        (ISSUE 12 signal family 3a): one small host fetch, attributed to
        module groups through the transport's bucket layout.  Skipped
        when no residual is carried, when the config opts out, or when
        the sharded residual's shards are not addressable (multi-host —
        a diagnostic must never wedge the step path)."""
        m = self._numerics
        if m is None or not m.cfg.wire_error:
            return
        try:
            from stoke_tpu.telemetry.numerics import (
                wire_residual_group_norms,
            )

            m.observe_wire(
                wire_residual_group_norms(
                    self._engine.transport,
                    self._comm_state,
                    self._variables["params"],
                    m.groups,
                )
            )
        except Exception as e:
            # non-addressable sharded shards (multi-host) and any future
            # attribution defect degrade to "no wire signal" — but say so
            # ONCE, the bounded-warning discipline: a silently-absent
            # signal family reads as "nothing to report" when it is
            # actually broken
            if not self._wire_error_warned:
                self._wire_error_warned = True
                self.warn(
                    f"per-layer wire-error attribution unavailable "
                    f"({type(e).__name__}: {e}); numerics wire_err will "
                    f"be absent this run"
                )

    @property
    def numerics(self):
        """The run's per-layer numerics monitor (None without a
        ``NumericsConfig``) — per-group stats, NaN provenance history,
        quantization-error attribution."""
        return self._numerics

    @property
    def numerics_summary(self) -> Optional[Dict[str, Any]]:
        """End-of-run per-layer numerics ranking: groups ordered by
        gradient-noise (running std/mean of each group's grad rms) and by
        quantization error, the latest per-group stats, and every
        non-finite provenance event.  None without a
        ``NumericsConfig``."""
        if self._numerics is None:
            return None
        return self._numerics.summary()

    @property
    def memory(self):
        """The run's HBM capacity observatory (None without a
        ``MemoryConfig``) — subsystem ledger callables, per-program
        memory cards, pre-flight verdicts."""
        return self._memory_obs

    @property
    def memory_summary(self) -> Optional[Dict[str, Any]]:
        """HBM capacity ledger (ISSUE 19): subsystems ranked by resident
        bytes (params / optimizer state / grad transport / KV cache /
        staged snapshots — the components recombine exactly into the
        resident total), per-program ``memory_analysis`` peaks, the OOM
        pre-flight verdicts, and the analytic-vs-live reconciliation.
        None without a ``MemoryConfig``."""
        if self._memory_obs is None:
            return None
        return self._memory_obs.summary()

    @property
    def health(self) -> Optional[HealthMonitor]:
        """The run's health monitor (None without a ``HealthConfig``)."""
        return self._health

    @property
    def opsplane(self):
        """The run's live ops plane (None without an ``OpsPlaneConfig``)
        — the bound HTTP observatory serving /metrics, /healthz,
        /statusz, /requests, /trace and /profile for this rank."""
        return self._opsplane

    @property
    def attribution(self):
        """The run's step-time attribution monitor (None without an
        ``AttributionConfig``) — cost cards, live MFU gauges, goodput
        ledger, auto-capture state."""
        return self._attribution

    @property
    def goodput(self) -> Optional[Dict[str, Any]]:
        """End-of-run goodput accounting: cumulative bucket seconds
        (productive/compile/recompile/loader/checkpoint/halt), goodput
        fraction, aggregate achieved TFLOP/s + MFU, capture paths.  None
        without an ``AttributionConfig``."""
        return self._telemetry.goodput_summary()

    @property
    def fleet(self):
        """The run's fleet monitor (None without a ``FleetConfig``) —
        per-host signal matrix, skew aggregates, straggler streak state."""
        return self._fleet

    @property
    def compile_cache(self):
        """The run's persistent AOT compile cache (None without a
        ``CompileConfig``) — hit/miss counts, reclaimed compile seconds
        (``.stats()``), and the cache directory."""
        return self._compile_cache

    @property
    def fleet_summary(self) -> Optional[Dict[str, Any]]:
        """End-of-run fleet accounting: exchange windows, the latest
        per-host signal matrix + aggregates + straggler verdict, and the
        straggler counts.  None without a ``FleetConfig``."""
        return self._telemetry.fleet_summary()

    @property
    def tracer(self):
        """The run's structured-trace recorder (None without a
        ``TraceConfig``) — the bounded span ring, Perfetto exporter, and
        critical-path summary."""
        return self._tracer

    @property
    def trace_summary(self) -> Optional[Dict[str, Any]]:
        """Critical-path/self-time summary of the trace ring's window
        (per-span-name counts, total and self seconds, and the ranked
        ``critical_path`` — host spans are serial, so the top self-time
        entries are where the host wall clock went).  None without a
        ``TraceConfig``.  A nonzero ``trace/dropped_total`` key means the
        bounded ring evicted spans — the window describes the RECENT
        tail, and any span-derived walk (critical path, serve SLO
        attribution) is partial, not complete."""
        if self._tracer is None:
            return None
        return self._tracer.summary()

    def export_trace(self, path: Optional[str] = None) -> Optional[str]:
        """Write the span ring as Chrome/Perfetto trace-event JSON
        (``trace.rank<N>.json`` under ``TraceConfig.output_dir`` unless
        ``path`` overrides); returns the path, or None without a
        ``TraceConfig``.  ``close_telemetry()`` calls this automatically
        when ``TraceConfig.export_on_close`` is set; calling it mid-run
        snapshots the current ring (load in ui.perfetto.dev, or merge
        ranks with ``scripts/merge_rank_traces.py``)."""
        if self._tracer is None:
            return None
        return self._tracer.export(path)

    def audit(
        self,
        serve=None,
        *,
        replicated_bytes_threshold: Optional[int] = None,
        churn_threshold: Optional[int] = None,
        cost_manifest: Optional[dict] = None,
        cost_tolerance: Optional[float] = None,
        mem_manifest: Optional[dict] = None,
        mem_tolerance: Optional[float] = None,
    ):
        """Static program audit of this LIVE build (ISSUE 15): re-lower
        every step program the engine has dispatched (and, with
        ``serve=engine``, a serving engine's prefill/decode/chunk
        programs) from their recorded abstract specs and check the
        repo's codified program invariants — donation integrity (every
        declared ``donate_argnums`` entry actually aliased; no
        deserialized-executable dispatch, the PR-6/PR-14 hazard), hidden
        host round-trips (callbacks/infeed in a step program), recompile
        hazards (weak-typed scalar args, shape-signature churn against
        the engine's 1024-entry memo), and the sharding audit (large
        replicated tensors on a partitioned program; collectives
        cross-checked against the gradient transport's analytic
        ``bytes_per_step``).

        Lowering/tracing only — NO compile, NO dispatch: the compiled
        programs, dispatch count, and training state are untouched
        (dispatch-count equality is acceptance-tested).  Returns an
        :class:`~stoke_tpu.analysis.program.AuditReport`; findings carry
        rule ids and named remedies (the status-rule discipline), tick
        ``analysis/programs_audited_total`` /
        ``analysis/audit_findings_total`` on the telemetry registry, and
        are warned once rank-0 so an interactive audit is never silent.

        Run the step APIs you care about first — the audit covers what
        the engine actually dispatched (``scripts/stoke_lint.py
        --programs`` drives all four step APIs end-to-end; the jax-free
        source lints live there too)."""
        from stoke_tpu.analysis.program import audit_program_specs

        specs = self._engine.audit_specs()
        if serve is not None:
            specs += serve.audit_specs()
        kwargs = {}
        if replicated_bytes_threshold is not None:
            kwargs["replicated_bytes_threshold"] = replicated_bytes_threshold
        if churn_threshold is not None:
            kwargs["churn_threshold"] = churn_threshold
        if cost_manifest is not None:
            # cost-drift gate (ISSUE 18): re-lower each serve spec's cost
            # against the committed analytic manifest
            kwargs["cost_manifest"] = cost_manifest
        if cost_tolerance is not None:
            kwargs["cost_tolerance"] = cost_tolerance
        if mem_manifest is not None:
            # memory-drift gate (ISSUE 19): re-compile each serve spec and
            # compare its memory_analysis temp/peak bytes against the
            # committed manifest (both directions, grew AND shrank)
            kwargs["mem_manifest"] = mem_manifest
        if mem_tolerance is not None:
            kwargs["mem_tolerance"] = mem_tolerance
        report = audit_program_specs(
            specs,
            transport_active=self._engine.transport.active,
            comm_bytes=self._comm_bytes,
            # None (not {}) when the engine never tracked signatures —
            # the churn rule then reports itself unchecked instead of
            # vacuously clean
            shape_sig_counts=(
                self._engine.shape_sig_counts()
                if self._engine._compile_tracker is not None
                else None
            ),
            **kwargs,
        )
        if self._engine._audit_truncated:
            report.notes.append(
                f"program inventory truncated at the engine's "
                f"{self._engine._MAX_AUDIT_SPECS}-spec audit cap — "
                f"programs first dispatched after the cap were NOT "
                f"audited"
            )
        reg = self._telemetry.registry
        reg.counter(
            "analysis/programs_audited_total",
            help="programs checked by Stoke.audit()",
        ).inc(len(report.programs))
        reg.counter(
            "analysis/audit_findings_total",
            help="program-audit findings (docs/analysis.md rule catalog)",
        ).inc(len(report.findings))
        if report.findings and self.is_rank_0:
            import warnings

            warnings.warn(
                "Stoke -- program audit found "
                f"{len(report.findings)} issue(s):\n" + report.format()
            )
        return report

    @property
    def dispatch_count(self) -> int:
        """Compiled-program invocations issued by this run's engine (the
        health acceptance counter: sentinels must not add dispatches)."""
        return self._engine.dispatch_count

    @property
    def comm_bytes(self) -> Optional[Dict[str, int]]:
        """Analytic per-device bytes-on-wire of ONE optimizer step's
        gradient exchange (None without a ``CommConfig``): ``prequant``
        what the schedule moves in fp32, ``onwire`` what the configured
        wire dtype moves, and — under the ISSUE 8 weight-update-sharded
        path — ``param_gather``, the updated-parameter all-gather leg
        (0 under fsdp, where params stay sharded)."""
        return None if self._comm_bytes is None else dict(self._comm_bytes)

    def _maybe_emit_telemetry(self, window: int = 1) -> None:
        """Assemble + emit one structured step event at the telemetry
        cadence (JSONL / Prometheus / TB sinks).  Device->host transfers
        (EMA loss, loss scale) happen only here, never per micro-batch."""
        if self._tracer is not None:
            # tag subsequent spans with the last completed optimizer step
            # (the step anchor the cross-rank trace merge aligns on)
            self._tracer.set_step(self._optimizer_steps)
        t = self._telemetry
        if not t.enabled or self._optimizer_steps == 0:
            return
        if self._attribution is not None:
            # per-boundary hook: closes an in-flight auto-capture trace
            # window once it covered its configured step count
            self._attribution.on_step(self._optimizer_steps)
        # samples/sec source of truth: one optimizer step consumes one
        # (global) effective batch — counted per boundary, emitted at the
        # cadence
        t.add_samples((self._status_obj.effective_batch_size or 0) * window)
        # gradient bytes-on-wire: analytic per-step counts (ISSUE 2) —
        # ``prequant`` what the fp32 schedule would move, ``onwire`` what
        # the configured wire dtype moves; the JSONL record carries the
        # per-window deltas so the compression win is measurable per run
        if self._comm_bytes is not None:
            t.registry.counter("comm/grad_bytes_prequant_total").inc(
                self._comm_bytes["prequant"] * window
            )
            t.registry.counter("comm/grad_bytes_onwire_total").inc(
                self._comm_bytes["onwire"] * window
            )
            # sharded weight-update path (ISSUE 8): the second wire leg —
            # updated-parameter all-gather back to the tier placement
            # (present only for a ShardedGradTransport; 0 under fsdp
            # where params stay sharded)
            if "param_gather" in self._comm_bytes:
                t.registry.counter("comm/param_gather_bytes_total").inc(
                    self._comm_bytes["param_gather"] * window
                )
        if not self._crossed_boundary(
            self._optimizer_steps, t.config.log_every_n_steps, window
        ):
            return
        # per-layer wire-error attribution (ISSUE 12): refresh the
        # per-group residual norms once per logged window so the record
        # assembled below carries them
        self._sample_wire_error()
        scaled = self._precision.scaled
        sent = (
            unpack_sentinels(self._last_sentinels)
            if self._last_sentinels is not None
            else {}
        )
        record = t.record_step(
            self._optimizer_steps,
            window_steps=window,
            ema_loss=self.ema_loss,
            step_loss=self.step_loss,
            grad_norm=self._last_grad_norm,
            loss_scale=self.loss_scale if scaled else None,
            skipped_steps=self.skipped_optimizer_steps if scaled else 0.0,
            comm_residual_norm=self._sample_comm_residual_norm(),
            param_norm=sent.get("param_norm"),
            update_ratio=sent.get("update_ratio"),
            nonfinite_leaves=sent.get("nonfinite_leaves"),
            health_anomalies=(
                float(self._health.anomaly_count)
                if self._health is not None
                else None
            ),
        )
        if record is not None and self._health is not None:
            # flight-recorder ring: the post-mortem bundle replays the
            # last N structured step events alongside the sentinel rows
            self._health.recorder.record_event(record)
        self._last_grad_norm = None

    def close_telemetry(self) -> None:
        """Flush + close the telemetry sinks and the health monitor
        (watchdog thread + signal handlers); idempotent — sinks are
        line-buffered/atomic, so skipping this loses at most nothing."""
        if (
            self._health is not None
            and self._fleet is not None
            and self._fleet._pending_straggler is not None
        ):
            # a straggler streak that completed on the run's FINAL window
            # has no later step observation to drain it — run the
            # detectors once more so the anomaly (and its dump bundle,
            # for action='dump') is recorded instead of silently lost.
            # Sentinel-driven detectors skip on None; a halt from a
            # registry-driven detector must not raise out of shutdown.
            try:
                self._health.observe(self._optimizer_steps, None)
            except HealthHaltError:
                pass
        if self._opsplane is not None:
            # unbind the socket FIRST: a scraper hitting a half-closed
            # run would read torn summaries from closing subsystems
            self._opsplane.close()
        if self._tracer is not None:
            # stop receiving other runs' spans, then export the final ring
            # (idempotent: a second close re-exports the same ring)
            from stoke_tpu.telemetry.tracing import unregister_recorder

            unregister_recorder(self._tracer)
            tcfg = self._status_obj.trace_config
            if tcfg is not None and tcfg.export_on_close:
                try:
                    self._tracer.export()
                except OSError as e:
                    self.warn(f"trace export failed: {e}")
        self._telemetry.close()
        if self._resilience is not None:
            # uninstall the preemption signal handlers BEFORE the health
            # recorder's (reverse install order, idempotent): resilience
            # installed last, so its saved "previous" SIGTERM handler is
            # the recorder's — restoring it AFTER the recorder uninstalled
            # would leave a closed recorder's handler claiming the signal
            # with nothing to chain to, and SIGTERM would be swallowed
            self._resilience.close()
        if self._health is not None:
            self._health.close()

    def _maybe_auto_save(self, window: int = 1) -> None:
        """Periodic checkpoint from the step path when
        ``CheckpointConfig.save_every_n_steps`` is set — the crash-recovery
        half of checkpoint-restart (SURVEY.md §5: the reference has none).
        ``window``: how many optimizer steps the caller just advanced (a
        train_steps segment may cross a save boundary mid-segment)."""
        cfg = self._status_obj.checkpoint_config
        if (
            cfg.save_every_n_steps
            and cfg.auto_path
            and self._crossed_boundary(
                self._optimizer_steps, cfg.save_every_n_steps, window
            )
        ):
            self.save(cfg.auto_path, name=cfg.auto_name)

    def wait_for_checkpoint(self) -> None:
        """Block until in-flight async checkpoint saves finish
        (``CheckpointConfig(async_save=True)``)."""
        from stoke_tpu import io_ops

        io_ops.wait_for_saves()

    def _note_durable_save(self, step: int) -> None:
        """One checkpoint's write fully landed (io_ops ``on_durable``,
        possibly from a background thread — a GIL-atomic max-update).
        The lost-goodput estimate prices steps beyond THIS point."""
        self._last_save_step = max(self._last_save_step, int(step))

    def maybe_resume(self, path: Optional[str] = None) -> bool:
        """Resume from the newest auto-checkpoint if one exists; otherwise
        start fresh.  Returns True when a checkpoint was loaded.  Combined
        with ``CheckpointConfig(save_every_n_steps=..., auto_path=...)`` this
        makes training loops restart-safe:

            stoke.maybe_resume()
            for batch in loader: stoke.train_step(*batch)
        """
        cfg = self._status_obj.checkpoint_config
        target = path or cfg.auto_path
        if not target:
            return False
        try:
            self.load(target, name=cfg.auto_name)
            return True
        except FileNotFoundError:
            return False

    # ------------------------------------------------------------------ #
    # pod-scale resilience (ISSUE 7: preemption-aware save / verified
    # resume / fault injection; every hook below is a no-op without a
    # ResilienceConfig)
    # ------------------------------------------------------------------ #

    @property
    def resilience(self):
        """The run's resilience monitor (None without a
        ``ResilienceConfig``) — preemption flag, chaos injector,
        ``resilience/*`` counters."""
        return self._resilience

    @property
    def resilience_summary(self) -> Optional[Dict[str, Any]]:
        """End-of-run resilience accounting: restarts, preemptions,
        emergency saves, quarantined tags, resumed/lost steps.  None
        without a ``ResilienceConfig``."""
        if self._resilience is None:
            return None
        return self._resilience.summary()

    def topology_descriptor(self) -> Dict[str, Any]:
        """This run's topology/sharding descriptor (ISSUE 14): mesh shape,
        process count, sharding tier, the resolved ``shard_updates``, and
        the gradient transport's state-layout (per-bucket padding is
        world-size-dependent — the ZeRO partition algebra elastic resume
        re-maps through).  Embedded in every manifest this facade writes;
        compared against a checkpoint's saved descriptor at resume."""
        from stoke_tpu.configs import comm_shard_updates

        st = self._status_obj
        mesh = self._mesh
        params = self._variables["params"]
        leaves = jax.tree_util.tree_leaves(params)
        comm = None
        transport = getattr(self._engine, "transport", None)
        if transport is not None:
            comm = transport.layout_descriptor(params)
        return {
            "version": 1,
            "process_count": int(jax.process_count()),
            "device_count": int(mesh.size) if mesh is not None else 1,
            "mesh_axes": (
                list(mesh.axis_names) if mesh is not None else None
            ),
            "mesh_shape": (
                [int(mesh.shape[a]) for a in mesh.axis_names]
                if mesh is not None
                else None
            ),
            "tier": st.sharding_tier.value,
            "shard_updates": bool(
                comm_shard_updates(st.comm_config, st.sharding_tier)
            ),
            "axis_name": (
                self._rules.axis_name if self._rules is not None else None
            ),
            "param_leaves": len(leaves),
            "param_elems": int(
                sum(
                    int(np.prod(l.shape)) if l.shape else 1 for l in leaves
                )
            ),
            "comm": comm,
        }

    def _descriptor_incompatible(
        self, saved: Optional[Dict[str, Any]]
    ) -> Optional[str]:
        """Why a saved topology descriptor CANNOT serve this run (None =
        compatible; topology differences are fine — that is what elastic
        resume re-shards across).  Genuinely incompatible means the state
        itself cannot re-map: a different parameter tree.  The returned
        reason names the remedy (the quarantine record an operator reads)."""
        if not saved:
            return None  # legacy manifest without a descriptor
        cur = self.topology_descriptor()
        for key in ("param_elems", "param_leaves"):
            if key in saved and saved[key] != cur[key]:
                return (
                    f"incompatible checkpoint: saved {key}={saved[key]} "
                    f"vs current {key}={cur[key]} — the checkpoint was "
                    f"written by a different MODEL; resume with the "
                    f"saving architecture, or point resume() at this "
                    f"run's own checkpoint root"
                )
        return None

    @staticmethod
    def _topology_changed(
        saved: Optional[Dict[str, Any]], cur: Dict[str, Any]
    ) -> bool:
        """Did the fleet change shape between save and resume?  (The
        ``resilience/elastic_resumes`` accounting predicate.)"""
        if not saved:
            return False
        return any(
            saved.get(k) != cur.get(k)
            for k in (
                "mesh_shape", "process_count", "device_count", "tier",
                "shard_updates",
            )
        )

    def resume(self, path: Optional[str] = None, name: str = "stoke") -> bool:
        """Restore the newest VALID checkpoint and the step counters; the
        auto-resume half of preemption survival (ISSUE 7).

        Discovery order: the resilience emergency root first (a preempted
        run's freshest state lives there), then the explicit ``path`` (or
        ``CheckpointConfig.auto_path``).  Candidates are ordered by
        backward step across all roots and each is validated against its
        ``manifest.json`` digests before being trusted — a corrupt or
        partially-written tag is QUARANTINED (renamed under
        ``<root>/quarantine/``, never deleted) and discovery falls back to
        the next-newest valid tag.  An emergency checkpoint additionally
        restores the out-of-payload state its extras carried (rng, loss
        EMA, error-feedback residual), so a resumed trajectory is
        bit-identical to an uninterrupted one.

        Multi-host: rank 0 verifies and quarantines (one validator —
        concurrent quarantine renames from N ranks would race), then
        broadcasts its (root, step) pick so every rank restores the same
        tag.

        Returns True when a checkpoint was restored; False when none
        (valid) exists — start fresh.  Works without a
        ``ResilienceConfig`` too (then: no manifest requirement, no
        quarantine — invalid tags are skipped in place)."""
        from stoke_tpu.resilience import (
            find_latest_valid_checkpoint,
            list_checkpoints,
            read_manifest,
        )

        mon = self._resilience
        ckpt_cfg = self._status_obj.checkpoint_config
        roots = []
        if mon is not None:
            roots.append((mon.cfg.save_path, mon.cfg.save_name))
        if path:
            roots.append((path, name))
        elif ckpt_cfg.auto_path:
            roots.append((ckpt_cfg.auto_path, ckpt_cfg.auto_name))
        if not roots:
            return False
        # the newest backward step recorded ANYWHERE (valid or not), taken
        # BEFORE quarantine renames: the lost-steps accounting below
        # charges the gap between it and the tag actually restored
        newest_step = max(
            (
                c["step"]
                for root, nm in roots
                for c in list_checkpoints(root, nm)
            ),
            default=None,
        )
        verify = mon.cfg.verify_on_resume if mon is not None else True
        quarantine = mon.cfg.quarantine if mon is not None else False

        manifest_cache: Dict[str, Any] = {}

        def _validate_descriptor(tag_dir):
            """Post-digest candidate check (ISSUE 14): a checkpoint whose
            topology descriptor cannot serve this run is quarantined with
            the remedy named, never crash-restored.  Topology DIFFERENCES
            pass — re-sharding them is elastic resume's whole point.  The
            parsed manifest is cached so the elastic-resume decision below
            reads the SAME descriptor that passed validation."""
            manifest = read_manifest(tag_dir)
            manifest_cache[tag_dir] = manifest
            topo = (manifest or {}).get("topology")
            reason = self._descriptor_incompatible(topo)
            if reason is not None:
                return False, reason
            return True, "ok"

        def _on_quarantine(tag_dir, dest, reason):
            self.warn(
                f"quarantined corrupt checkpoint {tag_dir} -> "
                f"{dest or '<rename failed>'} ({reason})"
            )
            if mon is not None:
                mon.note_quarantined(tag_dir, dest, reason)

        if jax.process_count() > 1:
            # one validator, one choice: rank 0 verifies/quarantines, then
            # BROADCASTS its (root, step) pick — peers re-discovering by
            # meta.json presence could disagree with rank 0 whenever a
            # quarantine rename failed, quarantine is off, or the roots
            # are per-host local disks, and ranks loading different tags
            # is an SPMD hang or silent divergence.  Every root name in
            # ``roots`` is concrete, so (root index, step) reconstructs
            # the tag deterministically on every rank.
            from jax.experimental import multihost_utils

            from stoke_tpu.io_ops import checkpoint_tag

            pick = np.array([-1, -1], np.int64)
            if self.is_rank_0:
                cand = find_latest_valid_checkpoint(
                    roots,
                    verify=verify,
                    quarantine=quarantine,
                    on_quarantine=_on_quarantine,
                    validate_fn=_validate_descriptor,
                )
                if cand is not None:
                    pick = np.array(
                        [
                            # match root AND name: the emergency root and
                            # auto_path may share a directory (distinct
                            # names keep their prune cadences apart)
                            next(
                                i for i, (r, n) in enumerate(roots)
                                if r == cand["root"] and n == cand["name"]
                            ),
                            cand["step"],
                        ],
                        np.int64,
                    )
            pick = np.asarray(multihost_utils.broadcast_one_to_all(pick))
            if pick[0] < 0:
                cand = None
            else:
                root, nm = roots[int(pick[0])]
                tag = checkpoint_tag(nm, int(pick[1]))
                cand = {
                    "root": root,
                    "tag": tag,
                    "tag_dir": os.path.join(root, tag),
                    "name": nm,
                    "step": int(pick[1]),
                }
        else:
            cand = find_latest_valid_checkpoint(
                roots,
                verify=verify,
                quarantine=quarantine,
                on_quarantine=_on_quarantine,
                validate_fn=_validate_descriptor,
            )
        if cand is None:
            return False
        manifest = manifest_cache.get(cand["tag_dir"])
        if manifest is None:
            # multi-host non-validating path (rank 0 validated + broadcast)
            manifest = read_manifest(cand["tag_dir"])
        saved_topo = (manifest or {}).get("topology")
        extras = self.load(cand["root"], tag=cand["tag"])
        rs = extras.get("resilience") if isinstance(extras, dict) else None
        if rs:
            self._restore_resume_state(rs)
        if mon is not None:
            lost = None
            if newest_step is not None:
                # backward-step gap -> optimizer steps (the unit the
                # resumed_step gauge uses)
                lost = max(0, newest_step - cand["step"]) // max(
                    self._status_obj.grad_accum, 1
                )
            mon.note_resumed(self._optimizer_steps, lost_steps=lost)
            cur_topo = self.topology_descriptor()
            if self._topology_changed(saved_topo, cur_topo):
                # topology-elastic resume (ISSUE 14): the fleet that
                # resumed is NOT the fleet that saved — params/opt/EF
                # state were re-sharded onto the new layout at load
                mon.note_elastic_resume(saved_topo, cur_topo)
                self.info(
                    f"elastic resume: checkpoint saved on mesh "
                    f"{(saved_topo or {}).get('mesh_shape')} "
                    f"(tier {(saved_topo or {}).get('tier')}), resumed "
                    f"onto {cur_topo.get('mesh_shape')} "
                    f"(tier {cur_topo.get('tier')})"
                )
        self.info(
            f"resumed from {cand['tag_dir']} at optimizer step "
            f"{self._optimizer_steps}"
        )
        return True

    def _resilience_boundary(self, window: int = 1) -> None:
        """Optimizer-step-boundary hook: drives the fault injector and —
        when a preemption notice arrived mid-step — runs the
        drain→save→exit sequence HERE, on the training thread, with the
        step complete and the engine state consistent (the signal handler
        itself only sets a flag)."""
        mon = self._resilience
        if mon is None:
            return
        # host-wall EMA of one optimizer step (resilience-on only; two
        # perf_counter reads per boundary): the preemption bundle's
        # lost-goodput price basis
        now = time.perf_counter()
        if self._last_boundary_t is not None and window > 0:
            per_step = (now - self._last_boundary_t) / max(window, 1)
            self._step_wall_ema = (
                per_step
                if self._step_wall_ema is None
                else 0.7 * self._step_wall_ema + 0.3 * per_step
            )
        self._last_boundary_t = now
        mon.chaos.on_step(self._optimizer_steps, window)
        preempt = mon.preempt_requested
        if jax.process_count() > 1:
            # cross-host agreement: SIGTERM delivery is per-VM and skewed
            # (often only the preempted VM is signaled).  One host entering
            # the emergency save's collectives while a peer dispatches the
            # next SPMD step is a pod-wide hang that burns the whole grace
            # window — so every boundary reduces the local flag across
            # hosts and ALL ranks enter the drain at the same step.  One
            # tiny host-level allgather per optimizer step, only with
            # resilience ON under multi-host (single process: no
            # collective at all, the default-OFF HLO/dispatch guarantee
            # is untouched).
            from jax.experimental import multihost_utils

            flags = np.asarray(
                multihost_utils.process_allgather(
                    np.array([1 if preempt else 0], np.int32)
                )
            )
            if int(flags.max()) and not preempt:
                # a PEER got the notice; drain in lockstep with it
                mon.request_preemption("peer-preemption")
            preempt = bool(int(flags.max()))
        if preempt:
            self._handle_preemption()

    def _handle_preemption(self) -> None:
        mon = self._resilience
        mon.note_preemption_honored()
        step = self._optimizer_steps
        self.warn(
            f"preemption notice ({mon.preempt_signal}) honored at "
            f"optimizer step {step}: draining async saves, writing the "
            f"emergency checkpoint"
        )
        tag_dir = None
        try:
            tag_dir = self._emergency_save()
            mon.note_emergency_saved(tag_dir)
        except Exception as e:
            # a failed emergency save must not mask the preemption exit —
            # the supervisor still restarts from the last periodic tag
            self.warn(f"emergency checkpoint failed: {e!r}")
        if self._health is not None:
            # the post-mortem bundle rides along (fleet verdict included):
            # the restart record shows WHY this host died, not just that
            # it did.  step_ema_s + lost_steps_estimate (ISSUE 14
            # satellite) let the supervisor price the attempt's lost
            # goodput straight from the bundle manifest: 0 lost when the
            # emergency save landed, steps-since-last-durable-save when
            # it failed.
            try:
                self._health.dump(
                    "preemption",
                    extra={
                        "step": step,
                        "signal": mon.preempt_signal,
                        "emergency_tag": tag_dir,
                        "step_ema_s": self._step_wall_ema,
                        "lost_steps_estimate": (
                            0
                            if tag_dir is not None
                            else max(0, step - self._last_save_step)
                        ),
                    },
                )
            except Exception:
                pass
        if mon.cfg.exit_on_preempt:
            # flush sinks before the no-teardown exit; for the in-process
            # PreemptedError path the pipeline stays open (the caller owns
            # the facade's shutdown)
            try:
                self.close_telemetry()
            except Exception:
                pass
        mon.exit_or_raise(step, tag_dir)

    def _emergency_save(self) -> str:
        """Synchronous emergency checkpoint under the resilience root:
        drain the in-flight async saves first (their tags must finish or
        fail before this one claims 'newest'), then write with the
        emergency keep window.  The extras carry the out-of-payload resume
        state (rng / loss EMA / EF residual / counters)."""
        import dataclasses as _dc

        mon = self._resilience
        try:
            # facade drain (not bare wait_for_saves): a successful drain
            # also promotes the pending async save into the durable
            # lost-goodput accounting
            self.wait_for_checkpoint()
        except RuntimeError as e:
            # failed EARLIER async saves must not block the emergency save
            self.warn(f"async checkpoint drain reported failures: {e}")
        cfg = _dc.replace(
            self._status_obj.checkpoint_config,
            async_save=False,
            max_to_keep=mon.cfg.max_to_keep,
        )
        return self._save_with_config(
            mon.cfg.save_path,
            mon.cfg.save_name,
            cfg,
            {"resilience": self._resume_state()},
        )

    def _resume_state(self) -> Dict[str, Any]:
        """Host-side snapshot of the training state that lives OUTSIDE the
        checkpoint payload trees — pickled into the emergency checkpoint's
        extras so a resumed run is bit-identical, not just close."""
        mon = self._resilience
        state: Dict[str, Any] = {
            "optimizer_step": self._optimizer_steps,
            "backward_step": self._backward_steps,
            "preempt_signal": mon.preempt_signal if mon is not None else None,
            "restart_attempt": mon.restarts if mon is not None else 0,
            "rng": self._rng_to_host(),
            "ema_loss": float(jax.device_get(self._rolling_mean_loss)),
            "ema_initialized": self._ema_initialized,
            "skipped_steps": float(jax.device_get(self._skipped_steps)),
        }
        if self._comm_state:
            # error-feedback residual (ISSUE 2 state): without it a
            # resumed int8 run would drop the carried quantization error.
            # _gather_to_host, not device_get: the ISSUE 8 sharded residual
            # spans the GLOBAL data axis, and device_get raises on arrays
            # with non-addressable shards — the consolidation gather is
            # safe here because every rank enters the emergency save
            # (the resilience boundary agreed on the flag collectively)
            from stoke_tpu.io_ops import _gather_to_host

            state["comm_state"] = _gather_to_host(self._comm_state)
            # layout descriptor (ISSUE 14): the key that lets a resume on
            # a DIFFERENT topology re-partition the residual instead of
            # dropping it — bucket padding is world-size-dependent
            state["comm_layout"] = self._engine.transport.layout_descriptor(
                self._variables["params"]
            )
        return state

    def _restore_resume_state(self, rs: Dict[str, Any]) -> None:
        try:
            if rs.get("rng") is not None:
                self._rng_from_host(rs["rng"])
            if rs.get("ema_loss") is not None:
                self._rolling_mean_loss = self._place_scalar_tree(
                    np.float32(rs["ema_loss"])
                )
                self._ema_initialized = bool(rs.get("ema_initialized", True))
            if rs.get("skipped_steps") is not None:
                self._skipped_steps = self._place_scalar_tree(
                    np.float32(rs["skipped_steps"])
                )
            host_comm = rs.get("comm_state")
            if host_comm and self._comm_state:
                saved_desc = rs.get("comm_layout")
                cur_desc = self._engine.transport.layout_descriptor(
                    self._variables["params"]
                )
                if (
                    saved_desc
                    and cur_desc
                    and "residual" in host_comm
                    and "residual" in self._comm_state
                    and (
                        saved_desc["kind"] != cur_desc["kind"]
                        or saved_desc["buckets"] != cur_desc["buckets"]
                        or saved_desc["world"] != cur_desc["world"]
                    )
                ):
                    # topology-elastic residual re-map (ISSUE 14): the
                    # saved layout (bucket padding, sharded vs replicated
                    # packing) differs from this run's — unpack to the
                    # flat per-element vector under the SAVED descriptor,
                    # repack under the CURRENT one (zero.py partition
                    # algebra), then place as usual below
                    from stoke_tpu.parallel.zero import remap_residual

                    host_comm = {
                        **host_comm,
                        "residual": remap_residual(
                            host_comm["residual"],
                            saved_desc,
                            cur_desc,
                            self._comm_state["residual"],
                        ),
                    }

                def _leaf(cur, new):
                    if isinstance(cur, jax.Array):
                        arr = np.asarray(new)
                        if self._rules is not None:
                            return place_global_tree(arr, cur.sharding)
                        return jax.device_put(arr, self._device)
                    return new

                self._comm_state = jax.tree_util.tree_map(
                    _leaf, self._comm_state, host_comm
                )
        except Exception as e:
            # a structurally-incompatible extras blob (model/transport
            # changed between save and resume) degrades to a plain
            # counter-restoring resume instead of failing it
            self.warn(f"could not restore emergency resume extras: {e!r}")

    def _rng_to_host(self) -> Dict[str, Any]:
        k = self._rng
        try:
            if jnp.issubdtype(k.dtype, jax.dtypes.prng_key):
                return {
                    "typed": True,
                    "data": np.asarray(jax.random.key_data(k)),
                }
        except (AttributeError, TypeError):
            pass
        return {"typed": False, "data": np.asarray(jax.device_get(k))}

    def _rng_from_host(self, d: Dict[str, Any]) -> None:
        data = jnp.asarray(np.asarray(d["data"]))
        key = jax.random.wrap_key_data(data) if d.get("typed") else data
        self._rng = self._place_scalar_tree(key)

    @_health_guarded
    @_timed("train_step_window")
    def train_step_window(
        self,
        model_args: Any,
        loss_args: Any = (),
        model_kwargs: Optional[dict] = None,
    ):
        """A whole accumulation window (``grad_accum`` micro-batches) in ONE
        compiled dispatch via ``lax.scan``, apply included.

        Args are stacked micro-batches: each array leaf has shape
        ``[grad_accum, micro_batch, ...]``.  Must be called at a window
        boundary (``grad_accum_counter == 0``).  Returns the per-micro loss
        reports stacked on axis 0.
        """
        if not self._training:
            raise RuntimeError("Stoke -- train_step_window() called in eval mode")
        if self._grad_accum_counter != 0:
            raise RuntimeError(
                "Stoke -- train_step_window() must start at an accumulation "
                f"boundary (counter={self._grad_accum_counter}); finish the "
                "window with backward()/step() or reset() first"
            )
        k = self._status_obj.grad_accum
        if not isinstance(model_args, tuple):
            model_args = (model_args,)
        if not isinstance(loss_args, tuple):
            loss_args = (loss_args,)
        for leaf in jax.tree_util.tree_leaves(
            (model_args, loss_args, model_kwargs or {})
        ):
            if hasattr(leaf, "shape") and (not leaf.shape or leaf.shape[0] != k):
                raise ValueError(
                    f"Stoke -- train_step_window() expects leaves stacked to "
                    f"[grad_accum={k}, ...]; got shape {getattr(leaf, 'shape', ())}"
                )
        margs = self._place_batch(model_args, batch_dim=1)
        mkwargs = self._place_batch(model_kwargs or {}, batch_dim=1)
        sentinel = DeferredOutput(None, -1)
        flat, treedef = jax.tree_util.tree_flatten(
            ((sentinel, *loss_args), {}), is_leaf=is_deferred
        )
        arrays = self._place_batch(
            [l for l in flat if not is_deferred(l)], batch_dim=1
        )
        deferred_info = tuple(
            (i, l._path) for i, l in enumerate(flat) if is_deferred(l)
        )
        (
            reports,
            self._variables,
            new_opt,
            self._grad_buf,
            self._scaler_state,
            self._comm_state,
            self._rng,
            sentinels,
            numerics,
            finite,
        ) = self._engine.window_step(
            self._variables,
            self._opt_materialize(),
            self._grad_buf,
            self._scaler_state,
            self._comm_state,
            self._rng,
            margs,
            mkwargs,
            arrays,
            treedef,
            deferred_info,
        )
        self._opt_commit(new_opt)
        self._pending = None
        self._backward_steps += k
        # track the window-mean micro loss once (per-micro EMA would need k
        # host round trips; the stacked reports carry the detail)
        mean_report = jax.tree_util.tree_map(lambda r: r.mean(axis=0), reports)
        self._update_loss_tracking(mean_report)
        if self._precision.scaled:
            self._skipped_steps = self._skipped_steps + (
                1.0 - finite.astype(jnp.float32)
            )
        self._optimizer_steps += 1
        self._reset_tracking_window()
        self._observe_numerics(numerics)
        self._observe_health(sentinels)
        self._maybe_log_metrics()
        self._maybe_emit_telemetry()
        self._maybe_auto_save()
        self._resilience_boundary()
        return reports

    @_health_guarded
    @_timed("train_steps")
    def train_steps(
        self,
        model_args: Any,
        loss_args: Any = (),
        model_kwargs: Optional[dict] = None,
        segment_size: Optional[int] = None,
    ):
        """N complete optimizer steps in ONE compiled dispatch (outer
        ``lax.scan`` over steps, inner scan over each accumulation window,
        fused apply per step).

        The TPU-idiomatic answer to dispatch-bound loops: a whole training
        segment is one XLA program, so host dispatch overhead (and, through
        remote-device links, per-dispatch round-trip latency) is amortized
        over ``n x grad_accum`` micro-batches.

        Args are stacked micro-batches: each array leaf has shape
        ``[total_micro, micro_batch, ...]`` where ``total_micro`` is a
        multiple of ``grad_accum``; ``n = total_micro // grad_accum``
        optimizer steps run.  Must be called at a window boundary.  Returns
        per-micro loss reports stacked to ``[n, grad_accum, ...]``.

        **Memory**: the whole stacked segment is resident in device memory
        for the dispatch (it competes with activations for HBM — see
        docs/performance.md).  ``segment_size=c`` bounds this by streaming
        the segment host→device in chunks of ``c`` optimizer steps (one
        dispatch per chunk, identical numerics and loss tracking); without
        it, a guard raises a clear error when the stack obviously exceeds
        the device's free memory instead of letting the runtime OOM.

        Loss tracking: the EMA advances once per optimizer step with that
        step's window-mean loss (same semantics as ``n`` calls to
        ``train_step_window``).  Auto-save and metric logging fire at the end
        of the segment whenever their step cadence was crossed anywhere
        inside it (a save_every_n_steps boundary mid-segment is honored, just
        deferred to the segment end).
        """
        if not self._training:
            raise RuntimeError("Stoke -- train_steps() called in eval mode")
        if self._grad_accum_counter != 0:
            raise RuntimeError(
                "Stoke -- train_steps() must start at an accumulation "
                f"boundary (counter={self._grad_accum_counter}); finish the "
                "window with backward()/step() or reset() first"
            )
        k = self._status_obj.grad_accum
        if not isinstance(model_args, tuple):
            model_args = (model_args,)
        if not isinstance(loss_args, tuple):
            loss_args = (loss_args,)
        n = None
        seg_bytes = 0
        for leaf in jax.tree_util.tree_leaves(
            (model_args, loss_args, model_kwargs or {})
        ):
            if hasattr(leaf, "shape") and leaf.shape:
                if leaf.shape[0] % k:
                    raise ValueError(
                        f"Stoke -- train_steps() leaves must stack "
                        f"[total_micro, micro_batch, ...] with total_micro a "
                        f"multiple of grad_accum={k}; got {leaf.shape}"
                    )
                if n is None:
                    n = leaf.shape[0] // k
                elif leaf.shape[0] // k != n:
                    raise ValueError(
                        "Stoke -- train_steps() leaves disagree on the "
                        "number of stacked micro-batches"
                    )
                # the memory guard estimates the upcoming host->device
                # transfer: arrays already resident on an accelerator are
                # counted in the device's bytes_in_use (double-billing
                # them would spuriously trip the guard), while host-side
                # data — numpy OR jax Arrays committed to a CPU device —
                # still has to cross the wire and counts
                if not _on_accelerator(leaf):
                    seg_bytes += getattr(leaf, "nbytes", 0)
        if not n:
            raise ValueError(
                "Stoke -- train_steps() found no stacked array leaves"
            )
        # the batch dim shards over the data axis, so each device holds only
        # its 1/world_size share of the stacked segment
        seg_bytes_per_device = seg_bytes // max(self.world_size, 1)
        if segment_size is not None and segment_size < 1:
            raise ValueError(
                f"Stoke -- segment_size must be >= 1, got {segment_size}"
            )
        if segment_size is not None and segment_size < n:
            # chunked variant: stream the segment host->device one chunk at
            # a time; each chunk is a full train_steps dispatch, so counters,
            # EMA, auto-save and metric cadence compose exactly
            def _slice(t, sl):
                return jax.tree_util.tree_map(
                    lambda l: l[sl]
                    if hasattr(l, "shape") and getattr(l, "shape", ())
                    else l,
                    t,
                )

            chunk_reports = []
            for c0 in range(0, n, segment_size):
                c1 = min(c0 + segment_size, n)
                sl = slice(c0 * k, c1 * k)
                chunk_reports.append(
                    self.train_steps(
                        _slice(model_args, sl),
                        _slice(loss_args, sl),
                        _slice(model_kwargs, sl)
                        if model_kwargs is not None
                        else None,
                    )
                )
            return jax.tree_util.tree_map(
                lambda *rs: jnp.concatenate(rs, axis=0), *chunk_reports
            )
        _check_segment_memory(seg_bytes_per_device, _device_memory_stats())

        def _fold(t):
            return jax.tree_util.tree_map(
                lambda l: l.reshape((n, k) + tuple(l.shape[1:]))
                if hasattr(l, "shape") and l.shape
                else l,
                t,
            )

        margs = self._place_batch(_fold(model_args), batch_dim=2)
        mkwargs = self._place_batch(_fold(model_kwargs or {}), batch_dim=2)
        sentinel = DeferredOutput(None, -1)
        flat, treedef = jax.tree_util.tree_flatten(
            ((sentinel, *loss_args), {}), is_leaf=is_deferred
        )
        arrays = self._place_batch(
            _fold([l for l in flat if not is_deferred(l)]), batch_dim=2
        )
        deferred_info = tuple(
            (i, l._path) for i, l in enumerate(flat) if is_deferred(l)
        )
        if self._health is not None:
            # one dispatch legitimately covers n optimizer steps: re-arm
            # the watchdog with the per-segment deadline (n x timeout)
            self._health.arm_watchdog(steps=n)
        (
            reports,
            self._variables,
            new_opt,
            self._grad_buf,
            self._scaler_state,
            self._comm_state,
            self._rng,
            sentinels,
            numerics,
            skipped,
        ) = self._engine.multi_step(
            self._variables,
            self._opt_materialize(),
            self._grad_buf,
            self._scaler_state,
            self._comm_state,
            self._rng,
            margs,
            mkwargs,
            arrays,
            treedef,
            deferred_info,
        )
        self._opt_commit(new_opt)
        self._pending = None
        self._backward_steps += n * k
        # EMA per optimizer step: ONE device reduction ([n, k, ...] ->
        # [n, ...]) and ONE host transfer for the whole segment, then a pure
        # host loop — not n per-step device dispatches
        step_means = jax.device_get(
            jax.tree_util.tree_map(lambda r: r.mean(axis=1), reports)
        )
        for i in range(n):
            self._update_loss_tracking(
                jax.tree_util.tree_map(lambda m: m[i], step_means)
            )
            self._reset_tracking_window()
        if self._precision.scaled:
            self._skipped_steps = self._skipped_steps + skipped
        self._optimizer_steps += n
        self._observe_numerics(numerics, window=n)
        self._observe_health(sentinels, window=n)
        self._maybe_log_metrics(window=n)
        self._maybe_emit_telemetry(window=n)
        self._maybe_auto_save(window=n)
        self._resilience_boundary(window=n)
        return reports

    def reset(self) -> None:
        """Zero the accumulation buffer and counters without stepping
        (reference ``reset`` helpers, stoke.py:1042-1058)."""
        self._grad_buf = self._engine.init_grad_buffer(self._variables)
        self._grad_accum_counter = 0
        self._pending = None
        self._reset_tracking_window()

    # ------------------------------------------------------------------ #
    # loss tracking (reference stoke.py:371-520, :914-958)
    # ------------------------------------------------------------------ #

    def _loss_total(self, report) -> jax.Array:
        leaves = jax.tree_util.tree_leaves(report)
        total = leaves[0]
        for l in leaves[1:]:
            total = total + l
        return total

    def _update_loss_tracking(self, report) -> None:
        # a handful of eager scalar dispatches per micro-step: its own
        # span, so a trace tells them from the step programs' dispatches
        with trace_span("stoke/track", track="facade"):
            # losses arrive divided by grad_accum; track the undivided
            # micro loss
            micro = self._loss_total(report) * self._status_obj.grad_accum
            self._last_step_loss = micro
            self._agg_loss = self._agg_loss + micro
            self._agg_count += 1
            w = self._ema_weight
            if not self._ema_initialized:
                self._rolling_mean_loss = micro
                self._ema_initialized = True
            else:
                self._rolling_mean_loss = (
                    1.0 - w
                ) * self._rolling_mean_loss + w * micro

    def _reset_tracking_window(self) -> None:
        self._agg_loss = self._zero_scalar()
        self._agg_count = 0

    def detach_and_sync_loss(self, loss: Any, user_reduction: str = "mean") -> float:
        """Host float of a (possibly structured) loss, synced across the mesh
        (reference detach_and_sync_loss, distributed.py:619-646 — there a
        barrier + allreduce + ``.item()``; here the value is already the
        global-batch loss, so this is just the host transfer).

        ``LossReduction.sum`` reproduces the reference's summed-across-ranks
        value (hvd Sum, distributed.py:1461-1490).  With a **mean**-reduced
        ``loss_fn`` (the default contract) that is exactly
        ``world_size × global-batch mean`` — per-device batches are equal, so
        the sum of per-rank means equals world × global mean.  If your
        ``loss_fn`` **sums** over the batch instead, pass
        ``user_reduction="sum"``: the value is then already a global sum and
        no scaling is applied."""
        if user_reduction not in ("mean", "sum"):
            raise ValueError(
                f"user_reduction must be 'mean' or 'sum', got {user_reduction!r}"
            )
        val = float(jax.device_get(self._loss_total(loss)))
        if (
            self._status_obj.dp_config.loss_reduction is LossReduction.sum
            and user_reduction == "mean"
        ):
            val *= self.world_size
        return val

    @property
    def ema_loss(self) -> float:
        """Rolling EMA of the (undivided) micro losses (reference
        stoke.py:914-958)."""
        return float(jax.device_get(self._rolling_mean_loss))

    @property
    def step_loss(self) -> Optional[float]:
        if self._last_step_loss is None:
            return None
        return float(jax.device_get(self._last_step_loss))

    @property
    def mean_accumulated_loss(self) -> Optional[float]:
        if self._agg_count == 0:
            return None
        return float(jax.device_get(self._agg_loss)) / self._agg_count

    def print_ema_loss(self, prepend_msg: str = "EMA Loss") -> None:
        """(reference print_ema_loss, stoke.py:447-460)"""
        self.print_on_devices(f"{prepend_msg}: {self.ema_loss:.6f}")

    def print_mean_accumulated_synced_loss(
        self, prepend_msg: str = "Mean accumulated loss"
    ) -> None:
        """(reference stoke.py:462-482)"""
        v = self.mean_accumulated_loss
        self.print_on_devices(
            f"{prepend_msg}: {v:.6f}" if v is not None else f"{prepend_msg}: n/a"
        )

    def print_synced_loss(
        self, loss: Any, prepend_msg: str = "Step loss", scale_by_accum: bool = True
    ) -> None:
        """(reference print_synced_loss, stoke.py:484-505)"""
        v = self.detach_and_sync_loss(loss)
        if scale_by_accum:
            v *= self._status_obj.grad_accum
        self.print_on_devices(f"{prepend_msg}: {v:.6f}")

    # ------------------------------------------------------------------ #
    # printing / rank helpers (reference distributed.py:238-271)
    # ------------------------------------------------------------------ #

    @property
    def rank(self) -> int:
        """Process index (reference rank property; on TPU, one process per
        host, each feeding its local devices)."""
        return jax.process_index()

    @property
    def is_rank_0(self) -> bool:
        return self.rank == 0

    @property
    def world_size(self) -> int:
        return self._status_obj.world_size or 1

    @property
    def n_processes(self) -> int:
        return jax.process_count()

    def print_on_devices(self, msg: str, rank: Optional[int] = 0) -> None:
        """Print on a specific process rank, or all when rank=None
        (reference print_device, distributed.py:238-271)."""
        if rank is None or self.rank == rank:
            unrolled_print(f"(rank {self.rank}) {msg}")

    def info(self, msg: str) -> None:
        if self.is_rank_0:
            unrolled_print(f"INFO: {msg}")

    def warn(self, msg: str) -> None:
        if self.is_rank_0:
            unrolled_print(f"WARN: {msg}")

    def barrier(self) -> None:
        """Cross-process sync (reference barrier/hvd.join,
        distributed.py:671-692).  In-step SPMD needs no barriers; this exists
        for host-side coordination around IO.

        Instrumented (ISSUE 5 satellite): the elapsed wait — near zero for
        the last arrival, the full skew for the first — lands in
        ``sync/barrier_wait_s`` / ``sync/barriers_total`` of every live
        telemetry registry, FleetConfig or not, so cross-process sync time
        is visible in the wall-clock breakdown and (with a ``FleetConfig``)
        chargeable to the straggler host."""
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            from stoke_tpu.telemetry.fleet import timed_sync

            with timed_sync("barrier"):
                multihost_utils.sync_global_devices("stoke_barrier")

    def block_until_ready(self) -> None:
        """Wait for all in-flight device work (a fence for timing and tests)."""
        jax.block_until_ready(
            (self._variables, self._opt_state, self._grad_buf)
        )

    # ------------------------------------------------------------------ #
    # profiling / observability (SURVEY.md §5 — first-class here vs the
    # reference's DeepSpeed flops-profiler passthrough, configs.py:252-279)
    # ------------------------------------------------------------------ #

    def _clock(self, phase: str):
        """The ``stoke/<phase>`` span of a user-facing call, always in a
        profiler trace; with the wall-clock breakdown on it also
        accumulates the telemetry registry's ``facade/<phase>_s``
        counter."""
        if not self._wall_clock_enabled:
            return trace_span(f"stoke/{phase}", track="facade")
        return self._telemetry.phase(phase)

    @property
    def telemetry(self) -> Telemetry:
        """The run's telemetry pipeline (registry always live; sinks and
        collectors attach when a ``TelemetryConfig`` is supplied)."""
        return self._telemetry

    @property
    def wall_clock_breakdown(self) -> Dict[str, float]:
        """Cumulative host seconds per facade phase (enable via
        ``ProfilerConfig(wall_clock_breakdown=True)`` or any
        ``TelemetryConfig``; reference configs.py:540).  Host dispatch time
        only — device execution is asynchronous; use :meth:`profile_trace`
        for device timelines.  Registry-backed alias: the same numbers flow
        into the telemetry sinks as ``facade/<phase>_s``."""
        return self._telemetry.wall_clock_breakdown()

    def print_wall_clock_breakdown(self) -> None:
        # the goodput/* entries (attribution on) partition TOTAL wall
        # clock, not host-dispatch time — percentaging each group against
        # its own total keeps both reports truthful side by side
        breakdown = self.wall_clock_breakdown
        phases = {
            k: v for k, v in breakdown.items() if not k.startswith("goodput/")
        }
        goodput = {
            k: v for k, v in breakdown.items() if k.startswith("goodput/")
        }
        for group in (phases, goodput):
            total = sum(group.values()) or 1.0
            for phase, secs in sorted(group.items(), key=lambda kv: -kv[1]):
                self.print_on_devices(
                    f"wall_clock {phase}: {secs:.3f}s "
                    f"({100 * secs / total:.1f}%)"
                )

    def profile_trace(self, name: str = "stoke"):
        """Context manager capturing a ``jax.profiler`` trace (serves the
        TensorBoard profile plugin / xprof) when ``ProfilerConfig.trace_dir``
        is set; no-op otherwise.

        Usage:
            with stoke.profile_trace():
                for batch in loader: ...
        """
        import contextlib

        cfg = self._status_obj.profiler_config
        if cfg.trace_dir is None:
            return contextlib.nullcontext()

        @contextlib.contextmanager
        def _trace():
            jax.profiler.start_trace(cfg.trace_dir)
            try:
                yield
            finally:
                jax.profiler.stop_trace()
                self.info(f"profiler trace written to {cfg.trace_dir}")

        return _trace()

    def estimate_step_flops(
        self, model_args: Any, loss_args: Any = ()
    ) -> Optional[float]:
        """XLA cost-analysis FLOPs estimate of one fused optimizer step
        (replaces the reference's DeepSpeed flops profiler passthrough,
        distributed.py:985-1004).  Thin wrapper over
        :meth:`estimate_step_cost` (the shared CostCard path, ISSUE 4);
        returns None if the backend does not report cost analysis —
        warned ONCE per backend, with the negative result cached so
        repeated calls neither warn nor re-lower."""
        card = self.estimate_step_cost(model_args, loss_args)
        if card is None or not card.flops:
            return None
        return float(card.flops)

    def estimate_step_cost(self, model_args: Any, loss_args: Any = ()):
        """Analytic :class:`~stoke_tpu.telemetry.attribution.CostCard` of
        one fused optimizer step at these batch shapes: FLOPs, bytes
        accessed, and (when an ``AttributionConfig`` supplies peaks) the
        roofline-optimal step time.  The same cost-analysis funnel the
        live attribution gauges use.
        Returns None when the backend reports no cost analysis."""
        if not isinstance(model_args, tuple):
            model_args = (model_args,)
        if not isinstance(loss_args, tuple):
            loss_args = (loss_args,)
        from stoke_tpu.engine import DeferredOutput as _D
        from stoke_tpu.telemetry.attribution import (
            CostCard,
            cost_analysis_of,
        )

        margs = self._place_batch(model_args)
        sentinel = _D(None, -1)
        flat, treedef = jax.tree_util.tree_flatten(
            ((sentinel, *loss_args), {}), is_leaf=is_deferred
        )
        arrays = self._place_batch([l for l in flat if not is_deferred(l)])
        deferred_info = tuple(
            (i, l._path) for i, l in enumerate(flat) if is_deferred(l)
        )
        fn = self._engine._build_fused(treedef, deferred_info, True)
        # abstract avals for spilled state: lowering must not page the whole
        # optimizer state back into HBM just to trace shapes
        if self._disk_store is not None and self._disk_store.spilled:
            opt_arg = self._disk_store.abstract()
        else:
            opt_arg = self._opt_state
        cost = cost_analysis_of(
            fn,
            self._variables,
            opt_arg,
            self._grad_buf,
            self._scaler_state,
            self._comm_state,
            self._rng,
            margs,
            {},
            arrays,
        )
        if cost is None:
            return None
        acfg = self._status_obj.attribution_config
        return CostCard.from_cost(
            cost,
            "fused",
            1,
            peak_tflops=acfg.peak_tflops if acfg is not None else 0.0,
            peak_hbm_gbps=acfg.peak_hbm_gbps if acfg is not None else 0.0,
        )

    # ------------------------------------------------------------------ #
    # DataLoader factory (reference stoke.py:737-851)
    # ------------------------------------------------------------------ #

    def DataLoader(self, dataset, **kwargs):
        """Build a :class:`~stoke_tpu.data.StokeDataLoader` wired to this
        run's topology: the per-process loader batch is
        ``batch_size_per_device × local-mesh-share`` and batches land sharded
        over the mesh data axis (reference stoke.py:737-851 + SURVEY.md §3.3).
        A DistributedSampler is required when multiple processes each load a
        slice (reference stoke.py:822-826)."""
        from stoke_tpu.data import StokeDataLoader

        world = self.world_size
        per_process = world // max(jax.process_count(), 1)
        batch_size = self._status_obj.batch_size * max(per_process, 1)
        if jax.process_count() > 1 and kwargs.get("sampler") is None:
            raise ValueError(
                "Stoke -- multi-process runs require a distributed sampler "
                "(see BucketedDistributedSampler / DistributedSampler) — "
                "reference stoke.py:822-826"
            )
        fcfg = self._status_obj.fleet_config
        if (
            "rebalancer" not in kwargs
            and fcfg is not None
            and getattr(fcfg, "rebalance", False)
            and self._fleet is not None
            and jax.process_count() > 1
        ):
            # skew-reactive input rebalancing (ISSUE 14): build the
            # actuator and hand it to both sides — the fleet monitor
            # proposes bounded share shifts at straggler-streak
            # boundaries, the loader applies them at an agreed future
            # fetch index.  Single-process runs skip it entirely (a fleet
            # of one has nothing to rebalance; behavior is untouched).
            from stoke_tpu.data import InputRebalancer

            rb = InputRebalancer(
                n_hosts=jax.process_count(),
                rank=jax.process_index(),
                batch_size=batch_size,
                max_frac=fcfg.rebalance_max_frac,
                # apply strictly past every host's prefetch lookahead
                apply_slack=int(kwargs.get("prefetch", 2)) + 2,
            )
            self._fleet.attach_rebalancer(rb)
            kwargs["rebalancer"] = rb
        return StokeDataLoader(
            dataset,
            batch_size=batch_size,
            place_fn=self._place_batch,
            telemetry=self._telemetry if self._telemetry.enabled else None,
            **kwargs,
        )

    # ------------------------------------------------------------------ #
    # serving (ISSUE 9: continuous-batching inference behind the facade)
    # ------------------------------------------------------------------ #

    def serve(self, **overrides):
        """Build the continuous-batching inference engine over this run's
        model + current params (ISSUE 9 tentpole entry point).

        Requires a :class:`~stoke_tpu.configs.ServeConfig` in
        ``Stoke(configs=[...])`` and a :class:`~stoke_tpu.models.gpt.GPT`
        model — serving is the paged-KV decode path models/gpt.py grew;
        ``overrides`` are ``ServeConfig`` field replacements applied for
        this engine only (e.g. ``stoke.serve(max_seqs=16)``).

        The engine inherits this facade's plumbing: the telemetry
        pipeline (``serve/*`` JSONL fields + Prometheus gauges land in
        the same sinks), and — with a ``CompileConfig`` — the PR-6
        AOT program ledger, so prefill/decode warm-start like the step
        programs do.  The config's presence alone changes NOTHING about
        training (it is only read here; tests assert step-program HLO
        bit-identity).

        SLOs (ISSUE 16): ``engine.submit(..., slo=RequestSLO(...))``
        tags requests with a priority class + TTFT/TPOT deadlines
        (defaults from ``ServeConfig.slo_ttft_target_s`` /
        ``slo_tpot_target_s``); ``engine.summary()["slo"]`` then carries
        per-class attainment, goodput-under-SLO tokens/s, and queue-ETA
        forecasts, and ``engine.slo.attributions`` the span-walked
        queue/prefill/decode violation buckets (docs/serving.md, "SLOs &
        priority classes").

        Params note: the engine reads the facade's LIVE params.  The
        int8/bf16 quantized stores copy into their own (smaller) buffers;
        ``quant="none"`` ALIASES the training params — build the engine
        after training finishes, and rebuild it (``serve()`` again) if
        you train further, since the step programs donate those buffers.
        """
        import dataclasses as _dc

        from stoke_tpu.models.gpt import GPT
        from stoke_tpu.serving import ServingEngine
        from stoke_tpu.status import StokeValidationError

        scfg = self._status_obj.serve_config
        if scfg is None:
            raise StokeValidationError(
                "Stoke.serve() requires a ServeConfig — add one to "
                "Stoke(configs=[ServeConfig(...)]) (the serving stack is "
                "opt-in; docs/serving.md)"
            )
        if overrides:
            scfg = _dc.replace(scfg, **overrides)
            # replaced fields re-validate through the same status rules a
            # constructor-supplied config passes — with THIS run's device:
            # the pallas-decode-needs-TPU rule (ISSUE 13) must judge the
            # override against the facade's real backend, not the
            # StokeStatus default.  Cross-config rules (cost_cards needs
            # an AttributionConfig, ISSUE 18) must see the run's real
            # companions, so they ride along.
            companions = [
                c
                for c in (
                    self._status_obj.attribution_config,
                    self._status_obj.telemetry_config,
                )
                if c is not None
            ]
            StokeStatus(
                batch_size_per_device=self._status_obj.batch_size,
                device=self._status_obj.device,
                configs=[scfg] + companions,
            )
        module = getattr(self._adapter, "module", None)
        if not isinstance(module, GPT):
            raise TypeError(
                f"Stoke.serve() serves GPT models (the paged-KV decode "
                f"forward lives in models/gpt.py); this facade wraps "
                f"{type(module or self._adapter).__name__}"
            )
        kv_sharding = None
        if self._mesh is not None:
            # replicated pool on the mesh: each data-parallel serving
            # replica owns a full cache (model-sharded pools are a
            # placement change in PagedKVCache, not an engine change)
            kv_sharding = NamedSharding(self._mesh, P())
        engine = ServingEngine(
            module,
            self.params,
            scfg,
            telemetry=self._telemetry,
            compile_cache=self._compile_cache,
            kv_sharding=kv_sharding,
            # roofline observatory (ISSUE 18): the run's AttributionConfig
            # carries the hardware peaks the serve roofline divides by
            attribution=(
                self._status_obj.attribution_config
                if scfg.cost_cards
                else None
            ),
            # HBM capacity observatory (ISSUE 19): the engine constructs
            # its OWN observatory (quantized weights + KV pool components)
            # and runs the serve-side OOM pre-flight at construction
            memory=self._status_obj.memory_config,
        )
        if self._opsplane is not None:
            # the live ops plane's /requests + /statusz serving block
            # (ISSUE 20) follow the newest engine this facade built
            self._opsplane.attach_engine(engine)
        if self._numerics is not None and engine.quant_errors_by_group:
            # per-layer dequant-error attribution (ISSUE 12): the engine
            # computed it once at quantize time; installing it here is
            # what surfaces numerics/quant_err_max / quant_err_group in
            # this run's JSONL records and numerics_summary
            self._numerics.set_quant_errors(engine.quant_errors_by_group)
        return engine

    # ------------------------------------------------------------------ #
    # save / load (reference stoke.py:1060-1142)
    # ------------------------------------------------------------------ #

    def save(
        self,
        path: str,
        name: str = "stoke",
        extras: Optional[Dict[str, Any]] = None,
    ) -> str:
        """Unified checkpoint save (reference stoke.py:1060-1106).  Layout is
        chosen by ``CheckpointConfig.format``; the payload schema mirrors the
        reference (io_ops.py:224-236): counters, status dict, model/optimizer
        /scaler state, user extras."""
        return self._save_with_config(
            path, name, self._status_obj.checkpoint_config, extras
        )

    @_timed("save")
    def _save_with_config(
        self,
        path: str,
        name: str,
        config,
        extras: Optional[Dict[str, Any]],
    ) -> str:
        """The shared save body, parameterized on the ``CheckpointConfig``
        so the emergency path (ISSUE 7) can force a synchronous write with
        its own keep window without mutating the run's config."""
        from stoke_tpu import io_ops

        # the sown "losses" collection is transient per-step output (MoE aux
        # terms), not state: excluding it keeps checkpoints loadable across
        # model versions that add/remove sown losses, and it is regenerated
        # by the first forward after a restore anyway
        vars_to_save = {
            k: v for k, v in self._variables.items() if k != "losses"
        }
        mon = self._resilience
        with_manifest = mon is not None and mon.cfg.manifest
        with trace_span("stoke/io", track="io"):
            tag_dir = io_ops.save_checkpoint(
                path=path,
                name=name,
                variables=vars_to_save,
                opt_state=self._opt_materialize(),
                scaler_state=self._scaler_state,
                counters={
                    "backward_step": self._backward_steps,
                    "grad_accum_step": self._grad_accum_counter,
                    "optimizer_step": self._optimizer_steps,
                },
                status=self._status_obj.to_dict(),
                extras=extras,
                config=config,
                backward_step=self._backward_steps,
                grad_buf=(
                    self._grad_buf if self._grad_accum_counter > 0 else None
                ),
                # integrity manifests (ISSUE 7): every checkpoint this
                # facade writes under a ResilienceConfig carries per-file
                # digests — the record resume() validates before trusting
                manifest=with_manifest,
                # topology/sharding descriptor (ISSUE 14): what elastic
                # resume re-shards against — rides the manifest
                topology=(
                    self.topology_descriptor() if with_manifest else None
                ),
                # kill_during_save injector hook (ISSUE 14 satellite)
                chaos=(
                    mon.chaos
                    if mon is not None and mon.chaos.active
                    else None
                ),
                # restart-cost accounting (ISSUE 14 satellite): each save
                # promotes ITS OWN step into "last durable save" only when
                # its write fully lands — sync saves on return, async ones
                # from the background thread after meta.json.  Per-save,
                # so an older save that completed stays counted even when
                # a newer one is still in flight or fails.
                on_durable=functools.partial(
                    self._note_durable_save, self._optimizer_steps
                ),
            )
        if mon is not None and mon.chaos.active:
            # corrupt_save injection (the quarantine path's deterministic
            # trigger) needs the payload bytes on disk; chaos is a test
            # harness, so draining an async save here is acceptable
            if config.async_save and mon.chaos.spec.corrupt_save is not None:
                io_ops.wait_for_saves()
            mon.chaos.note_saved(tag_dir)
        return tag_dir

    @_timed("load")
    def load(
        self, path: str, tag: Optional[str] = None, name: str = "stoke"
    ) -> Dict[str, Any]:
        """Unified checkpoint load (reference stoke.py:1108-1142): restores
        model/optimizer/scaler state *onto the current sharding layout* (the
        FSDP shard-extraction of the reference, io_ops.py:298-306, is just
        "load into the declared shardings" here) and the step counters.  A
        mid-accumulation-window save restores its partial gradient buffer;
        if the checkpoint carries none, the window restarts cleanly."""
        from stoke_tpu import io_ops

        # abstract avals when spilled: the restore template only needs
        # shape/dtype/sharding, and materializing would put ~2x the state in
        # HBM during restore — the exact memory the disk tier exists to avoid
        if self._disk_store is not None and self._disk_store.spilled:
            opt_like = self._disk_store.abstract()
        else:
            opt_like = self._opt_state
        # mirror save(): "losses" is transient output, never checkpointed —
        # load against the stripped template, then re-attach the live
        # collection so the compiled step's state structure is unchanged
        vars_like = {
            k: v for k, v in self._variables.items() if k != "losses"
        }

        def _load(like):
            with trace_span("stoke/io", track="io"):
                return io_ops.load_checkpoint(
                    path=path,
                    tag=tag,
                    variables_like=like,
                    opt_state_like=opt_like,
                    scaler_like=self._scaler_state,
                    config=self._status_obj.checkpoint_config,
                    name=name if tag is None else None,
                    grad_buf_like=self._grad_buf,
                )

        try:
            payload = _load(vars_like)
            loaded_vars = payload["variables"]
            if "losses" in self._variables:
                loaded_vars = {
                    **loaded_vars, "losses": self._variables["losses"]
                }
        except Exception as first_err:
            # legacy layout: a checkpoint saved before sown losses were
            # excluded mismatches the stripped template (consolidated:
            # leaf-count ValueError; sharded: orbax structure errors, which
            # surface as KeyError/TypeError or orbax-specific types — so the
            # retry decision cannot key on the exception class).  Retry with
            # the full template — but if that fails too, surface the
            # ORIGINAL error (a genuine incompatibility), not the retry's.
            # Errors that cannot possibly be a template mismatch skip the
            # retry — a second full restore of a multi-GB sharded checkpoint
            # is expensive and would surface the same error anyway
            if isinstance(first_err, (FileNotFoundError, NotADirectoryError,
                                      PermissionError, IsADirectoryError)):
                raise
            if "losses" not in self._variables:
                raise
            try:
                payload = _load(self._variables)
            except Exception:
                raise first_err
            loaded_vars = payload["variables"]
        self._variables = loaded_vars
        self._opt_commit(payload["opt_state"])
        self._scaler_state = payload["scaler_state"]
        counters = payload["counters"]
        self._backward_steps = counters["backward_step"]
        self._optimizer_steps = counters["optimizer_step"]
        if payload.get("grad_buf") is not None:
            self._grad_buf = payload["grad_buf"]
            self._grad_accum_counter = counters["grad_accum_step"]
        else:
            # no saved buffer → restart the accumulation window from zero
            # rather than under-filling the next optimizer step
            self._grad_buf = self._engine.init_grad_buffer(self._variables)
            self._grad_accum_counter = 0
        return payload.get("extras") or {}

    # ------------------------------------------------------------------ #
    # introspection properties (reference stoke.py:1271-1466)
    # ------------------------------------------------------------------ #

    @property
    def status(self) -> Dict[str, Any]:
        return self._status_obj.status

    def print_status(self) -> None:
        """Pretty-print the full run status (reference status repr,
        status.py:629-654; printed automatically at init when verbose)."""
        if self.is_rank_0:
            unrolled_print(repr(self._status_obj).splitlines())

    @property
    def model_access(self):
        """The underlying model adapter (reference model_access property)."""
        return self._adapter

    @property
    def loss_access(self) -> Callable:
        return self._loss_fn

    @property
    def optimizer(self):
        return self._optimizer

    @property
    def variables(self) -> Dict[str, Any]:
        return self._variables

    @property
    def params(self) -> Any:
        return self._variables["params"]

    @property
    def aux_losses(self) -> Optional[Any]:
        """Latest model-internal auxiliary losses (the flax "losses"
        collection, e.g. MoE load-balancing terms) as of the last training
        step — ``None`` for models that sow none.  These feed the objective
        weighted by ``aux_loss_weight``; they are not part of ``loss()``'s
        report."""
        return self._variables.get("losses")

    @property
    def opt_state(self) -> Any:
        return self._opt_materialize()

    @property
    def scaler(self) -> Dict[str, Any]:
        """Loss-scaler state (reference scaler property / fp16_state_dict,
        stoke.py:1300-1316)."""
        return self._scaler_state

    @property
    def loss_scale(self):
        """Current dynamic loss scale: a float, or (per-loss mode,
        ``PrecisionConfig.num_losses > 1``) a list of one scale per loss."""
        s = jax.device_get(self._scaler_state["scale"])
        if getattr(s, "ndim", 0):
            return [float(v) for v in s]
        return float(s)

    @property
    def mesh(self):
        return self._mesh

    @property
    def sharding_rules(self):
        return self._rules

    @property
    def batch_size(self) -> int:
        return self._status_obj.batch_size

    @property
    def effective_batch_size(self) -> int:
        return self._status_obj.effective_batch_size

    @property
    def grad_accum_steps(self) -> int:
        return self._status_obj.grad_accum

    @property
    def grad_clip(self):
        return self._status_obj.grad_clip

    @property
    def grad_accum_counter(self) -> int:
        return self._grad_accum_counter

    @property
    def optimizer_steps(self) -> int:
        return self._optimizer_steps

    @property
    def backward_steps(self) -> int:
        return self._backward_steps

    @property
    def skipped_optimizer_steps(self) -> float:
        """fp16 steps skipped on overflow (GradScaler semantics)."""
        return float(jax.device_get(self._skipped_steps))

    @property
    def is_distributed(self) -> bool:
        return self._status_obj.is_distributed

    @property
    def is_scaled_precision(self) -> bool:
        return self._status_obj.is_scaled_precision

    @property
    def precision(self) -> PrecisionOptions:
        return self._status_obj.precision

    @property
    def oss(self) -> bool:
        return self._status_obj.oss

    @property
    def sddp(self) -> bool:
        return self._status_obj.sddp

    @property
    def fsdp(self) -> bool:
        return self._status_obj.fsdp

    # ----- reference-parity aliases & config accessors (stoke.py:1271-1466,
    #       status.py:473-627) -----

    @property
    def grad_accum(self) -> int:
        """Alias of grad_accum_steps (reference ``grad_accum`` property)."""
        return self._status_obj.grad_accum

    @property
    def sharded(self) -> bool:
        """Gradient sharding active (reference ``sharded`` ≈ SDDP)."""
        return self._status_obj.sddp

    @property
    def fully_sharded(self) -> bool:
        """Parameter sharding active (reference ``fully_sharded`` ≈ FSDP)."""
        return self._status_obj.fsdp

    @property
    def tpu(self) -> bool:
        """Running on the TPU backend (reference ``gpu``/``cuda`` probes)."""
        return self._status_obj.is_tpu

    @property
    def is_fp16(self) -> bool:
        return self._status_obj.precision is PrecisionOptions.fp16

    @property
    def is_bf16(self) -> bool:
        return self._status_obj.precision is PrecisionOptions.bf16

    @property
    def precision_config(self):
        """(reference amp_config/apex_config, status.py:473-627)"""
        return self._status_obj.precision_config

    @property
    def dp_config(self):
        """(reference ddp_config/horovod_config/deepspeed_config)"""
        return self._status_obj.dp_config

    @property
    def mesh_config(self):
        return self._status_obj.mesh_config

    @property
    def oss_config(self):
        return self._status_obj.oss_config

    @property
    def sddp_config(self):
        return self._status_obj.sddp_config

    @property
    def fsdp_config(self):
        return self._status_obj.fsdp_config

    @property
    def checkpoint_config(self):
        return self._status_obj.checkpoint_config

    @property
    def profiler_config(self):
        return self._status_obj.profiler_config

    def reset_ema(self) -> None:
        """Restart the EMA loss series (reference ``reset_ema``)."""
        self._rolling_mean_loss = self._zero_scalar()
        self._ema_initialized = False

    def reset_tracking(self) -> None:
        """Clear all loss tracking AND step counters (reference
        ``reset_tracking``, stoke.py:1209-1221, zeroes the counters too);
        the partial gradient window is discarded with them."""
        self.reset_ema()
        self._reset_tracking_window()
        self._last_step_loss = None
        self._grad_accum_counter = 0
        self._optimizer_steps = 0
        self._backward_steps = 0
        self._pending = None
        self._grad_buf = self._engine.init_grad_buffer(self._variables)

    def num_model_parameters(
        self, normalize: Optional[ParamNormalize] = None
    ) -> float:
        """Total parameter count (reference stoke.py:1144-1162)."""
        n = tree_count_params(self._variables["params"])
        return n / normalize.value if normalize is not None else n

    def print_num_model_parameters(
        self, normalize: Optional[ParamNormalize] = None
    ) -> None:
        n = self.num_model_parameters(normalize)
        suffix = f" ({normalize.name})" if normalize else ""
        self.print_on_devices(f"Model parameters: {n}{suffix}")

    def dump_model_parameter_info(self) -> None:
        """Per-leaf name/shape/dtype dump (reference stoke.py:1226-1240).
        Names use the SAME rendering as the per-layer numerics surfaces
        (leaf provenance, quantization-error join keys), so they
        cross-reference exactly."""
        from stoke_tpu.telemetry.numerics import leaf_path_names

        params = self._variables["params"]
        leaves = jax.tree_util.tree_leaves(params)
        for name, leaf in zip(leaf_path_names(params), leaves):
            self.print_on_devices(
                f"param {name}: shape={tuple(leaf.shape)} dtype={leaf.dtype}"
            )
