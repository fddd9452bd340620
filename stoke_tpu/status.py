"""State & validation layer: `StokeStatus`.

TPU-native re-design of the reference status layer (stoke/status.py:54-654):
a single source of truth for the run configuration that

1. deduplicates user-supplied config objects by class (reference
   ``_set_configs``, status.py:321-343),
2. enforces the legal-combination matrix *before* any device work happens
   (reference ``_check_all_raised_combinations``, status.py:192-289 — the
   README compatibility table), and
3. lazily materializes per-concern default configs via properties
   (reference status.py:473-627).

The combination matrix is table-driven (a list of rule functions) so tests can
enumerate it exhaustively — SURVEY.md §4 calls this "a table-driven test
goldmine".
"""

from __future__ import annotations

import os
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

from stoke_tpu.configs import (
    ALL_CONFIG_CLASSES,
    COMM_DTYPES,
    COMM_STRATEGIES,
    comm_shard_updates,
    FLEET_ACTIONS,
    HEALTH_ACTIONS,
    ActivationCheckpointingConfig,
    AttributionConfig,
    CheckpointConfig,
    CheckpointFormat,
    HealthConfig,
    ClipGradConfig,
    ClipGradNormConfig,
    CommConfig,
    CompileConfig,
    DataParallelConfig,
    DeviceOptions,
    DistributedInitConfig,
    DistributedOptions,
    FleetConfig,
    FSDPConfig,
    MemoryConfig,
    MeshConfig,
    NumericsConfig,
    OffloadDiskConfig,
    OffloadOptimizerConfig,
    OffloadParamsConfig,
    OpsPlaneConfig,
    OSSConfig,
    PartitionRulesConfig,
    PrecisionConfig,
    PrecisionOptions,
    ProfilerConfig,
    ResilienceConfig,
    SDDPConfig,
    SERVE_ATTENTION_KERNELS,
    SERVE_DECODE_KERNELS,
    SERVE_KV_DTYPES,
    SERVE_QUANT_MODES,
    ServeConfig,
    ShardingOptions,
    TelemetryConfig,
    TensorboardConfig,
    TraceConfig,
    asdict_config,
)


class StokeValidationError(ValueError):
    """Raised when constructor flags form an illegal combination
    (reference raises bare ValueError from status.py:192-289)."""


# Aliases accepted for reference-API compatibility: users of the reference
# select among {ddp, horovod, deepspeed} (status.py:31-38); on TPU these are
# all the one SPMD data-parallel engine.
_DISTRIBUTED_ALIASES = {
    "ddp": DistributedOptions.dp,
    "horovod": DistributedOptions.dp,
    "deepspeed": DistributedOptions.dp,
    "dp": DistributedOptions.dp,
    "xla": DistributedOptions.dp,
}

# Reference FP16Options {apex_O1, apex_O2, amp, deepspeed} (status.py:40-45)
# all meant "fp16 with a loss scaler" on GPU; on TPU the native answer is bf16.
_PRECISION_ALIASES = {
    "full": PrecisionOptions.full,
    "fp32": PrecisionOptions.full,
    "bf16": PrecisionOptions.bf16,
    "bfloat16": PrecisionOptions.bf16,
    "fp16": PrecisionOptions.fp16,
    "float16": PrecisionOptions.fp16,
    "amp": PrecisionOptions.bf16,
    "apex_O1": PrecisionOptions.bf16,
    "apex_O2": PrecisionOptions.bf16,
    "deepspeed": PrecisionOptions.bf16,
}


def _coerce(value, enum_cls, aliases, what):
    if value is None:
        return None
    if isinstance(value, enum_cls):
        return value
    if isinstance(value, str):
        if value in aliases:
            return aliases[value]
        try:
            return enum_cls(value)
        except ValueError:
            pass
    raise StokeValidationError(
        f"Unknown {what} option {value!r}; valid: "
        f"{sorted({*aliases, *[e.value for e in enum_cls]})}"
    )


class StokeStatus:
    """Single source of truth for the run configuration.

    Mirrors reference ``StokeStatus`` (status.py:54-654): holds the canonical
    status dict, validates flag combinations, and materializes per-concern
    default configs lazily.

    Args:
        batch_size_per_device: micro-batch size per device (reference
            ``batch_size`` is per-process; on TPU one process feeds all local
            devices so per-device is the invariant unit).
        grad_accum: gradient accumulation steps (reference stoke.py:137).
        grad_clip: ClipGradConfig | ClipGradNormConfig | None (stoke.py:139).
        device: "cpu" | "tpu" (reference ``gpu: bool``, stoke.py:141).
        distributed: None | "dp" (+ reference aliases ddp/horovod/deepspeed).
        precision: None/"full" | "bf16" | "fp16" (+ reference FP16 aliases).
        oss / sddp / fsdp: the sharding-tier ladder (reference
            fairscale_oss/sddp/fsdp flags, stoke.py:147-152).
        configs: optional list of config-class instances, deduped by class
            (reference status.py:321-343).
    """

    def __init__(
        self,
        batch_size_per_device: int,
        grad_accum: Optional[int] = None,
        grad_clip: Optional[Union[ClipGradConfig, ClipGradNormConfig]] = None,
        device: Union[str, DeviceOptions] = DeviceOptions.cpu,
        distributed: Optional[Union[str, DistributedOptions]] = None,
        precision: Optional[Union[str, PrecisionOptions]] = None,
        oss: bool = False,
        sddp: bool = False,
        fsdp: bool = False,
        configs: Optional[Sequence[Any]] = None,
    ):
        self._configs = self._set_configs(configs)
        self._status: Dict[str, Any] = {
            "batch_size_per_device": batch_size_per_device,
            "grad_accum": 1 if grad_accum is None else int(grad_accum),
            "grad_clip": grad_clip,
            "device": _coerce(device, DeviceOptions, {}, "device"),
            "distributed": _coerce(
                distributed, DistributedOptions, _DISTRIBUTED_ALIASES, "distributed"
            ),
            "precision": _coerce(
                precision, PrecisionOptions, _PRECISION_ALIASES, "precision"
            )
            or PrecisionOptions.full,
            "oss": bool(oss),
            "sddp": bool(sddp),
            "fsdp": bool(fsdp),
            # filled in post-init (reference set_post_init_values, status.py:345)
            "world_size": None,
            "n_devices": None,
            "n_processes": None,
            "effective_batch_size": None,
        }
        self._check_all_raised_combinations()

    # ------------------------------------------------------------------ #
    # Config dedupe (reference status.py:321-343)
    # ------------------------------------------------------------------ #

    @staticmethod
    def _set_configs(configs: Optional[Sequence[Any]]) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for cfg in configs or ():
            name = type(cfg).__name__
            if not isinstance(cfg, ALL_CONFIG_CLASSES):
                raise StokeValidationError(
                    f"Unrecognized config object of type {name}; expected one of "
                    f"{[c.__name__ for c in ALL_CONFIG_CLASSES]}"
                )
            if name in out:
                warnings.warn(
                    f"Stoke -- Duplicate config {name} supplied; keeping the "
                    f"last one (mirrors reference status.py:321-343)"
                )
            out[name] = cfg
        return out

    # ------------------------------------------------------------------ #
    # The legal-combination matrix (reference status.py:192-289)
    # ------------------------------------------------------------------ #

    def _mesh_axes(self) -> Tuple[str, ...]:
        """Axis names of the mesh this run would build (build_mesh uses
        MeshConfig.axes verbatim; default 1-D ("data",))."""
        mc = self._configs.get("MeshConfig")
        return tuple(mc.axes) if mc is not None else ("data",)

    def _rules(self) -> List[Tuple[Callable[[Dict[str, Any]], Any], str]]:
        """Table of (predicate, message).  A predicate returning truthy means
        the combination is ILLEGAL; returning a string overrides the static
        message (for named-axis diagnostics).  Table-driven so tests
        enumerate it (reference ``_check_all_raised_combinations``,
        status.py:192-289)."""

        def _ignored_without_distributed(cfg_name):
            def rule(s):
                return cfg_name in self._configs and s["distributed"] is None
            return rule

        def _mesh_shape_mismatch(s):
            mc = self._configs.get("MeshConfig")
            if mc is None:
                return False
            if len(set(mc.axes)) != len(mc.axes):
                return f"MeshConfig has duplicate axis names {mc.axes}"
            if mc.shape is not None and len(mc.shape) != len(mc.axes):
                return (
                    f"MeshConfig shape {mc.shape} has {len(mc.shape)} entries "
                    f"but axes {mc.axes} has {len(mc.axes)}"
                )
            return False

        def _partition_rule_axis_unknown(s):
            prc = self._configs.get("PartitionRulesConfig")
            if prc is None or s["distributed"] is None:
                return False
            axes = set(self._mesh_axes())
            for rx, spec in prc.rules:
                for entry in spec:
                    # multi-axis dims may arrive as tuples or (from YAML) lists
                    names = (
                        tuple(entry)
                        if isinstance(entry, (tuple, list))
                        else (entry,)
                    )
                    for n in names:
                        if isinstance(n, str) and n != "..." and n not in axes:
                            return (
                                f"partition rule {rx!r} names mesh axis "
                                f"{n!r} but the mesh only has axes "
                                f"{sorted(axes)} — add it to MeshConfig.axes "
                                f"or fix the rule"
                            )
            return False

        def _seq_axis_missing(s):
            dp = self._configs.get("DataParallelConfig")
            if dp is None or dp.shard_seq_dim is None:
                return False
            if s["distributed"] is None:
                return (
                    "DataParallelConfig.shard_seq_dim is set but "
                    "distributed=None; it would be silently ignored"
                )
            if dp.seq_axis_name not in self._mesh_axes():
                return (
                    f"DataParallelConfig.shard_seq_dim is set but the mesh "
                    f"has no {dp.seq_axis_name!r} axis (axes: "
                    f"{list(self._mesh_axes())}) — add it to MeshConfig.axes"
                )
            return False

        def _tier_axis_missing(s):
            if not (s["oss"] or s["sddp"] or s["fsdp"]):
                return False
            dp = self._configs.get("DataParallelConfig")
            axis = dp.axis_name if dp is not None else "data"
            if axis not in self._mesh_axes():
                tier = "fsdp" if s["fsdp"] else ("sddp" if s["sddp"] else "oss")
                return (
                    f"{tier} shards state over mesh axis {axis!r} but the "
                    f"mesh only has axes {list(self._mesh_axes())} — the "
                    f"tier would silently do nothing"
                )
            return False

        def _probe_writable(target):
            """Create ``target`` and prove a file can be written there.
            Returns the OSError on failure, None on success.  NOTE:
            validation intentionally creates the directory (so the first
            mid-training log call can't fail on a missing path) and probes
            actual writability with a throwaway file — makedirs succeeding
            does not prove files can be written (permissions/quota can
            still fail at first write)."""
            import os
            import uuid

            try:
                os.makedirs(target, exist_ok=True)
                probe = os.path.join(
                    target, f".stoke-write-probe-{uuid.uuid4().hex[:8]}"
                )
                with open(probe, "wb") as f:
                    f.write(b"ok")
                os.remove(probe)
                return None
            except OSError as e:
                return e

        def _rank0_only(message):
            """Sink-path failures only matter on the writing process — a
            worker on a read-only mount of a coordinator-owned log dir must
            not kill the whole job."""
            import jax

            if jax.process_index() != 0:
                return False
            return message

        def _tensorboard_writable(s):
            # metrics use the in-repo native event writer
            # (utils/tb_writer.py) — no import to validate, but the output
            # path must be creatable so failures surface at init, not at
            # the first mid-training log call
            cfg = self._configs.get("TensorboardConfig")
            if cfg is None:
                return False
            import os

            err = _probe_writable(os.path.join(cfg.output_path, cfg.job_name))
            if err is None:
                return False
            return _rank0_only(
                f"TensorboardConfig output path "
                f"{cfg.output_path!r}/{cfg.job_name!r} is not writable: {err}"
            )

        def _telemetry_invalid(s):
            # merged observability validation (TelemetryConfig +
            # ProfilerConfig) — cadence/flag errors are structural (raise on
            # every rank); sink-path errors are rank-0 only, same policy as
            # the TB rule above
            cfg = self._configs.get("TelemetryConfig")
            if cfg is None:
                return False
            if cfg.log_every_n_steps < 1:
                return (
                    f"TelemetryConfig.log_every_n_steps must be >= 1, got "
                    f"{cfg.log_every_n_steps}"
                )
            if cfg.prometheus or cfg.tensorboard or cfg.jsonl:
                err = _probe_writable(cfg.output_dir)
                if err is not None:
                    msg = (
                        f"TelemetryConfig.output_dir {cfg.output_dir!r} is "
                        f"not writable: {err}"
                    )
                    # all-rank sinks write on every process: the error is
                    # fatal everywhere, not only on rank 0
                    if (cfg.jsonl and cfg.jsonl_all_ranks) or (
                        cfg.prometheus and cfg.prometheus_all_ranks
                    ):
                        return msg
                    return _rank0_only(msg)
            return False

        def _profiler_invalid(s):
            cfg = self._configs.get("ProfilerConfig")
            if cfg is None or cfg.trace_dir is None:
                return False
            err = _probe_writable(cfg.trace_dir)
            if err is None:
                return False
            # jax.profiler traces write from every process
            return (
                f"ProfilerConfig.trace_dir {cfg.trace_dir!r} is not "
                f"writable: {err}"
            )

        def _comm_invalid(s):
            """Gradient-transport legality (ISSUE 2, extended by ISSUE 8):
            a CommConfig that would silently do nothing (no distributed
            engine), that names an unknown dtype/strategy, or that
            combines quantization with incompatible features is rejected
            HERE — not at compile time, not silently.

            The quantized wire format reaches every sharding tier now:
            tiers none/oss keep PR 2's replicated exchange by default,
            sddp/fsdp auto-engage the ISSUE 8 weight-update-sharded path
            (quantized reduce-scatter → shard-local step → param
            all-gather; ``CommConfig.shard_updates`` overrides either
            way).  Still illegal: fp16 loss scalers with any lossy wire,
            the replicated exchange forced under a sharded grad buffer,
            sharded updates with nothing sharded (tier none) or with the
            single-stage ``all_reduce`` schedule, and a missing data
            axis."""
            cfg = self._configs.get("CommConfig")
            if cfg is None:
                return False
            if s["distributed"] is None:
                return (
                    "CommConfig supplied but distributed=None; the gradient "
                    "transport would be silently ignored — set "
                    "distributed='dp' or drop the config"
                )
            if cfg.dtype not in COMM_DTYPES:
                return (
                    f"CommConfig.dtype {cfg.dtype!r} unknown; valid: "
                    f"{list(COMM_DTYPES)}"
                )
            if cfg.strategy not in COMM_STRATEGIES:
                return (
                    f"CommConfig.strategy {cfg.strategy!r} unknown; valid: "
                    f"{list(COMM_STRATEGIES)}"
                )
            if cfg.bucket_mb <= 0:
                return f"CommConfig.bucket_mb must be > 0, got {cfg.bucket_mb}"
            if cfg.chunk_elems < 1:
                return (
                    f"CommConfig.chunk_elems must be >= 1, got "
                    f"{cfg.chunk_elems}"
                )
            if cfg.dtype == "fp32":
                return False  # exact pass-through composes with everything
            if s["precision"] is PrecisionOptions.fp16:
                # fp16 carries dynamic loss scalers: the single-scaler mode
                # stores SCALED grads in the buffer (quantization chunk
                # scales would alias the loss scale) and per-loss mode
                # updates scaler state from per-micro finiteness — both
                # interact with lossy transport in ways neither the
                # replicated nor the sharded path supports
                return (
                    f"CommConfig(dtype={cfg.dtype!r}) with precision='fp16' "
                    f"is unsupported — the dynamic loss scaler interacts "
                    f"with lossy gradient transport; use bf16 (the TPU "
                    f"path) or full precision"
                )
            tier = self.sharding_tier
            if comm_shard_updates(cfg, tier):
                # ISSUE 8 sharded weight-update path: quantized
                # reduce-scatter → per-shard EF + dequantize → shard-local
                # optimizer step → param all-gather
                if tier is ShardingOptions.none:
                    return (
                        f"CommConfig(dtype={cfg.dtype!r}, shard_updates="
                        f"True) needs a sharded tier — the weight-update-"
                        f"sharded transport partitions the optimizer step "
                        f"over the data axis; enable oss/sddp/fsdp or drop "
                        f"shard_updates"
                    )
                if cfg.strategy != "rs_ag":
                    return (
                        f"CommConfig(strategy={cfg.strategy!r}) cannot "
                        f"shard weight updates — the sharded path IS the "
                        f"rs_ag schedule (quantized reduce-scatter + param "
                        f"all-gather); the single-stage all_reduce assumes "
                        f"every replica consumes the full gradient"
                    )
            elif s["sddp"] or s["fsdp"]:
                # only reachable with an explicit shard_updates=False:
                # sddp/fsdp shard the gradient accumulation buffer over the
                # data axis and the REPLICATED transport needs the
                # replicated grad buffer of tiers none/oss
                tier_name = "fsdp" if s["fsdp"] else "sddp"
                return (
                    f"CommConfig(dtype={cfg.dtype!r}, shard_updates=False) "
                    f"forces the replicated gradient exchange under "
                    f"{tier_name} gradient sharding — the replicated "
                    f"transport needs the replicated grad buffer of tiers "
                    f"none/oss; drop shard_updates to use the sharded "
                    f"weight-update path"
                )
            dp = self._configs.get("DataParallelConfig")
            axis = dp.axis_name if dp is not None else "data"
            if axis not in self._mesh_axes():
                return (
                    f"CommConfig(dtype={cfg.dtype!r}) exchanges gradients "
                    f"over mesh axis {axis!r} but the mesh only has axes "
                    f"{list(self._mesh_axes())} — add it to MeshConfig.axes"
                )
            return False

        def _health_invalid(s):
            """Health-monitor legality (ISSUE 3): sentinels ride the
            telemetry pipeline (their values surface in the JSONL step
            events), halting on non-finite gradients conflicts with fp16's
            skip-on-overflow scaler (transient infs are its normal
            operation), and a watchdog without a positive timeout would
            either never fire or fire immediately."""
            cfg = self._configs.get("HealthConfig")
            if cfg is None:
                return False
            if cfg.sentinels and "TelemetryConfig" not in self._configs:
                return (
                    "HealthConfig(sentinels=True) requires a TelemetryConfig"
                    " — the sentinel values surface through the telemetry "
                    "step events; add one or set sentinels=False"
                )
            if cfg.ring_size < 1:
                return (
                    f"HealthConfig.ring_size must be >= 1, got "
                    f"{cfg.ring_size}"
                )
            if cfg.detector_warmup_steps < 1:
                return (
                    f"HealthConfig.detector_warmup_steps must be >= 1, got "
                    f"{cfg.detector_warmup_steps}"
                )
            for field in (
                "loss_spike_action", "grad_spike_action", "nonfinite_action",
                "scaler_skip_action", "recompile_storm_action",
                "starvation_action", "comm_residual_action",
            ):
                action = getattr(cfg, field)
                if action not in HEALTH_ACTIONS:
                    return (
                        f"HealthConfig.{field} {action!r} unknown; valid: "
                        f"{list(HEALTH_ACTIONS)}"
                    )
            if (
                cfg.nonfinite_action == "halt"
                and s["precision"] is PrecisionOptions.fp16
            ):
                return (
                    "HealthConfig(nonfinite_action='halt') is incompatible "
                    "with precision='fp16' — the dynamic loss scaler "
                    "tolerates transient infs by skipping the step; use "
                    "'record'/'warn'/'dump', or bf16/full precision"
                )
            if cfg.watchdog and cfg.watchdog_timeout_s <= 0:
                return (
                    f"HealthConfig.watchdog requires watchdog_timeout_s > 0,"
                    f" got {cfg.watchdog_timeout_s}"
                )
            # detector-threshold sanity (ISSUE 15 knob-coverage lint): a
            # zero/negative threshold is a detector that fires every step
            # or never — a typo, not a tuning choice
            if not (0.0 < cfg.ema_alpha <= 1.0):
                return (
                    f"HealthConfig.ema_alpha must be in (0, 1], got "
                    f"{cfg.ema_alpha}"
                )
            for field in ("loss_spike_zscore", "grad_spike_zscore",
                          "comm_residual_factor"):
                if getattr(cfg, field) <= 0:
                    return (
                        f"HealthConfig.{field} must be > 0, got "
                        f"{getattr(cfg, field)}"
                    )
            for field in ("scaler_skip_streak", "recompile_storm_threshold",
                          "recompile_storm_window", "starvation_streak"):
                if getattr(cfg, field) < 1:
                    return (
                        f"HealthConfig.{field} must be >= 1, got "
                        f"{getattr(cfg, field)}"
                    )
            if cfg.max_dumps < 0:
                return (
                    f"HealthConfig.max_dumps must be >= 0 (0 disables "
                    f"capped dumps), got {cfg.max_dumps}"
                )
            if cfg.watchdog_compile_grace_s < 0:
                return (
                    f"HealthConfig.watchdog_compile_grace_s must be >= 0,"
                    f" got {cfg.watchdog_compile_grace_s}"
                )
            return False

        def _attribution_invalid(s):
            """Attribution legality (ISSUE 4): the MFU/goodput gauges
            surface through the telemetry step events (so a
            TelemetryConfig is required), MFU needs a positive peak to
            divide by, and the anomaly-triggered profiler capture writes
            xprof traces into ``ProfilerConfig.trace_dir`` (so enabling
            it without one would silently capture nothing)."""
            cfg = self._configs.get("AttributionConfig")
            if cfg is None:
                return False
            if "TelemetryConfig" not in self._configs:
                return (
                    "AttributionConfig requires a TelemetryConfig — the "
                    "MFU/goodput attribution surfaces through the telemetry "
                    "step events; add one or drop the config"
                )
            if cfg.peak_tflops <= 0:
                return (
                    f"AttributionConfig.peak_tflops must be > 0 (MFU's "
                    f"denominator — use the datasheet number), got "
                    f"{cfg.peak_tflops}"
                )
            if cfg.peak_hbm_gbps < 0 or cfg.ici_gbps < 0:
                return (
                    "AttributionConfig.peak_hbm_gbps/ici_gbps must be >= 0 "
                    "(0 disables that roofline leg)"
                )
            if not (0.0 < cfg.ema_alpha <= 1.0):
                return (
                    f"AttributionConfig.ema_alpha must be in (0, 1], got "
                    f"{cfg.ema_alpha}"
                )
            if cfg.capture_warmup_windows < 0:
                return (
                    f"AttributionConfig.capture_warmup_windows must be "
                    f">= 0, got {cfg.capture_warmup_windows}"
                )
            if cfg.auto_capture:
                pc = self._configs.get("ProfilerConfig")
                if pc is None or pc.trace_dir is None:
                    return (
                        "AttributionConfig(auto_capture=True) requires "
                        "ProfilerConfig.trace_dir — the captured xprof "
                        "trace windows are written there; set it or "
                        "disable auto_capture"
                    )
                if cfg.max_captures < 1 or cfg.capture_steps < 1:
                    return (
                        "AttributionConfig auto-capture needs "
                        "max_captures >= 1 and capture_steps >= 1"
                    )
                if (
                    cfg.capture_mfu_below <= 0
                    and cfg.capture_step_zscore <= 0
                ):
                    return (
                        "AttributionConfig(auto_capture=True) with both "
                        "triggers disabled (capture_mfu_below <= 0 and "
                        "capture_step_zscore <= 0) would never capture — "
                        "enable at least one trigger"
                    )
            # 'halt' is deliberately excluded: a diagnostic trace capture
            # must never be able to kill a multi-day run
            valid_capture = [a for a in HEALTH_ACTIONS if a != "halt"]
            if cfg.capture_action not in valid_capture:
                return (
                    f"AttributionConfig.capture_action "
                    f"{cfg.capture_action!r} invalid; valid: "
                    f"{valid_capture} (halt is not allowed — a profiler "
                    f"capture is diagnostic, not fatal)"
                )
            return False

        def _fleet_invalid(s):
            """Fleet-observability legality (ISSUE 5): the fleet view
            surfaces through the telemetry step events (so a
            TelemetryConfig is required), the exchange window must be a
            positive step count, the straggler thresholds must be able to
            fire, and the detector action must be a known non-fatal one
            (a slow host is a diagnosis, never a reason to halt)."""
            cfg = self._configs.get("FleetConfig")
            if cfg is None:
                return False
            if "TelemetryConfig" not in self._configs:
                return (
                    "FleetConfig requires a TelemetryConfig — the fleet "
                    "view surfaces through the telemetry step events; add "
                    "one or drop the config"
                )
            if cfg.window_steps < 1:
                return (
                    f"FleetConfig.window_steps must be >= 1, got "
                    f"{cfg.window_steps}"
                )
            if cfg.straggler_zscore <= 0:
                return (
                    f"FleetConfig.straggler_zscore must be > 0, got "
                    f"{cfg.straggler_zscore}"
                )
            if cfg.straggler_rel_frac <= 0:
                return (
                    f"FleetConfig.straggler_rel_frac must be > 0, got "
                    f"{cfg.straggler_rel_frac}"
                )
            if cfg.straggler_windows < 1:
                return (
                    f"FleetConfig.straggler_windows must be >= 1, got "
                    f"{cfg.straggler_windows}"
                )
            if cfg.straggler_action not in FLEET_ACTIONS:
                return (
                    f"FleetConfig.straggler_action "
                    f"{cfg.straggler_action!r} unknown; valid: "
                    f"{list(FLEET_ACTIONS)} (halt is not allowed — a "
                    f"straggler is a performance diagnosis, not fatal)"
                )
            if cfg.rebalance:
                # skew-reactive input rebalancing (ISSUE 14): the bounded
                # actuator's knobs must be able to act — a zero step size
                # or an empty/full share band is a silently-dead actuator
                # (the chaos-spec discipline: loud, never a no-op)
                if cfg.rebalance_rows < 1:
                    return (
                        f"FleetConfig.rebalance_rows must be >= 1, got "
                        f"{cfg.rebalance_rows}"
                    )
                if not (0.0 < cfg.rebalance_max_frac < 1.0):
                    return (
                        f"FleetConfig.rebalance_max_frac must be in "
                        f"(0, 1) — a host sheds at most that fraction of "
                        f"its read share, never all of it; got "
                        f"{cfg.rebalance_max_frac}"
                    )
            return False

        def _numerics_invalid(s):
            """Per-layer-numerics legality (ISSUE 12): the per-group view
            surfaces through the telemetry pipeline (so a TelemetryConfig
            is required), the provenance action must be a known health
            action — with ``halt`` banned under fp16 for the same reason
            the nonfinite detector's is (transient infs are the dynamic
            scaler's normal operation) — and the config must observe at
            least one signal family (a fully-disabled observatory would
            silently record nothing)."""
            cfg = self._configs.get("NumericsConfig")
            if cfg is None:
                return False
            if "TelemetryConfig" not in self._configs:
                return (
                    "NumericsConfig requires a TelemetryConfig — the "
                    "per-layer numerics surface through the telemetry step "
                    "events; add one or drop the config"
                )
            if cfg.provenance_action not in HEALTH_ACTIONS:
                return (
                    f"NumericsConfig.provenance_action "
                    f"{cfg.provenance_action!r} unknown; valid: "
                    f"{list(HEALTH_ACTIONS)}"
                )
            if (
                cfg.provenance_action == "halt"
                and s["precision"] is PrecisionOptions.fp16
            ):
                return (
                    "NumericsConfig(provenance_action='halt') is "
                    "incompatible with precision='fp16' — the dynamic loss "
                    "scaler tolerates transient infs by skipping the step; "
                    "use 'record'/'warn'/'dump', or bf16/full precision"
                )
            if cfg.top_k < 1:
                return (
                    f"NumericsConfig.top_k must be >= 1, got {cfg.top_k}"
                )
            if not (cfg.grad_stats or cfg.wire_error):
                return (
                    "NumericsConfig with grad_stats=False and "
                    "wire_error=False observes nothing — enable at least "
                    "one signal family or drop the config"
                )
            if not cfg.grad_stats and cfg.provenance_action in (
                "dump", "halt"
            ):
                # provenance is derived FROM the grad-stats matrix: with
                # grad_stats off the detector can never fire, and an
                # explicit escalation that silently no-ops would fake a
                # guarded run (the chaos-spec discipline: typo'd intent
                # is a status error, never a silent no-op)
                return (
                    f"NumericsConfig(provenance_action="
                    f"{cfg.provenance_action!r}) requires grad_stats=True "
                    f"— NaN provenance is derived from the per-group "
                    f"stats matrix, so with grad_stats=False it can "
                    f"never fire; enable grad_stats or drop the "
                    f"escalated action"
                )
            return False

        def _memory_invalid(s):
            """HBM-observatory legality (ISSUE 19): the ledger surfaces
            through the telemetry pipeline (so a TelemetryConfig is
            required), the pre-flight margin must be a usable fraction,
            and a capacity override must be a positive byte count (the
            silently-ignored-knob anti-pattern: a zero/negative capacity
            would make the pre-flight fire always or never)."""
            cfg = self._configs.get("MemoryConfig")
            if cfg is None:
                return False
            if "TelemetryConfig" not in self._configs:
                return (
                    "MemoryConfig requires a TelemetryConfig — the HBM "
                    "capacity ledger surfaces through the telemetry step "
                    "events; add one or drop the config"
                )
            if not (0.0 < cfg.oom_margin_frac <= 1.0):
                return (
                    f"MemoryConfig.oom_margin_frac must be in (0, 1] — "
                    f"the pre-flight warns when predicted peak crosses "
                    f"that fraction of capacity; got "
                    f"{cfg.oom_margin_frac}"
                )
            if cfg.capacity_bytes is not None and cfg.capacity_bytes <= 0:
                return (
                    f"MemoryConfig.capacity_bytes must be a positive "
                    f"byte count when set (None reads the live "
                    f"memory_stats limit); got {cfg.capacity_bytes}"
                )
            return False

        def _opsplane_invalid(s):
            """Ops-plane legality (ISSUE 20): the plane serves the
            telemetry registry (so a TelemetryConfig is required), the
            bind address/port must be usable, and the capture/table
            bounds must actually bound (the silently-ignored-knob
            anti-pattern: a zero requests_limit or an inverted
            default-vs-max capture length would make an endpoint lie)."""
            cfg = self._configs.get("OpsPlaneConfig")
            if cfg is None:
                return False
            if "TelemetryConfig" not in self._configs:
                return (
                    "OpsPlaneConfig requires a TelemetryConfig — the "
                    "plane serves the telemetry registry and reuses its "
                    "Prometheus sink labels; add one or drop the config"
                )
            if not (0 <= cfg.port <= 65535):
                return (
                    f"OpsPlaneConfig.port must be in 0..65535 (0 binds "
                    f"an ephemeral port; rank r binds port + r); got "
                    f"{cfg.port}"
                )
            if not isinstance(cfg.host, str) or not cfg.host:
                return (
                    f"OpsPlaneConfig.host must be a non-empty bind "
                    f"address (loopback '127.0.0.1' by default; "
                    f"'0.0.0.0' to expose to fleet scrapers); got "
                    f"{cfg.host!r}"
                )
            if cfg.profile_max_seconds <= 0:
                return (
                    f"OpsPlaneConfig.profile_max_seconds must be > 0 — "
                    f"it is the hard per-capture ceiling /profile clamps "
                    f"to; got {cfg.profile_max_seconds}"
                )
            if not (
                0 < cfg.profile_default_seconds <= cfg.profile_max_seconds
            ):
                return (
                    f"OpsPlaneConfig.profile_default_seconds must be in "
                    f"(0, profile_max_seconds={cfg.profile_max_seconds}] "
                    f"— /profile without ?seconds= uses it, and a "
                    f"default above the ceiling would silently clamp; "
                    f"got {cfg.profile_default_seconds}"
                )
            if cfg.requests_limit < 1:
                return (
                    f"OpsPlaneConfig.requests_limit must be >= 1 — it "
                    f"caps the /requests table (the response marks "
                    f"itself truncated past it); got {cfg.requests_limit}"
                )
            return False

        def _checkpoint_invalid(s):
            """Checkpoint-layout legality (ISSUE 14, extended by ISSUE
            15's knob-coverage lint): the periodic-save cadence must be
            able to fire — ``save_every_n_steps`` without an
            ``auto_path`` makes ``_maybe_auto_save`` a silent no-op
            (the silently-ignored-knob anti-pattern) — and offload
            staging is the zero-stall path for ASYNC CONSOLIDATED saves
            only — on the sync path there is no background writer to
            hand the staged references to, and the sharded (orbax) path
            already stages its own device→host copy."""
            cfg = self._configs.get("CheckpointConfig")
            if cfg is None:
                return False
            if cfg.save_every_n_steps is not None:
                if cfg.save_every_n_steps < 1:
                    return (
                        f"CheckpointConfig.save_every_n_steps must be "
                        f">= 1 or None, got {cfg.save_every_n_steps}"
                    )
                if not cfg.auto_path:
                    return (
                        "CheckpointConfig.save_every_n_steps is set but "
                        "auto_path is not — the periodic auto-save would "
                        "silently never write; set auto_path or drop the "
                        "cadence"
                    )
            if cfg.save_rank < 0:
                return (
                    f"CheckpointConfig.save_rank must be >= 0 (taken "
                    f"modulo the process count), got {cfg.save_rank}"
                )
            if not getattr(cfg, "offload_staging", False):
                return False
            if not cfg.async_save:
                return (
                    "CheckpointConfig.offload_staging requires "
                    "async_save=True — staging hands device references to "
                    "the background writer; a synchronous save has none. "
                    "Enable async_save or drop offload_staging"
                )
            if cfg.format is CheckpointFormat.sharded:
                return (
                    "CheckpointConfig.offload_staging applies to the "
                    "consolidated format only — the sharded (orbax) async "
                    "path stages its own device→host copy. Use "
                    "format='consolidated' or drop offload_staging"
                )
            return False

        def _resilience_invalid(s):
            """Resilience legality (ISSUE 7): the emergency-save root must
            be writable on EVERY process (sharded emergency saves write
            from all ranks), the resumable exit code must be expressible
            AND distinct from the health watchdog's (supervisors classify
            drained-vs-hung on exactly that difference), the preemption
            signals must exist on this platform, and a chaos spec — config
            field or ``STOKE_CHAOS`` env — must parse (a typo'd plan
            silently injecting nothing would fake a green chaos test)."""
            cfg = self._configs.get("ResilienceConfig")
            if cfg is None:
                return False
            from stoke_tpu.resilience import (
                CHAOS_ENV,
                _WATCHDOG_EXIT_CODE,
                parse_chaos,
            )

            if not (0 < cfg.exit_code < 256):
                return (
                    f"ResilienceConfig.exit_code must be 1..255 (a process "
                    f"exit status), got {cfg.exit_code}"
                )
            if cfg.exit_code == _WATCHDOG_EXIT_CODE:
                return (
                    f"ResilienceConfig.exit_code {cfg.exit_code} collides "
                    f"with the health watchdog's exit code — supervisors "
                    f"classify 'drained cleanly' vs 'hung and self-killed' "
                    f"on that difference; pick another code"
                )
            if not cfg.preempt_signals:
                return (
                    "ResilienceConfig.preempt_signals is empty — the "
                    "preemption handler would never arm; name at least one "
                    "signal or drop the config"
                )
            import signal as _signal

            for name in cfg.preempt_signals:
                if not isinstance(name, str) or getattr(
                    _signal, name, None
                ) is None:
                    return (
                        f"ResilienceConfig.preempt_signals names unknown "
                        f"signal {name!r} (e.g. 'SIGTERM', 'SIGUSR1')"
                    )
            if cfg.max_to_keep is not None and cfg.max_to_keep < 1:
                return (
                    f"ResilienceConfig.max_to_keep must be >= 1 or None, "
                    f"got {cfg.max_to_keep}"
                )
            ckpt = self._configs.get("CheckpointConfig")
            if (
                ckpt is not None
                and ckpt.auto_path
                and cfg.save_name == ckpt.auto_name
                and os.path.abspath(cfg.save_path)
                == os.path.abspath(ckpt.auto_path)
            ):
                return (
                    f"ResilienceConfig.save_name {cfg.save_name!r} "
                    f"collides with CheckpointConfig.auto_name under the "
                    f"same directory — the two save cadences would prune "
                    f"each other's tags; rename one or separate the paths"
                )
            spec = (
                cfg.chaos if cfg.chaos is not None
                else os.environ.get(CHAOS_ENV)
            )
            try:
                parse_chaos(spec)
            except ValueError as e:
                return str(e)
            err = _probe_writable(cfg.save_path)
            if err is not None:
                return (
                    f"ResilienceConfig.save_path {cfg.save_path!r} is not "
                    f"writable: {err}"
                )
            return False

        def _compile_invalid(s):
            """Compile-cache legality (ISSUE 6): the cache directory must
            be writable on EVERY process (each serializes its own step
            executables), and the XLA-cache persistence threshold must be
            a sane duration."""
            cfg = self._configs.get("CompileConfig")
            if cfg is None:
                return False
            if cfg.min_compile_time_s < 0:
                return (
                    f"CompileConfig.min_compile_time_s must be >= 0, got "
                    f"{cfg.min_compile_time_s}"
                )
            if not (cfg.aot or cfg.xla_cache):
                return (
                    "CompileConfig with aot=False and xla_cache=False "
                    "caches nothing — enable a layer or drop the config"
                )
            from stoke_tpu.compile_cache import ledger_dir

            target = cfg.cache_dir or ledger_dir(cfg)
            err = _probe_writable(target)
            if err is not None:
                return (
                    f"CompileConfig.cache_dir {target!r} is not "
                    f"writable: {err}"
                )
            return False

        def _trace_invalid(s):
            """Structured-tracing legality (ISSUE 10): the recorder's ring
            must be able to hold at least one span, and — since EVERY rank
            exports its own ``trace.rank<N>.json`` — an unwritable output
            dir is fatal on every process, not only rank 0.  The config is
            purely host-side; its presence never touches the compiled step
            programs (default-OFF contract, tests/test_tracing.py asserts
            HLO bit-identity)."""
            cfg = self._configs.get("TraceConfig")
            if cfg is None:
                return False
            if cfg.ring_size < 1:
                return (
                    f"TraceConfig.ring_size must be >= 1, got "
                    f"{cfg.ring_size}"
                )
            if cfg.export_on_close:
                err = _probe_writable(cfg.output_dir)
                if err is not None:
                    return (
                        f"TraceConfig.output_dir {cfg.output_dir!r} is not "
                        f"writable: {err}"
                    )
            return False

        def _serve_invalid(s):
            """Serving-stack legality (ISSUE 9): a ServeConfig that could
            never admit a request, that names an unknown kernel/dtype/
            quant mode, or whose block pool cannot hold even one
            max-length sequence is rejected at construction — not at the
            first ``serve()`` call mid-deployment.  The config is only
            READ by ``Stoke.serve()``; its presence never touches the
            training paths (default-OFF contract, tests/test_serving.py
            asserts HLO bit-identity)."""
            cfg = self._configs.get("ServeConfig")
            if cfg is None:
                return False
            for field in ("max_seqs", "kv_block_size", "max_seq_len",
                          "max_new_tokens", "prefill_pad_multiple",
                          "log_every_n_steps"):
                if getattr(cfg, field) < 1:
                    return (
                        f"ServeConfig.{field} must be >= 1, got "
                        f"{getattr(cfg, field)}"
                    )
            if cfg.attention not in SERVE_ATTENTION_KERNELS:
                return (
                    f"ServeConfig.attention {cfg.attention!r} unknown; "
                    f"valid: {list(SERVE_ATTENTION_KERNELS)}"
                )
            if cfg.decode_kernel not in SERVE_DECODE_KERNELS:
                return (
                    f"ServeConfig.decode_kernel {cfg.decode_kernel!r} "
                    f"unknown; valid: {list(SERVE_DECODE_KERNELS)}"
                )
            if cfg.attention == "flash":
                # every prefill bucket is one flash call over the whole
                # padded prompt: reject a bucket ladder the kernel's block
                # picker would refuse at trace time, mid-deployment
                from stoke_tpu.ops.flash_attention import (
                    DEFAULT_BLOCK_K,
                    _pick_block,
                )

                pad = cfg.prefill_pad_multiple
                longest = min(
                    cfg.max_seq_len, cfg.prefill_chunk_tokens or cfg.max_seq_len
                )
                for bucket in range(pad, longest + pad, pad):
                    try:
                        # prefill masks the prompt padding: key-masked
                        _pick_block(
                            None, bucket, DEFAULT_BLOCK_K, lane_aligned=True
                        )
                    except ValueError:
                        return (
                            f"ServeConfig.attention='flash' with "
                            f"prefill_pad_multiple={pad}: the {bucket}-token "
                            f"prefill bucket has no legal flash block "
                            f"(buckets above {DEFAULT_BLOCK_K} tokens must "
                            f"be multiples of 128). Use a "
                            f"prefill_pad_multiple that is a multiple of "
                            f"128, or cap the unchunked prompt at "
                            f"{DEFAULT_BLOCK_K} tokens (max_seq_len / "
                            f"prefill_chunk_tokens)"
                        )
            if (
                cfg.decode_kernel == "pallas"
                and s["device"] is DeviceOptions.cpu
            ):
                # the streaming kernel is a TPU fast path; a REAL serve
                # config on a CPU device would silently run the pallas
                # INTERPRETER (orders of magnitude slower than the
                # reference kernel it exists to beat).  Tests exercise
                # interpreter parity through ServingEngine directly.
                return (
                    "ServeConfig.decode_kernel='pallas' on device='cpu': "
                    "the streaming decode kernel needs a TPU backend — "
                    "use decode_kernel='reference' on CPU (the pallas "
                    "interpreter parity mode is for tests, via a "
                    "standalone ServingEngine)"
                )
            if cfg.prefill_chunk_tokens is not None:
                c = cfg.prefill_chunk_tokens
                if c < 1:
                    return (
                        f"ServeConfig.prefill_chunk_tokens must be >= 1, "
                        f"got {c}"
                    )
                if c % cfg.prefill_pad_multiple:
                    return (
                        f"ServeConfig.prefill_chunk_tokens={c} must be a "
                        f"multiple of prefill_pad_multiple="
                        f"{cfg.prefill_pad_multiple} — chunk shapes ride "
                        f"the same bucket discipline that bounds compiled-"
                        f"program count"
                    )
                if c > cfg.max_seq_len:
                    return (
                        f"ServeConfig.prefill_chunk_tokens={c} exceeds "
                        f"max_seq_len={cfg.max_seq_len} — no prompt could "
                        f"ever be chunked"
                    )
            if cfg.temperature < 0.0:
                return (
                    f"ServeConfig.temperature must be >= 0, got "
                    f"{cfg.temperature}"
                )
            if cfg.top_k is not None and cfg.top_k < 1:
                return (
                    f"ServeConfig.top_k must be >= 1 when set, got "
                    f"{cfg.top_k}"
                )
            if cfg.top_p is not None and not (0.0 < cfg.top_p <= 1.0):
                return (
                    f"ServeConfig.top_p must be in (0, 1] when set, got "
                    f"{cfg.top_p}"
                )
            if not cfg.sampling and (
                cfg.temperature != 0.0
                or cfg.top_k is not None
                or cfg.top_p is not None
            ):
                # a sampled-looking config that silently serves greedy is
                # the chaos-spec anti-pattern: never ignore, always name
                # the remedy
                return (
                    "ServeConfig sampling knobs set (temperature/top_k/"
                    "top_p) but sampling=False — the greedy programs "
                    "would silently ignore them; set sampling=True or "
                    "drop the knobs"
                )
            if cfg.quant not in SERVE_QUANT_MODES:
                return (
                    f"ServeConfig.quant {cfg.quant!r} unknown; valid: "
                    f"{list(SERVE_QUANT_MODES)}"
                )
            if cfg.kv_dtype not in SERVE_KV_DTYPES:
                return (
                    f"ServeConfig.kv_dtype {cfg.kv_dtype!r} unknown; "
                    f"valid: {list(SERVE_KV_DTYPES)}"
                )
            if cfg.quant_chunk_elems < 1:
                return (
                    f"ServeConfig.quant_chunk_elems must be >= 1, got "
                    f"{cfg.quant_chunk_elems}"
                )
            if cfg.quant_min_size < 0:
                return (
                    f"ServeConfig.quant_min_size must be >= 0 (leaves "
                    f"below it stay unquantized), got {cfg.quant_min_size}"
                )
            if cfg.eos_id is not None and cfg.eos_id < 0:
                return (
                    f"ServeConfig.eos_id must be a token id >= 0 when "
                    f"set (None = run to the token cap), got {cfg.eos_id}"
                )
            if cfg.prefill_pad_multiple > cfg.max_seq_len:
                return (
                    f"ServeConfig.prefill_pad_multiple "
                    f"{cfg.prefill_pad_multiple} exceeds max_seq_len "
                    f"{cfg.max_seq_len} — every padded prompt would be "
                    f"rejected"
                )
            if cfg.kv_blocks is not None:
                # one max-length sequence needs ceil(max_seq_len/bs)
                # blocks, plus the reserved scratch block 0
                need = -(-cfg.max_seq_len // cfg.kv_block_size) + 1
                if cfg.kv_blocks < need:
                    return (
                        f"ServeConfig.kv_blocks={cfg.kv_blocks} cannot "
                        f"hold one max_seq_len={cfg.max_seq_len} sequence "
                        f"(needs {need} blocks of {cfg.kv_block_size} "
                        f"tokens incl. the reserved scratch block 0) — no "
                        f"request could ever be admitted"
                    )
            for field in ("slo_ttft_target_s", "slo_tpot_target_s"):
                v = getattr(cfg, field)
                if v is not None and not v > 0.0:
                    # a non-positive deadline is violated before the
                    # request even arrives — reject with the remedy, not
                    # a 100%-violation dashboard mystery (ISSUE 16)
                    return (
                        f"ServeConfig.{field} must be > 0 seconds when "
                        f"set, got {v} (None = requests carry their own "
                        f"RequestSLO targets)"
                    )
            # speculative decoding (ISSUE 17): same knob discipline as
            # sampling — misconfigurations name the remedy, knobs a
            # disabled feature would silently ignore are rejected
            if cfg.speculative_k is not None:
                if cfg.speculative_k < 1:
                    return (
                        f"ServeConfig.speculative_k must be >= 1 when set "
                        f"(None = speculative decoding off), got "
                        f"{cfg.speculative_k}"
                    )
                if not cfg.sampling:
                    return (
                        f"ServeConfig.speculative_k={cfg.speculative_k} "
                        f"needs sampling=True — the verify program rides "
                        f"the key-threaded sampling programs "
                        f"(temperature=0.0 keeps exact greedy streams); "
                        f"set sampling=True or drop speculative_k"
                    )
                if (
                    cfg.prefill_chunk_tokens is not None
                    and cfg.speculative_k + 1 > cfg.prefill_chunk_tokens
                ):
                    return (
                        f"ServeConfig.speculative_k={cfg.speculative_k} "
                        f"puts the verify query width (k+1="
                        f"{cfg.speculative_k + 1}) over the chunk budget "
                        f"prefill_chunk_tokens={cfg.prefill_chunk_tokens} "
                        f"— the multi-token programs share that "
                        f"per-iteration bound; shrink speculative_k or "
                        f"raise prefill_chunk_tokens"
                    )
                if cfg.speculative_ngram_min < 1:
                    return (
                        f"ServeConfig.speculative_ngram_min must be >= 1, "
                        f"got {cfg.speculative_ngram_min}"
                    )
                if cfg.speculative_ngram_max < cfg.speculative_ngram_min:
                    return (
                        f"ServeConfig.speculative_ngram_max="
                        f"{cfg.speculative_ngram_max} < "
                        f"speculative_ngram_min="
                        f"{cfg.speculative_ngram_min} — the drafter's "
                        f"n-gram range is empty"
                    )
            else:
                if (
                    cfg.speculative_ngram_max != 3
                    or cfg.speculative_ngram_min != 1
                ):
                    return (
                        "ServeConfig speculative drafter knobs set "
                        "(speculative_ngram_max/speculative_ngram_min) "
                        "but speculative_k=None — the non-speculative "
                        "engine would silently ignore them; set "
                        "speculative_k or drop the knobs"
                    )
            # roofline observatory (ISSUE 18): the cost cards divide by
            # hardware peaks — both roofline legs need a ceiling, so an
            # AttributionConfig with a positive HBM bandwidth is required
            # (peak_tflops > 0 the attribution rule already enforces)
            if cfg.cost_cards:
                attr = self._configs.get("AttributionConfig")
                if attr is None:
                    return (
                        "ServeConfig.cost_cards=True requires an "
                        "AttributionConfig — the serve roofline divides "
                        "by its peak_tflops / peak_hbm_gbps ceilings; "
                        "add one or drop cost_cards"
                    )
                if attr.peak_hbm_gbps <= 0:
                    return (
                        f"ServeConfig.cost_cards=True needs "
                        f"AttributionConfig.peak_hbm_gbps > 0 (the "
                        f"memory leg of the decode roofline — attainable "
                        f"TPOT is bandwidth-bound), got "
                        f"{attr.peak_hbm_gbps}"
                    )
            return False

        def _remat_invalid(s):
            """Rematerialization legality (ISSUE 15 knob-coverage lint):
            a typo'd checkpoint policy previously surfaced as a bare
            AttributeError at the FIRST step compile, deep inside the
            engine — validate it here with the remedy named instead."""
            cfg = self._configs.get("ActivationCheckpointingConfig")
            if cfg is None:
                return False
            import jax

            if not isinstance(cfg.policy, str) or not hasattr(
                jax.checkpoint_policies, cfg.policy
            ):
                return (
                    f"ActivationCheckpointingConfig.policy {cfg.policy!r} "
                    f"is not a jax.checkpoint_policies member — use e.g. "
                    f"'nothing_saveable', 'dots_saveable', "
                    f"'dots_with_no_batch_dims_saveable', or "
                    f"'everything_saveable'"
                )
            return False

        def _precision_scaler_invalid(s):
            """Loss-scaler knob sanity (ISSUE 15 knob-coverage lint): a
            non-positive scale or a backoff that GROWS the scale is a
            scaler that can never recover from overflow — a typo, not a
            tuning choice.  Checked whenever a PrecisionConfig is
            supplied (the values must be sane even while fp16 is off)."""
            cfg = self._configs.get("PrecisionConfig")
            if cfg is None:
                return False
            if cfg.init_scale <= 0 or cfg.min_scale <= 0:
                return (
                    f"PrecisionConfig.init_scale/min_scale must be > 0, "
                    f"got {cfg.init_scale}/{cfg.min_scale}"
                )
            if cfg.growth_factor < 1.0:
                return (
                    f"PrecisionConfig.growth_factor must be >= 1 (growth "
                    f"never shrinks the scale), got {cfg.growth_factor}"
                )
            if not (0.0 < cfg.backoff_factor <= 1.0):
                return (
                    f"PrecisionConfig.backoff_factor must be in (0, 1] "
                    f"(backoff never grows the scale), got "
                    f"{cfg.backoff_factor}"
                )
            if cfg.growth_interval < 1:
                return (
                    f"PrecisionConfig.growth_interval must be >= 1, got "
                    f"{cfg.growth_interval}"
                )
            return False

        def _fsdp_pref_invalid(s):
            """A typo'd ``shard_axis_preference`` previously fell through
            to the 'largest' branch silently (ISSUE 15 knob-coverage
            lint caught it; parallel/sharding.py dispatches on the
            string)."""
            cfg = self._configs.get("FSDPConfig")
            if cfg is None:
                return False
            if cfg.shard_axis_preference not in ("largest", "first"):
                return (
                    f"FSDPConfig.shard_axis_preference "
                    f"{cfg.shard_axis_preference!r} unknown; valid: "
                    f"['largest', 'first'] — any other value would "
                    f"silently act as 'largest'"
                )
            return False

        def _offload_cpu_no_fallback(s):
            for name in ("OffloadOptimizerConfig", "OffloadParamsConfig"):
                cfg = self._configs.get(name)
                if (
                    cfg is not None
                    and not cfg.fallback_to_device
                    and s["device"] is DeviceOptions.cpu
                ):
                    return (
                        f"{name}(fallback_to_device=False) on device='cpu': "
                        f"the CPU runtime has no pinned_host memory kind; "
                        f"allow fallback or use device='tpu'"
                    )
            return False

        def _param_offload_requires_fsdp(s):
            return "OffloadParamsConfig" in self._configs and not s["fsdp"]

        def _offload_tier_conflict(s):
            return (
                "OffloadDiskConfig" in self._configs
                and "OffloadOptimizerConfig" in self._configs
            )

        return [
            (
                lambda s: s["batch_size_per_device"] is None
                or s["batch_size_per_device"] < 1,
                "batch_size_per_device must be >= 1",
            ),
            (
                lambda s: s["grad_accum"] < 1,
                "grad_accum must be >= 1",
            ),
            (
                lambda s: s["grad_clip"] is not None
                and not isinstance(s["grad_clip"], (ClipGradConfig, ClipGradNormConfig)),
                "grad_clip must be ClipGradConfig, ClipGradNormConfig, or None",
            ),
            # clip-bound sanity (ISSUE 15 knob-coverage lint): a zero or
            # negative bound zeroes/flips every gradient — a typo, never
            # a tuning choice; norm_type < 1 is not a norm
            (
                lambda s: isinstance(s["grad_clip"], ClipGradConfig)
                and s["grad_clip"].clip_value <= 0,
                "ClipGradConfig.clip_value must be > 0 (an elementwise "
                "bound of 0 zeroes every gradient)",
            ),
            (
                lambda s: isinstance(s["grad_clip"], ClipGradNormConfig)
                and (
                    s["grad_clip"].max_norm <= 0
                    or s["grad_clip"].norm_type < 1
                ),
                "ClipGradNormConfig needs max_norm > 0 and norm_type >= 1 "
                "(inf is legal)",
            ),
            # per-loss scalers are an fp16 feature (reference: Apex
            # num_losses configures amp loss scalers, fp16.py:656-691;
            # full/bf16 have no scaler to multiply)
            (
                lambda s: (
                    (pc := self._configs.get("PrecisionConfig")) is not None
                    and pc.num_losses != 1
                    and (
                        pc.num_losses < 1
                        or s["precision"] is not PrecisionOptions.fp16
                    )
                ),
                "PrecisionConfig.num_losses > 1 (per-loss scalers) requires "
                "precision='fp16' and num_losses >= 1 — reference Apex "
                "num_losses, fp16.py:656-691",
            ),
            # sharding ladder legality (reference status.py:239-263):
            # SDDP requires OSS (status.py:240-243)
            (
                lambda s: s["sddp"] and not s["oss"],
                "sddp (gradient sharding) requires oss (optimizer-state "
                "sharding) — reference status.py:240-243",
            ),
            # FSDP subsumes and excludes OSS/SDDP (reference status.py:244-263)
            (
                lambda s: s["fsdp"] and (s["oss"] or s["sddp"]),
                "fsdp (fully-sharded) already shards optimizer state and "
                "gradients; combining with oss/sddp is illegal — reference "
                "status.py:244-263",
            ),
            # sharding requires the distributed engine (reference: fairscale
            # extensions require DDP, status.py:231-263)
            (
                lambda s: (s["oss"] or s["sddp"] or s["fsdp"])
                and s["distributed"] is None,
                "sharding tiers (oss/sddp/fsdp) require distributed='dp' — "
                "reference status.py:231-263",
            ),
            # --- configs supplied but structurally ignored (fail loud at
            # init instead of silently doing nothing / erroring at compile) ---
            (
                _ignored_without_distributed("MeshConfig"),
                "MeshConfig supplied but distributed=None; the mesh would be "
                "silently ignored — set distributed='dp' or drop the config",
            ),
            (
                _ignored_without_distributed("PartitionRulesConfig"),
                "PartitionRulesConfig supplied but distributed=None; the "
                "rules would be silently ignored — set distributed='dp' or "
                "drop the config",
            ),
            # --- mesh-axis consistency (a bad axis otherwise surfaces as a
            # cryptic GSPMD error at compile time) ---
            (
                _mesh_shape_mismatch,
                "MeshConfig axes/shape inconsistent",
            ),
            (
                _partition_rule_axis_unknown,
                "partition rule names an unknown mesh axis",
            ),
            (
                _seq_axis_missing,
                "sequence-dim sharding configured without a seq mesh axis",
            ),
            (
                _tier_axis_missing,
                "sharding tier's data axis missing from the mesh",
            ),
            # --- dependency checks ---
            (
                _tensorboard_writable,
                "TensorboardConfig output path is not writable",
            ),
            (
                _telemetry_invalid,
                "TelemetryConfig is invalid",
            ),
            (
                _profiler_invalid,
                "ProfilerConfig.trace_dir is not writable",
            ),
            (
                _comm_invalid,
                "CommConfig is invalid for this combination",
            ),
            (
                _health_invalid,
                "HealthConfig is invalid for this combination",
            ),
            (
                _attribution_invalid,
                "AttributionConfig is invalid for this combination",
            ),
            (
                _fleet_invalid,
                "FleetConfig is invalid for this combination",
            ),
            (
                _numerics_invalid,
                "NumericsConfig is invalid for this combination",
            ),
            (
                _memory_invalid,
                "MemoryConfig is invalid for this combination",
            ),
            (
                _opsplane_invalid,
                "OpsPlaneConfig is invalid for this combination",
            ),
            (
                _checkpoint_invalid,
                "CheckpointConfig is invalid",
            ),
            (
                _resilience_invalid,
                "ResilienceConfig is invalid",
            ),
            (
                _compile_invalid,
                "CompileConfig is invalid",
            ),
            (
                _serve_invalid,
                "ServeConfig is invalid",
            ),
            (
                _trace_invalid,
                "TraceConfig is invalid",
            ),
            (
                _remat_invalid,
                "ActivationCheckpointingConfig.policy is invalid",
            ),
            (
                _precision_scaler_invalid,
                "PrecisionConfig scaler knobs are invalid",
            ),
            (
                _fsdp_pref_invalid,
                "FSDPConfig.shard_axis_preference is invalid",
            ),
            (
                _offload_cpu_no_fallback,
                "offload config with fallback_to_device=False on device='cpu'",
            ),
            (
                _param_offload_requires_fsdp,
                "OffloadParamsConfig requires fsdp=True — parameter offload "
                "is a ZeRO-3 feature (reference DeepspeedOffloadParamConfig "
                "legal only at stage 3, configs.py:346-372)",
            ),
            (
                _offload_tier_conflict,
                "OffloadDiskConfig and OffloadOptimizerConfig are mutually "
                "exclusive — one offload tier per state (reference: a single "
                "offload_optimizer device choice, configs.py:309-343)",
            ),
        ]

    def _check_all_raised_combinations(self) -> None:
        for predicate, message in self._rules():
            result = predicate(self._status)
            if result:
                msg = result if isinstance(result, str) else message
                raise StokeValidationError(f"Stoke -- illegal combination: {msg}")

    # ------------------------------------------------------------------ #
    # Post-init values (reference status.py:345-372, effective batch :373-375)
    # ------------------------------------------------------------------ #

    def set_post_init_values(
        self, world_size: int, n_processes: int = 1
    ) -> None:
        """Record device/process topology once the engine exists (reference
        ``set_post_init_values``, status.py:345; effective batch size calc
        status.py:373-375)."""
        self._status["world_size"] = world_size
        self._status["n_devices"] = world_size
        self._status["n_processes"] = n_processes
        self._status["effective_batch_size"] = (
            self._status["batch_size_per_device"]
            * world_size
            * self._status["grad_accum"]
        )

    # ------------------------------------------------------------------ #
    # Flag accessors
    # ------------------------------------------------------------------ #

    @property
    def status(self) -> Dict[str, Any]:
        """Canonical status dict (reference status.py:171-188)."""
        return dict(self._status)

    @property
    def batch_size(self) -> int:
        return self._status["batch_size_per_device"]

    @property
    def effective_batch_size(self) -> Optional[int]:
        return self._status["effective_batch_size"]

    @property
    def grad_accum(self) -> int:
        return self._status["grad_accum"]

    @property
    def grad_clip(self):
        return self._status["grad_clip"]

    @property
    def device(self) -> DeviceOptions:
        return self._status["device"]

    @property
    def is_tpu(self) -> bool:
        return self._status["device"] is DeviceOptions.tpu

    @property
    def distributed(self) -> Optional[DistributedOptions]:
        return self._status["distributed"]

    @property
    def is_distributed(self) -> bool:
        return self._status["distributed"] is not None

    @property
    def precision(self) -> PrecisionOptions:
        return self._status["precision"]

    @property
    def is_scaled_precision(self) -> bool:
        """True when a dynamic loss scaler is in play (fp16 only; bf16 needs
        none — SURVEY.md §3.2 hot-loop observation (c))."""
        return self._status["precision"] is PrecisionOptions.fp16

    @property
    def oss(self) -> bool:
        return self._status["oss"]

    @property
    def sddp(self) -> bool:
        return self._status["sddp"]

    @property
    def fsdp(self) -> bool:
        return self._status["fsdp"]

    @property
    def sharding_tier(self) -> ShardingOptions:
        """Collapse the three booleans to the ladder rung (post-validation the
        combinations are mutually consistent)."""
        if self._status["fsdp"]:
            return ShardingOptions.fsdp
        if self._status["sddp"]:
            return ShardingOptions.sddp
        if self._status["oss"]:
            return ShardingOptions.oss
        return ShardingOptions.none

    @property
    def world_size(self) -> Optional[int]:
        return self._status["world_size"]

    # ------------------------------------------------------------------ #
    # Lazily-materialized per-concern configs (reference status.py:473-627)
    # ------------------------------------------------------------------ #

    def _get_or_default(self, cls):
        name = cls.__name__
        if name not in self._configs:
            self._configs[name] = cls()
        return self._configs[name]

    @property
    def precision_config(self) -> PrecisionConfig:
        return self._get_or_default(PrecisionConfig)

    @property
    def dp_config(self) -> DataParallelConfig:
        return self._get_or_default(DataParallelConfig)

    @property
    def mesh_config(self) -> MeshConfig:
        return self._get_or_default(MeshConfig)

    @property
    def dist_init_config(self) -> DistributedInitConfig:
        return self._get_or_default(DistributedInitConfig)

    @property
    def oss_config(self) -> OSSConfig:
        return self._get_or_default(OSSConfig)

    @property
    def sddp_config(self) -> SDDPConfig:
        return self._get_or_default(SDDPConfig)

    @property
    def fsdp_config(self) -> FSDPConfig:
        return self._get_or_default(FSDPConfig)

    @property
    def comm_config(self) -> Optional[CommConfig]:
        """None unless explicitly supplied (the gradient-transport layer is
        opt-in and defaults OFF; without it gradients sync through the
        compiler-inserted fp32 collectives exactly as before)."""
        return self._configs.get("CommConfig")

    @property
    def partition_rules_config(self):
        """None unless explicitly supplied (tensor parallelism is opt-in)."""
        return self._configs.get("PartitionRulesConfig")

    @property
    def offload_optimizer_config(self):
        """None unless explicitly supplied (offload is opt-in, reference
        configs.py:309-343)."""
        return self._configs.get("OffloadOptimizerConfig")

    @property
    def offload_params_config(self):
        """None unless explicitly supplied (param offload is opt-in and
        fsdp-only, reference configs.py:346-372)."""
        return self._configs.get("OffloadParamsConfig")

    @property
    def offload_disk_config(self):
        """None unless explicitly supplied (disk/NVMe tier is opt-in,
        reference DeepspeedAIOConfig configs.py:192-221)."""
        return self._configs.get("OffloadDiskConfig")

    @property
    def activation_checkpointing_config(self) -> Optional[ActivationCheckpointingConfig]:
        """None unless explicitly supplied (remat is opt-in, matching the
        reference where activation checkpointing is DeepSpeed-only
        passthrough, configs.py:222-248)."""
        return self._configs.get("ActivationCheckpointingConfig")

    @property
    def checkpoint_config(self) -> CheckpointConfig:
        return self._get_or_default(CheckpointConfig)

    @property
    def profiler_config(self) -> ProfilerConfig:
        return self._get_or_default(ProfilerConfig)

    @property
    def tensorboard_config(self):
        """None unless explicitly supplied (metrics logging is opt-in,
        reference configs.py:392-405)."""
        return self._configs.get("TensorboardConfig")

    @property
    def health_config(self) -> Optional[HealthConfig]:
        """None unless explicitly supplied (the health monitor is opt-in;
        without it the step paths are bit-identical to pre-ISSUE-3)."""
        return self._configs.get("HealthConfig")

    @property
    def attribution_config(self) -> Optional[AttributionConfig]:
        """None unless explicitly supplied (step-time attribution is
        opt-in; without it the step paths run no cost analysis and the
        compiled programs are bit-identical to pre-ISSUE-4)."""
        return self._configs.get("AttributionConfig")

    @property
    def fleet_config(self) -> Optional[FleetConfig]:
        """None unless explicitly supplied (fleet observability is
        opt-in; without it no cross-host exchange ever runs and the step
        paths are bit-identical to pre-ISSUE-5)."""
        return self._configs.get("FleetConfig")

    @property
    def numerics_config(self) -> Optional[NumericsConfig]:
        """None unless explicitly supplied (the per-layer numerics
        observatory is opt-in; without it the compiled step programs are
        bit-identical to pre-ISSUE-12)."""
        return self._configs.get("NumericsConfig")

    @property
    def memory_config(self) -> Optional[MemoryConfig]:
        """None unless explicitly supplied (the HBM capacity observatory
        is opt-in; without it no ``mem/*`` field or gauge exists and the
        compiled programs are bit-identical to pre-ISSUE-19)."""
        return self._configs.get("MemoryConfig")

    @property
    def opsplane_config(self) -> Optional[OpsPlaneConfig]:
        """None unless explicitly supplied (the live ops plane is
        opt-in; without it no thread starts and no socket binds, and the
        step paths are bit-identical to pre-ISSUE-20)."""
        return self._configs.get("OpsPlaneConfig")

    @property
    def resilience_config(self) -> Optional[ResilienceConfig]:
        """None unless explicitly supplied (pod-scale resilience is
        opt-in; without it the step paths, signal dispositions, and
        checkpoint layout are bit-identical to pre-ISSUE-7)."""
        return self._configs.get("ResilienceConfig")

    @property
    def compile_config(self) -> Optional[CompileConfig]:
        """None unless explicitly supplied (the persistent compilation
        cache is opt-in; without it the engine dispatches its jit
        programs exactly as before — bit-identical HLO)."""
        return self._configs.get("CompileConfig")

    @property
    def serve_config(self) -> Optional[ServeConfig]:
        """None unless explicitly supplied (the serving stack is opt-in
        and only read by ``Stoke.serve()``; without — or even with — the
        config the training step paths are bit-identical to pre-ISSUE-9)."""
        return self._configs.get("ServeConfig")

    @property
    def telemetry_config(self) -> Optional[TelemetryConfig]:
        """None unless explicitly supplied (the unified telemetry pipeline
        is opt-in; a None config keeps the facade's registry alive but
        attaches no sinks/collectors)."""
        return self._configs.get("TelemetryConfig")

    @property
    def trace_config(self) -> Optional[TraceConfig]:
        """None unless explicitly supplied (structured tracing is opt-in;
        without it no span recorder is registered and the composed span
        helper degrades to the bare xprof annotation)."""
        return self._configs.get("TraceConfig")

    # ------------------------------------------------------------------ #
    # Serialization / display (reference status.py:629-654)
    # ------------------------------------------------------------------ #

    def to_dict(self) -> Dict[str, Any]:
        """JSON-friendly dump for checkpoints (reference saves the status dict
        inside every checkpoint, io_ops.py:224-236)."""
        out = {}
        for k, v in self._status.items():
            if hasattr(v, "value") and not isinstance(v, (int, float, str)):
                v = v.value
            elif isinstance(v, (ClipGradConfig, ClipGradNormConfig)):
                v = {"type": type(v).__name__, **asdict_config(v)}
            out[k] = v
        out["configs"] = {k: asdict_config(v) for k, v in self._configs.items()}
        return out

    def __repr__(self) -> str:  # reference status.py:629-654
        lines = ["Stoke -- Status:"]
        for k, v in self.to_dict().items():
            lines.append(f"  {k}: {v}")
        return "\n".join(lines)
