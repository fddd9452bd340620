"""Functional core: compiled train/eval steps behind the imperative facade.

This module solves SURVEY.md §7 hard part #1 — keeping the reference's
imperative 4-call contract (``model → loss → backward → step``,
stoke/stoke.py:853-1040) over a purely functional JAX core — with a *lazy
fused step*:

- ``model(x)`` (train mode) returns a :class:`DeferredOutput` handle and
  stashes the batch; nothing runs.
- ``loss(out, y)`` runs ONE compiled function that does forward + loss +
  grad + accumulate-into-buffer (micro-step), returning device-scalar losses.
  This is the TPU answer to the reference's per-micro-batch synchronous
  ``.item()`` + allreduce (distributed.py:619-646): the loss stays on device,
  the gradient all-reduce/reduce-scatter is compiler-inserted, and there is
  exactly one dispatch per micro-batch.
- ``backward(loss)`` commits the accumulated buffer (pointer swap — the
  accumulation already happened inside the compiled step; un-committed
  buffers are simply dropped, preserving "no backward → no grads").
- ``step()`` runs the compiled apply: unscale → clip → optimizer update →
  zero the buffer, under the sharding rules of the active tier.

Precision policy (SURVEY.md §3.2 observation (c)): params live in fp32
(master weights), compute runs in the policy dtype (bf16 natively on TPU; no
loss scaler needed — fp32-range exponent).  fp16 gets a *functional* dynamic
loss scaler (scale/growth_count carried as device state) replacing
``torch.cuda.amp.GradScaler`` (reference fp16.py:694-806).

Gradient accumulation lives inside the compiled step as a buffer add
(reference: Python-side counters + DDP ``no_sync``, stoke.py:326-344,
distributed.py:648-669 — no ``no_sync`` needed here: nothing eagerly syncs).
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from stoke_tpu.configs import (
    ActivationCheckpointingConfig,
    ClipGradConfig,
    ClipGradNormConfig,
    PrecisionConfig,
    PrecisionOptions,
    StokeOptimizer,
)
from stoke_tpu.ops.flash_attention import partition_kernels_over
from stoke_tpu.parallel.zero import make_transport
from stoke_tpu.parallel.sharding import ShardingRules, place_global_tree
from stoke_tpu.telemetry.tracing import trace_span
from stoke_tpu.telemetry.health import compute_sentinels
from stoke_tpu.telemetry.numerics import compute_group_stats
from stoke_tpu.utils.trees import tree_cast, tree_finite, tree_zeros_like


# --------------------------------------------------------------------------- #
# Model adapters
# --------------------------------------------------------------------------- #


class ModelAdapter:
    """Contract between the facade and any model flavor.

    ``variables`` is a dict of collections with a ``"params"`` entry (flax
    convention); gradients are taken w.r.t. ``variables["params"]`` only.
    ``apply_train`` may update non-param collections (e.g. BatchNorm
    ``batch_stats`` — the reference needs SyncBatchNorm conversion for this,
    distributed.py:575-579; under jit-GSPMD the batch moments are computed
    over the logically-global batch, so cross-replica sync is automatic).
    """

    def apply_train(
        self, variables: Dict[str, Any], rng, args: tuple, kwargs: dict
    ) -> Tuple[Any, Dict[str, Any]]:
        raise NotImplementedError

    def apply_eval(self, variables: Dict[str, Any], args: tuple, kwargs: dict) -> Any:
        raise NotImplementedError


class FlaxModelAdapter(ModelAdapter):
    """Adapter for ``flax.linen.Module`` models.

    Args:
        module: the linen module.
        train_kwargs / eval_kwargs: extra kwargs distinguishing train/eval
            application (e.g. ``{"train": True}`` / ``{"train": False}`` for
            modules with dropout/BN) — replaces torch's implicit
            ``module.train()/eval()`` mode bit the reference relies on.
        rng_keys: names of rng streams to thread (default ``("dropout",)``).
    """

    def __init__(
        self,
        module,
        train_kwargs: Optional[dict] = None,
        eval_kwargs: Optional[dict] = None,
        rng_keys: Sequence[str] = ("dropout",),
    ):
        self.module = module
        self.train_kwargs = dict(train_kwargs or {})
        self.eval_kwargs = dict(eval_kwargs or {})
        self.rng_keys = tuple(rng_keys)

    def apply_train(self, variables, rng, args, kwargs):
        mutable = [k for k in variables.keys() if k != "params"]
        rngs = None
        if self.rng_keys:
            keys = jax.random.split(rng, len(self.rng_keys))
            rngs = {name: keys[i] for i, name in enumerate(self.rng_keys)}
        merged = {**kwargs, **self.train_kwargs}
        if mutable:
            out, updated = self.module.apply(
                variables, *args, rngs=rngs, mutable=mutable, **merged
            )
            return out, dict(updated)
        out = self.module.apply(variables, *args, rngs=rngs, **merged)
        return out, {}

    def apply_eval(self, variables, args, kwargs):
        merged = {**kwargs, **self.eval_kwargs}
        return self.module.apply(variables, *args, **merged)


class FunctionalModelAdapter(ModelAdapter):
    """Adapter for a plain callable ``fn(params, *args, **kwargs) -> out``
    (no rng, no mutable collections, identical train/eval behavior)."""

    def __init__(self, fn: Callable, eval_fn: Optional[Callable] = None):
        self.fn = fn
        self.eval_fn = eval_fn or fn

    def apply_train(self, variables, rng, args, kwargs):
        return self.fn(variables["params"], *args, **kwargs), {}

    def apply_eval(self, variables, args, kwargs):
        return self.eval_fn(variables["params"], *args, **kwargs)


def as_adapter(model: Any, **adapter_kwargs) -> ModelAdapter:
    """Coerce user input to a ModelAdapter: an adapter instance, a flax
    module (has ``.apply``), or a plain callable."""
    if isinstance(model, ModelAdapter):
        return model
    if hasattr(model, "apply") and hasattr(model, "init"):
        return FlaxModelAdapter(model, **adapter_kwargs)
    if callable(model):
        return FunctionalModelAdapter(model)
    raise TypeError(
        f"Stoke -- model must be a flax Module, a callable, or a ModelAdapter; "
        f"got {type(model)}"
    )


# --------------------------------------------------------------------------- #
# Deferred outputs (lazy model() handles)
# --------------------------------------------------------------------------- #


class DeferredOutput:
    """Lazy handle returned by ``Stoke.model`` in train mode.

    Records an extraction *path* (``out[0].logits`` → ``(("getitem", 0),
    ("getattr", "logits"))``) instead of values, so ``loss()`` can substitute
    the real forward output inside the compiled fused step — avoiding the
    extra forward pass an eager ``model()`` would force.  ``.value``
    materializes through a separate compiled forward with the SAME rng the
    fused step will use, so dropout masks agree.
    """

    __slots__ = ("_materialize", "_token", "_path")

    def __init__(self, materialize_fn, token: int, path: Tuple = ()):
        object.__setattr__(self, "_materialize", materialize_fn)
        object.__setattr__(self, "_token", token)
        object.__setattr__(self, "_path", path)

    def __getitem__(self, key):
        return DeferredOutput(
            self._materialize, self._token, self._path + (("getitem", key),)
        )

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)
        return DeferredOutput(
            self._materialize, self._token, self._path + (("getattr", name),)
        )

    @property
    def value(self):
        """Materialize the real output (runs a compiled train-mode forward)."""
        return apply_path(self._materialize(self._token), self._path)

    def __array__(self, dtype=None):
        return np.asarray(self.value, dtype=dtype)

    def __repr__(self):
        return f"DeferredOutput(token={self._token}, path={self._path})"


def is_deferred(x) -> bool:
    return isinstance(x, DeferredOutput)


def apply_path(out, path: Tuple) -> Any:
    for kind, key in path:
        out = out[key] if kind == "getitem" else getattr(out, key)
    return out


# --------------------------------------------------------------------------- #
# Loss pytree helpers (multi-loss support; reference stoke.py:872-912)
# --------------------------------------------------------------------------- #


def flatten_losses(loss_result: Any) -> Tuple[list, Any]:
    """User loss fns may return a scalar, tuple/list, or dict of scalars
    (reference supports single + list/tuple, stoke.py:891-902).  Returns
    (leaves, treedef)."""
    leaves, treedef = jax.tree_util.tree_flatten(loss_result)
    return leaves, treedef


# --------------------------------------------------------------------------- #
# Precision policy
# --------------------------------------------------------------------------- #


class PrecisionPolicy(NamedTuple):
    """Dtype policy: fp32 master params, policy compute dtype, fp32 outputs
    (replaces autocast contexts + GradScaler, reference fp16.py:694-806)."""

    param_dtype: Any
    compute_dtype: Optional[Any]  # None = no cast (full precision)
    output_dtype: Optional[Any]
    scaled: bool  # True only for fp16 (dynamic loss scaler active)

    @staticmethod
    def make(option: PrecisionOptions, cfg: PrecisionConfig) -> "PrecisionPolicy":
        if option is PrecisionOptions.full:
            return PrecisionPolicy(jnp.dtype(cfg.param_dtype), None, None, False)
        if option is PrecisionOptions.bf16:
            return PrecisionPolicy(
                jnp.dtype(cfg.param_dtype),
                jnp.bfloat16,
                jnp.dtype(cfg.output_dtype),
                False,
            )
        if option is PrecisionOptions.fp16:
            return PrecisionPolicy(
                jnp.dtype(cfg.param_dtype),
                jnp.float16,
                jnp.dtype(cfg.output_dtype),
                True,
            )
        raise ValueError(option)

    def cast_compute(self, tree):
        return tree_cast(tree, self.compute_dtype)

    def cast_output(self, tree):
        return tree_cast(tree, self.output_dtype)


def init_scaler_state(cfg: PrecisionConfig) -> Dict[str, Any]:
    """Dynamic loss-scaler state (functional GradScaler, reference
    fp16.py:731-748).  Created as host numpy so construction never touches
    the default accelerator backend (the facade places it explicitly).

    With ``num_losses > 1`` (reference Apex per-loss scalers,
    fp16.py:656-691) every field becomes a ``[num_losses]`` vector and a
    per-loss ``finite`` flag vector is carried: the accumulate step ANDs in
    each loss's backward finiteness, the apply step feeds the flags to the
    vectorized scaler update and resets them."""
    if cfg.num_losses > 1:
        n = cfg.num_losses
        return {
            "scale": np.full(n, cfg.init_scale, np.float32),
            "growth_count": np.zeros(n, np.int32),
            "finite": np.ones(n, np.bool_),
        }
    return {
        "scale": np.float32(cfg.init_scale),
        "growth_count": np.int32(0),
    }


def _scaler_update(state, finite, cfg: PrecisionConfig):
    """GradScaler.update() semantics (reference fp16.py:805-806): grow scale
    after ``growth_interval`` consecutive finite steps, back off on overflow.
    Elementwise, so a ``[num_losses]`` scale vector with a per-loss finite
    vector updates each loss's scaler independently."""
    grew = state["growth_count"] + 1 >= cfg.growth_interval
    new_scale = jnp.where(
        finite,
        jnp.where(grew, state["scale"] * cfg.growth_factor, state["scale"]),
        jnp.maximum(state["scale"] * cfg.backoff_factor, cfg.min_scale),
    )
    new_count = jnp.where(finite & ~grew, state["growth_count"] + 1, 0)
    return {"scale": new_scale, "growth_count": new_count}


# --------------------------------------------------------------------------- #
# Gradient clipping (reference fp16.py:84-156 dispatch)
# --------------------------------------------------------------------------- #


def clip_gradients(grads, grad_clip) -> Any:
    """Clip on the (already unscaled, logically-global) gradient pytree.

    The reference needs five backend-specific clip implementations
    (fp16.py:84-156: plain / scaler-unscaled / OSS synced-norm / FSDP
    model-level / horovod-synchronize-first); under SPMD jit the gradients
    are logically global, so one implementation serves every tier.
    """
    if grad_clip is None:
        return grads
    if isinstance(grad_clip, ClipGradConfig):
        v = grad_clip.clip_value
        return jax.tree_util.tree_map(lambda g: jnp.clip(g, -v, v), grads)
    if isinstance(grad_clip, ClipGradNormConfig):
        p = grad_clip.norm_type
        leaves = jax.tree_util.tree_leaves(grads)
        if p == np.inf:
            norm = jnp.max(jnp.stack([jnp.max(jnp.abs(l)) for l in leaves]))
        else:
            norm = (
                jnp.sum(
                    jnp.stack(
                        [jnp.sum(jnp.abs(l.astype(jnp.float32)) ** p) for l in leaves]
                    )
                )
                ** (1.0 / p)
            )
        factor = jnp.minimum(1.0, grad_clip.max_norm / (norm + 1e-6))
        return jax.tree_util.tree_map(lambda g: g * factor, grads)
    raise TypeError(f"unknown grad_clip {type(grad_clip)}")


# --------------------------------------------------------------------------- #
# Optimizer build (reference extensions.py:30-78 BaseOptimizer)
# --------------------------------------------------------------------------- #


def build_optimizer(optimizer: Any) -> optax.GradientTransformation:
    """Instantiate the optimizer from a StokeOptimizer TypedDict (constructor
    + kwargs, reference configs.py:754-770) or accept an already-built optax
    GradientTransformation."""
    if isinstance(optimizer, optax.GradientTransformation):
        return optimizer
    if isinstance(optimizer, dict) and "optimizer" in optimizer:
        ctor = optimizer["optimizer"]
        kwargs = optimizer.get("optimizer_kwargs", {})
        built = ctor(**kwargs)
        if not isinstance(built, optax.GradientTransformation):
            raise TypeError(
                f"Stoke -- StokeOptimizer['optimizer'] must construct an optax "
                f"GradientTransformation, got {type(built)}"
            )
        return built
    if callable(optimizer):
        built = optimizer()
        if isinstance(built, optax.GradientTransformation):
            return built
    raise TypeError(
        "Stoke -- optimizer must be an optax.GradientTransformation or a "
        "StokeOptimizer dict {'optimizer': ctor, 'optimizer_kwargs': {...}}"
    )


# --------------------------------------------------------------------------- #
# The engine
# --------------------------------------------------------------------------- #


class StepEngine:
    """Owns the compiled step functions and the sharding contract.

    One engine instance per ``Stoke`` facade.  All state (variables /
    opt_state / grad buffer / scaler / rng) is held by the *facade* and passed
    through; the engine is stateless apart from its jit caches, keeping the
    functional core testable in isolation.
    """

    def __init__(
        self,
        adapter: ModelAdapter,
        loss_fn: Callable,
        optimizer: optax.GradientTransformation,
        *,
        precision: PrecisionPolicy,
        precision_config: PrecisionConfig,
        grad_accum: int,
        grad_clip,
        rules: Optional[ShardingRules],
        remat: Optional[ActivationCheckpointingConfig] = None,
        offload_optimizer: Optional[Any] = None,
        offload_params: Optional[Any] = None,
        loss_weights: Optional[Any] = None,
        aux_loss_weight: float = 0.01,
        comm: Optional[Any] = None,
        health: Optional[Any] = None,
        numerics: Optional[Any] = None,
    ):
        self.adapter = adapter
        self.loss_fn = loss_fn
        self.loss_weights = loss_weights
        self.aux_loss_weight = float(aux_loss_weight)
        self.optimizer = optimizer
        self.precision = precision
        self.precision_config = precision_config
        self.grad_accum = int(grad_accum)
        self.grad_clip = grad_clip
        self.rules = rules
        self.remat = remat
        self.offload_optimizer = offload_optimizer
        self.offload_params = offload_params
        # gradient-transport layer (ISSUE 2): quantized collectives with
        # error feedback, applied ONCE per optimizer step inside the apply
        # core.  A None comm config (or dtype="fp32") makes the transport a
        # structural pass-through: the apply program is byte-for-byte the
        # same as before the layer existed.  Under the sharded tiers (or
        # CommConfig.shard_updates) the factory returns the ISSUE 8
        # weight-update-sharded variant: quantized reduce-scatter, sharded
        # EF residual, shard-local update, param all-gather.
        self.comm = comm
        self.transport = make_transport(comm, rules)
        # health sentinels (ISSUE 3): when on, the apply core additionally
        # returns a packed per-step diagnostics vector computed INSIDE the
        # same compiled program (zero extra dispatches).  When off, the
        # sentinel slot is an empty pytree (None) and a None loss input is
        # threaded — both contribute nothing to the flattened jit
        # arguments, so the compiled programs are bit-identical to a build
        # without the feature.
        self.health = health
        self.sentinels_enabled = bool(
            health is not None and getattr(health, "sentinels", False)
        )
        # per-layer numerics observatory (ISSUE 12): when on, the apply
        # core additionally returns a fixed-layout [n_groups, n_stats]
        # group-stats matrix computed INSIDE the same compiled program —
        # the sentinel discipline again: zero extra dispatches, and a
        # None slot (empty pytree) when off keeps the compiled programs
        # bit-identical to a build without the feature.
        self.numerics = numerics
        self.numerics_enabled = bool(
            numerics is not None and getattr(numerics, "grad_stats", False)
        )
        # compiled-program invocation counter: one increment per device
        # dispatch issued by this engine.  The health acceptance criterion
        # ("sentinels add zero dispatches") asserts equality of this
        # counter across health-on/off runs.
        self.dispatch_count = 0
        self._accum_cache: Dict[Any, Callable] = {}
        self._fwd_cache: Dict[Any, Callable] = {}
        self._loss_cache: Dict[Any, Callable] = {}
        self._apply_fn: Optional[Callable] = None
        # input-shape signatures per compiled program, for structural
        # recompile detection (telemetry): a warm program dispatched with a
        # NEW signature forces a silent XLA recompile.  The facade assigns
        # THIS engine's tracker (instance-scoped: another facade's shape
        # churn in the same process must not be charged to this run)
        self._shape_sigs: Dict[Any, set] = {}
        self._compile_tracker = None
        # step-time attribution (ISSUE 4): CostCardCache assigned by the
        # facade when an AttributionConfig is supplied.  Each dispatch
        # site reports (program key + shape signature, jitted fn, live
        # args) so the cache can run ONE cost_analysis per program
        # signature and account analytic FLOPs/bytes per dispatch.  None
        # -> zero bookkeeping, programs untouched.
        self._attribution = None
        # memory observatory (ISSUE 19): assigned by the facade when a
        # MemoryConfig is supplied.  _aot_call reports (program, fn, live
        # args, signature) so the observatory's CostCardCache can run ONE
        # XLA memory_analysis per program signature — temp/argument/
        # output peaks for the OOM pre-flight and the memory-drift gate.
        # None -> zero bookkeeping, programs untouched.
        self._memory = None
        # persistent AOT compile cache (ISSUE 6): assigned by the facade
        # when a CompileConfig is supplied.  Each step-program dispatch
        # site resolves its callable through _aot_call: with a cache, the
        # first dispatch per (program key, shape signature) lowers the
        # jitted fn and checks the HLO-keyed program ledger (warm-start
        # hit accounting; the persistent XLA cache serves the backend
        # compile), then dispatches through the jitted fn as always.
        # None -> zero bookkeeping, dispatch untouched.
        self._compile_cache = None
        # fault injector (ISSUE 7): assigned by the facade when a
        # ResilienceConfig arms a chaos spec.  _aot_call (the funnel every
        # dispatch site resolves its callable through) gives it a
        # pre-dispatch hook — host-side only, the compiled programs are
        # untouched.  None -> dispatch untouched.
        self._chaos = None
        # program-audit ledger (ISSUE 15): the FIRST dispatch per
        # (program, structure key, shape signature) records an abstract
        # spec — program name, jitted fn, ShapeDtypeStruct arg tree,
        # declared donations — so Stoke.audit() can re-lower and
        # statically check every program this engine actually ran,
        # without retaining live buffers (the next step's donation
        # deletes them) and without dispatching anything.  Purely
        # host-side bookkeeping: compiled programs and dispatch counts
        # are untouched (asserted in tests/test_analysis.py).
        self._audit_specs: list = []
        self._audit_seen: set = set()
        # set when the spec cap dropped a NEW program signature: the
        # audit surfaces it as a note — "zero findings" must stay
        # distinguishable from "not audited"
        self._audit_truncated = False
        # per-program declared donations, recorded by _jit_program at
        # the ONE place each build states them — the audit's donation-
        # integrity check reads this ledger (a hand-maintained mirror
        # of the _build_* donate_argnums would drift)
        self._program_donations: Dict[str, Tuple[int, ...]] = {}
        # shardings, resolved lazily once variables are known
        self._var_shardings = None
        self._grad_shardings = None
        self._opt_shardings = None
        self._param_device_sh = None
        self._opt_device_sh = None
        self._params_offloaded = False
        self._opt_offloaded = False
        self._repl = None

    # -------------------------- placement ----------------------------- #

    def resolve_placement_abstract(self, variables, opt_state_shapes):
        """Compute NamedSharding trees for all state pytrees from concrete
        variables + *abstract* optimizer-state shapes, and return the
        variables device_put onto their placement (the one-time analogue of
        the reference's wrap ordering dance, stoke.py:306-324).  The optimizer
        state itself is then created directly sharded by
        :meth:`init_opt_state` — big models never hold a replicated opt state
        (the ZeRO-1 memory win, reference extensions.py:81-141)."""
        if self.rules is None:
            return variables
        params_sh = self.rules.param_shardings(variables["params"])
        other = {k: v for k, v in variables.items() if k != "params"}
        # non-param collections (BN stats etc.) follow the param rule; tiny
        # leaves stay replicated via min_weight_size
        other_sh = {k: self.rules.param_shardings(v) for k, v in other.items()}
        self._var_shardings = {"params": params_sh, **other_sh}
        self._grad_shardings = self.rules.grad_shardings(variables["params"])
        self._opt_shardings = self.rules.opt_shardings(opt_state_shapes)
        self._param_device_sh = params_sh
        self._opt_device_sh = self._opt_shardings
        # device-memory layout of the variables (== _var_shardings unless
        # param offload retargets the latter to pinned_host)
        self._var_device_shardings = self._var_shardings
        if self.offload_optimizer is not None:
            self._opt_shardings, self._opt_offloaded = self._offload_shardings(
                self._opt_shardings, self.offload_optimizer, "optimizer-state"
            )
        if self.offload_params is not None:
            # ZeRO-3 param offload (reference DeepspeedOffloadParamConfig,
            # configs.py:346-372): each chip's fsdp parameter shard lives in
            # host RAM between steps; the compiled steps copy it into HBM
            # (see _vars_to_compute) and write the update back to host via
            # out_shardings.  Non-param collections (BN stats etc.) stay on
            # device — small and touched every micro-batch.
            host_sh, self._params_offloaded = self._offload_shardings(
                params_sh, self.offload_params, "parameter"
            )
            self._var_shardings = {**self._var_shardings, "params": host_sh}
        self._repl = self.rules.replicated()
        return place_global_tree(variables, self._var_shardings)

    def _nonparam_device_shardings(self):
        """Device shardings of the mutable (non-param) collections — the
        ``updated`` output of the accum/fused steps (engine.py:105 makes every
        non-param collection mutable)."""
        return {
            k: v for k, v in self._var_device_shardings.items() if k != "params"
        }

    def _scaler_shardings(self):
        """Replicated placement for every scaler-state leaf.  The structure
        varies with the mode: per-loss scaling (``num_losses > 1``) carries
        an extra ``finite`` flag vector alongside scale/growth_count."""
        base = {"scale": self._repl, "growth_count": self._repl}
        if self.precision.scaled and self.precision_config.num_losses > 1:
            base["finite"] = self._repl
        return base

    def _offload_shardings(self, shardings, cfg, what: str):
        """Re-target a sharding tree to host memory
        (``memory_kind="pinned_host"``) — the ZeRO-offload equivalent
        (reference DeepspeedOffloadOptimizerConfig configs.py:309-343,
        DeepspeedOffloadParamConfig :346-372).  Returns ``(shardings,
        engaged)``; falls back to device placement (engaged=False) where the
        runtime cannot compile host-memory round-trips (e.g. the CPU
        simulator) when the config allows."""
        import warnings

        from jax.sharding import NamedSharding as _NS, PartitionSpec as _P

        def _to_host(sh):
            return _NS(sh.mesh, sh.spec, memory_kind="pinned_host")

        try:
            probe = jax.tree_util.tree_leaves(shardings)[0]
            # capability probe: COMPILE the pattern offload actually uses —
            # host input → device copy → compute → host output.  (A bare
            # device_put to pinned_host succeeds on runtimes that still
            # cannot compile host-memory outputs, e.g. the CPU simulator's
            # "Side-effect ops cannot be replicated".)  Replicated spec: we
            # only ask "does this runtime support host memory round-trips?".
            host_sh = _NS(probe.mesh, _P(), memory_kind="pinned_host")
            dev_sh = _NS(probe.mesh, _P())
            with jax.default_device(probe.mesh.devices.flat[0]):
                seed = place_global_tree(
                    np.zeros((1,), np.float32), host_sh
                )
                roundtrip = jax.jit(
                    lambda a: jax.device_put(a, dev_sh) + 1.0,
                    out_shardings=host_sh,
                )
                jax.block_until_ready(roundtrip(seed))
            return jax.tree_util.tree_map(_to_host, shardings), True
        except Exception:
            if cfg.fallback_to_device:
                warnings.warn(
                    f"Stoke -- {what} host offload unsupported on "
                    f"this runtime; keeping state on device"
                )
                return shardings, False
            raise

    def _vars_to_compute(self, variables):
        """Copy host-offloaded params into device memory inside a trace
        (XLA compiles this into a streamable host→HBM transfer).  Identity
        when param offload is off / fell back."""
        if not self._params_offloaded:
            return variables
        return {
            **variables,
            "params": jax.device_put(variables["params"], self._param_device_sh),
        }

    def _opt_to_compute(self, opt_state):
        """Same as :meth:`_vars_to_compute` for host-offloaded optimizer
        state (the update math runs in HBM; out_shardings write back)."""
        if not self._opt_offloaded:
            return opt_state
        return jax.device_put(opt_state, self._opt_device_sh)

    def init_grad_buffer(self, variables):
        """Zero accumulation buffer, sharded per the tier's grad rule
        (SDDP/FSDP: 1/N memory — the ZeRO-2 win, reference
        extensions.py:219-286)."""
        zeros = tree_zeros_like(variables["params"])
        if self._grad_shardings is not None:
            zeros = place_global_tree(zeros, self._grad_shardings)
        return zeros

    def init_comm_state(self, variables):
        """Carried gradient-transport state (stochastic-rounding rng +
        error-feedback residual, placed like the gradient buffer).  An
        empty dict when the transport is inactive — threading it through
        the compiled steps is then structurally free."""
        state = self.transport.init_state(variables["params"])
        if not state:
            return state
        if self._grad_shardings is not None:
            return place_global_tree(state, self._comm_state_shardings())
        return state

    def _comm_state_shardings(self):
        """out_shardings tree matching the comm state structure ({} when
        the transport is inactive)."""
        if self._grad_shardings is None or not self.transport.active:
            return {}
        return self.transport.state_shardings(self._grad_shardings, self._repl)

    def comm_bytes_per_step(self, variables) -> Optional[Dict[str, int]]:
        """Analytic per-device gradient bytes-on-wire of one optimizer
        step (telemetry: pre-quantization vs wire-format bytes)."""
        return self.transport.bytes_per_step(variables["params"])

    def init_opt_state(self, variables):
        """Optimizer-state init, created directly onto the tier's placement
        via ``out_shardings`` (never materialized replicated)."""
        if self._opt_shardings is not None:
            init = jax.jit(self.optimizer.init, out_shardings=self._opt_shardings)
            return init(variables["params"])
        return self.optimizer.init(variables["params"])

    # ----------------------- forward passes --------------------------- #

    def _maybe_remat(self, fn):
        if self.remat is None:
            return fn
        policy = getattr(jax.checkpoint_policies, self.remat.policy)
        return jax.checkpoint(fn, policy=policy, prevent_cse=self.remat.prevent_cse)

    def _kernel_scope(self):
        """Trace-time scope around every model forward: under a mesh the
        Pallas kernels inside the model must ``shard_map`` themselves over
        it, batch rows split over the data axis (Mosaic kernels are never
        partitioned automatically)."""
        if self.rules is None:
            return contextlib.nullcontext()
        return partition_kernels_over(self.rules.mesh, (self.rules.axis_name,))

    def _run_forward_train(self, variables, rng, margs, mkwargs):
        cvars = {
            "params": self.precision.cast_compute(variables["params"]),
            **{k: v for k, v in variables.items() if k != "params"},
        }
        cargs = self.precision.cast_compute(margs)
        ckwargs = self.precision.cast_compute(mkwargs)
        with self._kernel_scope():
            out, updated = self.adapter.apply_train(
                cvars, rng, cargs, ckwargs
            )
        return self.precision.cast_output(out), updated

    def train_fwd(self, variables, rng, margs: tuple, mkwargs: dict):
        """Compiled train-mode forward for materializing DeferredOutputs.
        Uses the same rng-derivation as the fused step so dropout agrees."""
        key = ("fwd", jax.tree_util.tree_structure((margs, mkwargs)))
        if key not in self._fwd_cache:

            @jax.jit
            def _fwd(variables, rng, margs, mkwargs):
                variables = self._vars_to_compute(variables)
                sub = jax.random.split(rng)[1]
                out, _ = self._run_forward_train(variables, sub, margs, mkwargs)
                return out

            self._fwd_cache[key] = _fwd
        self._note_dispatch_shapes(key, margs, mkwargs)
        self.dispatch_count += 1
        return self._fwd_cache[key](variables, rng, margs, mkwargs)

    def eval_fwd(self, variables, margs: tuple, mkwargs: dict):
        key = ("eval", jax.tree_util.tree_structure((margs, mkwargs)))
        if key not in self._fwd_cache:

            @jax.jit
            def _efwd(variables, margs, mkwargs):
                variables = self._vars_to_compute(variables)
                cvars = {
                    "params": self.precision.cast_compute(variables["params"]),
                    **{k: v for k, v in variables.items() if k != "params"},
                }
                cargs = self.precision.cast_compute(margs)
                ckwargs = self.precision.cast_compute(mkwargs)
                with self._kernel_scope():
                    out = self.adapter.apply_eval(cvars, cargs, ckwargs)
                return self.precision.cast_output(out)

            self._fwd_cache[key] = _efwd
        self._note_dispatch_shapes(key, margs, mkwargs)
        self.dispatch_count += 1
        return self._fwd_cache[key](variables, margs, mkwargs)

    #: per-program cap on remembered shape signatures: beyond this the
    #: membership test can no longer distinguish new shapes from evicted
    #: ones, so detection FREEZES for that program (no more counting —
    #: repeat-counting already-compiled shapes would be a permanent false
    #: alarm) and host memory stays bounded under pathological shape churn
    _MAX_SHAPE_SIGS = 1024

    @staticmethod
    def _shape_sig(batch_trees) -> tuple:
        """Input-shape signature of a dispatch's batch leaves — the key
        both the structural recompile detector and the attribution
        CostCard cache use to tell programs apart."""
        return tuple(
            (tuple(l.shape), str(getattr(l, "dtype", "")))
            for l in jax.tree_util.tree_leaves(batch_trees)
            if hasattr(l, "shape")
        )

    def _note_dispatch_shapes(self, key, *batch_trees) -> Optional[tuple]:
        """Telemetry hook: record the input-shape signature of a dispatch.
        First signature per program = warm-up compile; any LATER new
        signature means XLA silently recompiles the warm program (ragged
        batch / drifting pad length) — reported to THIS engine's
        ``CompileTracker`` (assigned by the facade; no bookkeeping at all
        when telemetry is off).  Returns the signature so the attribution
        hook (:meth:`_note_cost`) reuses it instead of recomputing it on
        the dispatch hot path; None when nobody needs one."""
        tracker = self._compile_tracker
        if (
            tracker is None
            and self._attribution is None
            and self._compile_cache is None
            and self._memory is None
        ):
            return None
        sig = self._shape_sig(batch_trees)
        if tracker is None:
            return sig
        seen = self._shape_sigs.setdefault(key, set())
        if len(seen) >= self._MAX_SHAPE_SIGS or sig in seen:
            return sig
        if seen:
            tracker.note_recompile()
        seen.add(sig)
        return sig

    def _note_cost(self, program: str, key, fn, args: tuple, steps: int,
                   sig: Optional[tuple]) -> None:
        """Attribution hook (ISSUE 4): account this dispatch's analytic
        cost.  First call per (program key, shape signature) runs one XLA
        cost analysis on ``fn`` at ``args``; every call adds the cached
        card's FLOPs/bytes to the attribution counters.  ``sig`` is the
        signature :meth:`_note_dispatch_shapes` already computed for this
        dispatch.  No-op without an ``AttributionConfig`` (the facade
        never assigns the cache)."""
        attr = self._attribution
        if attr is None:
            return
        attr.note_dispatch((key, sig or ()), program, fn, args, steps)

    def _aot_call(self, program: str, key, sig: Optional[tuple], fn,
                  args: tuple):
        """Compile-cache hook (ISSUE 6): resolve the callable that will
        run this dispatch.  ``fn`` itself without a cache; with one, the
        first dispatch per (program key, shape signature) goes through
        the cache's HLO-keyed program ledger — which books the warm-start
        hit (the persistent XLA cache serves the impending backend
        compile) or records the cold cost — and every later dispatch is
        ``fn`` untouched.  Dispatch semantics (donation, async, numerics)
        are ALWAYS plain ``jax.jit``.

        Also the fault injector's pre-dispatch hook (ISSUE 7): with a
        chaos spec armed, ``wedge_at_step`` stalls the first dispatch after
        its step here — the deterministic stand-in for a wedged collective
        the hang watchdog exists to catch.  And the program-audit
        ledger's recording point (ISSUE 15): one abstract spec per
        (program, key, sig), first dispatch only."""
        if self._chaos is not None:
            self._chaos.on_dispatch(program)
        self._note_audit(program, key, sig, fn, args)
        if self._memory is not None:
            self._memory.note_program(program, fn, args, (key, sig))
        cache = self._compile_cache
        if cache is None:
            return fn
        return cache.executable(program, (key, sig), fn, args)

    #: bound on remembered audit specs (one per program signature; a
    #: shape-churning run stops recording, never errors)
    _MAX_AUDIT_SPECS = 64

    def _jit_program(self, program: str, fn, *, donate: Tuple[int, ...] = (),
                     out_shardings=None):
        """``jax.jit`` a step program AND record its declared donations
        under the program's audit name — stated once, here, so the
        ISSUE 15 donation-integrity check can never drift from what the
        jit actually received.  The program's name becomes the module's
        (``jit_accum``, ``jit_apply``, ``jit_fused``, ...): that is what a
        profiler trace's ``XLA Modules`` line calls each execution."""
        self._program_donations[program] = tuple(donate)
        fn.__name__ = fn.__qualname__ = program
        if out_shardings is not None:
            return jax.jit(fn, out_shardings=out_shardings,
                           donate_argnums=donate)
        return jax.jit(fn, donate_argnums=donate)

    def _note_audit(self, program: str, key, sig, fn, args: tuple) -> None:
        """Record one abstract ProgramSpec per (program, key, sig) for
        the ISSUE 15 auditor — shapes/dtypes/shardings only, taken while
        the args are still live (pre-donation)."""
        memo = (program, key, sig)
        if memo in self._audit_seen:
            return
        if len(self._audit_specs) >= self._MAX_AUDIT_SPECS:
            self._audit_truncated = True
            return
        self._audit_seen.add(memo)
        from stoke_tpu.analysis.program import ProgramSpec, abstractify_args

        avals, weak = abstractify_args(args)
        self._audit_specs.append(
            ProgramSpec(
                program=program,
                fn=fn,
                abstract_args=avals,
                donate_argnums=self._program_donations.get(program, ()),
                weak_leaves=weak,
                source="engine",
            )
        )

    def audit_specs(self) -> list:
        """The recorded program specs (ISSUE 15; ``Stoke.audit()`` is
        the consumer)."""
        return list(self._audit_specs)

    def shape_sig_counts(self) -> Dict[str, int]:
        """Distinct input-shape signatures seen per program key — the
        auditor's recompile-churn ledger.  Keyed by the program's
        human-readable name (the first key element)."""
        out: Dict[str, int] = {}
        for key, seen in self._shape_sigs.items():
            name = key[0] if isinstance(key, tuple) and key else str(key)
            name = str(name)
            out[name] = max(out.get(name, 0), len(seen))
        return out

    # -------------------------- fused micro-step ----------------------- #

    def accum_step(
        self,
        variables,
        grad_buf,
        scaler_state,
        rng,
        margs: tuple,
        mkwargs: dict,
        loss_args_flat: list,
        loss_treedef,
        deferred_info: Tuple[Tuple[int, Tuple], ...],
        training: bool,
    ):
        """One compiled micro-step: forward + loss + grad + buffer add.

        ``loss_args_flat``/``loss_treedef`` are the flattened (args, kwargs)
        of the user's ``loss()`` call with DeferredOutput leaves removed;
        ``deferred_info`` records (flat_index, extraction_path) for each
        removed leaf so the real forward output is substituted inside the
        trace.  Returns (loss_tree, updated_nonparam_vars, new_grad_buf,
        new_scaler_state, new_rng) — all device-resident; nothing syncs to
        host (SURVEY.md §3.2 observation (a)).  The scaler is pass-through
        except in per-loss mode (``PrecisionConfig.num_losses > 1``).
        """
        struct_key = (
            "accum",
            jax.tree_util.tree_structure((margs, mkwargs)),
            loss_treedef,
            deferred_info,
            training,
        )
        if struct_key not in self._accum_cache:
            self._accum_cache[struct_key] = self._build_accum(
                loss_treedef, deferred_info, training
            )
        sig = self._note_dispatch_shapes(
            struct_key, margs, mkwargs, loss_args_flat
        )
        # micro-step: contributes FLOPs but completes no optimizer step
        self._note_cost(
            "accum", struct_key, self._accum_cache[struct_key],
            (variables, grad_buf, scaler_state, rng, margs, mkwargs,
             loss_args_flat),
            0, sig,
        )
        call = self._aot_call(
            "accum", struct_key, sig, self._accum_cache[struct_key],
            (variables, grad_buf, scaler_state, rng, margs, mkwargs,
             loss_args_flat),
        )
        self.dispatch_count += 1
        with trace_span("stoke/accum", track="step"):
            return call(
                variables, grad_buf, scaler_state, rng, margs, mkwargs,
                loss_args_flat,
            )

    def _accum_core(self, loss_treedef, deferred_info, training):
        """Unjitted micro-step core: forward + loss + grad + buffer add.
        Shared by the lazy 4-call path and the fused train_step path.

        Returns ``(report, updated_nonparam, new_buf, new_scaler, new_rng)``.
        The scaler is pass-through except in per-loss mode (``num_losses >
        1``), where each micro-step ANDs per-loss backward finiteness into
        the carried flag vector (reference: Apex updates its per-loss
        scalers inside each ``scale_loss`` context, fp16.py:545-579)."""
        inv_scale_accum = 1.0 / self.grad_accum if training else 1.0
        scaled = self.precision.scaled
        per_loss = scaled and self.precision_config.num_losses > 1
        n_scales = self.precision_config.num_losses

        def _loss_from_out(out, loss_args_flat):
            flat = list(loss_args_flat)
            # re-insert deferred leaves (extracted views of the forward out)
            for idx, path in deferred_info:
                flat.insert(idx, apply_path(out, path))
            largs, lkwargs = jax.tree_util.tree_unflatten(loss_treedef, flat)
            return self.loss_fn(*largs, **lkwargs)

        def _step(variables, grad_buf, scaler_state, rng, margs, mkwargs, larr):
            # host-offloaded params → HBM copy OUTSIDE the grad closure, so
            # grad cotangents stay in device memory (a transfer inside the
            # closure would transpose to a host-memory cotangent and bounce
            # the gradients host→device for the buffer add)
            variables = self._vars_to_compute(variables)
            new_rng, sub = jax.random.split(rng)
            # per-loss mode scales the VJP seeds instead of the objective
            scale = (
                scaler_state["scale"]
                if scaled and not per_loss
                else jnp.float32(1.0)
            )

            def _forward_comps(params):
                """Shared forward + per-leaf weighted loss components.

                Returns ``(comps, report, updated)``: ``comps`` is one f32
                scalar per loss leaf (weights applied, model-internal aux
                losses folded into the FIRST component — they have no
                scaler/weight slot of their own), ``report`` the UNweighted
                per-loss values the user sees.  ``lf`` consumes the sum
                (single backward), ``lf_vec`` the stacked vector (one
                scale-seeded backward per loss) — sharing this body is what
                keeps the two objectives from drifting.
                """
                vars_in = {**variables, "params": params}
                fwd = self._maybe_remat(
                    lambda v: self._run_forward_train(v, sub, margs, mkwargs)
                )
                out, updated = fwd(vars_in)
                loss_result = _loss_from_out(out, larr)
                leaves, inner_def = jax.tree_util.tree_flatten(loss_result)
                if self.loss_weights is not None:
                    # weighted multi-loss: the objective is Σ wᵢ·lossᵢ.
                    # Gradients are linear, so one backward of the weighted
                    # sum ≡ the reference's per-loss backward passes with
                    # weights (fp16.py:545-579, stoke.py:891-902).
                    try:
                        weighted = jax.tree_util.tree_map(
                            lambda w, l: jnp.float32(w)
                            * jnp.asarray(l, jnp.float32).sum(),
                            self.loss_weights,
                            loss_result,
                        )
                    except ValueError as e:
                        raise ValueError(
                            "Stoke -- loss_weights structure must match the "
                            "loss() return structure"
                        ) from e
                    comps = jax.tree_util.tree_leaves(weighted)
                else:
                    comps = [
                        jnp.asarray(l, jnp.float32).sum() for l in leaves
                    ]
                # model-internal auxiliary losses (e.g. the MoE router's
                # load-balancing term) arrive sown into the "losses"
                # collection (models/moe.py); they join the objective with
                # the configured weight but are NOT part of the user's loss
                # report (observable via the facade's state instead)
                if self.aux_loss_weight and "losses" in updated:
                    aux_leaves = jax.tree_util.tree_leaves(updated["losses"])
                    if aux_leaves:
                        comps[0] = comps[0] + jnp.float32(
                            self.aux_loss_weight
                        ) * sum(
                            jnp.asarray(a, jnp.float32).sum()
                            for a in aux_leaves
                        )
                # reference divides the training loss by grad_accum at
                # loss() time (stoke.py:901-911).  Reported per-loss values
                # stay UNweighted.
                report = jax.tree_util.tree_unflatten(
                    inner_def, [l * inv_scale_accum for l in leaves]
                )
                return comps, report, updated

            def lf(params):
                comps, report, updated = _forward_comps(params)
                # fp16 single-scaler mode additionally multiplies by the
                # dynamic scale; per-loss overflow isolation is subsumed by
                # the single scaler here.
                objective = sum(comps) * inv_scale_accum * scale
                return objective, (report, updated)

            def lf_vec(params):
                # per-loss objective VECTOR: components stay separate so
                # each loss's backward can be seeded with its own scale
                comps, report, updated = _forward_comps(params)
                if len(comps) != n_scales:
                    raise ValueError(
                        f"Stoke -- PrecisionConfig.num_losses={n_scales} "
                        f"but loss() returned {len(comps)} loss leaves — "
                        f"per-loss scalers need one scale per loss"
                    )
                return (
                    jnp.stack(comps) * inv_scale_accum,
                    (report, updated),
                )

            if training and per_loss:
                # reference per-loss scalers (fp16.py:545-579): one forward,
                # one backward per loss.  jax.vjp shares the forward; each
                # backward is seeded with that loss's scale (protecting fp16
                # cotangents from underflow), checked for overflow, then
                # unscaled straight into the fp32 accumulation buffer —
                # which therefore holds UNSCALED gradients (apply's unscale
                # is the identity in this mode).
                scales = scaler_state["scale"]
                _, vjp_fn, (report, updated) = jax.vjp(
                    lf_vec, variables["params"], has_aux=True
                )
                new_buf = grad_buf
                new_finite = scaler_state["finite"]
                for i in range(n_scales):
                    seed = (
                        jnp.zeros((n_scales,), jnp.float32)
                        .at[i].set(scales[i])
                    )
                    (g_i,) = vjp_fn(seed)
                    new_finite = new_finite.at[i].set(
                        new_finite[i] & tree_finite(g_i)
                    )
                    inv_i = 1.0 / scales[i]
                    new_buf = jax.tree_util.tree_map(
                        lambda b, g: b + (g * inv_i).astype(b.dtype),
                        new_buf,
                        g_i,
                    )
                new_scaler = {**scaler_state, "finite": new_finite}
            elif training:
                grads, (report, updated) = jax.grad(lf, has_aux=True)(
                    variables["params"]
                )
                new_buf = jax.tree_util.tree_map(
                    lambda b, g: b + g.astype(b.dtype), grad_buf, grads
                )
                new_scaler = scaler_state
            else:
                _, (report, updated) = lf(variables["params"])
                new_buf = grad_buf
                new_scaler = scaler_state
            return report, updated, new_buf, new_scaler, new_rng

        return _step

    def _build_accum(self, loss_treedef, deferred_info, training):
        _step = self._accum_core(loss_treedef, deferred_info, training)
        if self.rules is not None:
            # Pin state outputs to the tier's placement so step-to-step
            # placement is deterministic (GSPMD would otherwise be free to
            # drift, changing collective schedules between steps).
            repl = self._repl
            out_sh = (
                None,  # loss report: let XLA keep it replicated (scalars)
                # updated non-param collections (BN stats etc.): pin to the
                # tier placement — left unconstrained, GSPMD shards them to
                # match the data-sharded activations they were reduced from,
                # which then defeats buffer donation (and forces a reshard)
                # at the apply boundary where the tier placement is required
                self._nonparam_device_shardings(),
                self._grad_shardings,
                self._scaler_shardings(),
                repl,  # rng
            )
            return self._jit_program("accum", _step, out_shardings=out_sh)
        return self._jit_program("accum", _step)

    # ----------------------- scan window step --------------------------- #

    def window_step(
        self,
        variables,
        opt_state,
        grad_buf,
        scaler_state,
        comm_state,
        rng,
        margs_stacked: tuple,
        mkwargs_stacked: dict,
        loss_args_flat_stacked: list,
        loss_treedef,
        deferred_info: Tuple[Tuple[int, Tuple], ...],
    ):
        """A WHOLE accumulation window in one compiled dispatch:
        ``lax.scan`` over the k stacked micro-batches (grad accumulation as
        compiler-visible control flow — SURVEY.md §3.2 observation (b)),
        then the fused optimizer apply.  Semantically identical to k
        ``train_step`` calls; one dispatch instead of k.

        Stacked args carry the micro dimension on axis 0 (leaf shape
        [k, micro_batch, ...]).  Returns (reports_stacked, variables,
        opt_state, grad_buf, scaler_state, comm_state, rng, sentinels,
        numerics, finite) — ``sentinels`` is the health diagnostics vector
        and ``numerics`` the per-group stats matrix (each None when its
        feature is off).
        """
        key = (
            "window",
            jax.tree_util.tree_structure((margs_stacked, mkwargs_stacked)),
            loss_treedef,
            deferred_info,
        )
        if key not in self._accum_cache:
            self._accum_cache[key] = self._build_window(loss_treedef, deferred_info)
        sig = self._note_dispatch_shapes(
            key, margs_stacked, mkwargs_stacked, loss_args_flat_stacked
        )
        self._note_cost(
            "window", key, self._accum_cache[key],
            (variables, opt_state, grad_buf, scaler_state, comm_state, rng,
             margs_stacked, mkwargs_stacked, loss_args_flat_stacked),
            1, sig,
        )
        call = self._aot_call(
            "window", key, sig, self._accum_cache[key],
            (variables, opt_state, grad_buf, scaler_state, comm_state, rng,
             margs_stacked, mkwargs_stacked, loss_args_flat_stacked),
        )
        self.dispatch_count += 1
        with trace_span("stoke/dispatch", track="step",
                        attrs={"program": "window"}):
            return call(
                variables, opt_state, grad_buf, scaler_state, comm_state,
                rng, margs_stacked, mkwargs_stacked, loss_args_flat_stacked,
            )

    def _report_loss(self, report):
        """Boundary-loss scalar for the health sentinels (traced): sum over
        loss leaves of each leaf's mean (collapsing any stacked micro axis),
        times ``grad_accum`` — undivided micro-loss units, matching the
        facade's ``step_loss`` tracking on every path."""
        total = jnp.float32(0.0)
        for l in jax.tree_util.tree_leaves(report):
            total = total + jnp.asarray(l, jnp.float32).mean()
        return total * jnp.float32(self.grad_accum)

    def _window_core(self, loss_treedef, deferred_info):
        """Unjitted whole-window core: inner ``lax.scan`` over the stacked
        micro-batches + the fused optimizer apply.  Shared by
        ``_build_window`` (jitted directly) and ``_build_multi`` (scanned
        over n windows) so the two APIs cannot diverge."""
        accum = self._accum_core(loss_treedef, deferred_info, training=True)
        apply_core = self._apply_core()

        def _window(variables, opt_state, grad_buf, scaler_state, comm_state,
                    rng, margs_s, mkwargs_s, larr_s):
            # host-offloaded params → HBM ONCE, outside the scan (the accum
            # core's own transfer is then a no-op on already-device params)
            variables = self._vars_to_compute(variables)
            params = variables["params"]
            nonparam0 = {k: v for k, v in variables.items() if k != "params"}

            def body(carry, xs):
                nonparam, buf, scaler, rng = carry
                margs, mkwargs, larr = xs
                report, updated, buf, scaler, rng = accum(
                    {"params": params, **nonparam}, buf, scaler, rng,
                    margs, mkwargs, larr,
                )
                return ({**nonparam, **updated}, buf, scaler, rng), report

            (nonparam_f, new_buf, scaler_mid, new_rng), reports = jax.lax.scan(
                body,
                (nonparam0, grad_buf, scaler_state, rng),
                (margs_s, mkwargs_s, larr_s),
            )
            merged = {"params": params, **nonparam_f}
            loss_val = (
                self._report_loss(reports) if self.sentinels_enabled else None
            )
            (new_vars, new_opt, zero_buf, new_scaler, new_comm, sentinels,
             numerics, finite) = apply_core(
                merged, opt_state, new_buf, scaler_mid, comm_state, loss_val
            )
            return (reports, new_vars, new_opt, zero_buf, new_scaler,
                    new_comm, new_rng, sentinels, numerics, finite)

        return _window

    def _build_window(self, loss_treedef, deferred_info):
        _window = self._window_core(loss_treedef, deferred_info)

        if self.rules is not None:
            repl = self._repl
            out_sh = (
                None,
                self._var_shardings,
                self._opt_shardings,
                self._grad_shardings,
                self._scaler_shardings(),
                self._comm_state_shardings(),
                repl,  # rng
                self._sentinel_shardings(),
                self._numerics_shardings(),
                repl,  # finite
            )
            return self._jit_program(
                "window", _window, out_shardings=out_sh,
                donate=(0, 1, 2, 4),
            )
        return self._jit_program("window", _window, donate=(0, 1, 2, 4))

    # ----------------------- multi-step scan ---------------------------- #

    def multi_step(
        self,
        variables,
        opt_state,
        grad_buf,
        scaler_state,
        comm_state,
        rng,
        margs_stacked: tuple,
        mkwargs_stacked: dict,
        loss_args_flat_stacked: list,
        loss_treedef,
        deferred_info: Tuple[Tuple[int, Tuple], ...],
    ):
        """N COMPLETE optimizer steps in one compiled dispatch: an outer
        ``lax.scan`` over steps, each iterating its accumulation window and
        the fused apply.  One XLA program drives a whole training segment —
        host dispatch (and, on remote-device links, per-dispatch round-trip
        latency) is amortized over ``n × grad_accum`` micro-batches.  No
        reference equivalent (the reference's hot loop is eager,
        stoke.py:853-1040).

        Stacked args carry [n_steps, grad_accum, micro_batch, ...] leaves.
        Returns (reports [n, k, ...], variables, opt_state, grad_buf,
        scaler_state, comm_state, rng, sentinels [n, S] (None when off),
        numerics [n, G, S'] (None when off), n_nonfinite_steps).
        """
        key = (
            "multi",
            jax.tree_util.tree_structure((margs_stacked, mkwargs_stacked)),
            loss_treedef,
            deferred_info,
        )
        if key not in self._accum_cache:
            self._accum_cache[key] = self._build_multi(loss_treedef, deferred_info)
        sig = self._note_dispatch_shapes(
            key, margs_stacked, mkwargs_stacked, loss_args_flat_stacked
        )
        if self._attribution is not None:
            # one dispatch covers n complete optimizer steps
            n_steps = next(
                (
                    l.shape[0]
                    for l in jax.tree_util.tree_leaves(
                        (margs_stacked, mkwargs_stacked,
                         loss_args_flat_stacked)
                    )
                    if hasattr(l, "shape") and l.shape
                ),
                1,
            )
            self._note_cost(
                "multi", key, self._accum_cache[key],
                (variables, opt_state, grad_buf, scaler_state, comm_state,
                 rng, margs_stacked, mkwargs_stacked,
                 loss_args_flat_stacked),
                int(n_steps), sig,
            )
        call = self._aot_call(
            "multi", key, sig, self._accum_cache[key],
            (variables, opt_state, grad_buf, scaler_state, comm_state, rng,
             margs_stacked, mkwargs_stacked, loss_args_flat_stacked),
        )
        self.dispatch_count += 1
        with trace_span("stoke/dispatch", track="step",
                        attrs={"program": "multi"}):
            return call(
                variables, opt_state, grad_buf, scaler_state, comm_state,
                rng, margs_stacked, mkwargs_stacked, loss_args_flat_stacked,
            )

    def _build_multi(self, loss_treedef, deferred_info):
        window = self._window_core(loss_treedef, deferred_info)

        def _multi(variables, opt_state, grad_buf, scaler_state, comm_state,
                   rng, margs_s, mkwargs_s, larr_s):
            # offloaded state → HBM ONCE, outside both scans (the cores'
            # internal transfers are no-ops on already-device state)
            variables = self._vars_to_compute(variables)
            opt_state = self._opt_to_compute(opt_state)

            def step_body(carry, xs):
                (variables, opt_state, buf, scaler_state, comm_state, rng,
                 skipped) = carry
                margs, mkwargs, larr = xs  # [k, ...] micro-batches
                (reports, new_vars, new_opt, zero_buf, new_scaler, new_comm,
                 new_rng, sentinels, numerics, finite) = window(
                    variables, opt_state, buf, scaler_state, comm_state, rng,
                    margs, mkwargs, larr,
                )
                skipped = skipped + (1.0 - finite.astype(jnp.float32))
                return (
                    (new_vars, new_opt, zero_buf, new_scaler, new_comm,
                     new_rng, skipped),
                    (reports, sentinels, numerics),
                )

            ((vars_f, opt_f, buf_f, scaler_f, comm_f, rng_f, skipped),
             (reports, sentinels_s, numerics_s)) = jax.lax.scan(
                step_body,
                (variables, opt_state, grad_buf, scaler_state, comm_state,
                 rng, jnp.float32(0.0)),
                (margs_s, mkwargs_s, larr_s),
            )
            return (reports, vars_f, opt_f, buf_f, scaler_f, comm_f, rng_f,
                    sentinels_s, numerics_s, skipped)

        if self.rules is not None:
            repl = self._repl
            out_sh = (
                None,
                self._var_shardings,
                self._opt_shardings,
                self._grad_shardings,
                self._scaler_shardings(),
                self._comm_state_shardings(),
                repl,  # rng
                self._sentinel_shardings(),  # stacked sentinel rows
                self._numerics_shardings(),  # stacked group-stats matrices
                repl,  # skipped count
            )
            return self._jit_program(
                "multi", _multi, out_shardings=out_sh, donate=(0, 1, 2, 4)
            )
        return self._jit_program("multi", _multi, donate=(0, 1, 2, 4))

    # ---------------------------- apply step --------------------------- #

    def apply_step(self, variables, opt_state, grad_buf, scaler_state,
                   comm_state, loss_val=None):
        """Compiled optimizer application: unscale → gradient transport →
        finite-check → clip → update → zero buffer → scaler update
        (reference step() path, stoke.py:990-1040 + fp16.py:788-806).

        ``loss_val``: boundary loss scalar for the health sentinels (None
        — an empty jit input — when sentinels are off).  Returns extra
        sentinel-vector and per-group numerics-matrix slots before
        ``finite`` (each None when its feature is off)."""
        if self._apply_fn is None:
            self._apply_fn = self._build_apply()
        self._note_cost(
            "apply", "apply", self._apply_fn,
            (variables, opt_state, grad_buf, scaler_state, comm_state,
             loss_val),
            1, (),
        )
        call = self._aot_call(
            "apply", "apply", (), self._apply_fn,
            (variables, opt_state, grad_buf, scaler_state, comm_state,
             loss_val),
        )
        self.dispatch_count += 1
        with trace_span("stoke/apply", track="step"):
            return call(
                variables, opt_state, grad_buf, scaler_state, comm_state,
                loss_val,
            )

    def _apply_core(self):
        """Unjitted apply core, shared by step() and the fused train_step."""
        scaled = self.precision.scaled
        cfg = self.precision_config
        grad_clip = self.grad_clip
        optimizer = self.optimizer
        transport = self.transport
        sentinels_on = self.sentinels_enabled
        numerics_on = self.numerics_enabled

        def _apply(variables, opt_state, grad_buf, scaler_state, comm_state,
                   loss_val=None):
            # host-offloaded state → HBM for the (bandwidth-bound) update;
            # out_shardings write new params / opt state back to host
            variables = self._vars_to_compute(variables)
            opt_state = self._opt_to_compute(opt_state)
            params = variables["params"]
            per_loss = scaled and cfg.num_losses > 1
            if per_loss:
                # per-loss mode unscales inside the accumulate step (each
                # backward by its own scale); the buffer is already unscaled
                inv = jnp.float32(1.0)
            else:
                inv = (
                    1.0 / scaler_state["scale"] if scaled else jnp.float32(1.0)
                )
            grads = jax.tree_util.tree_map(lambda g: g * inv, grad_buf)
            # gradient transport (ISSUE 2): quantized exchange + error
            # feedback on the UNSCALED, whole-window gradients — once per
            # optimizer step, never per micro-step.  Inactive transport
            # (no CommConfig / dtype="fp32") returns grads and the empty
            # state untouched: the compiled program is unchanged.
            grads, new_comm = transport.apply(grads, comm_state)
            # health sentinels AND the per-layer numerics matrix read the
            # unscaled post-transport gradients (pre-clip — a clipped-away
            # spike must still be visible; one shared tap point keeps the
            # per-group sums recombining exactly to the sentinel norm)
            health_grads = grads if (sentinels_on or numerics_on) else None
            finite = tree_finite(grads) if scaled else jnp.asarray(True)
            if per_loss:
                # any loss overflowing anywhere in the window skips the step
                # (reference: amp skips optimizer.step on overflow)
                finite = finite & jnp.all(scaler_state["finite"])
            grads = clip_gradients(grads, grad_clip)

            def do_update(_):
                updates, new_opt = optimizer.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                return new_params, new_opt

            def skip_update(_):
                return params, opt_state

            new_params, new_opt = jax.lax.cond(finite, do_update, skip_update, None)
            if per_loss:
                # vectorized update driven by the per-loss flags, which then
                # reset for the next accumulation window
                upd = _scaler_update(
                    {
                        "scale": scaler_state["scale"],
                        "growth_count": scaler_state["growth_count"],
                    },
                    scaler_state["finite"],
                    cfg,
                )
                new_scaler = {
                    **upd,
                    "finite": jnp.ones_like(scaler_state["finite"]),
                }
            elif scaled:
                new_scaler = _scaler_update(scaler_state, finite, cfg)
            else:
                new_scaler = scaler_state
            new_vars = {**variables, "params": new_params}
            zero_buf = tree_zeros_like(grad_buf)
            # sentinel vector (ISSUE 3): a handful of scalar reductions
            # fused into THIS program — None (empty pytree) when off, so
            # the default-off program is bit-identical
            sentinels = (
                compute_sentinels(
                    loss_val, health_grads, new_params, params, finite,
                    new_comm,
                )
                if sentinels_on
                else None
            )
            # per-layer numerics matrix (ISSUE 12): per-module-group raw
            # sums fused into THIS program — None (empty pytree) when off,
            # so the default-off program is bit-identical
            numerics = (
                compute_group_stats(health_grads, new_params, params)
                if numerics_on
                else None
            )
            return (new_vars, new_opt, zero_buf, new_scaler, new_comm,
                    sentinels, numerics, finite)

        return _apply

    def _sentinel_shardings(self):
        """out_shardings slot for the sentinel vector: replicated when on,
        None (matching the empty pytree) when off."""
        return self._repl if self.sentinels_enabled else None

    def _numerics_shardings(self):
        """out_shardings slot for the per-group numerics matrix (ISSUE
        12): replicated when on, None (empty pytree) when off."""
        return self._repl if self.numerics_enabled else None

    def _build_apply(self):
        _apply = self._apply_core()
        if self.rules is not None:
            out_sh = (
                self._var_shardings,
                self._opt_shardings,
                self._grad_shardings,
                self._scaler_shardings(),
                self._comm_state_shardings(),
                self._sentinel_shardings(),
                self._numerics_shardings(),
                self._repl,
            )
            return self._jit_program(
                "apply", _apply, out_shardings=out_sh, donate=(0, 1, 2, 4)
            )
        return self._jit_program("apply", _apply, donate=(0, 1, 2, 4))

    # ------------------------ fused train step -------------------------- #

    def fused_step(
        self,
        variables,
        opt_state,
        grad_buf,
        scaler_state,
        comm_state,
        rng,
        margs: tuple,
        mkwargs: dict,
        loss_args_flat: list,
        loss_treedef,
        deferred_info: Tuple[Tuple[int, Tuple], ...],
        do_apply: bool,
    ):
        """ONE compiled dispatch for a whole micro-step — and, at the
        accumulation boundary (``do_apply``), the optimizer apply fused in.

        This is the TPU-idiomatic fast path behind ``Stoke.train_step``: with
        ``grad_accum == 1`` an entire optimizer step (forward + loss + grad +
        clip + update) is a single XLA program — no reference equivalent (the
        reference's eager hot loop is stoke.py:853-1040).  The 4-call API
        compiles the same math split across two dispatches.

        Returns (report, updated_nonparam_vars, variables, opt_state,
        grad_buf, scaler_state, comm_state, rng, sentinels, numerics,
        finite) — ``sentinels``/``numerics`` are the health diagnostics
        vector and per-group stats matrix at apply boundaries (None
        off-boundary or when the feature is off).
        """
        key = (
            "fused",
            jax.tree_util.tree_structure((margs, mkwargs)),
            loss_treedef,
            deferred_info,
            bool(do_apply),
        )
        if key not in self._accum_cache:
            self._accum_cache[key] = self._build_fused(
                loss_treedef, deferred_info, bool(do_apply)
            )
        sig = self._note_dispatch_shapes(key, margs, mkwargs, loss_args_flat)
        self.dispatch_count += 1
        if do_apply:
            self._note_cost(
                "fused", key, self._accum_cache[key],
                (variables, opt_state, grad_buf, scaler_state, comm_state,
                 rng, margs, mkwargs, loss_args_flat),
                1, sig,
            )
            call = self._aot_call(
                "fused", key, sig, self._accum_cache[key],
                (variables, opt_state, grad_buf, scaler_state, comm_state,
                 rng, margs, mkwargs, loss_args_flat),
            )
            with trace_span("stoke/dispatch", track="step",
                            attrs={"program": "fused"}):
                return call(
                    variables, opt_state, grad_buf, scaler_state, comm_state,
                    rng, margs, mkwargs, loss_args_flat,
                )
        # non-boundary micro-steps never touch the optimizer state or the
        # transport state (quantization is once-per-step): both stay
        # wherever they live and the caller's references are echoed
        # untouched
        self._note_cost(
            "fused_nb", key, self._accum_cache[key],
            (variables, grad_buf, scaler_state, rng, margs, mkwargs,
             loss_args_flat),
            0, sig,
        )
        call = self._aot_call(
            "fused_nb", key, sig, self._accum_cache[key],
            (variables, grad_buf, scaler_state, rng, margs, mkwargs,
             loss_args_flat),
        )
        with trace_span("stoke/dispatch", track="step",
                        attrs={"program": "fused_nb"}):
            (report, updated, new_vars, new_buf, new_scaler, new_rng,
             finite) = call(
                variables, grad_buf, scaler_state, rng, margs, mkwargs,
                loss_args_flat,
            )
        return (report, updated, new_vars, opt_state, new_buf, new_scaler,
                comm_state, new_rng, None, None, finite)

    def _build_fused(self, loss_treedef, deferred_info, do_apply):
        accum = self._accum_core(loss_treedef, deferred_info, training=True)
        apply_core = self._apply_core()

        if do_apply:

            def _fused(variables, opt_state, grad_buf, scaler_state,
                       comm_state, rng, margs, mkwargs, larr):
                # host-offloaded params → HBM ONCE for both accum and apply
                # (the cores' own transfers become no-ops on already-device
                # params)
                variables = self._vars_to_compute(variables)
                report, updated, new_buf, scaler_mid, new_rng = accum(
                    variables, grad_buf, scaler_state, rng, margs, mkwargs,
                    larr
                )
                merged = {**variables, **updated}
                loss_val = (
                    self._report_loss(report)
                    if self.sentinels_enabled
                    else None
                )
                (new_vars, new_opt, zero_buf, new_scaler, new_comm,
                 sentinels, numerics, finite) = apply_core(
                    merged, opt_state, new_buf, scaler_mid, comm_state,
                    loss_val,
                )
                return (report, updated, new_vars, new_opt, zero_buf,
                        new_scaler, new_comm, new_rng, sentinels, numerics,
                        finite)

            if self.rules is not None:
                repl = self._repl
                out_sh = (
                    None,  # report
                    self._nonparam_device_shardings(),  # updated collections
                    self._var_shardings,
                    self._opt_shardings,
                    self._grad_shardings,
                    self._scaler_shardings(),
                    self._comm_state_shardings(),
                    repl,  # rng
                    self._sentinel_shardings(),
                    self._numerics_shardings(),
                    repl,  # finite
                )
                return self._jit_program(
                    "fused", _fused, out_shardings=out_sh,
                    donate=(0, 1, 2, 4),
                )
            return self._jit_program("fused", _fused, donate=(0, 1, 2, 4))

        def _fused_nb(variables, grad_buf, scaler_state, rng, margs, mkwargs,
                      larr):
            variables = self._vars_to_compute(variables)
            report, updated, new_buf, new_scaler, new_rng = accum(
                variables, grad_buf, scaler_state, rng, margs, mkwargs, larr
            )
            merged = {**variables, **updated}
            return (report, updated, merged, new_buf, new_scaler, new_rng,
                    jnp.asarray(True))

        if self.rules is not None:
            repl = self._repl
            out_sh = (
                None,  # report
                self._nonparam_device_shardings(),  # updated collections
                # non-boundary micro-steps leave params in device memory:
                # writing the UNCHANGED params back to pinned_host (and in
                # again next micro-step) would be a pure host<->HBM round
                # trip; only the boundary step persists to the offload tier
                self._var_device_shardings,
                self._grad_shardings,
                self._scaler_shardings(),
                repl,  # rng
                repl,  # finite
            )
            return self._jit_program(
                "fused_nb", _fused_nb, out_shardings=out_sh, donate=(0, 1)
            )
        return self._jit_program("fused_nb", _fused_nb, donate=(0, 1))

    # --------------------------- loss-only ----------------------------- #

    def loss_eval(self, loss_args_flat, loss_treedef):
        """Compiled loss-only evaluation (eval mode; outputs are real arrays
        so no substitution is needed)."""
        key = ("loss", loss_treedef)
        if key not in self._loss_cache:

            @jax.jit
            def _loss(flat):
                largs, lkwargs = jax.tree_util.tree_unflatten(loss_treedef, flat)
                return self.loss_fn(*largs, **lkwargs)

            self._loss_cache[key] = _loss
        self.dispatch_count += 1
        return self._loss_cache[key](loss_args_flat)
