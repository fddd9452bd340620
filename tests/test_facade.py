"""Facade behavior tests: the 4-call contract, grad accumulation semantics,
deferred outputs, multi-loss, fp16 skip-on-overflow, counters, mode toggles
(stoke_tpu/facade.py vs reference stoke/stoke.py:853-1040)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from stoke_tpu import (
    ClipGradConfig,
    ClipGradNormConfig,
    DeferredOutput,
    ParamNormalize,
    PrecisionConfig,
    Stoke,
    StokeOptimizer,
)


def linear_model(params, x):
    return x @ params["w"] + params["b"]


def mse(out, y):
    return jnp.mean((out - y) ** 2)


def make_stoke(loss=mse, model=linear_model, in_dim=4, out_dim=2, **kw):
    params = {"w": jnp.zeros((in_dim, out_dim)), "b": jnp.zeros((out_dim,))}
    kw.setdefault("batch_size_per_device", 8)
    kw.setdefault("verbose", False)
    opt = kw.pop("optimizer", StokeOptimizer(optimizer=optax.sgd, optimizer_kwargs={"learning_rate": 0.2}))
    return Stoke(model=model, optimizer=opt, loss=loss, params=params, **kw)


def batch(rng, n=8, in_dim=4, out_dim=2, W=None):
    x = rng.normal(size=(n, in_dim)).astype(np.float32)
    W = np.ones((in_dim, out_dim), np.float32) if W is None else W
    return x, (x @ W).astype(np.float32)


def test_four_call_training_converges(rng):
    s = make_stoke()
    for _ in range(60):
        x, y = batch(rng)
        out = s.model(x)
        l = s.loss(out, y)
        s.backward(l)
        s.step()
    assert float(l) < 1e-3
    assert s.optimizer_steps == 60
    assert s.backward_steps == 60


def test_grad_accum_equivalence(rng):
    """accum=4 on batch b must match accum=1 on the concatenated 4b batch
    (the semantics the reference implements with counters + no_sync,
    stoke.py:326-344)."""
    xs, ys = zip(*[batch(rng, n=8) for _ in range(4)])
    bigx, bigy = np.concatenate(xs), np.concatenate(ys)

    s1 = make_stoke(grad_accum=1, batch_size_per_device=32)
    out = s1.model(bigx)
    s1.backward(s1.loss(out, bigy))
    s1.step()

    s4 = make_stoke(grad_accum=4, batch_size_per_device=8)
    for x, y in zip(xs, ys):
        out = s4.model(x)
        s4.backward(s4.loss(out, y))
        s4.step()
    assert s4.optimizer_steps == 1  # only stepped at the boundary
    np.testing.assert_allclose(
        np.asarray(s1.params["w"]), np.asarray(s4.params["w"]), rtol=1e-5, atol=1e-6
    )


def test_step_is_noop_before_accum_boundary(rng):
    s = make_stoke(grad_accum=2)
    x, y = batch(rng)
    s.backward(s.loss(s.model(x), y))
    w_before = np.asarray(s.params["w"]).copy()
    s.step()  # counter=1 < 2 → no-op
    np.testing.assert_array_equal(w_before, np.asarray(s.params["w"]))
    assert s.optimizer_steps == 0
    s.backward(s.loss(s.model(x), y))
    s.step()
    assert s.optimizer_steps == 1


def test_loss_divided_by_accum(rng):
    """Training losses are returned divided by grad_accum
    (reference stoke.py:901-911)."""
    x, y = batch(rng)
    s1 = make_stoke(grad_accum=1)
    l1 = float(s1.loss(s1.model(x), y))
    s2 = make_stoke(grad_accum=4)
    l2 = float(s2.loss(s2.model(x), y))
    assert l1 == pytest.approx(4 * l2, rel=1e-5)


def test_no_backward_no_grads(rng):
    """Calling loss() without backward() must not contribute gradients."""
    s = make_stoke(grad_accum=1)
    x, y = batch(rng)
    s.loss(s.model(x), y)  # dropped pending
    x2, y2 = batch(rng)
    out = s.model(x2)
    s.backward(s.loss(out, y2))
    s.step()

    s_ref = make_stoke(grad_accum=1)
    out = s_ref.model(x2)
    s_ref.backward(s_ref.loss(out, y2))
    s_ref.step()
    np.testing.assert_allclose(
        np.asarray(s.params["w"]), np.asarray(s_ref.params["w"]), rtol=1e-6
    )


def test_materialized_loss_clears_stale_pending(rng):
    """loss() on materialized arrays produces no grads; a following
    backward() must error rather than commit an earlier call's gradients."""
    s = make_stoke()
    x, y = batch(rng)
    s.loss(s.model(x), y)  # creates pending grads (uncommitted)
    out2 = s.model(x)
    l2 = s.loss(out2.value, y)  # materialized → loss-only, no grads
    with pytest.raises(RuntimeError):
        s.backward(l2)


def test_backward_without_loss_raises(rng):
    s = make_stoke()
    with pytest.raises(RuntimeError):
        s.backward(None)


def test_eval_mode(rng):
    s = make_stoke()
    x, y = batch(rng)
    s.eval()
    out = s.model(x)  # eager in eval mode
    assert isinstance(out, jax.Array)
    l = s.loss(out, y)
    assert float(l) > 0
    with pytest.raises(RuntimeError):
        s.backward(l)
    s.train()
    out = s.model(x)
    assert isinstance(out, DeferredOutput)


def test_deferred_materialization_matches_fused(rng):
    """Materializing out.value must agree with what the fused step saw."""
    s = make_stoke()
    x, y = batch(rng)
    out = s.model(x)
    val = np.asarray(out.value)
    l = float(s.loss(out, y))
    manual = float(np.mean((val - y) ** 2))
    assert l == pytest.approx(manual, rel=1e-5)


def test_deferred_path_extraction(rng):
    """out[idx] handles route through the fused step (tuple-output model)."""

    def model2(params, x):
        h = x @ params["w"] + params["b"]
        return h, h * 2

    s = make_stoke(model=model2)
    x, y = batch(rng)
    out = s.model(x)
    l = s.loss(out[0], y)
    s.backward(l)
    s.step()
    assert s.optimizer_steps == 1
    np.testing.assert_allclose(np.asarray(out[1]), 2 * np.asarray(out[0]), rtol=1e-5)


def test_deferred_value_rng_stable_after_loss(rng):
    """.value must reproduce the dropout masks the fused step used, even when
    read AFTER loss() has advanced the live rng (rng stashed at model() time,
    ADVICE r1)."""
    import flax.linen as nn

    class Drop(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            h = nn.Dense(8)(x)
            return nn.Dropout(0.5, deterministic=not train)(h)

    model = Drop()
    x = rng.normal(size=(8, 4)).astype(np.float32)
    v = model.init(jax.random.PRNGKey(0), x, train=False)
    s = Stoke(
        model=model,
        optimizer=StokeOptimizer(
            optimizer=optax.sgd, optimizer_kwargs={"learning_rate": 0.1}
        ),
        loss=mse,
        params=v,
        batch_size_per_device=8,
        model_train_kwargs={"train": True},
        model_eval_kwargs={"train": False},
        verbose=False,
    )
    y = np.zeros((8, 8), np.float32)
    out = s.model(x)
    before = np.asarray(out.value)
    l = float(s.loss(out, y))  # fused step consumes + advances the rng
    after = np.asarray(out.value)
    np.testing.assert_array_equal(before, after)
    # and the fused step saw those SAME masks: loss(value, y) == reported loss
    assert l == pytest.approx(float(np.mean((before - y) ** 2)), rel=1e-5)


def test_stale_deferred_rejected(rng):
    s = make_stoke()
    x, y = batch(rng)
    out_old = s.model(x)
    s.model(x)  # new call invalidates the old handle
    with pytest.raises(RuntimeError):
        s.loss(out_old, y)


def test_multi_loss_tuple(rng):
    """Multi-loss: grads of the SUM, per-loss values reported
    (reference stoke.py:891-902, fp16.py:274-278)."""

    def two_losses(out, y):
        return (jnp.mean((out - y) ** 2), 0.01 * jnp.mean(out**2))

    s = make_stoke(loss=two_losses)
    x, y = batch(rng)
    out = s.model(x)
    l = s.loss(out, y)
    assert isinstance(l, tuple) and len(l) == 2
    s.backward(l)
    s.step()

    # equivalent single summed loss must give identical params
    def summed(out, y):
        return jnp.mean((out - y) ** 2) + 0.01 * jnp.mean(out**2)

    s2 = make_stoke(loss=summed)
    out = s2.model(x)
    s2.backward(s2.loss(out, y))
    s2.step()
    np.testing.assert_allclose(
        np.asarray(s.params["w"]), np.asarray(s2.params["w"]), rtol=1e-6
    )


def test_multi_loss_dict(rng):
    """Dict-valued losses report per-key and train on the sum."""

    def dict_loss(out, y):
        return {"mse": jnp.mean((out - y) ** 2), "reg": 0.01 * jnp.mean(out**2)}

    s = make_stoke(loss=dict_loss)
    x, y = batch(rng)
    l = s.loss(s.model(x), y)
    assert set(l) == {"mse", "reg"}
    s.backward(l)
    s.step()
    assert s.optimizer_steps == 1
    assert s.step_loss == pytest.approx(float(l["mse"]) + float(l["reg"]), rel=1e-5)


def test_loss_weights_match_hand_weighted_objective(rng):
    """loss_weights: grads of Σ wᵢ·lossᵢ (the reference's per-loss backward
    with weights, fp16.py:545-579), reports stay unweighted."""

    def two_losses(out, y):
        return (jnp.mean((out - y) ** 2), jnp.mean(out**2))

    w1, w2 = 0.7, 0.25
    s = make_stoke(loss=two_losses, loss_weights=(w1, w2))
    x, y = batch(rng)
    l = s.loss(s.model(x), y)
    s.backward(l)
    s.step()
    # reported values are the UNweighted per-loss values
    manual_out = np.zeros_like(y)  # zero-init params → out == 0
    assert float(l[0]) == pytest.approx(float(np.mean((manual_out - y) ** 2)), rel=1e-5)

    # equivalent hand-weighted single loss must give identical params
    def weighted(out, y):
        return w1 * jnp.mean((out - y) ** 2) + w2 * jnp.mean(out**2)

    s2 = make_stoke(loss=weighted)
    s2.backward(s2.loss(s2.model(x), y))
    s2.step()
    np.testing.assert_allclose(
        np.asarray(s.params["w"]), np.asarray(s2.params["w"]), rtol=1e-6
    )


def test_loss_weights_dict(rng):
    """Dict losses with dict weights."""

    def dict_loss(out, y):
        return {"mse": jnp.mean((out - y) ** 2), "reg": jnp.mean(out**2)}

    s = make_stoke(loss=dict_loss, loss_weights={"mse": 1.0, "reg": 0.5})
    x, y = batch(rng)
    s.backward(s.loss(s.model(x), y))
    s.step()
    assert s.optimizer_steps == 1

    def weighted(out, y):
        return jnp.mean((out - y) ** 2) + 0.5 * jnp.mean(out**2)

    s2 = make_stoke(loss=weighted)
    s2.backward(s2.loss(s2.model(x), y))
    s2.step()
    np.testing.assert_allclose(
        np.asarray(s.params["w"]), np.asarray(s2.params["w"]), rtol=1e-6
    )


def test_loss_weights_structure_mismatch_raises(rng):
    def two_losses(out, y):
        return (jnp.mean((out - y) ** 2), jnp.mean(out**2))

    s = make_stoke(loss=two_losses, loss_weights=(1.0,))  # wrong arity
    x, y = batch(rng)
    with pytest.raises(ValueError, match="loss_weights"):
        s.loss(s.model(x), y)


def test_deferred_dict_output_key_access(rng):
    """Models returning dicts: out['logits'] routes through the fused step."""

    def dict_model(params, x):
        h = x @ params["w"] + params["b"]
        return {"logits": h, "features": h * 2}

    s = make_stoke(model=dict_model)
    x, y = batch(rng)
    out = s.model(x)
    l = s.loss(out["logits"], y)
    s.backward(l)
    s.step()
    assert s.optimizer_steps == 1
    np.testing.assert_allclose(
        np.asarray(out["features"]), 2 * np.asarray(out["logits"]), rtol=1e-5
    )


def test_grad_clip_value_effect(rng):
    """With a harsh value clip, the SGD update is bounded by lr*clip."""
    s = make_stoke(
        grad_clip=ClipGradConfig(clip_value=0.001),
        optimizer=StokeOptimizer(optimizer=optax.sgd, optimizer_kwargs={"learning_rate": 1.0}),
    )
    x, y = batch(rng, W=100 * np.ones((4, 2), np.float32))  # huge grads
    s.backward(s.loss(s.model(x), y))
    s.step()
    assert np.abs(np.asarray(s.params["w"])).max() <= 0.001 + 1e-6


def test_fp16_overflow_skips_step(rng):
    """fp16 scaler: an overflowing micro-batch must skip the optimizer step
    and back off the scale (GradScaler semantics, reference fp16.py:788-806)."""

    def exploding_loss(out, y):
        return jnp.mean((out - y) ** 2) * 1e30

    s = make_stoke(loss=exploding_loss, precision="fp16")
    x, y = batch(rng)
    w_before = np.asarray(s.params["w"]).copy()
    scale_before = s.loss_scale
    s.backward(s.loss(s.model(x), y))
    s.step()
    np.testing.assert_array_equal(w_before, np.asarray(s.params["w"]))
    assert s.loss_scale == scale_before * 0.5
    assert s.skipped_optimizer_steps == 1.0


def test_fp16_normal_training_converges(rng):
    s = make_stoke(
        precision="fp16",
        configs=[PrecisionConfig(init_scale=2.0**8)],
    )
    for _ in range(60):
        x, y = batch(rng)
        s.backward(s.loss(s.model(x), y))
        s.step()
    assert float(s.ema_loss) < 0.05


def test_bf16_training_converges(rng):
    s = make_stoke(precision="bf16")
    for _ in range(60):
        x, y = batch(rng)
        s.backward(s.loss(s.model(x), y))
        s.step()
    assert float(s.ema_loss) < 0.05
    # master params stay fp32
    assert s.params["w"].dtype == jnp.float32


def test_loss_tracking_helpers(rng, capsys):
    s = make_stoke(grad_accum=2)
    x, y = batch(rng)
    s.backward(s.loss(s.model(x), y))
    assert s.ema_loss > 0
    assert s.mean_accumulated_loss is not None
    assert s.step_loss is not None
    s.print_ema_loss()
    s.print_mean_accumulated_synced_loss()
    s.print_synced_loss(s.step_loss and s._last_step_loss)
    out = capsys.readouterr().out
    assert "EMA Loss" in out and "Stoke --" in out


def test_properties_and_introspection(rng, capsys):
    s = make_stoke(grad_accum=3)
    assert s.batch_size == 8
    assert s.effective_batch_size == 8 * 1 * 3
    assert s.grad_accum_steps == 3
    assert s.world_size == 1
    assert s.rank == 0 and s.is_rank_0
    assert not s.is_distributed
    assert s.num_model_parameters() == 4 * 2 + 2
    assert s.num_model_parameters(ParamNormalize.THOUSAND) == pytest.approx(0.01)
    s.print_num_model_parameters()
    s.dump_model_parameter_info()
    out = capsys.readouterr().out
    assert "Model parameters" in out and "param w" in out
    assert callable(s.loss_access)
    assert s.optimizer is not None


def test_reference_parity_accessors(rng):
    """The reference's property surface (stoke.py:1271-1466) maps over."""
    from stoke_tpu.configs import PrecisionConfig

    s = make_stoke(grad_accum=2, precision="bf16")
    assert s.grad_accum == 2
    assert s.sharded is False and s.fully_sharded is False
    assert s.tpu is False
    assert s.is_bf16 and not s.is_fp16
    assert isinstance(s.precision_config, PrecisionConfig)
    assert s.dp_config.axis_name == "data"
    assert s.mesh_config.axes == ("data",)
    assert s.oss_config and s.sddp_config and s.fsdp_config
    assert s.checkpoint_config and s.profiler_config
    x, y = batch(rng)
    s.backward(s.loss(s.model(x), y))
    assert s.ema_loss > 0
    s.reset_ema()
    assert float(jax.device_get(s._rolling_mean_loss)) == 0.0
    s.reset_tracking()
    assert s.step_loss is None and s.mean_accumulated_loss is None


def test_reset(rng):
    s = make_stoke(grad_accum=4)
    x, y = batch(rng)
    s.backward(s.loss(s.model(x), y))
    assert s.grad_accum_counter == 1
    s.reset()
    assert s.grad_accum_counter == 0
    buf = np.asarray(jax.tree_util.tree_leaves(s._grad_buf)[0])
    assert (buf == 0).all()


def test_barrier_noop_single_process():
    make_stoke().barrier()  # must not raise


# ------------------------- fused train_step ------------------------------- #


def test_train_step_matches_four_call(rng):
    """The fused fast path must be numerically identical to the 4-call
    contract (same compiled math, fewer dispatches)."""
    batches = [batch(rng) for _ in range(6)]
    s1 = make_stoke(grad_accum=2)
    for x, y in batches:
        out = s1.model(x)
        s1.backward(s1.loss(out, y))
        s1.step()
    s2 = make_stoke(grad_accum=2)
    for x, y in batches:
        s2.train_step(x, y)
    np.testing.assert_allclose(
        np.asarray(s1.params["w"]), np.asarray(s2.params["w"]), rtol=1e-6
    )
    assert s1.optimizer_steps == s2.optimizer_steps == 3
    assert s1.backward_steps == s2.backward_steps == 6
    assert s1.ema_loss == pytest.approx(s2.ema_loss, rel=1e-5)


def test_train_step_multi_input_model(rng):
    def model2(params, x, bias):
        return x @ params["w"] + bias

    s = make_stoke(model=model2)
    x, y = batch(rng)
    bias = np.ones((2,), np.float32)
    l = s.train_step((x, bias), y)
    assert float(l) > 0
    assert s.optimizer_steps == 1


def test_train_step_eval_mode_raises(rng):
    s = make_stoke().eval()
    x, y = batch(rng)
    with pytest.raises(RuntimeError):
        s.train_step(x, y)


def test_train_step_fp16_skips_on_overflow(rng):
    def exploding(out, y):
        return jnp.mean((out - y) ** 2) * 1e30

    s = make_stoke(loss=exploding, precision="fp16")
    x, y = batch(rng)
    w_before = np.asarray(s.params["w"]).copy()
    s.train_step(x, y)
    np.testing.assert_array_equal(w_before, np.asarray(s.params["w"]))
    assert s.skipped_optimizer_steps == 1.0


def test_train_step_window_matches_four_call(rng):
    """One scanned dispatch for the whole window == k 4-call micro-steps."""
    k = 3
    micro = [batch(rng) for _ in range(k)]
    s1 = make_stoke(grad_accum=k)
    for x, y in micro:
        s1.backward(s1.loss(s1.model(x), y))
        s1.step()
    s2 = make_stoke(grad_accum=k)
    xs = np.stack([x for x, _ in micro])
    ys = np.stack([y for _, y in micro])
    reports = s2.train_step_window(xs, ys)
    assert np.asarray(reports).shape == (k,)
    np.testing.assert_allclose(
        np.asarray(s1.params["w"]), np.asarray(s2.params["w"]), rtol=1e-6
    )
    assert s2.optimizer_steps == 1 and s2.backward_steps == k
    # per-micro reports match the 4-call losses
    s3 = make_stoke(grad_accum=k)
    for i, (x, y) in enumerate(micro):
        l = s3.loss(s3.model(x), y)
        s3.backward(l)
        s3.step()
        assert float(np.asarray(reports)[i]) == pytest.approx(float(l), rel=1e-5)


def test_train_step_window_validations(rng):
    s = make_stoke(grad_accum=2)
    x, y = batch(rng)
    with pytest.raises(ValueError):  # not stacked to k
        s.train_step_window(x, y)
    s.backward(s.loss(s.model(x), y))
    with pytest.raises(RuntimeError):  # mid-window
        s.train_step_window(np.stack([x, x]), np.stack([y, y]))


# ------------------------- profiling -------------------------------------- #


def test_profile_trace_noop_without_dir(rng):
    s = make_stoke()
    with s.profile_trace():
        pass  # must not raise


def test_profile_trace_writes(tmp_path, rng):
    from stoke_tpu import ProfilerConfig

    s = make_stoke(configs=[ProfilerConfig(trace_dir=str(tmp_path))])
    x, y = batch(rng)
    with s.profile_trace():
        s.train_step(x, y)
    import os

    assert any(os.scandir(str(tmp_path)))  # trace files exist


def test_activation_checkpointing_matches(rng):
    """Remat through the facade: identical numerics, opt-in via config."""
    from stoke_tpu import ActivationCheckpointingConfig

    batches = [batch(rng) for _ in range(3)]
    s1 = make_stoke()
    s2 = make_stoke(
        configs=[ActivationCheckpointingConfig(policy="nothing_saveable")]
    )
    for x, y in batches:
        s1.train_step(x, y)
        s2.train_step(x, y)
    np.testing.assert_allclose(
        np.asarray(s1.params["w"]), np.asarray(s2.params["w"]), rtol=1e-6
    )


def test_seq_dim_batch_sharding(rng):
    """Opt-in sequence-dim sharding places [B, L, ...] batches over
    ("data","seq") (DataParallelConfig.shard_seq_dim)."""
    from jax.sharding import PartitionSpec as P

    from stoke_tpu import DataParallelConfig, MeshConfig

    def seq_model(params, x):
        return jnp.einsum("bld,dk->blk", x, params["w"])

    s = Stoke(
        model=seq_model,
        optimizer=StokeOptimizer(
            optimizer=optax.sgd, optimizer_kwargs={"learning_rate": 0.1}
        ),
        loss=lambda o, y: jnp.mean((o - y) ** 2),
        params={"w": jnp.zeros((4, 2))},
        batch_size_per_device=2,
        distributed="dp",
        configs=[
            MeshConfig(axes=("data", "seq"), shape=(2, 4)),
            DataParallelConfig(shard_seq_dim=1),
        ],
        verbose=False,
    )
    x = np.zeros((4, 8, 4), np.float32)  # B=4 (÷2), L=8 (÷4)
    placed = s._place_batch(x)
    assert placed.sharding.spec == P("data", "seq")
    y1d = s._place_batch(np.zeros((4,), np.float32))  # no seq dim
    assert y1d.sharding.spec == P("data")


def test_wall_clock_breakdown(rng):
    from stoke_tpu import ProfilerConfig

    s = make_stoke(configs=[ProfilerConfig(wall_clock_breakdown=True)])
    x, y = batch(rng)
    s.backward(s.loss(s.model(x), y))
    s.step()
    s.train_step(x, y)
    bd = s.wall_clock_breakdown
    assert {"model", "loss", "backward", "step", "train_step"} <= set(bd)
    assert bd["loss"] > 0
    s.print_wall_clock_breakdown()


def test_wall_clock_disabled_by_default(rng):
    s = make_stoke()
    x, y = batch(rng)
    s.train_step(x, y)
    assert s.wall_clock_breakdown == {}


def test_offload_optimizer_fallback_trains(rng):
    """On runtimes without host memory kinds the offload config must fall
    back to device placement with a warning and still train."""
    import warnings

    from stoke_tpu import OffloadOptimizerConfig

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        s = make_stoke(configs=[OffloadOptimizerConfig()])
    for _ in range(5):
        x, y = batch(rng)
        s.train_step(x, y)
    assert s.optimizer_steps == 5


def test_tensorboard_metrics_logging(tmp_path, rng):
    """TensorboardConfig: automatic loss metrics at the step cadence + user
    scalars land in event files (reference DeepspeedTensorboardConfig)."""
    import os

    from stoke_tpu import TensorboardConfig

    s = make_stoke(
        configs=[TensorboardConfig(output_path=str(tmp_path), job_name="run1",
                                   log_every_n_steps=2)]
    )
    for _ in range(4):
        x, y = batch(rng)
        s.train_step(x, y)
    s.log_scalar("custom/metric", 1.23)
    s._tb_writer.flush()
    run_dir = os.path.join(str(tmp_path), "run1")
    files = os.listdir(run_dir)
    assert any("tfevents" in f for f in files)
    # the native writer produces real TB records: parse them back
    from stoke_tpu.utils.tb_writer import read_scalar_events

    events = read_scalar_events(s._tb_writer.path)
    tags = {t for t, _, _ in events}
    assert "custom/metric" in tags
    assert "loss/ema" in tags  # auto metrics at the step cadence
    val = [v for t, v, _ in events if t == "custom/metric"][0]
    assert abs(val - 1.23) < 1e-6


def test_log_scalar_noop_without_config(rng):
    s = make_stoke()
    s.log_scalar("x", 1.0)  # must not raise or create files


def test_estimate_step_flops(rng):
    s = make_stoke()
    x, y = batch(rng)
    flops = s.estimate_step_flops(x, y)
    # CPU backend may not report cost analysis; when it does, the estimate
    # must at least cover the forward matmul FLOPs
    if flops is not None:
        assert flops >= 2 * 8 * 4 * 2


@pytest.mark.parametrize("distributed", [None, "dp"])
def test_device_tpu_means_a_tpu(distributed):
    """On a process where JAX exposes no TPU, device="tpu" raises — it never
    trains on the CPU under the name of the chip — and the error names the
    backend it found."""
    from stoke_tpu import StokeValidationError

    with pytest.raises(StokeValidationError) as e:
        make_stoke(device="tpu", distributed=distributed)
    msg = str(e.value)
    assert "device='tpu'" in msg
    assert "jax.default_backend()='cpu'" in msg
    assert "JAX_PLATFORMS" in msg

