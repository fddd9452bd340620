"""Utility-layer tests: tree helpers, printing, loss reduction semantics,
multihost env detection."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stoke_tpu.utils import make_folder, tree_count_params, unrolled_print
from stoke_tpu.utils.trees import (
    place_data_on_device,
    tree_add,
    tree_cast,
    tree_finite,
    tree_scale,
    tree_zeros_like,
)


def test_tree_count_params():
    tree = {"a": np.zeros((3, 4)), "b": {"c": np.zeros((5,))}}
    assert tree_count_params(tree) == 17


def test_tree_cast_only_floats():
    tree = {"f": jnp.ones((2,), jnp.float32), "i": jnp.ones((2,), jnp.int32)}
    out = tree_cast(tree, jnp.bfloat16)
    assert out["f"].dtype == jnp.bfloat16
    assert out["i"].dtype == jnp.int32
    assert tree_cast(tree, None) is tree


def test_tree_arithmetic():
    a = {"x": jnp.ones((3,))}
    z = tree_zeros_like(a)
    assert float(z["x"].sum()) == 0
    s = tree_add(a, a)
    np.testing.assert_array_equal(np.asarray(s["x"]), 2.0)
    sc = tree_scale(a, 3.0)
    np.testing.assert_array_equal(np.asarray(sc["x"]), 3.0)


def test_tree_finite():
    assert bool(tree_finite({"a": jnp.ones((2,))}))
    assert not bool(tree_finite({"a": jnp.asarray([1.0, np.inf])}))
    assert not bool(tree_finite({"a": jnp.asarray([np.nan])}))
    assert bool(tree_finite({}))


def test_place_data_on_device_torch_and_nested():
    import torch

    batch = {"x": torch.ones(2, 3), "y": [np.zeros(2), 5.0]}
    placed = place_data_on_device(batch)
    assert isinstance(placed["x"], jax.Array)
    assert placed["x"].shape == (2, 3)


def test_unrolled_print(capsys):
    unrolled_print("hello")
    unrolled_print(["a", "b"])
    unrolled_print(["a", "b"], single_line=True)
    out = capsys.readouterr().out
    assert out.count("Stoke --") == 4
    assert "a, b" in out


def test_make_folder(tmp_path):
    p = make_folder(str(tmp_path / "x" / "y"))
    import os

    assert os.path.isdir(p)
    assert make_folder(p) == p  # idempotent


def test_loss_reduction_sum(rng):
    """LossReduction.sum rescales the synced loss by world size (reference
    Horovod Sum op, configs.py:20-25)."""
    import optax

    from stoke_tpu import DataParallelConfig, LossReduction, Stoke, StokeOptimizer

    s = Stoke(
        model=lambda p, x: x @ p["w"],
        optimizer=StokeOptimizer(
            optimizer=optax.sgd, optimizer_kwargs={"learning_rate": 0.1}
        ),
        loss=lambda o, y: jnp.mean((o - y) ** 2),
        params={"w": jnp.ones((4, 2))},
        batch_size_per_device=4,
        distributed="dp",
        configs=[DataParallelConfig(loss_reduction=LossReduction.sum)],
        verbose=False,
    )
    x = np.ones((32, 4), np.float32)
    y = np.zeros((32, 2), np.float32)
    l = s.loss(s.model(x), y)
    assert s.detach_and_sync_loss(l) == pytest.approx(float(l) * 8, rel=1e-5)
    # a sum-reduced user loss is already a global sum: no extra scaling
    assert s.detach_and_sync_loss(l, user_reduction="sum") == pytest.approx(
        float(l), rel=1e-5
    )
    with pytest.raises(ValueError):
        s.detach_and_sync_loss(l, user_reduction="nope")


def test_multihost_env_detection(monkeypatch):
    from stoke_tpu.parallel.mesh import _multihost_env_present

    for var in ("JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS", "SLURM_NTASKS",
                "OMPI_COMM_WORLD_SIZE", "TPU_WORKER_HOSTNAMES",
                "MEGASCALE_NUM_SLICES"):
        monkeypatch.delenv(var, raising=False)
    assert _multihost_env_present() is False
    monkeypatch.setenv("SLURM_NTASKS", "4")
    assert _multihost_env_present() is True
    monkeypatch.delenv("SLURM_NTASKS")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "host-a,host-b")
    assert _multihost_env_present() is True
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    assert _multihost_env_present() is False
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:1234")
    assert _multihost_env_present() is True


@pytest.mark.slow
def test_tb_writer_format_contract(tmp_path):
    """The native TB event writer produces byte-correct TensorBoard files:
    CRC-checked round-trip through our parser, and — when the real
    ``tensorboard`` package is importable — through its own EventFileLoader
    (modern TB migrates simple_value into a scalar tensor; accept both)."""
    import struct

    from stoke_tpu.utils.tb_writer import TBEventWriter, read_scalar_events

    w = TBEventWriter(str(tmp_path))
    w.add_scalar("loss", 0.75, 3)
    w.add_scalar("acc", 0.5, 4)
    w.close()
    events = read_scalar_events(w.path)
    assert ("loss", 0.75, 3) in events and ("acc", 0.5, 4) in events

    try:
        from tensorboard.backend.event_processing.event_file_loader import (
            EventFileLoader,
        )
    except ImportError:
        return
    got = []
    for ev in EventFileLoader(w.path).Load():
        for v in ev.summary.value:
            which = v.WhichOneof("value")
            if which == "simple_value":
                got.append((v.tag, v.simple_value, ev.step))
            elif which == "tensor":
                got.append((v.tag, v.tensor.float_val[0], ev.step))
    assert ("loss", 0.75, 3) in got and ("acc", 0.5, 4) in got


def test_tb_writer_detects_corruption(tmp_path):
    from stoke_tpu.utils.tb_writer import TBEventWriter, read_scalar_events
    import pytest

    w = TBEventWriter(str(tmp_path))
    w.add_scalar("x", 1.0, 1)
    w.close()
    data = bytearray(open(w.path, "rb").read())
    data[-3] ^= 0xFF  # flip a payload byte
    open(w.path, "wb").write(bytes(data))
    with pytest.raises(ValueError, match="corrupt"):
        read_scalar_events(w.path)


def test_tb_writer_negative_step(tmp_path):
    """Negative steps encode as 64-bit two's-complement varints (proto
    int64 convention) instead of hanging the encoder."""
    from stoke_tpu.utils.tb_writer import TBEventWriter, read_scalar_events

    w = TBEventWriter(str(tmp_path))
    w.add_scalar("x", 2.5, -1)
    w.close()
    (tag, val, step) = read_scalar_events(w.path)[0]
    assert tag == "x" and val == 2.5
    assert step == (1 << 64) - 1  # the raw two's-complement encoding
