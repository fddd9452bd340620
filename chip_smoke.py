#!/usr/bin/env python3
"""Proof that the trainer and the server start on the chip.

One process, no arguments, run from the root of the checkout:

    python3 chip_smoke.py

It refuses anything but a TPU, then drives the main path once through the
entry points a user calls, at the full width of the widest transformer the
width table has (``GPT(size_name="large")``: 24 layers, hidden 1024, 16 heads
of 64, FFN 4096, vocabulary 50257, 1024 positions — GPT-2-medium's shape),
with random weights made from a seed:

- trainer leg: ``init_module`` -> ``Stoke(device="tpu", precision="bf16",
  grad_accum=2, ...)`` -> ``stoke.DataLoader``; optimizer steps through the
  4-call contract and through ``train_step``, causal flash attention on the
  path (the lowered step program must hold a Mosaic custom call);
- server leg: ``stoke.serve()`` on the params just trained; greedy requests
  run to their ``max_new_tokens`` and every served token is checked, through
  the cache, against a cache-free forward of the same sequence;
- kernel leg: the Pallas kernels compiled by Mosaic (``interpret=False``)
  against the ``jnp`` references in ``ops/flash_attention.py``;
- sharded leg, when more than one chip is visible: the same trainer under
  ``distributed="dp", fsdp=True`` over all of them.

No leg is wrapped in try/except: the first failure is the exit code.  The
last line of stdout is the result, ``{"ok": true, "device": {"platform",
"kind", "count"}}`` with the device as JAX reports it and no other key; the
line before it (``chip_smoke: report {...}``) carries the versions, the legs,
the compile cache and the wall seconds.  Those seconds are set-up
information, not a metric: no rate, utilization or peak is printed here.

The legs are importable functions with the model size as an argument;
``tests/test_chip_smoke.py`` rehearses them on the CPU at ``size_name="tiny"``
(interpreter kernels).
"""

from __future__ import annotations

import gc
import importlib.metadata
import json
import os
import sys
import time

import numpy as np

#: the one model this script runs at full width; nothing here is cut
FULL = dict(size_name="large", vocab_size=50257, seq_len=1024)
#: micro-batch rows per device (the global micro-batch is this times the
#: number of devices the leg runs on)
BATCH = 4
GRAD_ACCUM = 2
#: served-token tolerance: the served token's logit must lie within this
#: fraction of the reference forward's logit range below its maximum (exact
#: argmax equality would trip on bf16 near-ties)
LOGIT_TOL_FRAC = 0.02


# --------------------------------------------------------------------------- #
# trainer
# --------------------------------------------------------------------------- #


def trainer_leg(*, size_name, vocab_size, seq_len, batch, device,
                distributed=None, fsdp=False, serve_config=None,
                steps_4call=3, steps_fused=2, seed=0):
    """A few optimizer steps through the 4-call contract, then through
    ``train_step``, on a repeated batch.  Returns ``(stoke, model, info)``."""
    import jax
    import optax

    from stoke_tpu import (
        ArrayDataset,
        ClipGradNormConfig,
        Stoke,
        StokeOptimizer,
        init_module,
    )
    from stoke_tpu.models import GPT, causal_lm_loss
    from stoke_tpu.ops import make_flash_attention

    model = GPT(
        vocab_size=vocab_size, size_name=size_name, max_len=seq_len,
        dropout_rate=0.0, attention_fn=make_flash_attention(causal=True),
        attention_is_causal=True,
    )
    variables = init_module(
        model, jax.random.PRNGKey(seed),
        np.zeros((1, seq_len), np.int32), train=False,
    )
    stoke = Stoke(
        model=model,
        optimizer=StokeOptimizer(
            optimizer=optax.adamw, optimizer_kwargs={"learning_rate": 1e-4}
        ),
        loss=causal_lm_loss,
        params=variables,
        batch_size_per_device=batch,
        grad_accum=GRAD_ACCUM,
        grad_clip=ClipGradNormConfig(max_norm=1.0),
        device=device,
        distributed=distributed,
        precision="bf16",
        fsdp=fsdp,
        configs=[serve_config] if serve_config is not None else None,
        model_train_kwargs={"train": True},
        model_eval_kwargs={"train": False},
        verbose=False,
    )
    del variables  # the facade owns (and donates) these buffers now
    # exactly one optimizer step of micro-batches, replayed every epoch
    rows = batch * stoke.world_size * GRAD_ACCUM
    tokens = np.random.default_rng(seed).integers(
        0, vocab_size, size=(rows, seq_len), dtype=np.int32
    )
    loader = stoke.DataLoader(
        ArrayDataset(tokens), shuffle=False, drop_last=True
    )

    losses = []
    for _ in range(steps_4call):
        for micro in loader:
            out = stoke.model(micro)
            loss = stoke.loss(out, micro)
            stoke.backward(loss)
            stoke.step()
            losses.append(loss)
    for _ in range(steps_fused):
        for micro in loader:
            losses.append(stoke.train_step(micro, micro))
    losses = [float(np.asarray(l)) for l in losses]

    n_steps = steps_4call + steps_fused
    assert all(np.isfinite(l) for l in losses), losses
    assert len(losses) == n_steps * GRAD_ACCUM, len(losses)
    # same micro-batch, first optimizer step against the last
    assert losses[-GRAD_ACCUM] < losses[0], losses
    assert stoke.optimizer_steps == n_steps, stoke.optimizer_steps
    assert stoke.backward_steps == n_steps * GRAD_ACCUM, stoke.backward_steps
    leaves = jax.tree_util.tree_leaves(stoke.params)
    platforms = {d.platform for leaf in leaves for d in leaf.devices()}
    assert platforms == {device}, platforms
    # flash must have been compiled by Mosaic, not interpreted: a lowered
    # step program carries its custom call (the first one found will do —
    # re-lowering a 24-layer program takes seconds)
    mosaic_program = next(
        (
            spec.program
            for spec in stoke._engine.audit_specs()
            if "tpu_custom_call"
            in spec.fn.lower(*spec.abstract_args).as_text()
        ),
        None,
    )
    if device == "tpu":
        assert mosaic_program, "no step program holds a Mosaic custom call"
    info = {
        "losses": [round(l, 4) for l in losses],
        "optimizer_steps": stoke.optimizer_steps,
        "backward_steps": stoke.backward_steps,
        "params": int(sum(leaf.size for leaf in leaves)),
        "world_size": stoke.world_size,
        "mosaic_program": mosaic_program,
    }
    return stoke, model, info


# --------------------------------------------------------------------------- #
# server
# --------------------------------------------------------------------------- #


def serve_config_for(seq_len, pad_multiple):
    """The serving defaults ROADMAP S5 says move the most bytes — float32
    cache, ``decode_kernel="reference"`` — with flash prefill."""
    from stoke_tpu import ServeConfig

    return ServeConfig(
        max_seqs=4,
        kv_block_size=16,
        max_seq_len=seq_len,
        max_new_tokens=8,
        prefill_pad_multiple=pad_multiple,
        attention="flash",
        decode_kernel="reference",
    )


def server_leg(stoke, model, *, prompt_lens, vocab_size, seq_len,
               max_new_tokens=8, seed=1):
    """Greedy requests through ``stoke.serve()``, each run to its
    ``max_new_tokens``; every served token is then scored by ONE cache-free
    causal forward of ``prompt + served tokens`` (position ``p`` of that
    forward sees exactly what the engine had cached when it served the
    token at ``p + 1``)."""
    import jax
    import jax.numpy as jnp

    engine = stoke.serve()
    r = np.random.default_rng(seed)
    prompts = [
        r.integers(0, vocab_size, size=n, dtype=np.int32) for n in prompt_lens
    ]
    streams = engine.generate(prompts, max_new_tokens=max_new_tokens)
    assert [len(s) for s in streams] == [max_new_tokens] * len(prompts), [
        len(s) for s in streams
    ]

    full = np.zeros((len(prompts), seq_len), np.int32)
    for i, (p, s) in enumerate(zip(prompts, streams)):
        full[i, : len(p) + len(s)] = np.concatenate([p, s])
    # the position that produced served token t of request i
    at = np.asarray(
        [[len(p) - 1 + t for t in range(max_new_tokens)] for p in prompts],
        np.int32,
    )

    @jax.jit
    def reference_rows(params, ids, at):
        logits = model.apply({"params": params}, ids, train=False)
        return jnp.take_along_axis(
            logits.astype(jnp.float32), at[:, :, None], axis=1
        )  # [n, T, V]

    rows = np.asarray(reference_rows(stoke.params, full, at))
    assert np.isfinite(rows).all()
    served = np.take_along_axis(
        rows, np.asarray(streams, np.int64)[:, :, None], axis=2
    )[..., 0]
    gap = (rows.max(-1) - served) / (rows.max(-1) - rows.min(-1))
    assert gap.max() <= LOGIT_TOL_FRAC, (
        f"a served token's logit is {gap.max():.4f} of the logit range "
        f"below the cache-free forward's maximum (tolerance "
        f"{LOGIT_TOL_FRAC})"
    )
    s = engine.summary()
    info = {
        "requests": len(prompts),
        "prompt_lens": list(prompt_lens),
        "tokens_out": int(s["tokens_out"]),
        "prefills": int(s["prefills"]),
        "decode_steps": int(s["decode_steps"]),
        "argmax_matches": int((gap == 0).sum()),
        "served_tokens": int(gap.size),
        "worst_logit_gap_frac": round(float(gap.max()), 5),
    }
    return engine, info


# --------------------------------------------------------------------------- #
# kernels
# --------------------------------------------------------------------------- #


def check_flash_parity(*, heads, head_dim, seq_len, interpret, batch=2,
                       seed=0):
    """Causal flash forward and backward against ``dense_reference`` at
    bf16 inputs, with the tolerances kept beside the kernel."""
    import jax
    import jax.numpy as jnp

    from stoke_tpu.ops.flash_attention import (
        BWD_RTOL_BF16,
        FWD_ATOL_BF16,
        dense_reference,
        flash_attention,
    )

    r = np.random.default_rng(seed)
    q, k, v = (
        jnp.asarray(
            r.normal(size=(batch, heads, seq_len, head_dim)), jnp.bfloat16
        )
        for _ in range(3)
    )

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=interpret)

    def dense(q, k, v):
        return dense_reference(q, k, v, causal=True)

    with jax.default_matmul_precision("highest"):
        ref = dense(q, k, v)
        g_ref = jax.grad(
            lambda *a: jnp.sum(dense(*a) ** 2), argnums=(0, 1, 2)
        )(q, k, v)
    fwd_err = float(jnp.max(jnp.abs(flash(q, k, v).astype(jnp.float32) - ref)))
    g = jax.grad(
        lambda *a: jnp.sum(flash(*a).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_scale = max(float(jnp.max(jnp.abs(b.astype(jnp.float32)))) for b in g_ref)
    g_err = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(g, g_ref)
    )
    assert fwd_err < FWD_ATOL_BF16, (seq_len, fwd_err)
    assert g_err < BWD_RTOL_BF16 * max(g_scale, 1.0), (seq_len, g_err, g_scale)
    return {
        "fwd_err": round(fwd_err, 5),
        "bwd_rel_err": round(g_err / max(g_scale, 1.0), 5),
    }


def check_paged_parity(*, heads, head_dim, block_size, pool_dtype, n_q,
                       interpret, batch=3, max_blocks=8, seed=0):
    """The Pallas paged kernel against its ``jnp`` reference over a ragged
    batch: decode for ``n_q == 1``, speculative verify otherwise."""
    import jax
    import jax.numpy as jnp

    from stoke_tpu.ops.flash_attention import (
        paged_decode_attention,
        paged_decode_attention_pallas,
        paged_verify_attention,
        paged_verify_attention_pallas,
    )

    r = np.random.default_rng(seed)
    n_blocks = 1 + batch * max_blocks  # block 0 is the scratch block
    k_pages, v_pages = (
        jnp.asarray(
            r.normal(size=(n_blocks, block_size, heads, head_dim)), pool_dtype
        )
        for _ in range(2)
    )
    q = jnp.asarray(
        r.normal(size=(batch, heads, n_q, head_dim)), jnp.float32
    )
    tables = 1 + r.permutation(batch * max_blocks).reshape(batch, max_blocks)
    window = block_size * max_blocks
    ctx = np.linspace(n_q + 1, window - n_q, batch).astype(np.int32)
    # blocks past each request's context are unallocated: scratch
    used = -(-(ctx + n_q) // block_size)
    tables = np.where(
        np.arange(max_blocks)[None, :] < used[:, None], tables, 0
    ).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        if n_q == 1:
            args = (q, k_pages, v_pages, jnp.asarray(tables), jnp.asarray(ctx))
            ref = paged_decode_attention(*args)
            out = paged_decode_attention_pallas(*args, interpret=interpret)
        else:
            positions = ctx[:, None] + np.arange(n_q, dtype=np.int32)[None, :]
            args = (
                q, k_pages, v_pages, jnp.asarray(tables),
                jnp.asarray(positions),
            )
            ref = paged_verify_attention(*args)
            out = paged_verify_attention_pallas(*args, interpret=interpret)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert np.isfinite(err) and err < 2e-3, (n_q, str(pool_dtype), err)
    return {"max_abs_err": round(err, 6)}


def kernel_leg(*, heads, head_dim, seq_len, short_len, interpret,
               block_size=16, verify_rows=5):
    """Compile-and-parity of every Pallas kernel at this model's head
    geometry: flash forward+backward at the training length and at one
    short serve bucket that is not a multiple of 128, and the paged decode
    and verify kernels over float32 and bfloat16 pools."""
    import jax.numpy as jnp

    info = {}
    for L in (seq_len, short_len):
        info[f"flash_L{L}"] = check_flash_parity(
            heads=heads, head_dim=head_dim, seq_len=L, interpret=interpret
        )
    for dtype in (jnp.float32, jnp.bfloat16):
        for name, n_q in (("decode", 1), ("verify", verify_rows)):
            info[f"paged_{name}_{jnp.dtype(dtype).name}"] = check_paged_parity(
                heads=heads, head_dim=head_dim, block_size=block_size,
                pool_dtype=dtype, n_q=n_q, interpret=interpret,
            )
    return info


# --------------------------------------------------------------------------- #
# several chips
# --------------------------------------------------------------------------- #


def sharded_leg(*, size_name, vocab_size, seq_len, batch, device,
                large_leaf_elems=1 << 20):
    """The trainer under ``distributed="dp", fsdp=True`` over every visible
    device: the state must really be spread over all of them."""
    import jax

    n = jax.device_count()
    stoke, _, info = trainer_leg(
        size_name=size_name, vocab_size=vocab_size, seq_len=seq_len,
        batch=batch, device=device, distributed="dp", fsdp=True,
        steps_4call=2, steps_fused=1,
    )
    assert stoke.world_size == n, (stoke.world_size, n)
    leaves = jax.tree_util.tree_leaves(stoke.params)
    big = [leaf for leaf in leaves if leaf.size >= large_leaf_elems]
    assert big, "no large parameter leaf to check"
    for leaf in big:
        shards = leaf.addressable_shards
        assert len({s.device for s in shards}) == n, leaf.sharding
        share = max(s.data.nbytes for s in shards) / leaf.nbytes
        assert share <= 1.5 / n, (leaf.shape, share)
    info["large_leaves_sharded"] = len(big)
    stats = [d.memory_stats() for d in jax.local_devices()]
    if all(s and "bytes_in_use" in s for s in stats):
        # code that never ran on several chips may put everything on the
        # first one
        in_use = [s["bytes_in_use"] for s in stats]
        assert max(in_use) <= 4 * min(in_use), in_use
        info["bytes_in_use"] = in_use
    return stoke, info


# --------------------------------------------------------------------------- #
# the run
# --------------------------------------------------------------------------- #


def _cache_entries(path) -> int:
    """Files jax's persistent cache holds at its top level."""
    if not os.path.isdir(path):
        return 0
    return sum(e.is_file() for e in os.scandir(path))


def result_line(devices) -> dict:
    """The last line of stdout, to the driver's contract: exactly ``ok`` and
    ``device``, the device as JAX reports it.  Reached only when every leg
    passed; the run's details go on the ``report`` line before it."""
    return {
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }


def main() -> int:
    import jax

    from stoke_tpu.compile_cache import install_persistent_xla_cache

    backend = jax.default_backend()
    if backend != "tpu":
        print(
            f"chip_smoke: needs a TPU; jax.default_backend()={backend!r}, "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}",
            file=sys.stderr,
        )
        return 1
    # the repo's one cache rule, applied before anything compiles
    cache_dir = install_persistent_xla_cache()
    cache_before = _cache_entries(cache_dir)

    import jaxlib

    from stoke_tpu.models.bert import BERT_SIZES
    from stoke_tpu.native import NativeBatcher

    size = BERT_SIZES[FULL["size_name"]]
    legs, seconds = {}, {}

    def run(name, fn, **kwargs):
        t0 = time.perf_counter()
        out = fn(**kwargs)
        seconds[name] = round(time.perf_counter() - t0, 1)
        print(f"chip_smoke: {name} leg passed in {seconds[name]} s",
              flush=True)
        return out

    stoke, model, legs["trainer"] = run(
        "trainer", trainer_leg, **FULL, batch=BATCH, device="tpu",
        serve_config=serve_config_for(FULL["seq_len"], pad_multiple=128),
    )
    # one prompt longer than 512 tokens: its 640-token bucket takes the
    # blocked flash path, the short one a single whole-bucket block
    engine, legs["server"] = run(
        "server", server_leg, stoke=stoke, model=model,
        prompt_lens=(100, 520, 100, 520), vocab_size=FULL["vocab_size"],
        seq_len=FULL["seq_len"],
    )
    del stoke, model, engine
    gc.collect()
    legs["kernel"] = run(
        "kernel", kernel_leg, heads=size.heads,
        head_dim=size.hidden // size.heads, seq_len=FULL["seq_len"],
        short_len=96, interpret=False,
    )
    if jax.device_count() > 1:
        sharded, legs["sharded"] = run(
            "sharded", sharded_leg, **FULL, batch=BATCH, device="tpu"
        )
        del sharded
    else:
        legs["sharded"] = "skipped: one chip visible"
        print("chip_smoke: sharded leg skipped, one chip visible", flush=True)

    # everything worth reading about the run, then — last — the result line
    print("chip_smoke: report " + json.dumps({
        "versions": {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "libtpu": importlib.metadata.version("libtpu"),
        },
        "model": {**FULL, "layers": size.num_layers, "hidden": size.hidden,
                  "heads": size.heads, "ffn": size.ff,
                  "batch_per_device": BATCH, "grad_accum": GRAD_ACCUM},
        "cut": [],
        "legs": legs,
        "compile_cache": {
            "dir": cache_dir,
            "entries_before": cache_before,
            "entries_after": _cache_entries(cache_dir),
        },
        "native_batcher_built": NativeBatcher().available,
        "wall_s": seconds,
    }))
    print(json.dumps(result_line(jax.devices())), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
