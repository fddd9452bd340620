"""Pipeline-parallel causal LM integrated with the Stoke facade.

Takes pipeline parallelism from building block (``parallel/pipeline.py``) to
a trainable model: a decoder-only LM whose transformer blocks are split into
S pipeline stages on a mesh ``stage`` axis, driven through the normal
``Stoke`` facade (any precision / clipping / accumulation / checkpointing
flags compose).

Parameter layout: ``{"embed": ..., "stages": <stage-stacked block tree>,
"head": ...}`` — stage-stacked leaves carry a leading [S, ...] dimension and
are placed on the stage axis with the variadic partition rule from
:func:`pipeline_parallel_rules` (("stage", ...)).  Embedding/head stay
replicated.  Gradients flow through the pipeline automatically (the ppermute
rotation is linear), so this is a fully trainable pipeline out of the box.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from stoke_tpu.engine import ModelAdapter
from stoke_tpu.models.bert import BERT_SIZES, BertSize, TransformerBlock
from stoke_tpu.parallel.pipeline import pipeline, stack_stage_params


def pipeline_parallel_rules(stage_axis: str = "stage") -> Tuple:
    """Partition rule placing stage-stacked parameters on the stage axis
    (for ``PartitionRulesConfig``): every leaf under ``stages/`` gets its
    leading dim sharded, remaining dims replicated (variadic ``...``)."""
    return ((r"^stages/", (stage_axis, "...")),)


class _StageBlock(nn.Module):
    """One pipeline stage: ``layers_per_stage`` causal transformer blocks."""

    size: BertSize
    layers_per_stage: int
    dropout_rate: float = 0.0

    @nn.compact
    def __call__(self, x):
        L = x.shape[1]
        causal = jnp.tril(jnp.ones((L, L), bool))
        bias = jnp.where(causal, 0.0, -1e9)[None, None, :, :].astype(x.dtype)
        for i in range(self.layers_per_stage):
            x = TransformerBlock(
                self.size.hidden, self.size.heads, self.size.ff,
                self.dropout_rate, name=f"block_{i}",
            )(x, bias, True)  # deterministic inside the pipeline
        return x


class PipelinedLM(ModelAdapter):
    """Decoder-only LM with pipeline-parallel blocks (ModelAdapter flavor).

    Args:
        mesh: mesh containing ``stage_axis`` (size S).
        vocab_size / size_name / max_len: as in :class:`~stoke_tpu.models.GPT`.
        num_microbatches: microbatches the input batch is split into (batch
            must be divisible); more microbatches = less pipeline bubble.
        layers_per_stage: blocks per stage (total layers = rounds × S × this).
        rounds: virtual stages per device (circular/interleaved schedule;
            bubble shrinks from (S-1)/(M+S-1) to (S-1)/(rounds·M+S-1)).
        remat: rematerialize each per-tick stage application (1F1B-style
            activation memory).
        data_axis: optional mesh axis for dp×pp composition — the batch dim
            of the microbatch stream shards over it (mesh must then carry
            both axes, e.g. ``MeshConfig(axes=("data", "stage"), ...)``).

    Usage:
        adapter = PipelinedLM(mesh, vocab_size=..., num_microbatches=4)
        variables = adapter.init(jax.random.PRNGKey(0))
        stoke = Stoke(model=adapter, params=variables, ...,
                      configs=[MeshConfig(...same axes...),
                               PartitionRulesConfig(
                                   rules=pipeline_parallel_rules())])
    """

    def __init__(
        self,
        mesh,
        vocab_size: int = 50257,
        size_name: str = "tiny",
        max_len: int = 256,
        num_microbatches: int = 2,
        layers_per_stage: Optional[int] = None,
        stage_axis: str = "stage",
        rounds: int = 1,
        remat: bool = False,
        data_axis: Optional[str] = None,
    ):
        self.mesh = mesh
        self.vocab_size = vocab_size
        self.size = BERT_SIZES[size_name]
        self.max_len = max_len
        self.num_microbatches = num_microbatches
        self.stage_axis = stage_axis
        self.rounds = int(rounds)
        self.num_stages = mesh.shape[stage_axis] * self.rounds
        if layers_per_stage is None:
            layers_per_stage = max(1, self.size.num_layers // self.num_stages)
        self.layers_per_stage = layers_per_stage
        self._stage_module = _StageBlock(self.size, layers_per_stage)
        self._piped = pipeline(
            lambda p, x: self._stage_module.apply({"params": p}, x),
            mesh, stage_axis, rounds=self.rounds, remat=remat,
            data_axis=data_axis,
        )

    # ------------------------------------------------------------------ #

    def init(self, rng) -> dict:
        """Initialization of embed + S stage trees + head on the default
        device (the facade then places the tree onto the mesh)."""
        k_embed, k_pos, k_head, *k_stages = jax.random.split(
            rng, 3 + self.num_stages
        )
        H = self.size.hidden
        embed = {
            "tok": jax.random.normal(k_embed, (self.vocab_size, H)) * 0.02,
            "pos": jax.random.normal(k_pos, (self.max_len, H)) * 0.02,
        }
        dummy = jnp.zeros((1, 8, H), jnp.float32)
        stage_trees = [
            self._stage_module.init(k, dummy)["params"] for k in k_stages
        ]
        head = jax.random.normal(k_head, (H, self.vocab_size)) * 0.02
        return {
            "params": {
                "embed": embed,
                "stages": stack_stage_params(stage_trees),
                "head": head,
            }
        }

    def _forward(self, params, input_ids):
        B, L = input_ids.shape
        M = self.num_microbatches
        if B % M != 0:
            raise ValueError(
                f"PipelinedLM: batch {B} not divisible by "
                f"num_microbatches={M}"
            )
        h = params["embed"]["tok"][input_ids] + params["embed"]["pos"][None, :L]
        h = h.reshape(M, B // M, L, -1)  # microbatch stream
        h = self._piped(params["stages"], h)
        h = h.reshape(B, L, -1)
        return h @ params["head"]

    def apply_train(self, variables, rng, args, kwargs):
        return self._forward(variables["params"], args[0]), {}

    def apply_eval(self, variables, args, kwargs):
        return self._forward(variables["params"], args[0])
