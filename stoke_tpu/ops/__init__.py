"""TPU ops: sequence-parallel attention (ring / Ulysses) and the Pallas
kernels.  The reference has NO model-level long-context support (SURVEY.md
§2.8: "no sequence/context parallelism, no ring attention, no Ulysses") —
only the data-level BucketedDistributedSampler; these ops are capability
upside of the TPU build, designed in from the start.

The kernels, a module each, every one interpreted off the TPU and
``shard_map``-ped by itself under ``partition_kernels_over``:

- ``flash_attention``: flash forward and backward, the paged decode
  kernels of the MHA and the latent cache;
- ``grouped_matmul``: the held experts' products ``[M, K] x [G, K, N]``,
  each weight streamed once a call; counter ``expert_weight_passes``;
- ``delta_rule``: one position of the gated delta rule over every slot's
  ``[B, H, dk, dv]`` float32 state (``q``, ``k``, ``g [B, H, dk]``, ``v
  [B, H, dv]``, ``beta [B, H]``), the state aliased in to out so that a
  caller that donates it is updated in place; a call moves ``2 *
  state.nbytes`` and is bound by that stream; counter ``state_passes``.
"""

from stoke_tpu.ops.attention import (
    inverse_permutation,
    make_ring_attention,
    make_ulysses_attention,
    make_zigzag_ring_attention,
    ring_attention,
    ulysses_attention,
    zigzag_permutation,
    zigzag_ring_attention,
)
from stoke_tpu.ops.chunked_ce import (
    chunked_causal_lm_loss,
    chunked_softmax_cross_entropy,
)
from stoke_tpu.ops.delta_rule import delta_rule_step, state_passes
from stoke_tpu.ops.flash_attention import (
    flash_attention,
    make_flash_attention,
    paged_decode_attention,
    paged_decode_attention_pallas,
    paged_prefill_chunk_attention,
)

__all__ = [
    "paged_decode_attention",
    "paged_decode_attention_pallas",
    "paged_prefill_chunk_attention",
    "make_ring_attention",
    "make_ulysses_attention",
    "ring_attention",
    "ulysses_attention",
    "flash_attention",
    "make_flash_attention",
    "chunked_softmax_cross_entropy",
    "chunked_causal_lm_loss",
    "zigzag_ring_attention",
    "make_zigzag_ring_attention",
    "zigzag_permutation",
    "inverse_permutation",
    "delta_rule_step",
    "state_passes",
]
