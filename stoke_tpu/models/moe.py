"""Mixture-of-Experts FFN with expert parallelism.

Completes the parallelism menu (dp/tp/sp/pp/**ep**) — capability upside
beyond the reference (SURVEY.md §2.8: no expert parallelism).  The design
keeps the framework's theme: expert parallelism is *placement*, not code.
Expert weights are stacked on a leading expert dimension; shard that
dimension over a mesh ``expert`` axis with a partition rule
(:func:`moe_expert_parallel_rules`) and GSPMD lowers the dispatch/combine
einsums to the all-to-all pattern — no hand-written collectives.

Routing is switch-style top-k (k=1 default; k=2 gives GShard-style routing
with renormalized gates) with a capacity limit: experts accept at most
``ceil(tokens/E) * capacity_factor`` tokens per choice-priority order
(first choices fill capacity before second choices); overflow tokens pass
through the residual unchanged (combine weight 0).  Dispatch/combine are
one-hot einsums (MXU-friendly, static shapes — no gather/scatter).

**Load balancing**: the router computes the Switch-Transformer auxiliary
loss ``aux = E · Σ_e f_e · P_e`` (f_e = fraction of tokens whose first
choice is expert e, P_e = mean router probability of e; minimized at 1.0
by the uniform assignment) and sows it into the flax ``"losses"``
collection.  The training engine adds sown losses to the objective with
the facade's ``aux_loss_weight`` (default 0.01) — without this term,
top-1 routing collapses onto a few experts in real training.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from stoke_tpu.ops.grouped_matmul import grouped_matmul, grouped_swiglu


class MoEFFN(nn.Module):
    """Switch-routed expert FFN block (drop-in for a dense FFN).

    Args:
        hidden: model width (input/output dim).
        ff: per-expert feed-forward width.
        num_experts: expert count E (shard over the mesh ``expert`` axis via
            :func:`moe_expert_parallel_rules` for EP).
        capacity_factor: per-expert capacity = ceil(top_k·N/E) * factor
            (scaled by top_k per the GShard convention, so k=2 at the
            default factor does not structurally drop second choices).
        router_noise: train-time logit jitter (load balancing aid); needs the
            ``router`` rng stream when > 0.
        top_k: experts per token (1 = Switch, 2 = GShard-style with
            renormalized gates).
    """

    hidden: int
    ff: int
    num_experts: int = 8
    capacity_factor: float = 1.25
    router_noise: float = 0.0
    top_k: int = 1

    @nn.compact
    def __call__(self, x, train: bool = True):
        # Grouped dispatch (Switch/Mesh-TF layout): tokens are routed within
        # per-example groups of S = L tokens, so the one-hot dispatch/combine
        # tensors are [G, S, E, C] with C ≈ S/E — LINEAR in total tokens
        # (an ungrouped [N, E, N/E] layout would be quadratic and OOM at
        # real sequence lengths).
        G, S, H = x.shape
        E = self.num_experts
        k = self.top_k
        if not 1 <= k <= E:
            raise ValueError(f"MoEFFN: top_k must be in [1, {E}], got {k}")
        # GShard convention: tokens produce k assignments, so per-expert
        # capacity scales with k — otherwise top-2 at the default factor
        # would structurally drop every second choice
        C = max(1, int(np.ceil(k * S / E) * self.capacity_factor))

        logits = nn.Dense(E, use_bias=False, name="router")(x)  # [G, S, E]
        if self.router_noise > 0.0 and train:
            key = self.make_rng("router")
            logits = logits + self.router_noise * jax.random.normal(
                key, logits.shape, logits.dtype
            )
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        topk_probs, topk_idx = jax.lax.top_k(probs, k)  # [G, S, k]
        if k > 1:
            gates = topk_probs / jnp.maximum(
                jnp.sum(topk_probs, axis=-1, keepdims=True), 1e-9
            )
        else:
            gates = topk_probs

        # Switch load-balancing loss: E · Σ_e f_e·P_e (f from first choices,
        # P the mean router prob; ≥ 1 with equality at uniform).  Sown with
        # an overwriting reduce_fn so the collection stays a stable scalar
        # across steps (the engine folds it into the objective and the
        # facade surfaces it via ``aux_losses``).
        assign1 = jax.nn.one_hot(topk_idx[..., 0], E, dtype=jnp.float32)
        f_e = jnp.mean(assign1, axis=(0, 1))           # [E]
        p_e = jnp.mean(probs, axis=(0, 1))             # [E]
        aux = jnp.float32(E) * jnp.sum(f_e * p_e)
        self.sow(
            "losses", "aux_loss", aux,
            reduce_fn=lambda prev, new: new,
            init_fn=lambda: jnp.float32(0.0),
        )

        # capacity: queue position per (token, choice), choice-major priority
        # (all first choices claim capacity before any second choice)
        assign_k = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32)  # [G,S,k,E]
        prio = assign_k.transpose(0, 2, 1, 3).reshape(G, k * S, E)
        position = (jnp.cumsum(prio, axis=1) - 1.0) * prio
        pos_tok = jnp.sum(position, axis=-1)           # [G, k*S]
        pos_tok = pos_tok.reshape(G, k, S).transpose(0, 2, 1)  # [G, S, k]
        keep = pos_tok < C
        gates = gates * keep

        # dispatch/combine: [G, S, E, C] one-hot (static shapes, MXU)
        pos_oh = jax.nn.one_hot(
            pos_tok.astype(jnp.int32), C, dtype=jnp.float32
        )  # [G, S, k, C]
        dispatch = jnp.einsum(
            "gsje,gsjc->gsec", assign_k * keep[..., None], pos_oh
        )
        combine = jnp.einsum(
            "gsje,gsjc->gsec", assign_k * gates[..., None], pos_oh
        )

        # route → expert MLPs (weights stacked on the expert dim) → return
        expert_in = jnp.einsum(
            "gsec,gsh->egch", dispatch.astype(x.dtype), x
        )  # [E, G, C, H]
        w_in = self.param(
            "w_in", nn.initializers.lecun_normal(), (E, H, self.ff), jnp.float32
        ).astype(x.dtype)
        w_out = self.param(
            "w_out", nn.initializers.lecun_normal(), (E, self.ff, H), jnp.float32
        ).astype(x.dtype)
        h = jax.nn.gelu(jnp.einsum("egch,ehf->egcf", expert_in, w_in))
        expert_out = jnp.einsum("egcf,efh->egch", h, w_out)
        return jnp.einsum(
            "gsec,egch->gsh", combine.astype(x.dtype), expert_out
        )


def group_limited_topk(scores, n_group: int, topk_group: int, top_k: int,
                       scale: float = 1.0, norm: bool = True, bias=None):
    """Group-limited top-k routing (the DeepSeek-V3 family's, without a
    score-correction bias): ``scores [N, E]`` float32, one column an expert,
    the experts in ``n_group`` contiguous groups.  A group's score is the sum
    of its two highest scores; the ``topk_group`` best groups are kept; the
    ``top_k`` highest scores within them are the token's experts.  Returns
    ``(experts [N, top_k] int32, weights [N, top_k] float32)``: the chosen
    scores, renormalised over the ``top_k`` when ``norm``, times ``scale``.
    Ties go to the lower index, at both levels.  With a score-correction
    ``bias [E]`` (the family's ``noaux_tc``) groups and experts are chosen
    by ``scores + bias`` and weighed by the plain scores."""
    N, E = scores.shape
    if E % n_group:
        raise ValueError(f"{E} experts do not split into {n_group} groups")
    if bias is not None:
        return _biased_group_topk(scores, bias, n_group, topk_group, top_k,
                                  scale, norm)
    grouped = scores.reshape(N, n_group, E // n_group)
    group_score = jax.lax.top_k(grouped, min(2, E // n_group))[0].sum(-1)
    _, kept = jax.lax.top_k(group_score, topk_group)  # [N, topk_group]
    in_kept = (
        kept[:, :, None] == jnp.arange(n_group, dtype=kept.dtype)
    ).any(axis=1)  # [N, n_group]
    # scores are sigmoids, above 0: -1 ranks every masked expert last
    masked = jnp.where(in_kept[:, :, None], grouped, -1.0).reshape(N, E)
    weights, experts = jax.lax.top_k(masked, top_k)
    if norm:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights * scale


def _biased_group_topk(scores, bias, n_group, topk_group, top_k, scale,
                       norm):
    """:func:`group_limited_topk` choosing by ``scores + bias``."""
    N, E = scores.shape
    choice = (scores + bias).reshape(N, n_group, E // n_group)
    group_score = jax.lax.top_k(choice, min(2, E // n_group))[0].sum(-1)
    _, kept = jax.lax.top_k(group_score, topk_group)
    in_kept = (
        kept[:, :, None] == jnp.arange(n_group, dtype=kept.dtype)
    ).any(axis=1)
    masked = jnp.where(in_kept[:, :, None], choice, -jnp.inf).reshape(N, E)
    _, experts = jax.lax.top_k(masked, top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if norm:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights * scale


def softmax_topk(logits, bias, top_k: int, scale: float = 1.0,
                 norm: bool = False):
    """Softmax routing with a choice bias (the LongCat-Flash family's):
    ``logits [N, E]`` float32, one column a router output.  ``s =
    softmax(logits)`` over all ``E``; the token's experts are the ``top_k``
    largest of ``s + bias`` (``bias [E]`` or None: the score-correction bias
    moves the choice and not the weight); its weights are the chosen ``s``,
    unbiased, renormalised over the ``top_k`` when ``norm``, times
    ``scale``.  Returns ``(experts [N, top_k] int32, weights [N, top_k]
    float32)``.  Ties go to the lower index."""
    scores = jax.nn.softmax(logits, axis=-1)
    _, experts = jax.lax.top_k(
        scores if bias is None else scores + bias, top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if norm:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights * scale


class SwiGLU(nn.Module):
    """``W_d(silu(W_g x) * W_u x)``, no biases; products accumulate in
    float32 and come back in ``dtype``.  With a ``limit`` (gpt-oss's form):
    ``W_d(silu(min(W_g x, limit)) * clip(W_u x, -limit, limit))``."""

    width: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    limit: float = 0.0

    @nn.compact
    def __call__(self, x):
        def proj(name, features, y):
            return nn.Dense(
                features, use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype, name=name,
            )(y)

        if self.limit:
            gated = jax.nn.silu(jnp.minimum(
                proj("gate", self.width, x), self.limit)) * jnp.clip(
                    proj("up", self.width, x), -self.limit, self.limit)
        else:
            gated = jax.nn.silu(proj("gate", self.width, x)) * proj(
                "up", self.width, x
            )
        return proj("down", x.shape[-1], gated)


#: tokens of a long prompt an expert layer routes at a time, where the
#: prompt is longer and a multiple of it: its assignments' rows, gathered
#: and in float32, would not fit whole
EXPERT_TOKEN_CHUNK = 4096


class ExpertShareFFN(nn.Module):
    """One chip's share of a sparse expert layer: a router of the model's
    full width, the ``held = (first, count)`` routed experts that live here,
    the shared experts if the model has any, and the zero-compute experts'
    part if its router has such outputs.

    Every token is routed over all the router's outputs: ``num_experts``
    routed experts and, after them, ``zero_experts`` zero-compute ones
    (identity experts: an assignment to one adds ``weight * x`` and has no
    weights).  ``scoring`` is ``"sigmoid"`` (:func:`group_limited_topk`) or
    ``"softmax"`` (:func:`softmax_topk`, with the learned
    ``e_score_correction_bias`` on the choice under ``choice_bias``).  An
    assignment falls in one of three classes:

    - held (``first <= e < first + count``): all computed, none dropped.
      The ``N * top_k`` assignments are sorted by expert (every other
      assignment last), the held experts' SwiGLU runs as three grouped
      products over the sorted rows (:mod:`stoke_tpu.ops.grouped_matmul`:
      gate and up in one kernel, down in a second, each weight streamed
      once as stored; rows past the last group belong to no expert, are not
      computed and are never read back), and each token sums its own rows
      by the inverse permutation, weighted;
    - absent (another chip's routed expert): left out.  Its part of the sum
      is what the chip that holds it would add, and nothing here stands in
      for it;
    - zero-compute (``e >= num_experts``): ``weight * x``, summed a token
      over its such picks without a product; it needs no weight and no
      exchange, so it is computed here for every token, like a shared
      expert.

    The shared expert (``n_shared_experts`` > 0) is computed for every
    token.  Sows into the ``intermediates`` collection the per-held-expert
    assignment counts (``int32[count]``) as ``expert_counts`` and, where the
    router has zero-compute outputs, each token's count of picks among them
    (``int32[N]``) as ``zero_expert_count``.

    Router logits, scores, top-k and weights are float32 (the logits at
    ``Precision.HIGHEST``); expert products take ``dtype`` inputs and
    accumulate in float32; the zero-compute part is summed in float32."""

    hidden: int
    ff: int
    num_experts: int
    held: Tuple[int, int]
    top_k: int
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    n_shared_experts: int = 1
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    scoring: str = "sigmoid"
    choice_bias: bool = False
    zero_experts: int = 0
    swiglu_limit: float = 0.0

    @nn.compact
    def __call__(self, x):
        B, L, H = x.shape
        N, k = B * L, self.top_k
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(
                f"ExpertShareFFN: held={self.held} is no range of the "
                f"{self.num_experts} routed experts"
            )
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"ExpertShareFFN: scoring {self.scoring!r}")
        if self.scoring == "sigmoid" and self.zero_experts:
            raise ValueError(
                "ExpertShareFFN: the sigmoid router has no zero-compute "
                "outputs here")
        if self.scoring == "softmax" and self.n_group != 1:
            raise ValueError(
                "ExpertShareFFN: the softmax router has no groups here")
        xf = x.reshape(N, H)
        with jax.named_scope("router"):
            w_r = self.param(
                "router", nn.initializers.lecun_normal(),
                (H, self.num_experts + self.zero_experts), jnp.float32,
            )
            logits = jnp.dot(
                xf.astype(jnp.float32), w_r,
                precision=jax.lax.Precision.HIGHEST,
            )
            bias = self.param(
                "e_score_correction_bias", nn.initializers.zeros,
                (self.num_experts + self.zero_experts,), jnp.float32,
            ) if self.choice_bias else None
            if self.scoring == "sigmoid":
                experts, weights = group_limited_topk(
                    jax.nn.sigmoid(logits), self.n_group, self.topk_group,
                    k, self.routed_scaling_factor, self.norm_topk_prob, bias,
                )
            else:
                experts, weights = softmax_topk(
                    logits, bias, k, self.routed_scaling_factor,
                    self.norm_topk_prob,
                )
        zero_part = None
        if self.zero_experts:
            with jax.named_scope("zero"):
                is_zero = experts >= self.num_experts  # [N, k]
                zero_part = jnp.where(is_zero, weights, 0.0).sum(
                    axis=1, keepdims=True) * xf.astype(jnp.float32)
            self.sow("intermediates", "zero_expert_count",
                     is_zero.sum(axis=1, dtype=jnp.int32))
        with jax.named_scope("moe"):
            init = nn.initializers.lecun_normal(batch_axis=(0,))
            w_gate, w_up = (
                self.param(name, init, (count, H, self.ff), self.param_dtype)
                for name in ("w_gate", "w_up")
            )
            w_down = self.param(
                "w_down", init, (count, self.ff, H), self.param_dtype
            )

            def held_part(xf, experts, weights):
                n = xf.shape[0]
                local = experts - first
                is_held = (local >= 0) & (local < count)  # [n, k]
                key = jnp.where(is_held, local, count).reshape(n * k)
                counts = (
                    key[:, None] == jnp.arange(count, dtype=key.dtype)
                ).sum(axis=0, dtype=jnp.int32)
                order = jnp.argsort(key, stable=True)  # held first
                rows = xf.astype(self.dtype)[order // k]  # [n*k, H]
                mid = grouped_swiglu(rows, w_gate, w_up, counts,
                                     limit=self.swiglu_limit)
                y = grouped_matmul(mid, w_down, counts)  # [n*k, H] float32
                y = y[jnp.argsort(order)].reshape(n, k, H)
                # a row of no group holds whatever the grouped product left
                y = jnp.where(is_held[:, :, None], y, 0.0)
                return (y * weights[:, :, None]).sum(axis=1), counts

            chunk = EXPERT_TOKEN_CHUNK
            if N > chunk and N % chunk == 0:
                # a long prompt's assignments a chunk of tokens at a time:
                # its N x k rows, gathered and in float32, would not fit
                routed, counts = jax.lax.map(
                    lambda a: held_part(*a),
                    (xf.reshape(N // chunk, chunk, H),
                     experts.reshape(N // chunk, chunk, k),
                     weights.reshape(N // chunk, chunk, k)))
                routed, counts = routed.reshape(N, H), counts.sum(axis=0)
            else:
                routed, counts = held_part(xf, experts, weights)
            if zero_part is not None:
                routed = routed + zero_part
            shared = SwiGLU(
                self.ff * self.n_shared_experts, self.dtype,
                self.param_dtype, self.swiglu_limit, name="shared",
            )(x) if self.n_shared_experts else None
            out = routed.reshape(B, L, H).astype(self.dtype)
            if shared is not None:
                out = shared + out
        self.sow("intermediates", "expert_counts", counts)
        return out


def moe_expert_parallel_rules(expert_axis: str = "expert") -> Tuple:
    """Partition rules sharding the stacked expert weights over the mesh
    ``expert`` axis (for ``PartitionRulesConfig``); the router stays
    replicated.  With these placements GSPMD lowers the dispatch/combine
    einsums to the expert all-to-all."""
    return (
        (r"w_in$", (expert_axis, None, None)),
        (r"w_out$", (expert_axis, None, None)),
    )


class MoETransformerBlock(nn.Module):
    """Transformer block whose FFN is a switch MoE (attention unchanged) —
    composes with the BERT/GPT encoders via manual stacking or as a
    reference for building MoE models."""

    hidden: int
    heads: int
    ff: int
    num_experts: int = 8
    dropout_rate: float = 0.1
    capacity_factor: float = 1.25
    attention_fn: Optional[Callable] = None
    router_noise: float = 0.0
    top_k: int = 1

    @nn.compact
    def __call__(self, x, bias, deterministic: bool):
        from stoke_tpu.models.bert import MultiHeadAttention, dense_attention

        attn = self.attention_fn or dense_attention
        y = MultiHeadAttention(
            self.hidden, self.heads, self.dropout_rate, attn, name="attention"
        )(x, bias, deterministic)
        y = nn.Dropout(self.dropout_rate)(y, deterministic=deterministic)
        x = nn.LayerNorm(epsilon=1e-12, name="ln_attn")(x + y)
        y = MoEFFN(
            self.hidden, self.ff, self.num_experts, self.capacity_factor,
            self.router_noise, self.top_k, name="moe",
        )(x, train=not deterministic)
        y = nn.Dropout(self.dropout_rate)(y, deterministic=deterministic)
        return nn.LayerNorm(epsilon=1e-12, name="ln_ff")(x + y)
