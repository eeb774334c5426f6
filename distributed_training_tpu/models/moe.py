"""Mixture-of-Experts layer with expert parallelism.

The reference only *parses* a MoE surface — ``--moe``, ``--ep-world-size``,
``--num-experts``, ``--mlp-type {standard,residual}``, ``--top-k``,
``--min-capacity``, ``--noisy-gate-policy {None,RSample,Jitter}``,
``--moe-param-group`` (``resnet/deepspeed/deepspeed_train.py:61-106``) — and
never wires any of it into its plain ResNet (``:223``). Here the same knobs
drive a real GShard-style MoE:

TPU-first design decisions:

- **Static capacity, one-hot dispatch.** Token routing is expressed as two
  dense einsum contractions (dispatch: ``[tokens, E, C] × [tokens, d]``;
  combine: transpose thereof) instead of gather/scatter — static shapes, no
  dynamic slicing, everything tiles onto the MXU. Tokens over capacity are
  dropped (standard GShard semantics); the load-balancing auxiliary loss
  keeps drops rare.
- **Expert parallelism = sharding annotation.** The expert dimension of the
  per-expert weights and of the dispatched activations carries a sharding
  constraint on the ``expert`` mesh axis; GSPMD materializes the all-to-all
  that moves token blocks to their expert's chip. No hand-written
  ``ragged_all_to_all``: ICI-scheduled collectives come from the partitioner.
- **Gate math in fp32.** Softmax/argmax over expert logits is precision-
  critical; compute dtype may be bf16 but gating runs fp32.

Noisy gate policies (DeepSpeed names):
- ``RSample``: add standard-normal noise to the router logits (training
  only) — the sampled-softmax exploration used for top-1 gates.
- ``Jitter``: multiply the gate *input* by uniform(1-eps, 1+eps) noise.
"""

from __future__ import annotations

from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from distributed_training_tpu.ops import expert_ffn

AUX_LOSS_COLLECTION = "aux_loss"


def _expert_sharding_constraint(x: jnp.ndarray, expert_axis: str | None,
                                expert_dim: int):
    """Annotate the expert dimension of ``x`` as sharded over ``expert_axis``."""
    if expert_axis is None:
        return x
    spec = [None] * x.ndim
    spec[expert_dim] = expert_axis
    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except (ValueError, RuntimeError):
        # No mesh in scope (e.g. plain eager init) — constraint is advisory.
        return x


class TopKGate(nn.Module):
    """Top-k router with static capacity and load-balancing loss.

    Returns (combine_weights [T, E, C], dispatch_mask [T, E, C], aux_loss).
    """

    num_experts: int
    top_k: int = 1
    capacity_factor: float = 1.25
    min_capacity: int = 0
    noisy_gate_policy: str | None = None
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = True):
        if self.top_k not in (1, 2):
            raise ValueError("gating top 1 and 2 supported")  # DS parity
        tokens, d = x.shape
        e = self.num_experts
        capacity = max(
            int(self.min_capacity),
            -(-tokens * self.top_k * int(self.capacity_factor * 100) // (e * 100)),
        )
        capacity = min(max(capacity, 1), tokens)

        gate_in = x.astype(jnp.float32)
        if train and self.noisy_gate_policy == "Jitter":
            eps = 1e-2
            noise = jax.random.uniform(
                self.make_rng("gate"), gate_in.shape,
                minval=1.0 - eps, maxval=1.0 + eps)
            gate_in = gate_in * noise

        logits = nn.Dense(
            e, use_bias=False, dtype=jnp.float32, param_dtype=jnp.float32,
            name="router")(gate_in)
        if train and self.noisy_gate_policy == "RSample":
            logits = logits + jax.random.normal(
                self.make_rng("gate"), logits.shape)

        probs = jax.nn.softmax(logits, axis=-1)  # [T, E]

        combine = jnp.zeros((tokens, e, capacity), jnp.float32)
        dispatch = jnp.zeros((tokens, e, capacity), jnp.bool_)
        remaining = probs
        # Cumulative per-expert slot occupancy across the k rounds, so the
        # 2nd choice lands in the slots the 1st left free.
        occupancy = jnp.zeros((e,), jnp.int32)
        importance = probs.sum(axis=0)

        top1_idx = None
        for _ in range(self.top_k):
            idx = jnp.argmax(remaining, axis=-1)                # [T]
            if top1_idx is None:
                top1_idx = idx
            onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # [T, E]
            # Position of each token within its expert's queue this round:
            # running count of earlier tokens routed to the same expert.
            pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot   # [T, E]
            slot = (pos + occupancy[None, :]).astype(jnp.int32)
            in_cap = (slot < capacity) & (onehot > 0)
            gate_val = (remaining * onehot).sum(axis=-1)        # [T]
            slot_onehot = jax.nn.one_hot(
                (slot * onehot).sum(axis=-1).astype(jnp.int32), capacity,
                dtype=jnp.float32)                              # [T, C]
            keep = in_cap.any(axis=-1)
            contrib = (onehot[:, :, None] * slot_onehot[:, None, :]
                       * keep[:, None, None])
            combine = combine + gate_val[:, None, None] * contrib
            dispatch = dispatch | (contrib > 0)
            occupancy = occupancy + (onehot * in_cap).sum(axis=0).astype(jnp.int32)
            remaining = remaining * (1.0 - onehot)

        # top-1 (Switch): combine weight IS the router probability — scaling
        # the expert output by it is the router's gradient path; renormalizing
        # to 1 would starve the router of gradient. top-2 (GShard):
        # renormalize the two winners' probabilities to sum to 1.
        if self.top_k > 1:
            denom = combine.sum(axis=(1, 2), keepdims=True)
            combine = jnp.where(
                denom > 0, combine / jnp.maximum(denom, 1e-9), 0.0)

        # Shazeer load-balancing loss: E · ⟨fraction routed⟩ · ⟨router prob⟩.
        top1_onehot = jax.nn.one_hot(top1_idx, e, dtype=jnp.float32)
        load = top1_onehot.mean(axis=0)
        aux = e * jnp.sum(load * (importance / tokens))

        return combine.astype(self.dtype), dispatch, aux


class ExpertMlp(nn.Module):
    """E parallel FFNs as single batched einsums (expert dim sharded)."""

    num_experts: int
    hidden_dim: int
    expert_axis: str | None = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        # x: [E, C, d]
        e, c, d = x.shape
        w1 = self.param(
            "w1", nn.initializers.lecun_normal(),
            (self.num_experts, d, self.hidden_dim), self.param_dtype)
        b1 = self.param("b1", nn.initializers.zeros,
                        (self.num_experts, 1, self.hidden_dim), self.param_dtype)
        w2 = self.param(
            "w2", nn.initializers.lecun_normal(),
            (self.num_experts, self.hidden_dim, d), self.param_dtype)
        b2 = self.param("b2", nn.initializers.zeros,
                        (self.num_experts, 1, d), self.param_dtype)
        w1 = _expert_sharding_constraint(w1, self.expert_axis, 0)
        w2 = _expert_sharding_constraint(w2, self.expert_axis, 0)
        x = x.astype(self.dtype)
        h = jnp.einsum("ecd,edh->ech", x, w1.astype(self.dtype))
        h = h + b1.astype(self.dtype)
        h = nn.gelu(h)
        out = jnp.einsum("ech,ehd->ecd", h, w2.astype(self.dtype))
        return out + b2.astype(self.dtype)


class MoEMlp(nn.Module):
    """GShard-style MoE FFN block (optionally residual, DS ``--mlp-type``).

    Input [..., d] → routed through ``num_experts`` FFNs → [..., d].
    The auxiliary load-balancing loss is sown into the ``aux_loss``
    collection; the train step adds it to the objective.
    """

    num_experts: int
    hidden_dim: int
    top_k: int = 1
    capacity_factor: float = 1.25
    min_capacity: int = 0
    noisy_gate_policy: str | None = None
    mlp_type: str = "standard"  # standard | residual
    expert_axis: str | None = None
    aux_loss_weight: float = 1e-2
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = True):
        if self.mlp_type not in ("standard", "residual"):
            raise ValueError("accepts [standard, residual]")  # DS parity
        orig_shape = x.shape
        d = orig_shape[-1]
        tokens = x.reshape(-1, d)

        combine, dispatch, aux = TopKGate(
            num_experts=self.num_experts,
            top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            min_capacity=self.min_capacity,
            noisy_gate_policy=self.noisy_gate_policy,
            dtype=jnp.float32,
            name="gate",
        )(tokens, train=train)
        # Default sow semantics append each block's contribution to a tuple;
        # the train step sums all leaves of the collection.
        self.sow(AUX_LOSS_COLLECTION, "load_balancing",
                 self.aux_loss_weight * aux)

        # Dispatch: [T,E,C] × [T,d] → [E,C,d]; the all-to-all to expert
        # shards is GSPMD's job via the expert-dim constraint.
        expert_in = jnp.einsum(
            "tec,td->ecd", dispatch.astype(self.dtype),
            tokens.astype(self.dtype))
        expert_in = _expert_sharding_constraint(expert_in, self.expert_axis, 0)
        expert_out = ExpertMlp(
            num_experts=self.num_experts,
            hidden_dim=self.hidden_dim,
            expert_axis=self.expert_axis,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="experts",
        )(expert_in)
        expert_out = _expert_sharding_constraint(expert_out, self.expert_axis, 0)
        out = jnp.einsum(
            "tec,ecd->td", combine.astype(self.dtype), expert_out)

        if self.mlp_type == "residual":
            # DeepSpeed residual MoE: dense MLP path + coefficient-mixed
            # expert path.
            dense = nn.Dense(self.hidden_dim, dtype=self.dtype,
                             param_dtype=self.param_dtype, name="residual_in")(
                tokens.astype(self.dtype))
            dense = nn.gelu(dense)
            dense = nn.Dense(d, dtype=self.dtype,
                             param_dtype=self.param_dtype,
                             name="residual_out")(dense)
            coef = nn.Dense(2, dtype=jnp.float32, param_dtype=jnp.float32,
                            name="coefficient")(tokens.astype(jnp.float32))
            coef = jax.nn.softmax(coef, axis=-1)
            out = (out * coef[:, :1].astype(self.dtype)
                   + dense * coef[:, 1:].astype(self.dtype))

        return out.reshape(orig_shape)


def grouped_sigmoid_route(logits, bias, *, n_group: int, topk_group: int,
                          top_k: int, scale: float):
    """Dropless top-k routing by sigmoid scores with a selection bias and
    groups (the DeepSeek-V3 family's router), in float32.

    ``logits`` [T, E] are the router's outputs, ``bias`` [E] the learned
    selection bias. ``s = sigmoid(logits)``; selection runs on ``s + bias``:
    the experts are cut into ``n_group`` equal groups, a group scores the
    sum of its two highest entries, the ``topk_group`` best groups are
    kept, and among their experts the ``top_k`` highest are taken. The
    weights are ``s`` (without the bias) of the chosen, divided by their
    sum, times ``scale``. Returns ``(experts int32 [T, top_k], weights
    float32 [T, top_k])``. Ties go to the lower index, as ``lax.top_k``
    breaks them."""
    t, e = logits.shape
    if e % n_group:
        raise ValueError(f"{n_group} groups do not divide {e} experts")
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    sel = s + bias.astype(jnp.float32)
    grouped = sel.reshape(t, n_group, e // n_group)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)         # [T, G]
    kept = jax.lax.top_k(group_score, topk_group)[1]           # [T, topk_group]
    keep = jnp.zeros((t, n_group), bool).at[
        jnp.arange(t)[:, None], kept].set(True)
    masked = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(t, e)
    experts = jax.lax.top_k(masked, top_k)[1]
    w = jnp.take_along_axis(s, experts, axis=-1)
    w = w / w.sum(-1, keepdims=True) * scale
    return experts.astype(jnp.int32), w


def softmax_topk_route(logits, *, top_k: int):
    """Dropless top-k routing by softmax probabilities, renormalised (the
    Qwen3-MoE family's router with ``norm_topk_prob``), in float32.

    ``p = softmax(logits)`` over all experts; the ``top_k`` highest are
    taken (ties to the lower index, as ``lax.top_k`` breaks them) and their
    weights are ``p_i`` over the sum of the chosen: no bias, no groups, no
    scale. Returns ``(experts int32 [T, top_k], weights float32 [T,
    top_k])``."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, experts = jax.lax.top_k(p, top_k)
    return experts.astype(jnp.int32), w / w.sum(-1, keepdims=True)


def expert_lane(t: int, d: int, hidden: int, top_k: int, num_experts: int,
                dtype) -> str:
    """How :class:`HeldExpertsMlp` computes a pass of ``t`` tokens:
    ``kernel`` (``ops/expert_ffn.py``). A pass whose ``x`` and sum do not
    fit the kernel's VMEM beside its weight tiles is refused."""
    if not expert_ffn.kernel_fits(t, d, hidden, top_k, num_experts, dtype):
        raise ValueError(
            f"a pass of {t} tokens of width {d} does not fit the expert "
            f"kernel's VMEM ({expert_ffn.VMEM_BUDGET >> 20} MiB): pass fewer "
            "tokens at a time (a smaller prefill_chunk)")
    return "kernel"


def model_expert_lane(model, rows: int) -> str:
    """:func:`expert_lane` of a language model's held experts (its
    ``hidden_dim``, ``expert_dim``, ``experts_per_token``, ``num_experts``
    and ``dtype``) at a pass of ``rows`` tokens: the ``expert_lane``
    method of the models that have them."""
    return expert_lane(rows, model.hidden_dim, model.expert_dim,
                       model.experts_per_token, model.num_experts,
                       model.dtype)


def _silu_ffn(x, w1, w3, w2):
    """``(SiLU(x w1) * (x w3)) w2`` in ``x``'s type."""
    h = jax.nn.silu(jnp.dot(x, w1)) * jnp.dot(x, w3)
    return jnp.dot(h, w2)


class GatedMlp(nn.Module):
    """Bias-free gated SiLU FFN: ``(SiLU(x W1) * x W3) W2``."""

    hidden_dim: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        init = nn.initializers.normal(0.02)
        w1 = self.param("w1", init, (d, self.hidden_dim))
        w3 = self.param("w3", init, (d, self.hidden_dim))
        w2 = self.param("w2", init, (self.hidden_dim, d))
        return _silu_ffn(x.astype(self.dtype), w1.astype(self.dtype),
                         w3.astype(self.dtype), w2.astype(self.dtype))


class HeldExpertsMlp(nn.Module):
    """An expert layer that is told which experts it holds.

    ``held = (first, count)``: of the ``num_experts`` routed experts this
    chip holds ``first .. first + count - 1`` (expert parallelism's share
    of the layer). Every token is routed over ALL ``num_experts``, by the
    router the model's family has (``scoring``: ``"sigmoid"`` is
    :func:`grouped_sigmoid_route` with its selection bias, groups and
    scale; ``"softmax"`` is :func:`softmax_topk_route`, which has none of
    the three and no ``router_bias`` leaf); the layer computes the held
    experts' part of ``sum_i w_i E_i(x)`` for the tokens routed to them,
    adds the shared experts where the model has some (replicated: every
    chip computes them alike), and returns that partial sum. What the
    absent experts would add is left out and nothing stands in for their
    chips or the exchange with them.

    Dropless at static shapes: the (token, expert) pairs that landed on a
    held expert are sorted by expert, and the grouped SwiGLU kernel
    ``ops/expert_ffn.py::expert_ffn`` reads each hit expert's weights once
    and takes its rows ``row_tile`` at a time (an expert that got none is
    not visited and its weights are not read), picking them from ``x`` and
    adding them back under their routing weights inside the kernel.
    ``row_tile`` is :func:`~distributed_training_tpu.ops.expert_ffn.
    row_tile` of the pass's shape unless set; a pass too wide for the
    kernel is refused (:func:`expert_lane`). No capacity, no drop.

    ``valid`` [T] masks tokens that do not exist (padding rows, empty
    decode slots): they are routed nowhere and counted nowhere. Sows, in
    the ``counters`` collection when it is mutable, ``expert_rows`` (pairs
    that landed on held experts), ``expert_rows_max`` (on the busiest of
    them) and ``experts_hit`` (held experts that got at least one pair:
    the experts whose weights the step reads), int32 scalars."""

    num_experts: int
    held: tuple
    hidden_dim: int
    top_k: int
    n_group: int = 1
    topk_group: int = 1
    routed_scale: float = 1.0
    shared_experts: int = 1
    scoring: str = "sigmoid"
    row_tile: int | None = None
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, valid=None):
        shape = x.shape
        d = shape[-1]
        x = x.reshape(-1, d).astype(self.dtype)
        t = x.shape[0]
        first, count = (int(v) for v in self.held)
        if not (0 <= first and first + count <= self.num_experts):
            raise ValueError(f"held {self.held} outside the "
                             f"{self.num_experts} routed experts")
        init = nn.initializers.normal(0.02)
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring {self.scoring!r}: sigmoid or softmax")
        w_g = self.param("router", init, (d, self.num_experts))
        if self.scoring == "sigmoid":
            b_g = self.param("router_bias", init, (self.num_experts,))
        w1 = self.param("w1", init, (count, d, self.hidden_dim))
        w3 = self.param("w3", init, (count, d, self.hidden_dim))
        w2 = self.param("w2", init, (count, self.hidden_dim, d))

        with jax.named_scope("moe.route"):
            logits = jnp.dot(x.astype(jnp.float32), w_g.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            if self.scoring == "sigmoid":
                experts, weights = grouped_sigmoid_route(
                    logits, b_g, n_group=self.n_group,
                    topk_group=self.topk_group, top_k=self.top_k,
                    scale=self.routed_scale)
            else:
                experts, weights = softmax_topk_route(logits,
                                                      top_k=self.top_k)
            # Pairs by held expert; pairs routed elsewhere, or of tokens
            # that do not exist, go to the sentinel group ``count``.
            local = experts - first
            here = (local >= 0) & (local < count)
            if valid is not None:
                here &= valid.reshape(-1)[:, None]
            local = jnp.where(here, local, count).reshape(-1)
            rows = (local[:, None] == jnp.arange(count)[None, :]).sum(
                0, dtype=jnp.int32)                            # [count]
            order = jnp.argsort(local, stable=True)
            starts = jnp.cumsum(rows) - rows
            self.sow("counters", "expert_rows", rows.sum(),
                     init_fn=lambda: jnp.zeros((), jnp.int32),
                     reduce_fn=jnp.add)
            self.sow("counters", "expert_rows_max", rows.max(),
                     init_fn=lambda: jnp.zeros((), jnp.int32),
                     reduce_fn=jnp.add)
            self.sow("counters", "experts_hit",
                     (rows > 0).sum(dtype=jnp.int32),
                     init_fn=lambda: jnp.zeros((), jnp.int32),
                     reduce_fn=jnp.add)

        with jax.named_scope("moe.experts"):
            w1, w3, w2 = (w.astype(self.dtype) for w in (w1, w3, w2))
            tile = self.row_tile or expert_ffn.row_tile(
                t, self.top_k, self.num_experts)
            expert_lane(t, d, self.hidden_dim, self.top_k, self.num_experts,
                        self.dtype)
            acc = expert_ffn.expert_ffn(
                x, w1, w3, w2, (order // self.top_k).astype(jnp.int32),
                weights.reshape(-1)[order], rows, starts, row_tile=tile)
            out = acc.astype(self.dtype)
            if self.shared_experts:
                out = out + GatedMlp(
                    self.hidden_dim * self.shared_experts, dtype=self.dtype,
                    name="shared")(x)
        return out.reshape(shape)


class MoEImageClassifier(nn.Module):
    """Small patch-MLP vision model with MoE FFN blocks.

    The vehicle for exercising the MoE/EP surface on the CIFAR workload —
    the reference's flags never touch its model; here ``--moe`` selects this
    architecture (``model='moe_mlp'``).
    """

    num_classes: int = 10
    hidden_size: int = 128
    num_layers: int = 2
    num_experts: Sequence[int] = (4,)
    mlp_hidden: int = 256
    top_k: int = 1
    capacity_factor: float = 1.25
    min_capacity: int = 0
    noisy_gate_policy: str | None = None
    mlp_type: str = "standard"
    expert_axis: str | None = None
    patch_size: int = 4
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    axis_name: str | None = None  # registry uniformity (no BN here)

    @nn.compact
    def __call__(self, x, train: bool = True):
        b = x.shape[0]
        x = x.astype(self.dtype)
        x = nn.Conv(self.hidden_size,
                    (self.patch_size, self.patch_size),
                    strides=(self.patch_size, self.patch_size),
                    padding="VALID", dtype=self.dtype,
                    param_dtype=self.param_dtype, name="patch_embed")(x)
        x = x.reshape(b, -1, self.hidden_size)

        experts_per_layer = list(self.num_experts)
        if len(experts_per_layer) == 1:
            experts_per_layer = experts_per_layer * self.num_layers
        for i in range(self.num_layers):
            y = nn.LayerNorm(dtype=self.dtype)(x)
            n_exp = experts_per_layer[min(i, len(experts_per_layer) - 1)]
            if n_exp > 1:
                y = MoEMlp(
                    num_experts=n_exp,
                    hidden_dim=self.mlp_hidden,
                    top_k=self.top_k,
                    capacity_factor=self.capacity_factor,
                    min_capacity=self.min_capacity,
                    noisy_gate_policy=self.noisy_gate_policy,
                    mlp_type=self.mlp_type,
                    expert_axis=self.expert_axis,
                    dtype=self.dtype,
                    param_dtype=self.param_dtype,
                    name=f"moe_{i}",
                )(y, train=train)
            else:
                y = nn.Dense(self.mlp_hidden, dtype=self.dtype)(y)
                y = nn.gelu(y)
                y = nn.Dense(self.hidden_size, dtype=self.dtype)(y)
            x = x + y

        x = nn.LayerNorm(dtype=self.dtype)(x)
        x = x.mean(axis=1)
        x = nn.Dense(self.num_classes, dtype=self.dtype,
                     param_dtype=self.param_dtype, name="head")(x)
        return x.astype(jnp.float32)


def make_moe_classifier(**kwargs) -> MoEImageClassifier:
    return MoEImageClassifier(**kwargs)
