"""Pipeline parallelism: GPipe-style SPMD pipelining over a ``pipe`` mesh axis.

The reference exercises no pipeline parallelism (SURVEY.md §2.3 "PP:
Absent"); this module is the TPU-native extension alongside TP. GPU
frameworks implement PP as a *runtime scheduler*: per-stage processes,
P2P send/recv of activation tensors, hand-written 1F1B interleaving, and a
separate backward schedule. None of that maps to XLA's single-program model.

The TPU-native formulation is a single SPMD program:

- the transformer's decoder blocks are *stacked* into one pytree with a
  leading layer dimension and sharded over the ``pipe`` axis — each device
  holds a contiguous stage of ``L/S`` layers;
- a ``lax.scan`` over ``M + S - 1`` ticks runs the GPipe schedule: at tick
  ``t`` stage ``s`` processes microbatch ``t - s``; activations hop to the
  next stage with one ``lax.ppermute`` per tick (point-to-point on the ICI
  torus — the XLA analogue of the NCCL send/recv pair);
- the backward pass is not scheduled by hand: differentiating through the
  scan + ppermute yields the reverse pipeline automatically (ppermute's
  transpose is the inverse permutation, so gradients hop backwards through
  the stages in reverse tick order);
- embeddings, final LayerNorm, and the LM head run outside the pipeline as
  ordinary GSPMD-sharded ops, so PP composes freely with the ``data`` axis
  (and, via the TP rule table, with ``model``);
- a ``seq_axis`` model composes too (round 5): the sequence axis joins the
  manual set and each tick's attention rotates K/V around the ring INSIDE
  the stage — activations hop over ``pipe`` between ticks while K/V blocks
  hop over ``sequence`` within one, so long contexts and deep stacks shard
  simultaneously; homogeneous MoE stages (``moe_every=1``) likewise carry
  their expert FFNs with the aux loss collected through the tick scan.

The pipeline bubble is the usual GPipe ``(S-1)/(M+S-1)`` fraction; raise
``num_microbatches`` to amortize it, or ``virtual_stages`` (the
megatron-style interleaved/circular schedule, round 4) to divide the
numerator's weight: each device holds ``v`` non-contiguous layer chunks
(device d owns global chunks d, d+S, ..., d+(v-1)S) and the activation ring
wraps ``v`` times, giving bubble ``(S-1)/(v·M+S-1)``. The tick math stays a
single scan + one ppermute per tick: at local time ``u = t - d`` a device
runs local chunk ``(u // S) % v`` on microbatch ``(u // (v·S))·S + u % S``,
and every activation is consumed by the ring neighbor exactly one tick
after it is produced — including the wrap from the last device back to the
first, whose +S chunk offset cancels the -(S-1) device offset.
``virtual_stages=1`` degenerates to exactly GPipe.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_training_tpu.runtime.mesh import AXIS_DATA, AXIS_PIPE
from distributed_training_tpu.utils.compat import shard_map


def circular_layer_order(num_layers: int, stages: int,
                         virtual_stages: int) -> list[int]:
    """Storage order of layers for the circular schedule.

    The stacked dim is sharded P(pipe) in CONTIGUOUS slices, so device d's
    slice must contain its chunk set {d, d+S, ..., d+(v-1)S} in execution
    order: storage row ``d·(L/S) + ℓ·(L/C) + j`` holds layer
    ``(ℓ·S + d)·(L/C) + j`` (C = S·v chunks of L/C layers). v=1 is the
    identity (GPipe layout).
    """
    c = stages * virtual_stages
    per_chunk = num_layers // c
    order = []
    for d in range(stages):
        for ell in range(virtual_stages):
            g = ell * stages + d
            order.extend(range(g * per_chunk, (g + 1) * per_chunk))
    return order


def stack_block_params(params: dict, num_layers: int, prefix: str = "block",
                       layer_order: list[int] | None = None):
    """Split model params into (stacked decoder blocks, everything else).

    The per-layer trees ``params['block0'] .. params['block{L-1}']`` are
    congruent, so they stack leaf-wise into one tree with a leading layer
    dim — the representation the ``pipe`` axis shards (stage = a contiguous
    slice of layers). ``layer_order`` permutes the stacking (storage row i
    holds layer ``layer_order[i]``) — the circular schedule's strided
    chunk-to-device assignment rides the same contiguous P(pipe) sharding.
    """
    order = layer_order if layer_order is not None else range(num_layers)
    blocks = [params[f"{prefix}{i}"] for i in order]
    rest = {k: v for k, v in params.items()
            if not (k.startswith(prefix) and k[len(prefix):].isdigit())}
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
    return stacked, rest


def unstack_block_params(stacked, rest: dict, prefix: str = "block",
                         layer_order: list[int] | None = None) -> dict:
    """Inverse of :func:`stack_block_params` (checkpoint interop)."""
    num_layers = jax.tree.leaves(stacked)[0].shape[0]
    order = list(layer_order) if layer_order is not None \
        else list(range(num_layers))
    out = dict(rest)
    for i in range(num_layers):
        out[f"{prefix}{order[i]}"] = jax.tree.map(lambda x: x[i], stacked)
    return out


def spmd_pipeline(
    stage_fn: Callable[..., jnp.ndarray],
    stage_params: Any,
    x: jnp.ndarray,
    *,
    axis_name: str = AXIS_PIPE,
    num_microbatches: int,
    rng: jax.Array | None = None,
    virtual_stages: int = 1,
    with_aux: bool = False,
) -> jnp.ndarray:
    """Run ``x`` through the S-stage pipeline. Call inside ``shard_map``.

    Args:
      stage_fn: ``(stage_params, chunk, x_mb) -> y_mb`` applying local
        chunk ``chunk`` (a traced int32 in [0, virtual_stages)) of this
        device's layers to one microbatch (shape-preserving); with ``rng``
        set it is called as ``(stage_params, chunk, x_mb, mb_rng)`` where
        ``mb_rng`` is unique per (microbatch, global chunk) — fold in the
        layer index inside. With ``with_aux`` it returns ``(y_mb, aux)``
        (a scalar per application, e.g. the MoE load-balancing loss of
        this chunk's layers on this microbatch).
      stage_params: this device's stage shard (leading dim = L/S layers,
        laid out in local-chunk execution order — see
        :func:`circular_layer_order`).
      x: [B_local, ...] the full local batch of pipeline inputs.
      num_microbatches: M; B_local must divide by it.
      rng: optional dropout key threaded through the schedule.
      virtual_stages: v; 1 = GPipe, >1 = the interleaved/circular schedule
        (bubble ``(S-1)/(v·M+S-1)``). M must divide by S when v > 1 (the
        schedule moves microbatches in groups of S between chunk switches).

    Returns [B_local, ...] outputs, replicated over the pipe axis (the last
    stage's results are psum-broadcast so downstream unsharded ops — final
    LN, LM head — read them on every rank). With ``with_aux``:
    ``(outputs, aux)`` where aux = Σ_layers mean_microbatches(stage aux) —
    live ticks only (warmup/drain garbage is masked), psum'd over the pipe
    axis so every rank holds the full-depth value.
    """
    s = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    m = num_microbatches
    v = virtual_stages
    b = x.shape[0]
    if b % m:
        raise ValueError(f"local batch {b} not divisible by microbatches {m}")
    if v > 1 and m % s:
        # Silently violating this would zero the trailing microbatches'
        # outputs (their final-chunk ticks fall past the scan).
        raise ValueError(
            f"the circular schedule moves microbatches in groups of the "
            f"pipe size; num_microbatches {m} must divide by {s}")
    mb = x.reshape(m, b // m, *x.shape[1:])
    perm = [(j, (j + 1) % s) for j in range(s)]

    def tick(carry, t):
        recv, outputs, aux_sum = carry
        # Local schedule: device idx at tick t works local time u = t - idx
        # (valid when 0 <= u < v*m), running local chunk (u // S) % v on
        # microbatch (u // (v*S))*S + u % S. Clipped indices make warmup/
        # drain ticks well-defined (their results are masked); v == 1
        # degenerates to chunk 0 / microbatch u — exactly GPipe.
        u = t - idx
        chunk = (jnp.maximum(u, 0) // s) % v
        mu = jnp.clip((u // (v * s)) * s + u % s, 0, m - 1)
        # The first device feeds fresh microbatches only at its chunk-0
        # slots; every other slot consumes the ring (for the wrap, device
        # S-1's chunk ℓ output arrives as device 0's chunk ℓ+1 input one
        # tick later). Warmup ticks (u < 0) never write output, so their
        # garbage compute is masked.
        feed = (idx == 0) & (chunk == 0)
        inp = jnp.where(
            feed,
            lax.dynamic_index_in_dim(mb, mu, 0, keepdims=False),
            recv)
        # Global chunk = chunk*S + idx; folding (microbatch, global chunk)
        # decorrelates dropout across both without depending on ticks.
        if rng is None:
            res = stage_fn(stage_params, chunk, inp)
        else:
            mb_rng = jax.random.fold_in(rng, mu * (v * s) + chunk * s + idx)
            res = stage_fn(stage_params, chunk, inp, mb_rng)
        if with_aux:
            out, aux = res
            # Live ticks only: warmup/drain run garbage through the stage
            # (their OUTPUT writes are masked below) and must not pollute
            # the aux accumulator either.
            live_tick = (u >= 0) & (u < v * m)
            aux_sum = aux_sum + jnp.where(live_tick, aux, 0.0)
        else:
            out = res
        # The last device's last local chunk is global chunk C-1: its
        # output for microbatch mu is final. It runs at u = (mu//S)*v*S
        # + (v-1)*S + mu%S, i.e. any valid u with chunk == v-1.
        done = (idx == s - 1) & (chunk == v - 1) & (u >= 0) & (u < v * m)
        written = lax.dynamic_update_index_in_dim(outputs, out, mu, 0)
        outputs = jnp.where(done, written, outputs)
        return (lax.ppermute(out, axis_name, perm), outputs, aux_sum), None

    init = (jnp.zeros_like(mb[0]), jnp.zeros_like(mb), jnp.float32(0))
    (_, outputs, aux_sum), _ = lax.scan(tick, init, jnp.arange(v * m + s - 1))
    # Only the last stage holds real outputs; broadcast them to every pipe
    # rank (psum of a one-hot-by-rank value == broadcast from that rank).
    outputs = lax.psum(
        jnp.where(idx == s - 1, outputs, jnp.zeros_like(outputs)), axis_name)
    outputs = outputs.reshape(b, *x.shape[1:])
    if not with_aux:
        return outputs
    # Each device summed its own chunks' aux over all live (chunk, mb)
    # slots; the pipe psum completes the layer sum, and /m turns the
    # microbatch sum into the mean (the full-batch estimator — exact at
    # m == 1, the mean of per-microbatch load-balance terms otherwise).
    return outputs, lax.psum(aux_sum, axis_name) / m


def pp_tree_shardings(tree: Any, mesh: Mesh, *, tp: bool = False,
                      extra_axes: tuple = (),
                      memory_kind: str | None = None) -> Any:
    """Shardings for any tree congruent with PP params (incl. Adam moments):
    leaves under a ``blocks`` key shard their leading (layer) dim over
    ``pipe``; everything else is replicated. The match is on an exact path
    component (not a substring), so e.g. a ``res_blocks`` module is not
    accidentally pipe-sharded.

    ``tp=True`` composes the megatron rule table on top: block leaves get
    ``P(pipe, *tp_spec)`` (the stacking dim shifts the TP dims right by
    one), and the out-of-pipeline leaves (vocab-parallel ``tok_embed`` /
    ``lm_head``) take their TP spec directly — each pipeline stage then
    holds only its ``1/tp`` slice of its layers' weights.

    ``extra_axes`` recruits data(/fsdp) on a dim the pipe/TP specs left
    free, via the shared ZeRO placement rule — PP×ZeRO-1: each data
    replica of a pipeline stage owns a slice of that stage's optimizer
    state, exactly as DeepSpeed partitions ZeRO within pipeline stages.
    """
    from distributed_training_tpu.parallel.sharding import zero_leaf_sharding
    from distributed_training_tpu.parallel.tensor_parallel import (
        tp_spec_for_path,
    )
    from distributed_training_tpu.utils.tree import path_keys, path_str

    def leaf(path, x):
        if "blocks" in path_keys(path) and getattr(x, "ndim", 0) >= 1:
            spec = P(AXIS_PIPE)
            if tp:
                tp_spec = tp_spec_for_path(path_str(path))
                if len(tp_spec) == getattr(x, "ndim", 0) - 1:
                    spec = P(AXIS_PIPE, *tp_spec)
        elif tp:
            spec = tp_spec_for_path(path_str(path))
        else:
            spec = P()
        if extra_axes:
            return zero_leaf_sharding(x, mesh, extra_axes, base=spec,
                                      memory_kind=memory_kind)
        kw = {"memory_kind": memory_kind} if memory_kind else {}
        return NamedSharding(mesh, spec, **kw)

    return jax.tree_util.tree_map_with_path(leaf, tree)


class PipelinedLM:
    """A TransformerLM executed with its decoder blocks pipelined.

    Wraps an existing :class:`~distributed_training_tpu.models.gpt.TransformerLM`
    (same init, same math — the blocks run through the module's own
    ``DecoderBlock.apply``), re-homing the per-layer params into the stacked
    layout and the layer loop into :func:`spmd_pipeline`. ``apply_fn``
    mirrors the flax signature used by the train steps, so TrainState,
    ``commit_gradients`` and the LM metrics helpers all work unchanged.
    """

    def __init__(self, model, mesh: Mesh, *, num_microbatches: int,
                 virtual_stages: int = 1):
        from distributed_training_tpu.models.gpt import (
            DecoderBlock,
            moe_layer_experts,
        )

        self.model = model
        self.mesh = mesh
        mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        # SP×PP (round 5): a seq_axis model composes — each pipeline tick
        # runs ring attention over the (manual) sequence axis inside the
        # stage, so a microbatch's K/V blocks rotate over ``sequence``
        # while its activations hop over ``pipe``. The axis must exist on
        # the mesh (an unbound ring axis raises deep inside the kernel
        # with no actionable message).
        self.seq_size = mesh_shape.get(model.seq_axis, 1) \
            if model.seq_axis else 1
        if model.seq_axis is not None and self.seq_size <= 1:
            raise ValueError(
                f"model.seq_axis={model.seq_axis!r} needs that mesh axis "
                f"sized > 1 (got mesh {mesh_shape}); build the model with "
                "seq_axis=None for the plain pipeline")
        self.num_microbatches = num_microbatches
        self.virtual_stages = virtual_stages
        # MoE stages (round 5): the stacked-layer scan requires CONGRUENT
        # per-layer param trees, so the pipeline carries MoE only in the
        # homogeneous layout — EVERY layer an MoE block with ONE expert
        # count (moe_every=1, single count). The alternating GShard layout
        # stays refused with the DeepSpeed citation (its PipelineModule
        # cannot carry MoE layers at all; this engine goes one step
        # further than that parity bar by composing the uniform case).
        moe_kwargs = {}
        self.moe = bool(model.moe_num_experts)
        if self.moe:
            layer_map = moe_layer_experts(
                model.num_layers, model.moe_every, model.moe_num_experts)
            counts = set(layer_map.values())
            if len(layer_map) != model.num_layers or len(counts) != 1:
                raise NotImplementedError(
                    "the pipeline strategy stacks congruent decoder blocks; "
                    "MoE composes only in the homogeneous layout "
                    "(moe_every=1, one expert count for every layer) — got "
                    f"MoE layers {sorted(layer_map)} of {model.num_layers} "
                    f"with counts {sorted(counts)}. DeepSpeed's "
                    "PipelineModule cannot carry MoE layers at all; use "
                    "the tensor/dp or sequence strategies for alternating "
                    "or per-layer-count MoE")
            moe_kwargs = dict(
                moe_num_experts=counts.pop(),
                moe_top_k=model.moe_top_k,
                moe_capacity_factor=model.moe_capacity_factor,
                moe_min_capacity=model.moe_min_capacity,
                moe_noisy_gate_policy=model.moe_noisy_gate_policy,
                moe_mlp_type=model.moe_mlp_type,
                moe_expert_axis=model.moe_expert_axis,
            )
        self.block = DecoderBlock(
            num_heads=model.num_heads,
            mlp_dim=model.mlp_ratio * model.hidden_dim,
            dtype=model.dtype,
            seq_axis=model.seq_axis,
            dropout_rate=model.dropout_rate,
            attn_impl=model.attn_impl,
            name=None,
            **moe_kwargs)
        self.pipe_size = mesh_shape.get(AXIS_PIPE, 1)
        # TP composition: a model axis > 1 shards each stage's weights by
        # the megatron rule table; the pipeline shard_map is partial-manual
        # over (pipe, data) so GSPMD inserts the model-axis psums inside
        # each stage's compute.
        self.tp_size = mesh_shape.get("model", 1)
        if virtual_stages < 1:
            raise ValueError(f"virtual_stages must be >= 1, got "
                             f"{virtual_stages}")
        if model.num_layers % max(self.pipe_size * virtual_stages, 1):
            raise ValueError(
                f"{model.num_layers} layers not divisible into "
                f"{self.pipe_size} stages x {virtual_stages} virtual chunks")
        if virtual_stages > 1 and num_microbatches % max(self.pipe_size, 1):
            raise ValueError(
                f"the circular schedule moves microbatches in groups of the "
                f"pipe size; num_microbatches {num_microbatches} must divide "
                f"by {self.pipe_size}")
        self.layer_order = circular_layer_order(
            model.num_layers, max(self.pipe_size, 1), virtual_stages)

    @property
    def bubble_fraction(self) -> float:
        """Idle fraction of the pipeline schedule: (S-1)/(v·M+S-1)."""
        s = max(self.pipe_size, 1)
        return (s - 1) / (self.virtual_stages * self.num_microbatches + s - 1)

    def init_params(self, rng: jax.Array) -> dict:
        """Init via the wrapped model, then stack the blocks (in circular
        storage order when virtual_stages > 1)."""
        dummy = jnp.zeros((1, 8), jnp.int32)
        variables = self.model.init({"params": rng}, dummy, train=False)
        stacked, rest = stack_block_params(
            dict(variables["params"]), self.model.num_layers,
            layer_order=self.layer_order)
        return {"blocks": stacked, **rest}

    def param_shardings(self, params: dict) -> dict:
        """Blocks sharded over ``pipe`` on the layer dim; rest replicated
        (or megatron-TP-sharded when the mesh has a model axis)."""
        return pp_tree_shardings(params, self.mesh,
                                 tp=self.tp_size > 1 or self.moe)

    def _make_stage_fn(self, train: bool):
        moe = self.moe

        def run_layer(p, h, r):
            # Dropout keeps the RAW per-layer key (bit-reproducible with
            # pre-round-5 runs); only the new gate stream folds.
            rngs = {}
            if self.model.dropout_rate:
                rngs["dropout"] = r
            if moe and self.model.moe_noisy_gate_policy:
                rngs["gate"] = jax.random.fold_in(r, 1)
            if moe:
                # The MoE FFN sows its load-balancing term; collect it per
                # layer (the plain flax path gathers the same collection
                # at the model level, models/gpt.py).
                h, mut = self.block.apply(
                    {"params": p}, h, train, False, rngs=rngs or None,
                    mutable=["aux_loss"])
                aux = sum(jax.tree.leaves(dict(mut).get("aux_loss", {})),
                          jnp.float32(0))
                return h, aux
            return self.block.apply({"params": p}, h, train, False,
                                    rngs=rngs or None), jnp.float32(0)
        if self.model.remat:
            # Activation checkpointing per layer: the pipeline scan already
            # recomputes nothing across ticks, so remat here trades each
            # layer's internals for its input — the same lever as the plain
            # model's nn.remat(DecoderBlock).
            run_layer = jax.checkpoint(run_layer)

        v = self.virtual_stages

        def stage_fn(stage_params, chunk, x, mb_rng=None):
            n_rows = jax.tree.leaves(stage_params)[0].shape[0]
            per_chunk = n_rows // v
            # Local chunk ``chunk`` (traced) = rows [chunk*per_chunk, ...)
            # of this device's slice (execution order by construction of
            # circular_layer_order).
            chunk_params = jax.tree.map(
                lambda p: lax.dynamic_slice_in_dim(
                    p, chunk * per_chunk, per_chunk, 0),
                stage_params) if v > 1 else stage_params

            def layer(carry, args):
                h, aux = carry
                p, li = args
                r = (jax.random.fold_in(mb_rng, li)
                     if mb_rng is not None else jax.random.PRNGKey(0))
                h, a = run_layer(p, h, r)
                return (h, aux + a), None

            (h, aux), _ = lax.scan(layer, (x, jnp.float32(0)),
                                   (chunk_params, jnp.arange(per_chunk)))
            return (h, aux) if moe else h

        return stage_fn

    def apply_fn(self, variables, tokens, positions=None, train=False,
                 rngs=None, mutable=(), return_hidden=False):
        """Flax-shaped apply: embeddings/LN/head as plain GSPMD ops (module
        configs single-sourced from ``models/gpt.py`` factories), blocks
        through the shard_map pipeline. ``rngs={'dropout': key}`` threads
        dropout through the stage scan (unique fold per microbatch × stage
        × layer); ``return_hidden=True`` returns the final-norm hidden
        states for chunked CE (mirrors ``TransformerLM.__call__``)."""
        from distributed_training_tpu.models.gpt import (
            add_pos_embed,
            make_final_norm,
            make_lm_head,
            make_tok_embed,
        )

        params = variables["params"]
        m = self.model
        # The MoE stage sows its aux loss; mirror flax's mutable protocol
        # (True, a bare collection name, or a sequence of names) so the
        # train steps' ``(out, mutated)`` handling works unchanged.
        if mutable is True:
            want_aux = self.moe
        elif isinstance(mutable, str):
            want_aux = self.moe and mutable == "aux_loss"
        else:
            want_aux = self.moe and "aux_loss" in tuple(mutable)
        if tokens.shape[-1] > m.max_len:
            raise ValueError(
                f"sequence length {tokens.shape[-1]} exceeds "
                f"max_len={m.max_len}")
        if positions is None:
            positions = jnp.arange(tokens.shape[-1])[None, :]
        dropout_rng = None
        need_rng = train and (m.dropout_rate
                              or (self.moe and m.moe_noisy_gate_policy))
        if need_rng:
            if not rngs or "dropout" not in rngs:
                raise ValueError(
                    "dropout_rate / a noisy gate policy is set; pass "
                    "rngs={'dropout': key}")
            dropout_rng = rngs["dropout"]

        x = make_tok_embed(m).apply({"params": params["tok_embed"]}, tokens)
        x = add_pos_embed(m, params["pos_embed"], x, positions)

        # Partial-manual over (pipe, data) when TP is in play: the
        # scan/ppermute schedule is explicit, while the model-axis (TP)
        # sharding of the stage weights stays automatic — GSPMD inserts the
        # megatron psums inside each stage_fn call. Without a model axis,
        # full-manual is identical and keeps old-jax compatibility. With a
        # seq_axis model the sequence axis is ALSO manual (the ring
        # rotates K/V over it inside each stage) and x shards on dim 1.
        seq = m.seq_axis if self.seq_size > 1 else None
        x_spec = P(AXIS_DATA, seq, None)
        in_specs = [jax.tree.map(lambda _: P(AXIS_PIPE), params["blocks"]),
                    x_spec]
        args = [params["blocks"], x]
        if dropout_rng is not None:
            in_specs.append(P())
            args.append(dropout_rng)

        def run(blocks, x, *rng_arg):
            rng = rng_arg[0] if rng_arg else None
            if rng is not None:
                # Decorrelate dropout across data shards (each holds
                # different batch rows but would otherwise draw the same
                # local-shape masks from the replicated key).
                rng = jax.random.fold_in(rng, lax.axis_index(AXIS_DATA))
                if seq is not None:
                    # ...and across sequence shards (different positions).
                    rng = jax.random.fold_in(rng, lax.axis_index(seq))
            out = spmd_pipeline(
                self._make_stage_fn(train), blocks, x,
                num_microbatches=self.num_microbatches, rng=rng,
                virtual_stages=self.virtual_stages, with_aux=self.moe)
            if self.moe:
                y, aux = out
                # Shard-local aux covers this data(/sequence) shard's
                # tokens; the mean over those axes matches the plain
                # model's full-batch value (equal shard sizes by
                # construction).
                axes = (AXIS_DATA,) + ((seq,) if seq else ())
                return y, lax.pmean(aux, axes)
            return out

        # Partial-manual also for MoE stages (expert stays automatic, so
        # GSPMD inserts the dispatch/combine collectives and honors the
        # expert-dim sharding constraints inside the stage, exactly as the
        # model axis composes for TP) and for SP×PP (sequence is manual —
        # the ring's ppermutes — alongside pipe/data).
        partial_manual = self.tp_size > 1 or self.moe or seq is not None
        out_specs = (x_spec, P()) if self.moe else x_spec
        manual_axes = (AXIS_PIPE, AXIS_DATA) + ((seq,) if seq else ())
        pipeline = shard_map(
            run, self.mesh,
            in_specs=tuple(in_specs),
            out_specs=out_specs,
            axis_names=manual_axes if partial_manual else None,
        )
        out = pipeline(*args)
        x, aux = out if self.moe else (out, None)

        x = make_final_norm(m).apply({"params": params["ln_f"]}, x)
        out = (x if return_hidden
               else make_lm_head(m).apply({"params": params["lm_head"]}, x))
        if want_aux:
            return out, {"aux_loss": {"pipeline": (aux,)}}
        return out
