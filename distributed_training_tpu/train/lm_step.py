"""Sequence-parallel LM train step (context parallelism over the mesh).

The long-context training path the reference lacks entirely (SURVEY.md §5
"Long-context / sequence parallelism": absent — no attention model, no
sequence dimension). Design:

- the token batch [B, T] is sharded over BOTH mesh axes: ``data`` on the
  batch dim and ``sequence`` on the time dim, so a sequence 8× longer than
  one chip's HBM budget trains by adding devices to the ``sequence`` axis;
- the step is a ``shard_map`` over the mesh: each device runs the model on
  its [B/dp, T/sp] activation shard, with ring attention rotating K/V blocks
  via ``lax.ppermute`` (see ``parallel/ring_attention.py``) — the only
  communication the sequence axis needs;
- every device computes grads for the full (replicated) parameter set from
  its local tokens; the true gradient of the global mean loss is the mean of
  shard grads over ``(data, sequence)`` — one fused ``lax.pmean``, the
  direct generalization of DDP's all-reduce to context parallelism;
- global token positions come from ``lax.axis_index('sequence')`` so learned
  positional embeddings and causal masks are exact across shards.

Next-token targets are produced host-side (``targets[t] = tokens[t+1]``)
*before* sharding, so the shift crosses shard boundaries correctly without
any halo exchange.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_training_tpu.runtime.mesh import (
    AXIS_DATA,
    AXIS_MODEL,
    AXIS_SEQUENCE,
)
from distributed_training_tpu.train.precision import commit_gradients
from distributed_training_tpu.train.train_state import TrainState
from distributed_training_tpu.utils.compat import shard_map

_GRAD_AXES = (AXIS_DATA, AXIS_SEQUENCE)

SP_BATCH_SPEC = {"tokens": P(AXIS_DATA, AXIS_SEQUENCE),
                 "targets": P(AXIS_DATA, AXIS_SEQUENCE)}


def _sp_axis_names(mesh: Mesh):
    """shard_map manual axes for the sequence strategy: partial-manual over
    (data, sequence) only when a model or expert axis is actually in play —
    full-manual is semantically identical when every non-manual axis is
    size 1, and it keeps the plain SP path working on jax versions without
    axis_names. With ``model`` > 1 the megatron psums, and with ``expert``
    > 1 the MoE all-to-alls, are inserted by GSPMD inside the shards."""
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    return ((AXIS_DATA, AXIS_SEQUENCE)
            if shape.get("model", 1) > 1 or shape.get("expert", 1) > 1
            else None)


def _global_positions(t_local: int):
    """Global token positions of this shard's [*, t_local] slice (the
    sequence axis must be bound)."""
    seq_idx = lax.axis_index(AXIS_SEQUENCE)
    return (seq_idx * t_local + jnp.arange(t_local))[None, :]


def model_logits_dtype(model):
    """Head compute dtype of ``model`` (fp32 when absent/None) — the single
    resolver for every step/eval builder, so a bf16-logits model gets the
    same CE math on the chunked, unchunked, train, and eval paths."""
    return getattr(model, "logits_dtype", jnp.float32)


def parse_logits_dtype(name: str):
    """The ONE config-string → dtype mapping for the logits-dtype surface
    (LMConfig, bench, profiler). Unknown spellings raise — a silent fp32
    fallback would let e.g. ``"bfloat16"`` pass while quietly dropping the
    measured +7% lever the user asked for."""
    table = {"fp32": jnp.float32, "bf16": jnp.bfloat16}
    if name not in table:
        raise ValueError(
            f"logits_dtype must be one of {sorted(table)}, got {name!r}")
    return table[name]


def _fused_softmax_ce(logits, targets):
    """Mean CE as ``logsumexp − label_logit``, fusion-friendly.

    ``optax.softmax_cross_entropy_with_integer_labels`` goes through
    ``log_softmax``, which materializes a full fp32 [B, T, vocab] log-prob
    tensor — at GPT-2-small B16 T1024 a 3.3 GB HBM round-trip the profiler
    shows as its own 7.6 ms convert/loop fusion
    (profiles/gpt_t1024_r4b.json, fusion.1592). This form reduces straight
    out of the (bf16 or fp32) logits: the max and sum-exp passes fuse with
    the upcast in registers, and only [B, T] rows land in HBM. Same math,
    fp32 accumulation; the backward rematerializes ``softmax − onehot``
    into the head-matmul fusions instead of reading saved log-probs.
    """
    return _fused_ce_rows(logits, targets).mean()


def _fused_ce_rows(logits, targets, with_correct: bool = False):
    """Per-row CE ([..., vocab] logits → [...] fp32), fusion-friendly.

    Max and gather read the logits in their STORED dtype (a gather's
    operand cannot fuse, so gathering from an fp32 cast would materialize
    the full cast tensor — the exact round-trip this form removes); only
    the sum-exp reduction sees the in-register fp32 upcast.

    ``with_correct=True`` also returns per-row top-1 correctness derived
    from values the CE already has in hand: the label is top-1 iff its
    logit equals the row max (``lab >= m``; it cannot exceed it). This is
    tie-inclusive top-1 — identical to ``argmax(logits) == target`` except
    when the label logit exactly ties a different index's max. Under fp32
    logits such ties are measure-zero (continuously distributed values
    collide with probability ~0); under bf16 logits — the default since
    round 6 — the 8-bit mantissa makes collisions merely RARE, not
    impossible, so the metric can overcount top-1 by the (tiny) tie rate.
    Either way it deletes the separate argmax reduction, a full extra HBM
    pass over the [B, T, vocab] tensor (measured 4.4 ms / +3.8% tok/s on
    the GPT-2-small B16 T1024 step in round 4).
    """
    m = lax.stop_gradient(
        jnp.max(logits, axis=-1, keepdims=True)).astype(jnp.float32)
    lse = jnp.log(jnp.sum(
        jnp.exp(logits.astype(jnp.float32) - m), axis=-1)) + m[..., 0]
    lab = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0].astype(jnp.float32)
    rows = lse - lab
    if not with_correct:
        return rows
    return rows, (lab >= m[..., 0]).astype(jnp.float32)


def _ce_rows_saved_probs(logits, targets, with_correct: bool = False):
    """CE rows via a custom VJP that saves bf16 softmax probabilities.

    The default backward rematerializes ``softmax(logits)`` into BOTH
    lm_head backward matmul fusions: each re-reads the stored logits and
    re-runs the exp on the VPU, which stalls the MXU pipeline (the dx
    matmul measures 56% of bf16 peak, profiles/gpt_t1024_r4e.json).
    Saving ``p = softmax(logits)`` once in bf16 at forward makes both
    backward matmuls clean consumers: ``dlogits = (p − onehot)·g`` fuses
    from a bf16 read with no transcendentals, and under fp32 logits the
    backward reads halve. The trade is one extra forward pass over the
    logits (read + exp + bf16 write). Loss/accuracy math is bit-identical
    to :func:`_fused_ce_rows`; only the *gradient* sees bf16-rounded
    probabilities (~2^-8 relative, the same rounding the measured
    bf16-logits lever applies to the logits themselves).

    Measured (B16 T1024 GPT-2-small, one v5e): fp32 logits 117.2k →
    119.4k tok/s; bf16 logits 125.2k → 123.7k (the backward reads are
    already bf16, so the extra forward pass isn't paid back) — use under
    fp32 logits only.
    """
    rows, correct = _saved_probs_vjp(logits, targets)
    return (rows, correct) if with_correct else rows


@jax.custom_vjp
def _saved_probs_vjp(lg, tg):
    rows, correct, _ = _saved_probs_fwd(lg, tg)
    return rows, correct


def _saved_probs_vjp_fwd(lg, tg):
    rows, correct, p = _saved_probs_fwd(lg, tg)
    # The empty array carries lg's dtype to bwd (residual leaves must be
    # arrays; a bare dtype object is not a valid pytree leaf here).
    return (rows, correct), (p, tg, jnp.zeros((0,), lg.dtype))


def _saved_probs_vjp_bwd(res, ct):
    import numpy as np

    p, tg, dt = res
    g = ct[0][..., None]  # rows cotangent; correct has no gradient
    onehot = (lax.broadcasted_iota(jnp.int32, p.shape, p.ndim - 1)
              == tg[..., None])
    dlg = jnp.where(onehot, p.astype(jnp.float32) - 1,
                    p.astype(jnp.float32)) * g
    return dlg.astype(dt.dtype), np.zeros(tg.shape, jax.dtypes.float0)


_saved_probs_vjp.defvjp(_saved_probs_vjp_fwd, _saved_probs_vjp_bwd)


def _saved_probs_fwd(lg, tg):
    # A normalized-p residual written in its own pass measures FASTER
    # (119.4k tok/s at the fp32-logits gate config) than the "free"
    # alternative of emitting bf16 exp(logits − max) as a second output
    # of the exp-sum reduce fusion (117.2k — no better than not saving
    # probs at all): the extra fusion output deoptimizes the vocab
    # reduction more than one extra elementwise pass costs.
    m = lax.stop_gradient(
        jnp.max(lg, axis=-1, keepdims=True)).astype(jnp.float32)
    ex = jnp.exp(lg.astype(jnp.float32) - m)
    s = jnp.sum(ex, axis=-1)
    lse = jnp.log(s) + m[..., 0]
    lab = jnp.take_along_axis(
        lg, tg[..., None], axis=-1)[..., 0].astype(jnp.float32)
    rows = lse - lab
    correct = (lab >= m[..., 0]).astype(jnp.float32)
    p = (ex / s[..., None]).astype(jnp.bfloat16)
    return rows, correct, p


def _ce_rows_and_correct(logits, targets, accuracy_metric: bool,
                         save_probs: bool):
    """Dispatch between the remat CE backward (default) and the
    saved-probs variant; returns ``(rows, correct-or-None)``."""
    impl = _ce_rows_saved_probs if save_probs else _fused_ce_rows
    if accuracy_metric:
        return impl(logits, targets, with_correct=True)
    return impl(logits, targets), None


def chunked_ce_and_accuracy(hidden, head_params, targets, chunk: int,
                            accuracy_metric: bool = True,
                            logits_dtype=jnp.float32):
    """CE + token accuracy WITHOUT materializing the [B, T, vocab] logits.

    For long contexts × large vocabs the logits tensor dominates memory
    (B8·T16384·V50304 fp32 = 26 GB — measured OOM on v5e):
    scan over time chunks, apply the lm_head to one [B, C, D] slice at a
    time, and reduce CE/accuracy to scalars. The body is
    ``jax.checkpoint``-ed so the backward also recomputes each chunk's
    logits instead of saving softmax residuals (which would re-create the
    full tensor). Math matches ``make_lm_head`` exactly: callers pass the
    model's ``logits_dtype`` so the per-chunk matmul runs in the same
    dtype the unchunked head would (the CE reduction is fp32 either way,
    :func:`_fused_ce_rows`).
    """
    b, t, d = hidden.shape
    if t % chunk:
        raise ValueError(f"ce_chunk {chunk} must divide sequence length {t}")
    n = t // chunk
    w = head_params["kernel"].astype(logits_dtype)
    bias = (head_params["bias"].astype(logits_dtype)
            if "bias" in head_params else None)
    hs = jnp.swapaxes(hidden.reshape(b, n, chunk, d), 0, 1)  # [n, B, C, D]
    ts = jnp.swapaxes(targets.reshape(b, n, chunk), 0, 1)    # [n, B, C]

    @jax.checkpoint
    def body(carry, xs):
        ce_sum, acc_sum = carry
        hc, tc = xs
        logits = hc.astype(logits_dtype) @ w
        if bias is not None:
            logits = logits + bias
        rows, correct = _ce_rows_and_correct(
            logits, tc, accuracy_metric, save_probs=False)
        acc = correct.sum() if accuracy_metric else jnp.float32(0)
        return (ce_sum + rows.sum(), acc_sum + acc), None

    (ce_sum, acc_sum), _ = lax.scan(
        body, (jnp.float32(0), jnp.float32(0)), (hs, ts))
    denom = jnp.float32(b * t)
    return ce_sum / denom, (acc_sum / denom if accuracy_metric else None)


def _lm_loss_and_grads(state: TrainState, tokens, targets, rng,
                       positions=None, ce_chunk: int | None = None,
                       accuracy_metric: bool = True,
                       logits_dtype=jnp.float32,
                       ce_save_probs: bool = False):
    """Scaled-CE (+ MoE aux) value-and-grad shared by every LM step variant.

    Returns ``(grads, ce, aux, accuracy)`` — CE and the MoE load-balancing
    term separately, so metrics can report perplexity as ``exp(CE)``
    (comparable to the CE-only eval loss) while the gradient flows through
    ``CE + aux``. ``ce_chunk`` computes the CE through
    :func:`chunked_ce_and_accuracy` (the model returns hidden states and
    the head applies per chunk). ``accuracy_metric=False`` returns
    ``accuracy=None`` and drops the metric key; since round 5 the metric
    derives from the CE's own max (see :func:`_fused_ce_rows`) so keeping
    it on is nearly free — the flag remains for exact parity with the
    reference's loss-only trainers.
    """
    def sown_aux(mutated):
        return sum(jax.tree.leaves(dict(mutated).get("aux_loss", {})),
                   jnp.float32(0))

    def loss_fn(params):
        rngs = dict(zip(("dropout", "gate"), jax.random.split(rng)))
        if ce_chunk:
            out = state.apply_fn(
                {"params": params}, tokens, positions=positions, train=True,
                rngs=rngs, mutable=["aux_loss"], return_hidden=True)
            if isinstance(out, tuple):  # flax apply with mutable collection
                hidden, mutated = out
                aux = sown_aux(mutated)
            else:  # PipelinedLM.apply_fn (no collections)
                hidden, aux = out, jnp.float32(0)
            ce, accuracy = chunked_ce_and_accuracy(
                hidden, params["lm_head"], targets, ce_chunk,
                accuracy_metric=accuracy_metric, logits_dtype=logits_dtype)
            return state.loss_scale.scale_loss(ce + aux), (ce, aux, accuracy)
        out = state.apply_fn(
            {"params": params}, tokens, positions=positions, train=True,
            rngs=rngs, mutable=["aux_loss"])
        if isinstance(out, tuple):  # flax apply with a mutable collection
            logits, mutated = out
            aux = sown_aux(mutated)
        else:  # PipelinedLM.apply_fn (no collections)
            logits, aux = out, jnp.float32(0)
        rows, correct = _ce_rows_and_correct(
            logits, targets, accuracy_metric, ce_save_probs)
        ce = rows.mean()
        accuracy = correct.mean() if accuracy_metric else None
        return state.loss_scale.scale_loss(ce + aux), (ce, aux, accuracy)

    grads, (ce, aux, accuracy) = jax.grad(loss_fn, has_aux=True)(state.params)
    return grads, ce, aux, accuracy


def _lm_metrics(new_state: TrainState, ce, aux, accuracy, finite,
                pmean_axes=None, grad_norm=None):
    """The LM metrics contract; ``pmean_axes`` averages shard-local values
    (the GSPMD path computes global values already). ``loss`` is the full
    objective (CE + MoE aux); ``perplexity`` is ``exp(CE)`` so it stays
    comparable to eval perplexity. ``accuracy=None`` (metrics_accuracy off)
    drops the key, ``grad_norm`` (the observability knob, already a global
    scalar) adds one — the dict is static per compile. Keep this dict the
    single source of the metric key set."""
    if pmean_axes:
        ce = lax.pmean(ce, pmean_axes)
        aux = lax.pmean(aux, pmean_axes)
        if accuracy is not None:
            accuracy = lax.pmean(accuracy, pmean_axes)
    out = {
        "loss": (ce + aux).astype(jnp.float32),
        "aux_loss": jnp.asarray(aux, jnp.float32),
        "accuracy": accuracy,
        "perplexity": jnp.exp(ce).astype(jnp.float32),
        "loss_scale": new_state.loss_scale.scale,
        "grads_finite": finite.astype(jnp.float32),
    }
    if accuracy is None:
        del out["accuracy"]
    if grad_norm is not None:
        out["grad_norm"] = grad_norm
    return out


def _lm_accum_grads(state: TrainState, batch, rng, accum: int,
                    mesh, ce_chunk: int | None, positions=None,
                    accuracy_metric: bool = True,
                    logits_dtype=jnp.float32,
                    ce_save_probs: bool = False):
    """Shared LM accumulation wrapper over ``accumulate_grads``: scan
    microbatches through fwd/bwd, average grads and metrics. ``mesh=None``
    runs shard-locally (the sequence step's partial-manual body);
    a real mesh adds the GSPMD microbatch sharding constraint.
    Returns ``(avg_grads, ce, aux, accuracy)``."""
    from distributed_training_tpu.train.step import accumulate_grads

    def micro_fn(params, mbatch, r, carry):
        g, ce, aux, acc = _lm_loss_and_grads(
            state.replace(params=params), mbatch["tokens"],
            mbatch["targets"], r, positions=positions, ce_chunk=ce_chunk,
            accuracy_metric=accuracy_metric, logits_dtype=logits_dtype,
            ce_save_probs=ce_save_probs)
        return g, carry, (ce, aux, acc)

    grads, _, (ces, auxs, accs) = accumulate_grads(
        state.params, {"tokens": batch["tokens"], "targets": batch["targets"]},
        rng, accum, mesh, micro_fn, init_carry=jnp.zeros(()))
    return (grads, ces.mean(), auxs.mean(),
            accs.mean() if accs is not None else None)


def _lm_grads_body(gstate: TrainState, batch, rng,
                   ce_chunk: int | None = None, accum: int = 1,
                   accuracy_metric: bool = True,
                   logits_dtype=jnp.float32,
                   ce_save_probs: bool = False,
                   tp_overlap: bool = False):
    """The manual (shard_map) half of the sequence-parallel step: compute
    the globally-averaged, unscaled gradient and the shard-averaged metric
    scalars. The optimizer commit deliberately happens OUTSIDE the manual
    region (see :func:`make_lm_train_step`) so ZeRO placements of the
    optimizer state stay in GSPMD-land; ``gstate`` is the train state with
    ``opt_state`` stripped — the body must not touch it.

    ``tp_overlap=True`` runs the forward/backward under the ring-overlapped
    megatron schedule (``parallel/collective_matmul.py``): params enter as
    model-axis shards, the decoder stack's activations are time-sharded over
    ``model``, and the per-layer collectives are ppermute rings. The loss is
    computed on this rank's time chunk (targets sliced below), so metrics
    and replicated-leaf grads additionally reduce over ``model``.
    """
    import contextlib

    tokens = batch["tokens"]
    targets = batch["targets"]
    positions = _global_positions(tokens.shape[1])
    # Decorrelate dropout across shards; no-op when the model has none.
    fold = (lax.axis_index(AXIS_SEQUENCE) * lax.axis_size(AXIS_DATA)
            + lax.axis_index(AXIS_DATA))
    if tp_overlap:
        import flax.linen as nn

        from distributed_training_tpu.parallel.collective_matmul import (
            seq_overlap_interceptor,
        )

        tp = lax.axis_size(AXIS_MODEL)
        fold = fold * tp + lax.axis_index(AXIS_MODEL)
        # The stack's logits come out time-sharded over model (the overlap
        # layout never re-gathers them); slice the targets to match. The
        # loss/accuracy means then cover this rank's chunk only — the
        # model-axis pmeans below complete them.
        t_loc = targets.shape[1] // tp
        targets = lax.dynamic_slice_in_dim(
            targets, lax.axis_index(AXIS_MODEL) * t_loc, t_loc, axis=1)
        ctx = nn.intercept_methods(seq_overlap_interceptor(AXIS_MODEL))
    else:
        ctx = contextlib.nullcontext()
    shard_rng = jax.random.fold_in(rng, fold)

    with ctx:
        if accum > 1:
            # Long-context accumulation: the local batch dim is the
            # EFFECTIVE micro×accum slice; the shared scan runs
            # shard-locally (mesh=None), then one collective + one update.
            # Equal-sized microbatches ⇒ mean of micro-means is the full
            # mean.
            grads, ce, aux, accuracy = _lm_accum_grads(
                gstate, {"tokens": tokens, "targets": targets}, shard_rng,
                accum, None, ce_chunk, positions=positions,
                accuracy_metric=accuracy_metric, logits_dtype=logits_dtype,
                ce_save_probs=ce_save_probs)
        else:
            grads, ce, aux, accuracy = _lm_loss_and_grads(
                gstate, tokens, targets, shard_rng, positions=positions,
                ce_chunk=ce_chunk, accuracy_metric=accuracy_metric,
                logits_dtype=logits_dtype, ce_save_probs=ce_save_probs)
    metric_axes = _GRAD_AXES
    if tp_overlap:
        from distributed_training_tpu.parallel.collective_matmul import (
            overlap_finalize_grads,
        )

        grads = overlap_finalize_grads(grads)
        metric_axes = _GRAD_AXES + (AXIS_MODEL,)
    grads = lax.pmean(grads, _GRAD_AXES)
    grads = gstate.loss_scale.unscale_grads(grads)
    ce = lax.pmean(ce, metric_axes)
    aux = lax.pmean(aux, metric_axes)
    if accuracy is not None:
        accuracy = lax.pmean(accuracy, metric_axes)
    return grads, (ce, aux, accuracy)


def make_lm_train_step(
    mesh: Mesh, *, model=None, max_len: int | None = None,
    donate: bool = True, ce_chunk: int | None = None,
    grad_accum_steps: int = 1, zero_stage: int = 0,
    accuracy_metric: bool = True, cpu_offload: bool = False,
    logits_dtype=None, ce_save_probs: bool = False,
    tp_overlap: bool = False, grad_norm_metric: bool = False,
) -> Callable:
    """Build the (data × sequence)-parallel jitted LM train step.

    Returns ``step(state, batch, rng) -> (state, metrics)`` where ``batch``
    is ``{'tokens': i32[B, T], 'targets': i32[B, T]}`` as *global* arrays,
    plus ``.state_shardings(state)`` / ``.batch_shardings`` attributes like
    the GSPMD steps.

    ``zero_stage`` composes DeepSpeed-style state sharding with the ring:
    the step is split in two — the shard_map computes the pmean'd gradient
    only (params and loss scale in, grads out; the optimizer state never
    enters the manual region), and ``commit_gradients`` runs under plain
    GSPMD where the ZeRO placement of Adam moments (sharded over the
    data × sequence replica group, ``parallel/sharding.zero_stage_axes``)
    propagates automatically: each device updates its slice of the moments
    and XLA all-gathers the updated params — reduce-scatter/all-gather
    ZeRO-1 semantics without hand-written collectives. Stage 3 additionally
    stores params sharded; the shard_map's replicated in_spec makes GSPMD
    all-gather them once at step entry (gather-on-use).

    ``model`` or ``max_len`` (exactly one): the positional-table bound.
    Global positions are traced values inside shard_map, so the model cannot
    bound-check them itself, and JAX gathers clamp out-of-range indices —
    an oversized T would silently reuse the last positional embedding. The
    global sequence length is checked here, at the only place it is
    statically known. Pass ``model=`` (the :class:`TransformerLM`) to derive
    the bound from the table itself; a hand-passed ``max_len`` that
    disagrees with the model's would re-open the silent-clamp gap.

    The shard_map is *partial-manual* over ``(data, sequence)`` only: every
    other mesh axis stays automatic, so a state placed by the megatron TP
    rule table (weights sharded over ``model``) composes transparently —
    inside each sequence shard, GSPMD inserts the row-parallel psums over
    ``model`` while the ring hops K/V blocks over ``sequence`` (TP shards
    heads, SP shards positions; the two are orthogonal dims of attention).

    ``tp_overlap=True`` selects the ring-overlapped megatron schedule
    instead: the shard_map goes FULL-manual (model included), params enter
    as rule-table shards, and the per-layer TP collectives become
    ``collective_matmul`` ppermute rings overlapped with the partial
    matmuls (see ``parallel/collective_matmul.py``). Composes with ZeRO
    stages (the commit still runs in GSPMD-land), gradient accumulation,
    and a sequence axis (the K/V ring over ``sequence`` and the matmul
    rings over ``model`` rotate orthogonally). MoE models are refused —
    expert dispatch needs the GSPMD expert axis the manual region unbinds.
    """
    from distributed_training_tpu.parallel.collective_matmul import (
        overlap_param_specs,
    )
    from distributed_training_tpu.parallel.tensor_parallel import (
        tp_state_shardings,
    )

    if (model is None) == (max_len is None):
        raise ValueError("pass exactly one of model= or max_len=")
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp_size = mesh_shape.get(AXIS_MODEL, 1)
    sp_size = mesh_shape.get(AXIS_SEQUENCE, 1)
    if tp_overlap:
        if model is None:
            raise ValueError(
                "tp_overlap needs model= (the overlap schedule derives its "
                "head/mlp shard shapes from the model config)")
        experts = model.moe_num_experts
        moe_on = (any(int(e) > 0 for e in experts)
                  if isinstance(experts, (tuple, list))
                  else int(experts) > 0)
        if moe_on:
            raise NotImplementedError(
                "tp_overlap does not compose with MoE models: expert "
                "dispatch relies on GSPMD's expert axis, which the "
                "full-manual overlap region unbinds — run MoE with the "
                "declarative TP schedule (tp_overlap=False)")
        if mesh_shape.get("expert", 1) > 1:
            raise NotImplementedError(
                "tp_overlap does not compose with an expert mesh axis")
        for what, dim in (("num_heads", model.num_heads),
                          ("mlp dim", model.hidden_dim * model.mlp_ratio)):
            if dim % tp_size:
                raise ValueError(
                    f"tp_overlap: tensor-parallel size {tp_size} must "
                    f"divide {what} (= {dim})")
    if logits_dtype is None:
        if model is None and ce_chunk:
            # The chunked CE re-applies the head OUTSIDE the model, so it
            # must know the head's compute dtype; with only max_len= there
            # is no model to read it from, and silently assuming fp32
            # would diverge from a bf16-logits model's own head/eval math.
            raise ValueError(
                "ce_chunk with max_len= needs an explicit logits_dtype= "
                "(pass model= to derive it, or logits_dtype=jnp.float32/"
                "bfloat16 matching the model's head)")
        logits_dtype = model_logits_dtype(model)
    if model is not None:
        max_len = model.max_len
    batch_spec = SP_BATCH_SPEC
    # Overlap runs FULL-manual (the model-axis collectives are hand-written
    # rings, and full-manual works on every jax with shard_map at all);
    # otherwise partial-manual keeps `model`/`expert` automatic for GSPMD.
    axis_names = None if tp_overlap else _sp_axis_names(mesh)

    if grad_accum_steps < 1:
        raise ValueError(
            f"grad_accum_steps must be >= 1, got {grad_accum_steps}")
    _check_ce_options(ce_chunk, ce_save_probs, logits_dtype)

    def state_shardings_fn(state: TrainState):
        return tp_state_shardings(state, mesh, zero_stage=zero_stage,
                                  cpu_offload=cpu_offload,
                                  overlap=tp_overlap)

    batch_sh = {k: NamedSharding(mesh, s) for k, s in batch_spec.items()}

    def body(state: TrainState, batch, rng):
        if cpu_offload:
            from distributed_training_tpu.train.step import (
                fetch_offloaded_opt_state,
            )

            # The manual region never touches opt_state (gstate strips it);
            # the on-device copy only feeds the GSPMD commit below.
            state = fetch_offloaded_opt_state(state)
        gstate = state.replace(opt_state=None)
        gstate_specs = jax.tree.map(lambda _: P(), gstate)
        grads_specs = jax.tree.map(lambda _: P(), state.params)
        if tp_overlap:
            gstate_specs = gstate_specs.replace(
                params=overlap_param_specs(state.params))
            grads_specs = overlap_param_specs(state.params)
        sharded = shard_map(
            functools.partial(_lm_grads_body, ce_chunk=ce_chunk,
                              accum=grad_accum_steps,
                              accuracy_metric=accuracy_metric,
                              logits_dtype=logits_dtype,
                              ce_save_probs=ce_save_probs,
                              tp_overlap=tp_overlap), mesh,
            in_specs=(gstate_specs, batch_spec, P()),
            out_specs=(grads_specs, P()),
            axis_names=axis_names,
        )
        grads, (ce, aux, accuracy) = sharded(gstate, batch, rng)
        grad_norm = None
        if grad_norm_metric:
            # Outside the manual region the grads are GSPMD-global (the
            # ring body already pmean'd and unscaled them), so one fused
            # norm reduction yields the global value on every shard.
            from distributed_training_tpu.train.step import global_grad_norm

            grad_norm = global_grad_norm(grads)
        new_state, finite = commit_gradients(state, grads)
        return new_state, _lm_metrics(new_state, ce, aux, accuracy, finite,
                                      grad_norm=grad_norm)

    def extra_check(batch):
        if not tp_overlap:
            return
        t_shard = batch["tokens"].shape[1] // sp_size
        if t_shard % tp_size:
            raise ValueError(
                f"tp_overlap: the per-sequence-shard length (= {t_shard}) "
                f"must divide by the model-axis size {tp_size} (the overlap "
                f"schedule time-shards activations over `model`); pick a "
                f"divisible seq_len or disable tp_overlap")

    return _lazy_jit_step(mesh, state_shardings_fn, body,
                          batch_sh=batch_sh, max_len=max_len, donate=donate,
                          extra_check=extra_check)


def _check_ce_options(ce_chunk, ce_save_probs, logits_dtype=jnp.float32):
    """The two CE levers solve opposite problems and do not compose:
    ce_chunk remats per-chunk logits under ``jax.checkpoint`` for
    long-context memory (which would discard saved probabilities and
    silently fall back to the remat backward), while ce_save_probs spends
    memory to delete the remat's exp from the short-T backward. Refuse
    loudly rather than let the flag silently not engage.

    ce_save_probs × bf16 logits *works* but is a measured perf loss
    (123.7k vs 125.2k tok/s — the backward reads are already bf16, so
    the extra forward pass isn't paid back): warn, don't refuse, so the
    combination stays measurable."""
    if ce_chunk and ce_save_probs:
        raise ValueError(
            "ce_save_probs does not compose with ce_chunk (the chunked CE "
            "rematerializes each chunk's logits, discarding saved probs) — "
            "use ce_chunk for long-context memory or ce_save_probs for "
            "fp32-logits throughput, not both")
    if ce_save_probs and jnp.dtype(logits_dtype) == jnp.dtype(jnp.bfloat16):
        import warnings

        warnings.warn(
            "ce_save_probs under bf16 logits is a measured throughput "
            "LOSS (123.7k vs 125.2k tok/s at GPT-2-small B16 T1024, "
            "round 5) — its win is fp32 logits only",
            stacklevel=3)


def _lazy_jit_step(
    mesh: Mesh,
    state_shardings_fn: Callable,
    body: Callable,
    *,
    batch_sh: dict,
    max_len: int | None,
    donate: bool,
    extra_check: Callable | None = None,
) -> Callable:
    """Shared step scaffold for every LM step builder: global-length guard,
    lazy jit with explicit in/out placements once a concrete state's pytree
    is known, and the ``.state_shardings`` / ``.batch_shardings``
    attributes for placing host-built states and batches. ``extra_check``
    runs on every (eager) batch beside the length guard — e.g. the
    tp_overlap time-divisibility refusal."""
    jitted = None  # built lazily: shardings need a concrete state's pytree

    def ensure_jitted(state: TrainState):
        nonlocal jitted
        if jitted is None:
            repl = NamedSharding(mesh, P())
            jitted = jax.jit(
                body,
                in_shardings=(state_shardings_fn(state), batch_sh, repl),
                out_shardings=(state_shardings_fn(state), repl),
                donate_argnums=(0,) if donate else ())
        return jitted

    def check_len(batch):
        if max_len is not None and batch["tokens"].shape[1] > max_len:
            raise ValueError(
                f"global sequence length {batch['tokens'].shape[1]} exceeds "
                f"the positional table max_len={max_len}")
        if extra_check is not None:
            extra_check(batch)

    def step(state: TrainState, batch, rng):
        check_len(batch)
        return ensure_jitted(state)(state, batch, rng)

    def lower(state, batch, rng):
        # AOT hook for collective accounting (utils/hlo.py): lower the
        # exact step program without executing it. Same silent-clamp guard
        # as step() — a lowered program can also be compiled and run.
        check_len(batch)
        return ensure_jitted(state).lower(state, batch, rng)

    step.state_shardings = state_shardings_fn
    step.batch_shardings = batch_sh
    step.lower = lower
    return step


def make_lm_eval_fn(
    mesh: Mesh, *, model, ce_chunk: int | None = None,
    tp_overlap: bool = False,
) -> Callable:
    """Sharded eval forward for the sequence strategy: ``eval_fn(params,
    batch) -> mean token CE`` over a (data × sequence)-sharded batch.

    The ring-attention model only applies inside shard_map (its sequence
    axis must be bound), so eval reuses the train step's sharded forward —
    global positions from ``axis_index``, ring hops for K/V — with
    ``train=False`` and no gradient. This is what makes eval possible at
    contexts that only *fit* sharded (e.g. T16384 on 8 chips): the
    alternative unsharded twin would need the full [T, T] attention on one
    device. ``ce_chunk`` composes exactly as in training (the logits tensor
    never materializes).

    ``tp_overlap=True`` (the overlap trainer's SP×TP eval) goes
    FULL-manual with params replicated over ``model``: each model rank
    duplicates the eval forward — eval is a tiny fraction of a run, and
    this keeps the ring-attention eval working on jax versions without
    partial-manual shard_map.
    """
    axis_names = None if tp_overlap else _sp_axis_names(mesh)
    batch_spec = SP_BATCH_SPEC

    def body(params, batch):
        tokens = batch["tokens"]
        targets = batch["targets"]
        positions = _global_positions(tokens.shape[1])
        if ce_chunk:
            hidden = model.apply(
                {"params": params}, tokens, positions=positions,
                train=False, return_hidden=True)
            ce, _ = chunked_ce_and_accuracy(
                hidden, params["lm_head"], targets, ce_chunk,
                logits_dtype=model_logits_dtype(model))
        else:
            logits = model.apply(
                {"params": params}, tokens, positions=positions, train=False)
            ce = _fused_softmax_ce(logits, targets)
        return lax.pmean(ce, _GRAD_AXES)

    @jax.jit
    def jitted(params, batch):
        sharded = shard_map(
            body, mesh,
            in_specs=(jax.tree.map(lambda _: P(), params), batch_spec),
            out_specs=P(), axis_names=axis_names)
        return sharded(params, batch)

    def eval_fn(params, batch):
        # Same silent-clamp guard as the train factories: positions are
        # traced inside shard_map, so the global length is only checkable
        # here (an oversized T would silently reuse the last pos-embed row).
        if batch["tokens"].shape[1] > model.max_len:
            raise ValueError(
                f"global sequence length {batch['tokens'].shape[1]} exceeds "
                f"the positional table max_len={model.max_len}")
        return jitted(params, batch)

    return eval_fn


def _make_gspmd_lm_step(
    mesh: Mesh,
    state_shardings_fn: Callable,
    *,
    max_len: int | None = None,
    donate: bool = True,
    grad_accum_steps: int = 1,
    ce_chunk: int | None = None,
    accuracy_metric: bool = True,
    logits_dtype=jnp.float32,
    cpu_offload: bool = False,
    ce_save_probs: bool = False,
    batch_spec: P | None = None,
    grad_norm_metric: bool = False,
) -> Callable:
    """Shared GSPMD LM step builder (the TP and PP steps differ only in how
    the train state is placed): batch over ``data`` (or ``batch_spec`` —
    the SP×PP step shards tokens over data × sequence), lazy jit once a
    concrete state's pytree is known, placements from ``state_shardings_fn``.

    ``grad_accum_steps > 1`` scans microbatches through fwd/bwd inside the
    compiled step before the single update (DeepSpeed
    ``gradient_accumulation_steps`` semantics; see ``train/step.py``).
    """
    if grad_accum_steps < 1:
        raise ValueError(
            f"grad_accum_steps must be >= 1, got {grad_accum_steps}")
    _check_ce_options(ce_chunk, ce_save_probs, logits_dtype)
    spec = P(AXIS_DATA, None) if batch_spec is None else batch_spec
    batch_sh = {"tokens": NamedSharding(mesh, spec),
                "targets": NamedSharding(mesh, spec)}

    def body(state: TrainState, batch, rng):
        if cpu_offload:
            from distributed_training_tpu.train.step import (
                fetch_offloaded_opt_state,
            )

            state = fetch_offloaded_opt_state(state)
        if grad_accum_steps > 1:
            grads, ce, aux, accuracy = _lm_accum_grads(
                state, batch, rng, grad_accum_steps, mesh, ce_chunk,
                accuracy_metric=accuracy_metric, logits_dtype=logits_dtype,
                ce_save_probs=ce_save_probs)
        else:
            grads, ce, aux, accuracy = _lm_loss_and_grads(
                state, batch["tokens"], batch["targets"], rng,
                ce_chunk=ce_chunk, accuracy_metric=accuracy_metric,
                logits_dtype=logits_dtype, ce_save_probs=ce_save_probs)
        grads = state.loss_scale.unscale_grads(grads)
        grad_norm = None
        if grad_norm_metric:
            from distributed_training_tpu.train.step import global_grad_norm

            grad_norm = global_grad_norm(grads)
        new_state, finite = commit_gradients(state, grads)
        return new_state, _lm_metrics(new_state, ce, aux, accuracy, finite,
                                      grad_norm=grad_norm)

    return _lazy_jit_step(mesh, state_shardings_fn, body,
                          batch_sh=batch_sh, max_len=max_len, donate=donate)


def make_tp_lm_train_step(
    mesh: Mesh, *, model, zero_stage: int = 0, donate: bool = True,
    grad_accum_steps: int = 1, ce_chunk: int | None = None,
    accuracy_metric: bool = True, cpu_offload: bool = False,
    ce_save_probs: bool = False, tp_overlap: bool = False,
    grad_norm_metric: bool = False,
) -> Callable:
    """Tensor-parallel (megatron-style) LM train step via GSPMD placement.

    The conjugate of :func:`make_lm_train_step`: instead of sharding the
    sequence and replicating weights, this shards the *weights* over the
    ``model`` mesh axis (per ``parallel/tensor_parallel.py``'s rule table)
    and the batch over ``data``. No collective is written by hand — the
    row-parallel psums (attn/out, mlp/fc2, the vocab-sharded softmax-CE
    reduction) and the gradient all-reduce over ``data`` all come from GSPMD
    propagating the annotated placements, overlapped by XLA's scheduler.
    ``zero_stage`` composes DeepSpeed-style optimizer/param sharding on the
    dims TP left free (SURVEY.md §2.3 TP row: "natural extension via pjit
    with a ``model`` mesh axis").

    The model must be built with ``seq_axis=None`` (full attention; TP
    shards heads, which is orthogonal to — and composable with — the ring
    path, but the GSPMD step runs under plain ``jit``, where no ring axis is
    bound).

    ``tp_overlap=True`` swaps the declarative schedule for the
    ring-overlapped collective matmul (``parallel/collective_matmul.py``):
    the step is rebuilt on the shard_map scaffold of
    :func:`make_lm_train_step` with the model axis manual, so the per-layer
    all-gather/reduce-scatter become ppermute rings overlapped with the
    partial matmuls. Same params, same optimizer state, same ZeRO
    composition; only vocab/class-parallel params (lm_head, tok_embed)
    stay replicated over ``model`` (their softmax-CE psum is not part of
    the overlapped layer schedule).

    Returns ``step(state, batch, rng) -> (state, metrics)`` plus a
    ``.state_shardings(state)`` attribute for placing a host-built state.
    """
    from distributed_training_tpu.parallel.tensor_parallel import (
        tp_state_shardings,
    )

    if model.seq_axis is not None:
        raise ValueError(
            "TP step runs under plain jit; build the model with "
            "seq_axis=None (ring attention needs the shard_map step)")
    if tp_overlap:
        return make_lm_train_step(
            mesh, model=model, donate=donate, ce_chunk=ce_chunk,
            grad_accum_steps=grad_accum_steps, zero_stage=zero_stage,
            accuracy_metric=accuracy_metric, cpu_offload=cpu_offload,
            ce_save_probs=ce_save_probs, tp_overlap=True,
            grad_norm_metric=grad_norm_metric)
    return _make_gspmd_lm_step(
        mesh,
        lambda state: tp_state_shardings(state, mesh, zero_stage=zero_stage,
                                         cpu_offload=cpu_offload),
        max_len=model.max_len, donate=donate,
        grad_accum_steps=grad_accum_steps, ce_chunk=ce_chunk,
        accuracy_metric=accuracy_metric,
        logits_dtype=model_logits_dtype(model),
        cpu_offload=cpu_offload, ce_save_probs=ce_save_probs,
        grad_norm_metric=grad_norm_metric)


def make_pp_lm_train_step(
    mesh: Mesh, *, model, num_microbatches: int, donate: bool = True,
    ce_chunk: int | None = None, accuracy_metric: bool = True,
    zero_stage: int = 0, virtual_stages: int = 1,
    cpu_offload: bool = False, ce_save_probs: bool = False,
    grad_norm_metric: bool = False,
) -> Callable:
    """Pipeline-parallel LM train step (GPipe or circular schedule over
    ``pipe``).

    Decoder blocks are stacked and sharded over the ``pipe`` mesh axis; the
    forward runs the ``lax.scan`` + ``lax.ppermute`` schedule from
    ``parallel/pipeline.py`` and the backward pipeline falls out of
    autodiff (ppermute's transpose is the reverse hop). Embeddings and the
    LM head are plain GSPMD ops sharded over ``data``, so DP composes. A
    ``seq_axis`` model selects SP×PP (round 5): the batch shards over
    ``data × sequence`` and ring attention runs inside each stage.
    ``virtual_stages > 1`` selects the interleaved/circular schedule
    (bubble ``(S-1)/(v·M+S-1)`` instead of GPipe's ``(S-1)/(M+S-1)``).

    ``zero_stage`` 1/2 composes DeepSpeed-style: the optimizer state of
    every leaf — pipe-stacked blocks and the replicated embeddings/head —
    additionally shards over the data axis on a dim the pipe/TP specs left
    free, and ``commit_gradients`` runs under plain GSPMD where the
    placement propagates (reduce-scatter + sharded update + all-gather).
    Stage 3 is refused: sharding the *parameters* over data would make the
    pipeline shard_map all-gather every stage's weights each tick —
    DeepSpeed likewise does not compose ZeRO-3 with its pipeline engine.

    Returns ``step(state, batch, rng) -> (state, metrics)`` with a
    ``.pipelined`` attribute (the :class:`PipelinedLM`) and
    ``.batch_shardings`` / ``.state_shardings(state)`` like the TP step.
    """
    from distributed_training_tpu.parallel.pipeline import (
        PipelinedLM,
        pp_tree_shardings,
    )
    from distributed_training_tpu.parallel.sharding import (
        check_cpu_offload,
        zero_stage_axes,
    )

    if zero_stage >= 3:
        raise NotImplementedError(
            "zero stage 3 does not compose with the pipeline strategy "
            "(data-sharded params would be all-gathered every pipeline "
            "tick; DeepSpeed's pipeline engine refuses ZeRO-3 for the same "
            "reason) — use stage 1/2, or the tensor/dp or sequence "
            "strategies for stage 3")
    check_cpu_offload(cpu_offload, zero_stage)
    plm = PipelinedLM(model, mesh, num_microbatches=num_microbatches,
                      virtual_stages=virtual_stages)
    tp = plm.tp_size > 1 or plm.moe  # rule-table specs (model AND expert)
    _, opt_axes = zero_stage_axes(mesh, zero_stage)
    opt_mem = "pinned_host" if cpu_offload else None

    def state_shardings(state: TrainState):
        repl = NamedSharding(mesh, P())
        return state.replace(
            step=repl,
            params=pp_tree_shardings(state.params, mesh, tp=tp),
            batch_stats=jax.tree.map(lambda _: repl, state.batch_stats),
            opt_state=pp_tree_shardings(
                state.opt_state, mesh, tp=tp, extra_axes=opt_axes,
                memory_kind=opt_mem),
            loss_scale=jax.tree.map(lambda _: repl, state.loss_scale),
        )

    # max_len is enforced inside PipelinedLM.apply_fn (statically), so the
    # shared builder doesn't need to re-check it.
    step = _make_gspmd_lm_step(
        mesh, state_shardings, donate=donate, ce_chunk=ce_chunk,
        accuracy_metric=accuracy_metric,
        logits_dtype=model_logits_dtype(model),
        cpu_offload=cpu_offload, ce_save_probs=ce_save_probs,
        batch_spec=(P(AXIS_DATA, model.seq_axis)
                    if model.seq_axis else None),
        grad_norm_metric=grad_norm_metric)
    step.pipelined = plm
    return step


def lm_batch_shardings(mesh: Mesh) -> dict:
    """NamedShardings for placing host token arrays on the mesh."""
    spec = P(AXIS_DATA, AXIS_SEQUENCE)
    return {"tokens": NamedSharding(mesh, spec),
            "targets": NamedSharding(mesh, spec)}


def make_lm_batch(tokens) -> dict:
    """Host-side next-token split: inputs = tokens[:, :-1], targets = tokens[:, 1:].

    Done before device sharding so the one-position shift crosses sequence
    shard boundaries for free.
    """
    return {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
