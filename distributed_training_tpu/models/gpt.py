"""Decoder-only transformer LM with first-class sequence parallelism.

The reference has no attention model and no sequence dimension at all
(SURVEY.md §5 "Long-context": its only model is
``torchvision.models.resnet18``, ``resnet/pytorch_ddp/ddp_train.py:95``).
Long-context is nonetheless first-class in this framework, and this module
is the model family that exercises it: a GPT-style causal LM whose attention
is :class:`~distributed_training_tpu.parallel.ring_attention.RingSelfAttention`.

Sequence parallelism is a *constructor argument*, not a separate model: with
``seq_axis=None`` the model is an ordinary single-device causal LM (the test
oracle); with ``seq_axis='sequence'`` every activation is a local sequence
shard and only K/V blocks travel the ring (``lax.ppermute`` neighbor hops on
the ICI torus). All other ops — embeddings, LayerNorm, MLP, the LM head —
are position-wise, so they need no communication under sequence sharding.

Positions are explicit inputs: under ``shard_map`` each shard passes its
*global* token positions so learned positional embeddings and the causal
mask are exact across shards.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_training_tpu.parallel.ring_attention import (
    RingSelfAttention,
    paged_formulation,
)


class QuantFriendlyDense(nn.Dense):
    """``nn.Dense`` with its ``__call__`` restated so the kernel
    use-site is ``astype``.

    A SUBCLASS (not a from-scratch module) so every
    ``isinstance(mod, nn.Dense)`` dispatch keeps firing — the TP
    ring-overlap interceptors (parallel/collective_matmul.py) match
    fc1/fc2 by exactly that test and bypass the param shape check for
    their pre-sharded kernels. Params are the parent's (same names,
    same lecun_normal/zeros initializers, same RNG stream) and the math
    is bitwise-identical for plain fp32 trees. The one deliberate
    difference: the kernel reaches the matmul through
    ``kernel.astype(dtype)``, so when the serving engine binds a
    per-channel int8 :class:`~distributed_training_tpu.serving.quantize.
    QuantizedTensor` in the kernel's place, that same call dequantizes
    it (duck-typed ``astype``) and the module needs no quantization
    branch. ``nn.Dense``'s own ``promote_dtype`` would try to
    ``jnp.asarray`` the quantized node and fail.
    """

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", self.kernel_init,
                            (jnp.shape(x)[-1], self.features),
                            self.param_dtype)
        bias = self.param("bias", self.bias_init, (self.features,),
                          self.param_dtype)
        d = self.dtype or jnp.float32
        x = x.astype(d)
        y = jax.lax.dot_general(
            x, kernel.astype(d),
            (((x.ndim - 1,), (0,)), ((), ())))
        return y + jnp.reshape(bias.astype(d),
                               (1,) * (y.ndim - 1) + (-1,))


class MlpBlock(nn.Module):
    """Position-wise transformer MLP (fc1 → GELU → fc2).

    Kernel layout is TP-friendly: fc1 splits columns, fc2 splits rows over
    the ``model`` mesh axis (see ``parallel/tensor_parallel.py``).
    """

    mlp_dim: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        h = QuantFriendlyDense(self.mlp_dim, dtype=self.dtype, name="fc1")(x)
        h = nn.gelu(h)
        return QuantFriendlyDense(d, dtype=self.dtype, name="fc2")(h)


class DecoderBlock(nn.Module):
    """Pre-LN causal decoder block: LN → ring-MHA → residual → LN → FFN.

    The FFN is the dense :class:`MlpBlock`, or a GShard-style
    :class:`~distributed_training_tpu.models.moe.MoEMlp` when
    ``moe_num_experts > 0`` (expert-parallel over ``expert_axis``; the
    aux load-balancing loss is sown and added by the train step).
    """

    num_heads: int
    mlp_dim: int
    dtype: Any = jnp.float32
    seq_axis: str | None = None
    dropout_rate: float = 0.0
    attn_impl: str = "exact"
    moe_num_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_min_capacity: int = 0
    moe_noisy_gate_policy: str | None = None
    moe_mlp_type: str = "standard"
    moe_expert_axis: str | None = None
    cache_len: int | None = None
    kv_page_size: int | None = None
    kv_pages: int | None = None
    kv_dtype: str | None = None

    @nn.compact
    def __call__(self, x, train: bool = False, decode: bool = False,
                 pages=None):
        y = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        y = RingSelfAttention(
            num_heads=self.num_heads, dtype=self.dtype,
            axis_name=self.seq_axis, causal=True,
            attn_impl=self.attn_impl, cache_len=self.cache_len,
            kv_page_size=self.kv_page_size, kv_pages=self.kv_pages,
            kv_dtype=self.kv_dtype,
            name="attn")(y, decode=decode, pages=pages)
        if self.dropout_rate:
            y = nn.Dropout(self.dropout_rate, deterministic=not train)(y)
        x = x + y
        y = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        if self.moe_num_experts > 0:
            from distributed_training_tpu.models.moe import MoEMlp

            y = MoEMlp(
                num_experts=self.moe_num_experts,
                hidden_dim=self.mlp_dim,
                top_k=self.moe_top_k,
                capacity_factor=self.moe_capacity_factor,
                min_capacity=self.moe_min_capacity,
                noisy_gate_policy=self.moe_noisy_gate_policy,
                mlp_type=self.moe_mlp_type,
                expert_axis=self.moe_expert_axis,
                dtype=self.dtype,
                name="moe_mlp")(y, train=train)
        else:
            y = MlpBlock(mlp_dim=self.mlp_dim, dtype=self.dtype, name="mlp")(y)
        if self.dropout_rate:
            y = nn.Dropout(self.dropout_rate, deterministic=not train)(y)
        return x + y


def moe_layer_experts(num_layers: int, moe_every: int,
                      moe_num_experts) -> dict[int, int]:
    """{layer index: expert count} for the MoE layers of a decoder stack.

    ``moe_num_experts`` int → that count at every ``moe_every``-th layer;
    tuple → DeepSpeed per-layer semantics (length 1 broadcasts; length =
    number of MoE layers assigns in order; any other length raises — a
    truncated or padded assignment would silently train a different
    architecture than the flags describe).
    """
    counts = (tuple(int(c) for c in moe_num_experts)
              if isinstance(moe_num_experts, (tuple, list))
              else (int(moe_num_experts),))
    if moe_every <= 0 or not any(counts):
        return {}
    layers = [i for i in range(num_layers)
              if i % moe_every == moe_every - 1]
    if len(counts) == 1:
        counts = counts * len(layers)
    if len(counts) != len(layers):
        raise ValueError(
            f"per-layer expert counts {counts} do not match the "
            f"{len(layers)} MoE layers (num_layers={num_layers}, "
            f"moe_every={moe_every}); pass one count or exactly "
            f"{len(layers)}")
    return dict(zip(layers, counts))


class QuantFriendlyEmbed(nn.Module):
    """``nn.Embed`` restated to tolerate a per-row int8 quantized table.

    Param-compatible with ``nn.Embed`` (same ``embedding`` name, same
    variance-scaling init, fp32 param dtype) and bitwise-identical for
    plain tables (astype-then-take ≡ take-then-astype for a dtype-
    preserving cast). When the serving engine binds a per-row
    :class:`~distributed_training_tpu.serving.quantize.QuantizedTensor`
    ([vocab, D] int8 + [vocab, 1] scales), the lookup gathers int8 rows
    AND their scales, dequantizing only the gathered rows — the full
    table never materializes in fp32. Duck-typed on the node's
    ``q``/``scale`` attributes so the models layer stays import-free of
    the serving package.
    """

    num_embeddings: int
    features: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, inputs):
        embedding = self.param(
            "embedding",
            nn.initializers.variance_scaling(1.0, "fan_in", "normal",
                                             out_axis=0),
            (self.num_embeddings, self.features), jnp.float32)
        q = getattr(embedding, "q", None)
        if q is not None:  # quantized table: gather rows + row scales
            rows = jnp.take(q, inputs, axis=0).astype(self.dtype)
            scales = jnp.take(embedding.scale, inputs,
                              axis=0).astype(self.dtype)
            return rows * scales
        return jnp.take(embedding.astype(self.dtype), inputs, axis=0)


def make_tok_embed(m: "TransformerLM", name: str | None = None):
    """Token-embedding module; single source of its config for both the
    plain model and the pipelined executor (``parallel/pipeline.py``)."""
    return QuantFriendlyEmbed(m.vocab_size, m.hidden_dim, dtype=m.dtype,
                              name=name)


def make_final_norm(m: "TransformerLM", name: str | None = None) -> nn.LayerNorm:
    return nn.LayerNorm(dtype=m.dtype, name=name)


def make_lm_head(m: "TransformerLM", name: str | None = None) -> nn.Dense:
    # Untied head. Default fp32 logits (stable softmax under bf16 compute);
    # logits_dtype=bf16 halves the [B, T, vocab] HBM round-trips — at
    # GPT-2-small B16 T1024 the fp32 logits are 3.3 GB/step written forward
    # and re-read twice backward, the profiled top cost of the whole step
    # (profiles/gpt_t1024_r4.json: the head fusions at 330-420 GB/s). The
    # CE still reduces in fp32 (the loss path upcasts in-register); only
    # the stored logits are rounded, a ~2^-8 relative perturbation.
    # head_bias=False drops the bias the real GPT-2 head never had — its
    # gradient is a sum over all B·T rows of dlogits, a full extra HBM
    # pass over the [B, T, vocab] tensor (profiled 2.3 ms/step).
    return nn.Dense(m.vocab_size, dtype=m.logits_dtype,
                    use_bias=m.head_bias, name=name)


def add_pos_embed(m: "TransformerLM", pos_tab, x, positions):
    return x + pos_tab[positions].astype(m.dtype)


class TransformerLM(nn.Module):
    """GPT-style causal LM.

    Inputs: ``tokens`` int32 [B, T_local]; ``positions`` int32 [B, T_local]
    of *global* positions (None → 0..T-1, the unsharded case). Returns
    logits [B, T_local, vocab].
    """

    vocab_size: int
    num_layers: int = 4
    num_heads: int = 4
    hidden_dim: int = 256
    mlp_ratio: int = 4
    max_len: int = 2048
    dtype: Any = jnp.float32
    logits_dtype: Any = jnp.float32  # see make_lm_head
    # Default OFF since round 5 (GPT-2 parity; see make_lm_head). True
    # restores the pre-round-5 checkpoint tree.
    head_bias: bool = False
    seq_axis: str | None = None
    dropout_rate: float = 0.0
    attn_impl: str = "exact"  # exact | flash (pallas kernel, unsharded path)
    # MoE: every ``moe_every``-th block (GShard convention: alternating)
    # swaps its dense FFN for an expert-parallel MoEMlp. 0 experts = dense.
    # An int applies to every MoE layer; a tuple gives PER-MOE-LAYER counts
    # (DeepSpeed's `--num-experts 64 64 128` nargs surface,
    # resnet/deepspeed/deepspeed_train.py:71-75) — length 1 broadcasts,
    # length = number of MoE layers assigns in order, anything else raises
    # (see moe_layer_experts).
    moe_num_experts: int | tuple = 0
    moe_every: int = 2
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_min_capacity: int = 0
    moe_noisy_gate_policy: str | None = None
    moe_mlp_type: str = "standard"
    moe_expert_axis: str | None = None
    # KV-cache slots for decode=True; None → max_len. Smaller values (the
    # Generator sets prompt + max_new_tokens) shrink the scan carry and the
    # per-step attention width without touching params.
    cache_len: int | None = None
    # Paged KV cache (serving engine, parallel/ring_attention.py): the
    # decode cache becomes a shared pool of kv_pages fixed-size pages
    # (kv_page_size tokens each, physical page 0 reserved as the null
    # page) and decode calls must pass ``pages`` (a PagedKV of page
    # tables / write positions / validity). None → the contiguous
    # per-sequence cache the Generator uses. Config-only like cache_len:
    # params are identical either way.
    kv_page_size: int | None = None
    kv_pages: int | None = None
    # Paged-pool KV storage dtype: None = model dtype; "int8" = pages
    # stored int8 with per-row per-head fp32 scales alongside,
    # quantize-on-scatter / dequantize-in-gather (serving engine's
    # ServeConfig.kv_dtype; see ring_attention._paged_decode_attend).
    # Config-only like kv_page_size: params are identical either way.
    kv_dtype: str | None = None
    # Rematerialize each decoder block in the backward pass (activation
    # checkpointing: O(depth) activation memory for ~30% extra FLOPs).
    # Ignored in decode mode (no backward). The pipeline executor honors
    # it too (PipelinedLM checkpoints each layer inside its stage scan).
    remat: bool = False

    # What the serving engine asks a model about itself (serving/engine.py).
    # No step counters: nothing here sows into a ``counters`` collection.
    step_counters = ()
    # The blocks ``block0`` … repeat: the engine may hand their weights to
    # its step programs stacked over the layers (serving/stacked.py).
    stacked_blocks = "block"
    # Every cache leaf is a paged pool: no per-slot state.
    slot_state = ()

    def paged_lane(self, t_in: int, page_size: int,
                   kv_dtype: str | None) -> str:
        """The attention formulation a paged decode call ``t_in`` rows wide
        takes (``ring_attention.paged_formulation``: kernel or gather)."""
        return paged_formulation(t_in, self.num_heads,
                                 self.hidden_dim // self.num_heads,
                                 page_size, self.dtype, kv_dtype)

    def attended_rows(self, live: int) -> int:
        """Of ``live`` cached rows a query attends all: dense attention."""
        return live

    def index_rows_scored(self, live: int, budget: int) -> int:
        """No indexer: no index key is scored."""
        del live, budget
        return 0

    @nn.compact
    def __call__(self, tokens, positions=None, train: bool = False,
                 decode: bool = False, return_hidden: bool = False,
                 pages=None):
        """``decode=True`` runs the cached autoregressive path: every block
        appends K/V for this call's tokens to its ``cache`` collection
        (length ``cache_len``, default ``max_len``) and attends against the
        cache. The caller applies with ``mutable=['cache']`` (see
        ``inference/sampler.py``). ``positions`` feeds ONLY the positional
        embedding here — the causal offset and write slot come from each
        layer's internal ``cache_index`` counter, so callers must keep
        ``positions`` consistent with the number of tokens already decoded
        (position t == t-th token fed to this cache).

        ``return_hidden=True`` returns the final-norm hidden states
        [B, T, D] *instead of* logits — the hook for chunked
        cross-entropy, which applies the (untouched) ``lm_head`` params
        chunk-by-chunk so the [B, T, vocab] logits tensor never
        materializes (``train/lm_step.py::chunked_ce_and_accuracy``).
        Init always runs the head (default False) so its params exist."""
        if decode and positions is None:
            raise ValueError(
                "decode=True requires explicit positions (the pos-embed row "
                "of each incoming token)")
        if decode and self.cache_len is not None and (
                self.cache_len > self.max_len):
            # Cache slots past max_len would decode at silently-clamped
            # pos-embed rows (gathers clamp), defeating the overflow poison.
            raise ValueError(
                f"cache_len={self.cache_len} exceeds the positional table "
                f"(max_len={self.max_len})")
        if positions is None:
            # Unsharded path: the sequence length is static, so bound-check
            # it here — JAX gathers clamp out-of-range indices, which would
            # otherwise silently reuse pos_embed[max_len-1] for every token
            # past the table. (The sharded path's positions are traced and
            # cannot be checked here; make_lm_train_step requires max_len
            # and checks the global length instead.)
            if tokens.shape[-1] > self.max_len:
                raise ValueError(
                    f"sequence length {tokens.shape[-1]} exceeds "
                    f"max_len={self.max_len}")
            positions = jnp.arange(tokens.shape[-1])[None, :]
        x = make_tok_embed(self, name="tok_embed")(tokens)
        pos_tab = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (self.max_len, self.hidden_dim))
        x = add_pos_embed(self, pos_tab, x, positions)
        # static_argnums: train/decode are Python bools (2 and 3 counting
        # self); remat only matters when a backward pass exists.
        block_cls = (nn.remat(DecoderBlock, static_argnums=(2, 3))
                     if self.remat and not decode else DecoderBlock)
        experts_by_layer = moe_layer_experts(
            self.num_layers, self.moe_every, self.moe_num_experts)
        for i in range(self.num_layers):
            x = block_cls(
                num_heads=self.num_heads,
                mlp_dim=self.mlp_ratio * self.hidden_dim,
                dtype=self.dtype,
                seq_axis=self.seq_axis,
                dropout_rate=self.dropout_rate,
                attn_impl=self.attn_impl,
                moe_num_experts=experts_by_layer.get(i, 0),
                moe_top_k=self.moe_top_k,
                moe_capacity_factor=self.moe_capacity_factor,
                moe_min_capacity=self.moe_min_capacity,
                moe_noisy_gate_policy=self.moe_noisy_gate_policy,
                moe_mlp_type=self.moe_mlp_type,
                moe_expert_axis=self.moe_expert_axis,
                cache_len=self.cache_len or self.max_len,
                kv_page_size=self.kv_page_size,
                kv_pages=self.kv_pages,
                kv_dtype=self.kv_dtype,
                name=f"block{i}")(x, train, decode, pages)
        x = make_final_norm(self, name="ln_f")(x)
        if return_hidden:
            return x
        return make_lm_head(self, name="lm_head")(x)


def init_decode_cache(model: "TransformerLM", params: Any,
                      batch_size: int = 1):
    """Empty KV-cache pytree for ``decode=True`` without running a forward.

    ``jax.eval_shape`` traces a one-token decode apply (no FLOPs, no
    allocation) to learn the cache structure, then materializes zeros.

    Contiguous layout (``kv_page_size=None``): per block,
    ``cached_key``/``cached_value`` [B, cache_len, H, hd] plus the scalar
    ``cache_index`` write head. A zero cache with index 0 is exactly the
    state a prefill starts from (``inference/sampler.py::Generator``,
    the reference every serving test compares to, runs on this layout).

    Paged layout (``kv_page_size`` set): per block, the batch-free flat
    pools ``key_pages``/``value_pages`` [kv_pages * kv_page_size, H·hd]
    shared by every decode slot — routing state (page tables, write
    positions) is per-call :class:`~distributed_training_tpu.parallel.
    ring_attention.PagedKV` input, not cache state, so the same pool
    pytree serves the [max_batch, 1] decode batch, the
    [1, prefill_chunk] chunk inside the engine's fused step, and the
    [max_batch, spec_k + 1] speculative verify window — window width is
    a call shape, never cache state.
    """
    paged = getattr(model, "kv_page_size", None) is not None

    def shape_fn(p):
        toks = jnp.zeros((batch_size, 1), jnp.int32)
        pages = None
        if paged:
            from distributed_training_tpu.parallel.ring_attention import (
                PagedKV,
            )

            pages = PagedKV(
                table=jnp.zeros((batch_size, 1), jnp.int32),
                positions=jnp.zeros_like(toks),
                valid=jnp.zeros(toks.shape, bool))
        _, vars_out = model.apply(
            {"params": p}, toks, positions=jnp.zeros_like(toks),
            train=False, decode=True, mutable=["cache"], pages=pages)
        return vars_out["cache"]

    shapes = jax.eval_shape(shape_fn, params)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def make_transformer_lm(
    *,
    num_classes: int = 256,
    dtype: Any = jnp.float32,
    axis_name: str | None = None,
    seq_axis: str | None = None,
    num_layers: int = 4,
    num_heads: int = 4,
    hidden_dim: int = 256,
    mlp_ratio: int = 4,
    max_len: int = 2048,
    dropout_rate: float = 0.0,
    attn_impl: str = "exact",
    moe_num_experts: int | tuple = 0,
    moe_every: int = 2,
    moe_top_k: int = 1,
    moe_capacity_factor: float = 1.25,
    moe_min_capacity: int = 0,
    moe_noisy_gate_policy: str | None = None,
    moe_mlp_type: str = "standard",
    moe_expert_axis: str | None = None,
    remat: bool = False,
    logits_dtype: Any = jnp.float32,
    head_bias: bool = False,
) -> TransformerLM:
    """Registry factory. ``num_classes`` doubles as vocab size; ``axis_name``
    (the registry's SyncBN slot) is unused — LM has no BatchNorm. Unknown
    kwargs raise (a swallowed typo like ``seq_axis_name=`` would silently
    build an unsharded model that trains block-diagonal attention)."""
    del axis_name
    return TransformerLM(
        vocab_size=num_classes,
        num_layers=num_layers,
        num_heads=num_heads,
        hidden_dim=hidden_dim,
        mlp_ratio=mlp_ratio,
        max_len=max_len,
        dtype=dtype,
        seq_axis=seq_axis,
        dropout_rate=dropout_rate,
        attn_impl=attn_impl,
        moe_num_experts=moe_num_experts,
        moe_every=moe_every,
        moe_top_k=moe_top_k,
        moe_capacity_factor=moe_capacity_factor,
        moe_min_capacity=moe_min_capacity,
        moe_noisy_gate_policy=moe_noisy_gate_policy,
        moe_mlp_type=moe_mlp_type,
        moe_expert_axis=moe_expert_axis,
        remat=remat,
        logits_dtype=logits_dtype,
        head_bias=head_bias,
    )
