"""Vision Transformer (ViT-B/16).

Covers the ``BASELINE.json`` config "ViT-B/16 / ImageNet-1k reusing the same
DP loop (backbone swap)" — the reference itself has no attention model
(SURVEY.md §5, long-context: its only model is torchvision resnet18).

TPU-first choices:
- attention and MLP in ``dtype`` (bf16) with fp32 logits/softmax,
- optional ``seq_axis_name`` to run the encoder blocks under sequence
  parallelism (ring attention over a ``sequence`` mesh axis — see
  ``parallel/ring_attention.py``), which the standard DP configs leave None.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp


class MlpBlock(nn.Module):
    mlp_dim: int
    dtype: Any = jnp.float32
    dropout_rate: float = 0.0

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        # fc1/fc2 names match the megatron rule table
        # (parallel/tensor_parallel.py): fc1 column-parallel, fc2
        # row-parallel over the ``model`` mesh axis.
        d = x.shape[-1]
        x = nn.Dense(self.mlp_dim, dtype=self.dtype, name="fc1")(x)
        x = nn.gelu(x)
        x = nn.Dropout(self.dropout_rate)(x, deterministic=deterministic)
        x = nn.Dense(d, dtype=self.dtype, name="fc2")(x)
        return nn.Dropout(self.dropout_rate)(x, deterministic=deterministic)


class EncoderBlock(nn.Module):
    num_heads: int
    mlp_dim: int
    dtype: Any = jnp.float32
    dropout_rate: float = 0.0
    seq_axis_name: str | None = None

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        y = nn.LayerNorm(dtype=self.dtype)(x)
        if self.seq_axis_name is not None:
            from distributed_training_tpu.parallel.ring_attention import (
                RingSelfAttention,
            )

            y = RingSelfAttention(
                num_heads=self.num_heads,
                dtype=self.dtype,
                axis_name=self.seq_axis_name,
                name="attn",
            )(y, deterministic=deterministic)
        else:
            # Named so the TP rule table reaches the projections
            # (query/key/value column-parallel over heads, out
            # row-parallel).
            y = nn.MultiHeadDotProductAttention(
                num_heads=self.num_heads,
                dtype=self.dtype,
                dropout_rate=self.dropout_rate,
                name="attn",
            )(y, y, deterministic=deterministic)
        x = x + y
        y = nn.LayerNorm(dtype=self.dtype)(x)
        y = MlpBlock(self.mlp_dim, dtype=self.dtype, dropout_rate=self.dropout_rate)(
            y, deterministic=deterministic)
        return x + y


class ViT(nn.Module):
    """ViT with a learnable class token and 1D learned position embeddings."""

    num_classes: int = 1000
    patch_size: int = 16
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    dropout_rate: float = 0.0
    axis_name: str | None = None      # accepted for registry uniformity (no BN)
    seq_axis_name: str | None = None  # sequence-parallel mesh axis
    # Rematerialize each encoder block in the backward pass (activation
    # checkpointing): O(depth) activation memory for ~30% extra FLOPs —
    # measured to unlock batch 512/chip on v5e where plain bf16 OOMs by
    # 16 MB.
    remat: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        b = x.shape[0]
        x = x.astype(self.dtype)
        x = nn.Conv(
            self.hidden_size,
            (self.patch_size, self.patch_size),
            strides=(self.patch_size, self.patch_size),
            padding="VALID",
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="patch_embed",
        )(x)
        x = x.reshape(b, -1, self.hidden_size)

        cls = self.param(
            "cls_token", nn.initializers.zeros, (1, 1, self.hidden_size),
            self.param_dtype)
        x = jnp.concatenate([jnp.broadcast_to(cls, (b, 1, self.hidden_size)).astype(self.dtype), x], axis=1)
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(stddev=0.02),
            (1, x.shape[1], self.hidden_size),
            self.param_dtype,
        )
        x = x + pos.astype(self.dtype)
        x = nn.Dropout(self.dropout_rate)(x, deterministic=not train)

        # static_argnums: `deterministic` is a Python bool — tracing it
        # through the checkpoint boundary would fail inside nn.Dropout.
        block_cls = (nn.remat(EncoderBlock, static_argnums=(2,))
                     if self.remat else EncoderBlock)
        for i in range(self.num_layers):
            x = block_cls(
                num_heads=self.num_heads,
                mlp_dim=self.mlp_dim,
                dtype=self.dtype,
                dropout_rate=self.dropout_rate,
                seq_axis_name=self.seq_axis_name,
                name=f"encoder_{i}",
            )(x, not train)

        x = nn.LayerNorm(dtype=self.dtype, name="encoder_norm")(x)
        x = x[:, 0]
        x = nn.Dense(
            self.num_classes, dtype=self.dtype, param_dtype=self.param_dtype,
            kernel_init=nn.initializers.zeros_init(), name="head")(x)
        return x.astype(jnp.float32)


def make_vit(**kwargs) -> ViT:
    return ViT(**kwargs)
