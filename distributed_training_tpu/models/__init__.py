"""Model registry.

Replaces the reference's direct torchvision zoo reuse
(``torchvision.models.resnet18(num_classes=10)``,
``resnet/pytorch_ddp/ddp_train.py:95``) with a name → Flax module factory.
"""

from __future__ import annotations

from typing import Any, Callable

import jax.numpy as jnp

from distributed_training_tpu.models.resnet import STAGE_SIZES, make_resnet

_REGISTRY: dict[str, Callable[..., Any]] = {}


def register(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


for _name in STAGE_SIZES:
    _REGISTRY[_name] = (lambda n: (lambda **kw: make_resnet(n, **kw)))(_name)


def _vit(**kw):
    from distributed_training_tpu.models.vit import make_vit
    return make_vit(**kw)


def _moe(**kw):
    from distributed_training_tpu.models.moe import make_moe_classifier
    return make_moe_classifier(**kw)


def _lm(**kw):
    from distributed_training_tpu.models.gpt import make_transformer_lm
    return make_transformer_lm(**kw)


def _deepseek_v32(**kw):
    from distributed_training_tpu.models.deepseek_v32 import make_deepseek_v32
    return make_deepseek_v32(**kw)


def _sarvam_mla(**kw):
    from distributed_training_tpu.models.deepseek_v32 import make_sarvam_mla
    return make_sarvam_mla(**kw)


def _keye_vl2(**kw):
    from distributed_training_tpu.models.keye_vl2 import make_keye_vl2
    return make_keye_vl2(**kw)


_REGISTRY["vit_b16"] = _vit
_REGISTRY["moe_mlp"] = _moe
_REGISTRY["transformer_lm"] = _lm
_REGISTRY["deepseek_v32"] = _deepseek_v32
_REGISTRY["sarvam_mla"] = _sarvam_mla
_REGISTRY["keye_vl2"] = _keye_vl2


def available_models() -> list[str]:
    return sorted(_REGISTRY)


def get_model(
    name: str,
    *,
    num_classes: int = 10,
    dtype: Any = jnp.float32,
    axis_name: str | None = None,
    **kwargs: Any,
):
    """Instantiate a model by name.

    Args:
      name: one of :func:`available_models`.
      num_classes: classifier width (10 = CIFAR parity, 1000 = ImageNet).
      dtype: compute dtype (bf16 recommended on TPU; params stay fp32).
      axis_name: mesh axis for SyncBN under shard_map; None under GSPMD jit.
    """
    if name not in _REGISTRY:
        raise ValueError(f"unknown model {name!r}; available: {available_models()}")
    return _REGISTRY[name](
        num_classes=num_classes, dtype=dtype, axis_name=axis_name, **kwargs)
