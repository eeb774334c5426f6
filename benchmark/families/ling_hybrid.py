"""The ``ling_hybrid`` family (Ling-3.0-flash): what the harness needs from
a configuration file with the hub's keys of that model, beside the plain
reference (``benchmark/reference/ling_hybrid.py``).

The counts are the numerators of ``mfu.*``, ``decode_roofline.*`` and the
kernels' roofline shares, so they count the **algorithm's least**, never a
formulation's: per token the matrices it multiplies (of the routed experts
``per token x held / routed`` in expectation: 8 x 64 / 512 = one expert),
a KDA layer's recurrence token by token (the decay, ``S^T k``, the rank-1
update and ``S^T q``: 7 x dk x dv a head) and its convolutions, and the
latent layer's dense attention over every earlier key in the cheaper of
its two forms (per-head for a prompt's tokens, absorbed for a decoded
token). 2 per multiply-add, causal counted as causal.
"""

from __future__ import annotations

from benchmark.reference import ling_hybrid as reference

# Keys of this family's files that set a shape though their names do not
# say so: never in ``reduced``.
WIDTH_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
              "qk_head_dim", "rotary_dim", "num_shared_experts",
              "routed_scaling_factor", "rope_theta", "rope_scaling",
              "rms_norm_eps", "use_qk_norm", "kda_lower_bound",
              "kda_safe_gate", "n_group", "topk_group",
              "layer_group_size", "group_norm_size",
              "moe_router_enable_expert_bias", "partial_rotary_factor")
STATE_BYTES = 4          # the recurrent state is float32


def validate(cfg: dict) -> None:
    """What must hold of the file's keys."""
    s = reference.sizes(cfg)
    if not (0 <= s["held_first"]
            and s["held_first"] + s["held"] <= s["experts"]):
        raise ValueError("the held experts are not among the router's")
    if int(cfg.get("n_routed_experts", s["held"])) != s["held"]:
        raise ValueError("n_routed_experts (what the accepted reader of "
                         "expert_imbalance.tokens divides by) is not the "
                         "count of experts held, num_experts")
    if s["experts"] % s["n_group"] or s["topk_group"] > s["n_group"]:
        raise ValueError("the router's groups do not divide its experts")
    if s["per_token"] > s["experts"] * s["topk_group"] // s["n_group"]:
        raise ValueError("more experts per token than the kept groups hold")
    if len(cfg["held_layers"]) != s["layers"]:
        raise ValueError("held_layers does not list num_hidden_layers layers")
    if list(cfg.get("layer_kinds", s["kinds"])) != s["kinds"]:
        raise ValueError("layer_kinds is not what layer_group_size gives "
                         "the held layers")
    if not 0 <= s["dense_layers"] <= s["layers"]:
        raise ValueError("first_k_dense_replace outside the layers held")
    if s["rope"] % 2:
        raise ValueError("qk_rope_head_dim must be even")
    if int(cfg["qk_head_dim"]) != s["nope"] + s["rope"]:
        raise ValueError("qk_head_dim is qk_nope_head_dim + qk_rope_head_dim")
    if s["shared_width"] != s["expert"] * max(s["shared"], 1):
        raise ValueError("the shared expert is not num_shared_experts "
                         "experts wide")
    if s["rows"] > int(cfg["published"]["vocab_size"]):
        raise ValueError("more rows of the vocabulary than published")
    if cfg.get("q_lora_rank") is not None:
        raise ValueError("this family has no query latent")
    if cfg.get("rope_scaling") is not None:
        raise ValueError("this family's latent layer has plain rotary")


def token_ids(cfg: dict) -> int:
    """Traffic draws token ids below this (the slice of the vocabulary)."""
    return int(cfg["vocab_size"])


def build_model(cfg: dict, model_spec: dict):
    """The program's model for the serving driver; ``model_spec`` is the
    traffic file's ``model`` group (the types it is served in)."""
    import jax.numpy as jnp

    from distributed_training_tpu.models import get_model

    dtypes = {"bf16": jnp.bfloat16, "fp32": jnp.float32}
    s = reference.sizes(cfg)
    return get_model(
        "ling_hybrid", num_classes=s["rows"],
        dtype=dtypes[model_spec["dtype"]],
        logits_dtype=dtypes[model_spec["logits_dtype"]],
        kinds=tuple(s["kinds"]), first_dense=s["dense_layers"],
        hidden_dim=s["d"], dense_dim=s["dense"], expert_dim=s["expert"],
        num_heads=s["heads"], head_dim=s["head_dim"], kv_rank=s["kv_rank"],
        nope_dim=s["nope"], rope_dim=s["rope"], v_dim=s["v"],
        num_experts=s["experts"], held=(s["held_first"], s["held"]),
        experts_per_token=s["per_token"], n_group=s["n_group"],
        topk_group=s["topk_group"], routed_scale=s["routed_scale"],
        shared_experts=s["shared"], conv_size=s["conv"],
        gate_lower_bound=s["lower_bound"], rope_theta=s["theta"],
        max_len=int(cfg["max_position_embeddings"]), norm_eps=s["eps"])


def train_flags(cfg: dict) -> list[str]:
    raise NotImplementedError(
        "this configuration is served, not trained: 2804 M parameters at "
        "16 bytes a parameter is 44.9 GB")


def _kda_layers(s: dict) -> int:
    return s["kinds"].count("kda")


def _mla_layers(s: dict) -> int:
    return s["kinds"].count("mla")


def _kda_params(s: dict) -> int:
    d, h, hd = s["d"], s["heads"], s["head_dim"]
    width = h * hd
    return (4 * d * width + width * d + 3 * s["conv"] * width + width + h
            + 2 * d * h + hd)


def _mla_params(s: dict) -> int:
    d, h = s["d"], s["heads"]
    return (d * h * (s["nope"] + s["rope"]) + d * (s["kv_rank"] + s["rope"])
            + s["kv_rank"] + s["kv_rank"] * h * (s["nope"] + s["v"])
            + h * s["v"] * d + d * h)


def _expert_params(s: dict) -> int:
    return 3 * s["d"] * s["expert"]


def _mixers_and_outside(s: dict) -> int:
    """Every parameter of the layers but the routed experts' (norms,
    mixers, dense FFNs, routers, shared experts)."""
    total = 0
    for i, kind in enumerate(s["kinds"]):
        total += 2 * s["d"] + (_kda_params(s) if kind == "kda"
                               else _mla_params(s))
        if i < s["dense_layers"]:
            total += 3 * s["d"] * s["dense"]
        else:
            total += (s["d"] * s["experts"] + s["experts"]
                      + 3 * s["d"] * s["shared_width"] * s["shared"])
    return total


def param_count(cfg: dict) -> int:
    """Parameters of the share as run."""
    s = reference.sizes(cfg)
    moe_layers = s["layers"] - s["dense_layers"]
    return (_mixers_and_outside(s) + moe_layers * s["held"]
            * _expert_params(s) + 2 * s["rows"] * s["d"] + s["d"])


def matmul_params_read(cfg: dict) -> int:
    """Parameters a forward pass reads whatever the batch: everything but
    the embedding table (gathered by row) and the routed experts (read by
    the tokens routed to them: :func:`decode_iteration_bytes`)."""
    s = reference.sizes(cfg)
    moe_layers = s["layers"] - s["dense_layers"]
    return (param_count(cfg) - s["rows"] * s["d"]
            - moe_layers * s["held"] * _expert_params(s))


def _token_matmul_flops(s: dict) -> float:
    """FLOPs of the matrices one token multiplies, the head included; of
    the routed experts its expected share of the held ones."""
    moe_layers = s["layers"] - s["dense_layers"]
    routed = s["per_token"] * s["held"] / s["experts"] * _expert_params(s)
    return 2.0 * (_mixers_and_outside(s) + moe_layers * routed
                  + s["d"] * s["rows"])


def _kda_token_flops(s: dict) -> float:
    """One token through one KDA layer's recurrence and convolutions: the
    decay (1), ``S^T k`` (2), the rank-1 update (2) and ``S^T q`` (2) per
    state entry; a multiply-add per tap of the three convolutions."""
    hd = s["head_dim"]
    return 7.0 * s["heads"] * hd * hd \
        + 2.0 * s["conv"] * 3 * s["heads"] * hd


def _absorbed_flops_key(s: dict) -> float:
    """One decoded query against one cached row, all heads: a score over
    ``kv_rank + rope``, a value over ``kv_rank``."""
    return 2.0 * s["heads"] * (2 * s["kv_rank"] + s["rope"])


def _per_head_flops_key(s: dict) -> float:
    return 2.0 * s["heads"] * (s["nope"] + s["rope"] + s["v"])


def forward_flops_token(cfg: dict, keys: float) -> float:
    """Forward FLOPs of one decoded token whose context holds ``keys``
    positions: the KDA layers' recurrence, absorbed attention in the
    latent layers over all of them."""
    s = reference.sizes(cfg)
    return (_token_matmul_flops(s) + _kda_layers(s) * _kda_token_flops(s)
            + _mla_layers(s) * _absorbed_flops_key(s) * keys)


def prompt_forward_flops(cfg: dict, length: int) -> float:
    """Forward FLOPs of prefilling ``length`` prompt tokens: every token
    through the KDA recurrence, the query at position t of a latent layer
    over its t + 1 keys in the per-head form."""
    s = reference.sizes(cfg)
    return (length * (_token_matmul_flops(s)
                      + _kda_layers(s) * _kda_token_flops(s))
            + _mla_layers(s) * _per_head_flops_key(s) * length
            * (length + 1) / 2.0)


def chunk_attention_call(cfg: dict, start: int, tokens: int,
                         itemsize: int = 2) -> dict:
    """FLOPs and bytes of the latent layers' attention of one prefill
    chunk, rows ``start .. start + tokens`` of a prompt (what the kernel
    ``masked_attention`` is there for; the KDA layers take no part): the
    query at position t attends its t + 1 keys in the per-head form.
    Bytes: each query and output row once, and once the latent row of every
    key the chunk can see."""
    s = reference.sizes(cfg)
    attended = tokens * start + tokens * (tokens + 1) / 2.0
    rows = tokens * s["heads"] * (s["nope"] + s["rope"] + s["v"]) \
        + (start + tokens) * (s["kv_rank"] + s["rope"])
    return {"flops": _mla_layers(s) * _per_head_flops_key(s) * attended,
            "bytes": _mla_layers(s) * itemsize * rows}


def kda_decode_call(cfg: dict, slots: int) -> dict:
    """FLOPs and bytes of the kernel ``kda_decode`` over every KDA layer
    of one decode step that carries ``slots`` slots: per slot and layer
    the state read and written once in float32, and q, k, v, g and beta in
    and o out in float32."""
    s = reference.sizes(cfg)
    h, hd = s["heads"], s["head_dim"]
    state = h * hd * hd * STATE_BYTES
    vectors = (4 * h * hd + h + h * hd) * 4
    n = _kda_layers(s) * slots
    return {"flops": n * 7.0 * h * hd * hd,
            "bytes": n * float(2 * state + vectors)}


def slab_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One slot's per-slot slabs over the KDA layers: the float32 state and
    the last ``conv - 1`` inputs of the three convolutions."""
    s = reference.sizes(cfg)
    width = s["heads"] * s["head_dim"]
    return _kda_layers(s) * (s["heads"] * s["head_dim"] ** 2 * STATE_BYTES
                             + (s["conv"] - 1) * 3 * width * itemsize)


def train_flops_token(cfg: dict, seq_len: int) -> float:
    raise NotImplementedError("not trained: see train_flags")


def decode_iteration_bytes(cfg: dict, live_contexts: list,
                           itemsize: int = 2) -> float:
    """Bytes one decode iteration needs: every matrix outside the routed
    experts once; of each expert layer's held experts the expected number
    that ``n`` decoding tokens hit, ``held x (1 - (1 - per_token /
    routed)^n)``; per decoding slot its slabs read and written once; and
    per slot and latent layer the latent row of every live position."""
    s = reference.sizes(cfg)
    n = len(live_contexts)
    moe_layers = s["layers"] - s["dense_layers"]
    hit = s["held"] * (1.0 - (1.0 - s["per_token"] / s["experts"]) ** n)
    weights = matmul_params_read(cfg) + moe_layers * hit * _expert_params(s)
    rows = sum(live_contexts) * (s["kv_rank"] + s["rope"])
    return (itemsize * (weights + _mla_layers(s) * rows)
            + 2.0 * n * slab_bytes(cfg, itemsize))
