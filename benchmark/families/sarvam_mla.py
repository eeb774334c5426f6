"""The ``sarvam_mla`` family (sarvam-105b): what the harness needs from a
configuration file with the hub's keys of that model, beside the plain
reference (``benchmark/reference/sarvam_mla.py``).

The counts are the numerators of ``mfu.*``, ``decode_roofline.*`` and the
two kernels' roofline shares, so they count the **algorithm's least**,
never a formulation's: per token the matrices it multiplies (of the routed
experts ``per token x held / routed`` in expectation: 8 x 32 / 128 = two
experts), and dense attention over every earlier key in the cheaper of
latent attention's two forms (per-head for a prompt's tokens, whose keys
are shared by a chunk of queries; absorbed for a decoded token). 2 per
multiply-add, causal counted as causal. A count that is too high reads
over 100% one day and gets a sound optimisation refused.
"""

from __future__ import annotations

from benchmark.reference import sarvam_mla as reference

# Keys of this family's files that set a shape though their names do not
# say so: never in ``reduced``.
WIDTH_KEYS = ("num_attention_heads", "head_dim", "q_head_dim",
              "num_shared_experts", "routed_scaling_factor", "rope_theta",
              "default_theta", "rope_scaling", "rms_norm_eps", "use_qk_norm",
              "moe_router_enable_expert_bias")


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
    if s["per_token"] > s["experts"]:
        raise ValueError("more experts per token than the router has")
    if not 0 <= s["dense_layers"] <= s["layers"]:
        raise ValueError("first_k_dense_replace outside the layers held")
    if s["rope"] % 2:
        raise ValueError("qk_rope_head_dim must be even")
    if int(cfg["q_head_dim"]) != s["nope"] + s["rope"] \
            or int(cfg["head_dim"]) != s["kv_rank"] + s["rope"]:
        raise ValueError("q_head_dim is nope + rope and head_dim the cache "
                         "row, kv_lora_rank + rope")
    if s["rows"] > int(cfg["published"]["vocab_size"]):
        raise ValueError("more rows of the vocabulary than published")
    if cfg.get("q_lora_rank") is not None:
        raise ValueError("this family has no query latent")


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
    r = cfg["rope_scaling"]
    return get_model(
        "sarvam_mla", num_classes=s["rows"],
        dtype=dtypes[model_spec["dtype"]],
        logits_dtype=dtypes[model_spec["logits_dtype"]],
        num_layers=s["layers"], first_dense=s["dense_layers"],
        hidden_dim=s["d"], dense_dim=s["dense"], expert_dim=s["expert"],
        num_heads=s["heads"], kv_rank=s["kv_rank"], nope_dim=s["nope"],
        rope_dim=s["rope"], v_dim=s["v"], num_experts=s["experts"],
        held=(s["held_first"], s["held"]), experts_per_token=s["per_token"],
        routed_scale=s["routed_scale"], shared_experts=s["shared"],
        rope=(float(cfg["rope_theta"]), float(r["factor"]),
              int(r["original_max_position_embeddings"]),
              float(r["beta_fast"]), float(r["beta_slow"]),
              float(r["mscale_all_dim"])),
        max_len=int(cfg["max_position_embeddings"]), norm_eps=s["eps"])


def train_flags(cfg: dict) -> list[str]:
    raise NotImplementedError(
        "this configuration is served, not trained: at 16 bytes a parameter "
        "no cut within the guide's floors fits one chip")


def _attention_params(s: dict) -> int:
    d, h = s["d"], s["heads"]
    mla = (d * h * (s["nope"] + s["rope"]) + d * (s["kv_rank"] + s["rope"])
           + s["kv_rank"] * h * (s["nope"] + s["v"]) + h * s["v"] * d)
    norms = 2 * d + s["kv_rank"]
    return mla + norms


def _expert_params(s: dict) -> int:
    return 3 * s["d"] * s["expert"]


def _layer_params_outside_routed(s: dict, dense: bool) -> int:
    if dense:
        return _attention_params(s) + 3 * s["d"] * s["dense"]
    return (_attention_params(s) + s["d"] * s["experts"] + s["experts"]
            + s["shared"] * _expert_params(s))


def param_count(cfg: dict) -> int:
    """Parameters of the share as run."""
    s = reference.sizes(cfg)
    moe_layers = s["layers"] - s["dense_layers"]
    return (s["dense_layers"] * _layer_params_outside_routed(s, True)
            + moe_layers * (_layer_params_outside_routed(s, False)
                            + s["held"] * _expert_params(s))
            + 2 * s["rows"] * s["d"] + s["d"])


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
    outside = (s["dense_layers"] * _layer_params_outside_routed(s, True)
               + moe_layers * _layer_params_outside_routed(s, False))
    return 2.0 * (outside + moe_layers * routed + s["d"] * s["rows"])


def _absorbed_flops_key(s: dict) -> float:
    """One decoded query against one cached row, all heads: a score over
    ``kv_rank + rope``, a value over ``kv_rank``."""
    return 2.0 * s["heads"] * (2 * s["kv_rank"] + s["rope"])


def _per_head_flops_key(s: dict) -> float:
    return 2.0 * s["heads"] * (s["nope"] + s["rope"] + s["v"])


def forward_flops_token(cfg: dict, keys: float) -> float:
    """Forward FLOPs of one decoded token whose context holds ``keys``
    positions: absorbed attention over all of them."""
    s = reference.sizes(cfg)
    return _token_matmul_flops(s) + s["layers"] * _absorbed_flops_key(s) \
        * keys


def prompt_forward_flops(cfg: dict, length: int) -> float:
    """Forward FLOPs of prefilling ``length`` prompt tokens: the query at
    position t attends its t + 1 keys in the per-head form."""
    s = reference.sizes(cfg)
    return length * _token_matmul_flops(s) + s["layers"] \
        * _per_head_flops_key(s) * length * (length + 1) / 2.0


def chunk_attention_call(cfg: dict, start: int, tokens: int,
                         itemsize: int = 2) -> dict:
    """FLOPs and bytes of the attention of one prefill chunk, rows ``start
    .. start + tokens`` of a prompt, over all layers (what the kernel
    ``masked_attention`` is there for): the query at position t attends
    its t + 1 keys in the per-head form. Bytes: each query and output row
    once, and once the latent row of every key the chunk can see."""
    s = reference.sizes(cfg)
    attended = tokens * start + tokens * (tokens + 1) / 2.0
    rows = tokens * s["heads"] * (s["nope"] + s["rope"] + s["v"]) \
        + (start + tokens) * (s["kv_rank"] + s["rope"])
    return {"flops": s["layers"] * _per_head_flops_key(s) * attended,
            "bytes": s["layers"] * itemsize * rows}


def decode_attention_call(cfg: dict, live_contexts: list,
                          itemsize: int = 2) -> dict:
    """FLOPs and bytes of ONE layer's attention of a decode step whose
    slots hold ``live_contexts`` rows (what the kernel
    ``paged_latent_attention`` is there for), in the absorbed form: every
    live row's ``kv_rank + rope`` lanes read once, scored against all
    heads and weighted into ``kv_rank`` lanes."""
    s = reference.sizes(cfg)
    rows = float(sum(live_contexts))
    return {"flops": _absorbed_flops_key(s) * rows,
            "bytes": itemsize * (s["kv_rank"] + s["rope"]) * rows}


def train_flops_token(cfg: dict, seq_len: int) -> float:
    raise NotImplementedError("not trained: see train_flags")


def decode_iteration_bytes(cfg: dict, live_contexts: list,
                           itemsize: int = 2) -> float:
    """Bytes one decode iteration needs: every matrix outside the routed
    experts once; of each expert layer's held experts the expected number
    that ``n`` decoding tokens hit, ``held x (1 - (1 - per_token /
    routed)^n)``; and per slot and layer the latent row of every live
    position."""
    s = reference.sizes(cfg)
    n = len(live_contexts)
    moe_layers = s["layers"] - s["dense_layers"]
    hit = s["held"] * (1.0 - (1.0 - s["per_token"] / s["experts"]) ** n)
    weights = matmul_params_read(cfg) + moe_layers * hit * _expert_params(s)
    rows = sum(live_contexts) * (s["kv_rank"] + s["rope"])
    return itemsize * (weights + s["layers"] * rows)
