"""The DeepSeek-V3.2 family: what the harness needs from a configuration
file with the hub's DeepSeek keys, beside the plain reference
(``benchmark/reference/deepseek_v32.py``).

The counts are the numerators of ``mfu.*`` and ``decode_roofline.*``, so
they count the **algorithm's least**, never a formulation's: per token the
matrices it multiplies (of the routed experts ``per token x held /
routed`` in expectation: 8 x 16 / 256 = half an expert), the index scores
over every live key, and attention over the selected keys alone in the
cheaper of MLA's two forms (per-head for a prompt's tokens, whose keys are
shared by a chunk of queries; absorbed for a decoded token). 2 per
multiply-add, causal counted as causal. A count that is too high reads
over 100% one day and gets a sound optimisation refused.
"""

from __future__ import annotations

from benchmark.reference import deepseek_v32 as reference

# Keys of this family's files that set a shape though their names do not
# say so: never in ``reduced``.
WIDTH_KEYS = ("num_attention_heads", "num_key_value_heads", "index_n_heads",
              "n_group", "n_shared_experts", "routed_scaling_factor",
              "rope_theta", "rope_scaling", "rms_norm_eps")


def validate(cfg: dict) -> None:
    """What must hold of the file's keys."""
    s = reference.sizes(cfg)
    if s["experts"] % s["groups"]:
        raise ValueError(f"{s['groups']} groups do not divide the router's "
                         f"{s['experts']} experts")
    if not 0 < s["kept_groups"] <= s["groups"]:
        raise ValueError("topk_group outside 1..n_group")
    if s["per_token"] > s["kept_groups"] * (s["experts"] // s["groups"]):
        raise ValueError("more experts per token than the kept groups hold")
    if not (0 <= s["held_first"]
            and s["held_first"] + s["held"] <= s["experts"]):
        raise ValueError("the held experts are not among the router's")
    if not 0 <= s["dense_layers"] <= s["layers"]:
        raise ValueError("first_k_dense_replace outside the layers held")
    if s["rope"] % 2 or s["rope"] > s["index_dim"]:
        raise ValueError("qk_rope_head_dim must be even and no wider than "
                         "index_head_dim")
    if s["rows"] > int(cfg["published"]["vocab_size"]):
        raise ValueError("more rows of the vocabulary than published")
    if int(cfg["num_nextn_predict_layers"]):
        raise ValueError("the multi-token-prediction module is not run")


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
        "deepseek_v32", num_classes=s["rows"],
        dtype=dtypes[model_spec["dtype"]],
        logits_dtype=dtypes[model_spec["logits_dtype"]],
        num_layers=s["layers"], first_dense=s["dense_layers"],
        hidden_dim=s["d"], dense_dim=s["dense"], expert_dim=s["expert"],
        num_heads=s["heads"], q_rank=s["q_rank"], kv_rank=s["kv_rank"],
        nope_dim=s["nope"], rope_dim=s["rope"], v_dim=s["v"],
        index_heads=s["index_heads"], index_dim=s["index_dim"],
        index_topk=s["index_topk"], num_experts=s["experts"],
        held=(s["held_first"], s["held"]), experts_per_token=s["per_token"],
        n_group=s["groups"], topk_group=s["kept_groups"],
        routed_scale=s["routed_scale"], shared_experts=s["shared"],
        rope=(float(cfg["rope_theta"]), float(r["factor"]),
              int(r["original_max_position_embeddings"]),
              float(r["beta_fast"]), float(r["beta_slow"]),
              float(r["mscale"])),
        max_len=int(cfg["max_position_embeddings"]), norm_eps=s["eps"])


def train_flags(cfg: dict) -> list[str]:
    raise NotImplementedError(
        "this configuration is served, not trained: at 16 bytes a parameter "
        "its share does not fit one chip")


def _attention_params(s: dict) -> int:
    d, h = s["d"], s["heads"]
    mla = (d * s["q_rank"] + s["q_rank"] * h * (s["nope"] + s["rope"])
           + d * (s["kv_rank"] + s["rope"])
           + s["kv_rank"] * h * (s["nope"] + s["v"]) + h * s["v"] * d)
    indexer = (s["q_rank"] * s["index_heads"] * s["index_dim"]
               + d * s["index_dim"] + d * s["index_heads"])
    norms = 2 * d + s["q_rank"] + s["kv_rank"] + 2 * s["index_dim"]
    return mla + indexer + norms


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


def _index_flops_key(s: dict) -> float:
    return 2.0 * s["index_heads"] * s["index_dim"]


def forward_flops_token(cfg: dict, keys: float) -> float:
    """Forward FLOPs of one decoded token whose context holds ``keys``
    positions: index scores over all of them, absorbed attention (scores
    over ``kv_rank + rope``, values over ``kv_rank``) over the selected."""
    s = reference.sizes(cfg)
    selected = min(keys, s["index_topk"])
    absorbed = 2.0 * s["heads"] * (2 * s["kv_rank"] + s["rope"])
    return _token_matmul_flops(s) + s["layers"] * (
        _index_flops_key(s) * keys + absorbed * selected)


def prompt_forward_flops(cfg: dict, length: int) -> float:
    """Forward FLOPs of prefilling ``length`` prompt tokens: the query at
    position t scores t + 1 keys and attends min(t + 1, index_topk) of
    them in the per-head form (``nope + rope`` for a score, ``v`` for a
    value, per head and key)."""
    s = reference.sizes(cfg)
    k = s["index_topk"]
    scored = length * (length + 1) / 2.0
    attended = scored if length <= k else \
        k * (k + 1) / 2.0 + (length - k) * k
    per_head = 2.0 * s["heads"] * (s["nope"] + s["rope"] + s["v"])
    return length * _token_matmul_flops(s) + s["layers"] * (
        _index_flops_key(s) * scored + per_head * attended)


def chunk_attention_call(cfg: dict, start: int, tokens: int,
                         itemsize: int = 2) -> dict:
    """FLOPs and bytes of the attention of one prefill chunk, rows ``start
    .. start + tokens`` of a prompt, over all layers (what the kernel
    ``masked_attention`` is there for): the query at position t attends
    min(t + 1, index_topk) keys in the per-head form. Bytes: each query and
    output row once, and once the latent row of every key the chunk can
    see."""
    s = reference.sizes(cfg)
    k = s["index_topk"]
    attended = sum(min(t + 1, k) for t in range(start, start + tokens))
    per_head = 2.0 * s["heads"] * (s["nope"] + s["rope"] + s["v"])
    rows = tokens * s["heads"] * (s["nope"] + s["rope"] + s["v"]) \
        + (start + tokens) * (s["kv_rank"] + s["rope"])
    return {"flops": s["layers"] * per_head * attended,
            "bytes": s["layers"] * itemsize * rows}


def train_flops_token(cfg: dict, seq_len: int) -> float:
    raise NotImplementedError("not trained: see train_flags")


def decode_iteration_bytes(cfg: dict, live_contexts: list,
                           itemsize: int = 2) -> float:
    """Bytes one decode iteration needs: every matrix outside the routed
    experts once; of each expert layer's held experts the expected number
    that ``n`` decoding tokens hit, ``held x (1 - (1 - per_token /
    routed)^n)``; and per slot and layer the index key of every live row
    and the latent row of every selected one."""
    s = reference.sizes(cfg)
    n = len(live_contexts)
    moe_layers = s["layers"] - s["dense_layers"]
    hit = s["held"] * (1.0 - (1.0 - s["per_token"] / s["experts"]) ** n)
    weights = matmul_params_read(cfg) + moe_layers * hit * _expert_params(s)
    rows = sum(live * s["index_dim"]
               + min(live, s["index_topk"]) * (s["kv_rank"] + s["rope"])
               for live in live_contexts)
    return itemsize * (weights + s["layers"] * rows)
