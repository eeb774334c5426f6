"""The ``keye_vl2`` family (the language model of Keye-VL-2.0-30B-A3B): what
the harness needs from a configuration file with the hub's keys of that
model, beside the plain reference (``benchmark/reference/keye_vl2.py``).

The counts are the numerators of ``mfu.*``, ``decode_roofline.*`` and the
chunk kernel's roofline share, so they count the **algorithm's least**,
never a formulation's: per token the matrices it multiplies (of the routed
experts ``per token x held / routed`` in expectation: all 8 where all 128
are held), the index scores over every live key, and grouped-query
attention over the selected keys alone (a score and a value of ``head_dim``
per query head and key; a key's K and V row read once for the 8 query heads
it serves). 2 per multiply-add, causal counted as causal. A count that is
too high reads over 100% one day and gets a sound optimisation refused.
"""

from __future__ import annotations

from benchmark.reference import keye_vl2 as reference

# Keys of this family's files that set a shape though their names do not
# say so: never in ``reduced``.
WIDTH_KEYS = ("num_attention_heads", "num_key_value_heads", "sa_config",
              "rope_theta", "rope_scaling", "rms_norm_eps", "norm_topk_prob",
              "decoder_sparse_step", "mlp_only_layers")


def validate(cfg: dict) -> None:
    """What must hold of the file's keys."""
    s = reference.sizes(cfg)
    if s["heads"] % s["kv_heads"]:
        raise ValueError("num_key_value_heads does not divide "
                         "num_attention_heads")
    if s["head_dim"] % 2 or s["index_dim"] % 2:
        raise ValueError("head_dim and indexer_head_dim must be even: rotary "
                         "turns pairs")
    if len(s["sections"]) != 3 or sum(s["sections"]) != s["head_dim"] // 2:
        raise ValueError("mrope_section must hold three counts that sum to "
                         "head_dim / 2")
    if int(cfg["sa_config"]["indexer_num_kv_heads"]) != 1:
        raise ValueError("the indexer has one key head a token")
    if not (0 <= s["held_first"]
            and s["held_first"] + s["held"] <= s["experts"]):
        raise ValueError("the held experts are not among the router's")
    # the accepted readers' keys: expert_imbalance.tokens divides by
    # n_routed_experts, the hub's file repeats the count as num_local_experts
    for key in ("n_routed_experts", "num_local_experts"):
        if int(cfg.get(key, s["held"])) != s["held"]:
            raise ValueError(f"{key} is not the count of experts held, "
                             "num_experts")
    # expert_hit_share.tokens takes the layers after first_k_dense_replace
    # for the expert layers: every layer of this model is one
    if int(cfg.get("first_k_dense_replace", 0)) != 0 \
            or int(cfg["decoder_sparse_step"]) != 1 or cfg["mlp_only_layers"]:
        raise ValueError("every layer is an expert layer: "
                         "first_k_dense_replace 0, decoder_sparse_step 1, "
                         "mlp_only_layers []")
    if s["per_token"] > s["experts"]:
        raise ValueError("more experts per token than the router has")
    if not cfg["norm_topk_prob"]:
        raise ValueError("the chosen experts' weights are renormalised")
    if s["rows"] > int(cfg["published"]["vocab_size"]):
        raise ValueError("more rows of the vocabulary than published")
    if cfg.get("use_sliding_window") or cfg.get("tie_word_embeddings") \
            or cfg.get("attention_bias"):
        raise ValueError("no sliding window, no tied head, no attention bias")


def token_ids(cfg: dict) -> int:
    """Traffic draws token ids below this (the rows of the vocabulary)."""
    return int(cfg["vocab_size"])


def build_model(cfg: dict, model_spec: dict):
    """The program's model for the serving driver; ``model_spec`` is the
    traffic file's ``model`` group (the types it is served in)."""
    import jax.numpy as jnp

    from distributed_training_tpu.models import get_model

    dtypes = {"bf16": jnp.bfloat16, "fp32": jnp.float32}
    s = reference.sizes(cfg)
    return get_model(
        "keye_vl2", num_classes=s["rows"],
        dtype=dtypes[model_spec["dtype"]],
        logits_dtype=dtypes[model_spec["logits_dtype"]],
        num_layers=s["layers"], hidden_dim=s["d"], expert_dim=s["expert"],
        num_heads=s["heads"], num_kv_heads=s["kv_heads"],
        head_dim=s["head_dim"], index_heads=s["index_heads"],
        index_dim=s["index_dim"], index_topk=s["index_topk"],
        num_experts=s["experts"], held=(s["held_first"], s["held"]),
        experts_per_token=s["per_token"], rope_theta=s["theta"],
        mrope_section=s["sections"],
        max_len=int(cfg["max_position_embeddings"]), norm_eps=s["eps"])


def train_flags(cfg: dict) -> list[str]:
    raise NotImplementedError(
        "this configuration is served, not trained: no backward pass through "
        "the selection, the masked kernel and the dropless expert layer "
        "exists in the program")


def _attention_params(s: dict) -> int:
    d, hd = s["d"], s["head_dim"]
    gqa = 2 * d * s["heads"] * hd + 2 * d * s["kv_heads"] * hd
    indexer = (d * s["index_heads"] * s["index_dim"] + d * s["index_dim"]
               + d * s["index_heads"])
    norms = 2 * d + 2 * hd + 2 * s["index_dim"]
    return gqa + indexer + norms


def _expert_params(s: dict) -> int:
    return 3 * s["d"] * s["expert"]


def _layer_params_outside_routed(s: dict) -> int:
    return _attention_params(s) + s["d"] * s["experts"]


def param_count(cfg: dict) -> int:
    """Parameters of the stage as run."""
    s = reference.sizes(cfg)
    return (s["layers"] * (_layer_params_outside_routed(s)
                           + s["held"] * _expert_params(s))
            + 2 * s["rows"] * s["d"] + s["d"])


def matmul_params_read(cfg: dict) -> int:
    """Parameters a forward pass reads whatever the batch: everything but
    the embedding table (gathered by row) and the routed experts (read by
    the tokens routed to them: :func:`decode_iteration_bytes`)."""
    s = reference.sizes(cfg)
    return (param_count(cfg) - s["rows"] * s["d"]
            - s["layers"] * s["held"] * _expert_params(s))


def _token_matmul_flops(s: dict) -> float:
    """FLOPs of the matrices one token multiplies, the head included; of
    the routed experts its expected share of the held ones."""
    routed = s["per_token"] * s["held"] / s["experts"] * _expert_params(s)
    return 2.0 * (s["layers"] * (_layer_params_outside_routed(s) + routed)
                  + s["d"] * s["rows"])


def _index_flops_key(s: dict) -> float:
    return 2.0 * s["index_heads"] * s["index_dim"]


def _attend_flops_key(s: dict) -> float:
    """One query against one selected key, all query heads: a score and a
    value of ``head_dim`` each."""
    return 2.0 * s["heads"] * 2 * s["head_dim"]


def _attended(start: int, tokens: int, k: int) -> float:
    """Keys the queries at positions ``start .. start + tokens`` attend:
    the query at position t its ``min(t + 1, k)`` selected ones."""
    return float(sum(min(t + 1, k) for t in range(start, start + tokens)))


def forward_flops_token(cfg: dict, keys: float) -> float:
    """Forward FLOPs of one decoded token whose context holds ``keys``
    positions: index scores over all of them, attention over the
    selected."""
    s = reference.sizes(cfg)
    return _token_matmul_flops(s) + s["layers"] * (
        _index_flops_key(s) * keys
        + _attend_flops_key(s) * min(keys, s["index_topk"]))


def prompt_forward_flops(cfg: dict, length: int) -> float:
    """Forward FLOPs of prefilling ``length`` prompt tokens: the query at
    position t scores t + 1 index keys and attends min(t + 1, topk)."""
    s = reference.sizes(cfg)
    scored = length * (length + 1) / 2.0
    return length * _token_matmul_flops(s) + s["layers"] * (
        _index_flops_key(s) * scored
        + _attend_flops_key(s) * _attended(0, length, s["index_topk"]))


def chunk_attention_call(cfg: dict, start: int, tokens: int,
                         itemsize: int = 2) -> dict:
    """FLOPs and bytes of the attention of one prefill chunk, rows ``start
    .. start + tokens`` of a prompt, over all layers (what the kernel
    ``masked_attention`` is there for, in its grouped form): the query at
    position t attends min(t + 1, topk) keys. Bytes: each query and output
    row once, and once the K and V row of every key the chunk can see — a
    key head's row serves its 8 query heads from one read."""
    s = reference.sizes(cfg)
    rows = tokens * 2 * s["heads"] * s["head_dim"] \
        + (start + tokens) * 2 * s["kv_heads"] * s["head_dim"]
    return {"flops": s["layers"] * _attend_flops_key(s)
            * _attended(start, tokens, s["index_topk"]),
            "bytes": s["layers"] * itemsize * rows}


def train_flops_token(cfg: dict, seq_len: int) -> float:
    raise NotImplementedError("not trained: see train_flags")


def decode_iteration_bytes(cfg: dict, live_contexts: list,
                           itemsize: int = 2) -> float:
    """Bytes one decode iteration needs: every matrix outside the routed
    experts once; of each layer's held experts the expected number that
    ``n`` decoding tokens hit, ``held x (1 - (1 - per_token / routed)^n)``;
    and per slot and layer the index key of every live row and the K and V
    row of every selected one."""
    s = reference.sizes(cfg)
    n = len(live_contexts)
    hit = s["held"] * (1.0 - (1.0 - s["per_token"] / s["experts"]) ** n)
    weights = matmul_params_read(cfg) \
        + s["layers"] * hit * _expert_params(s)
    rows = sum(live * s["index_dim"] + min(live, s["index_topk"])
               * 2 * s["kv_heads"] * s["head_dim"] for live in live_contexts)
    return itemsize * (weights + s["layers"] * rows)
