"""The GPT-2 family: what the harness needs from a configuration file with
GPT-2's keys, beside the plain reference (``benchmark/reference/gpt2.py``).

Counts follow the matmul convention (2 per multiply-add), count no
recomputation, and count causal attention as causal: a query at position t
attends t + 1 keys. They are the numerators of every ``mfu.*`` and
``decode_roofline.*``.
"""

from __future__ import annotations

# Keys of this family's files that are widths though their names do not say
# so (``n_head`` at a fixed ``n_embd`` sets the head size): never in
# ``reduced``.
WIDTH_KEYS = ("n_embd", "n_inner", "n_head")


def validate(cfg: dict) -> None:
    """What must hold of the file's keys."""
    if int(cfg["n_embd"]) % int(cfg["n_head"]):
        raise ValueError(f"n_embd {cfg['n_embd']} is not a multiple of "
                         f"n_head {cfg['n_head']}")


def token_ids(cfg: dict) -> int:
    """Traffic draws token ids below this."""
    return int(cfg["vocab_size"])


def build_model(cfg: dict, model_spec: dict):
    """The program's model for the serving driver; ``model_spec`` is the
    traffic file's ``model`` group (the types it is served in)."""
    import jax.numpy as jnp

    from distributed_training_tpu.models import get_model

    dtypes = {"bf16": jnp.bfloat16, "fp32": jnp.float32}
    return get_model(
        "transformer_lm",
        num_classes=int(cfg["assumed"]["padded_vocab_size"]),
        dtype=dtypes[model_spec["dtype"]], num_layers=int(cfg["n_layer"]),
        num_heads=int(cfg["n_head"]), hidden_dim=int(cfg["n_embd"]),
        max_len=int(cfg["n_positions"]),
        head_bias=bool(cfg["assumed"]["head_bias"]),
        logits_dtype=dtypes[model_spec["logits_dtype"]])


def train_flags(cfg: dict) -> list[str]:
    """The model flags of ``gpt/jax_tpu/train.py``."""
    return ["--num-layers", str(cfg["n_layer"]),
            "--num-heads", str(cfg["n_head"]),
            "--hidden-dim", str(cfg["n_embd"]),
            "--max-len", str(cfg["n_positions"]),
            "--vocab-size", str(cfg["assumed"]["padded_vocab_size"])]


def dims(cfg: dict) -> dict:
    """The sizes the formulas use, from a configuration file's keys."""
    d = int(cfg["n_embd"])
    return {
        "layers": int(cfg["n_layer"]), "heads": int(cfg["n_head"]),
        "d": d, "head_dim": d // int(cfg["n_head"]),
        "mlp": int(cfg.get("n_inner") or 4 * d),
        "rows": int(cfg["assumed"]["padded_vocab_size"]),
        "positions": int(cfg["n_positions"]),
    }


def param_count(cfg: dict) -> int:
    """Parameters of the model as run (untied head, no head bias)."""
    s = dims(cfg)
    d, m = s["d"], s["mlp"]
    per_layer = (3 * d * d + 3 * d) + (d * d + d) + (d * m + m) \
        + (m * d + d) + 4 * d
    return (s["layers"] * per_layer + 2 * s["rows"] * d
            + s["positions"] * d + 2 * d)


def matmul_params_read(cfg: dict) -> int:
    """Parameters a forward pass must read whatever the batch: every
    layer's matrices, biases and norms, the final norm and the output
    head. Embedding tables are gathered by row and not counted."""
    s = dims(cfg)
    return param_count(cfg) - s["rows"] * s["d"] - s["positions"] * s["d"]


def forward_flops_token(cfg: dict, keys: float) -> float:
    """Forward FLOPs of one token that attends ``keys`` positions."""
    s = dims(cfg)
    d, m = s["d"], s["mlp"]
    per_layer = 2 * d * 3 * d + 2 * d * d + 4 * d * m + 4 * d * keys
    return s["layers"] * per_layer + 2 * d * s["rows"]


def train_flops_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs per trained token (backward = 2 x forward),
    mean over the positions of a causal sequence of ``seq_len``."""
    return 3.0 * forward_flops_token(cfg, (seq_len + 1) / 2.0)


def prompt_forward_flops(cfg: dict, length: int) -> float:
    """Forward FLOPs of prefilling ``length`` prompt tokens."""
    return length * forward_flops_token(cfg, (length + 1) / 2.0)


def decode_iteration_bytes(cfg: dict, live_contexts: list,
                           itemsize: int = 2) -> float:
    """Bytes one decode iteration needs: the weights once, plus the K and
    V rows of the live context of each decoding slot (not the pool, not
    the budget). Dense attention reads every live row, so only their sum
    counts here."""
    s = dims(cfg)
    kv = 2 * s["layers"] * s["d"] * itemsize * sum(live_contexts)
    return matmul_params_read(cfg) * itemsize + kv
