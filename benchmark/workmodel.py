"""Operations and bytes the algorithm needs, from shapes alone.

Matmul convention (2 per multiply-add), no recomputation counted, and
causal attention counted as causal: a query at position t attends t + 1
keys. These are the numerators of every ``mfu.*`` and ``*_roofline``.
"""

from __future__ import annotations


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


def causal_attention_call(batch: int, heads: int, seq: int, head_dim: int,
                          itemsize: int = 2) -> dict:
    """FLOPs and bytes of one causal attention call, forward and backward.

    Forward: QK^T and PV over the causal half. Backward: dV, dP, dQ, dK
    (four products; the recomputed scores are not counted). Bytes: each
    operand and result crosses HBM once (q, k, v, o forward; q, k, v, o,
    do in and dq, dk, dv out backward), row statistics ignored."""
    pairs = batch * heads * seq * (seq + 1) / 2.0
    tensor = batch * heads * seq * head_dim * itemsize
    return {"fwd_flops": 4.0 * pairs * head_dim,
            "bwd_flops": 8.0 * pairs * head_dim,
            "fwd_bytes": 4.0 * tensor, "bwd_bytes": 8.0 * tensor}


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """The least time the chip could take, and which peak bounds it."""
    tf = flops / peaks["flops_per_s"]
    tb = nbytes / peaks["bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")


def decode_iteration_bytes(cfg: dict, live_context_rows: int,
                           itemsize: int = 2) -> float:
    """Bytes one decode iteration needs: the weights once, plus the K and
    V rows of the live context of live slots (not the pool, not the
    budget)."""
    s = dims(cfg)
    kv = 2 * s["layers"] * s["d"] * itemsize * live_context_rows
    return matmul_params_read(cfg) * itemsize + kv
