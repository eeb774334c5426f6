"""Operations and bytes the algorithm needs, from shapes alone, and the
look-up from a configuration to its family's counts.

What depends on a configuration file's keys (parameters, FLOPs of a token,
bytes of a decode iteration) is counted by the family's own file,
``benchmark/families/<reference>.py``; what is here needs shapes only.
"""

from __future__ import annotations

import importlib


def family(cfg: dict):
    """The module of the configuration's family, named by its ``reference``
    key: ``validate``, ``build_model``, ``train_flags``, ``token_ids`` and
    the counts ``param_count``, ``matmul_params_read``,
    ``forward_flops_token``, ``prompt_forward_flops``, ``train_flops_token``
    and ``decode_iteration_bytes``, each taking the configuration first."""
    return importlib.import_module(f"benchmark.families.{cfg['reference']}")


def reference(cfg: dict):
    """The plain reference of the configuration's family."""
    return importlib.import_module(f"benchmark.reference.{cfg['reference']}")


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
