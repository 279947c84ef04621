"""The operations and bytes the algorithms need, from static shapes.

Model FLOP per token for training is forward + backward with NO
recompute (remat's second forward does not count): ``6 x`` the matmul
parameters plus the causal attention term. The arithmetic is
``bench.py``'s (``count_params``; ``6 N + 12 L H Dh T`` halved for the
causal mask), copied here so that no later PR can change the yardstick.
"""
from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Parameters that take part in a matmul per token: embedding and
    head counted as ``bench.count_params`` does (both)."""
    D, L, V = m["hidden_size"], m["num_hidden_layers"], m["vocab_size"]
    H, Hkv, Dh, F = (m["num_attention_heads"], m["num_key_value_heads"],
                     m["head_dim"], m["intermediate_size"])
    return (V * D * 2
            + L * (D * H * Dh + 2 * D * Hkv * Dh + H * Dh * D + 3 * D * F))


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward + backward of a dense decoder, per token: ``6`` per
    matmul parameter (the embedding lookup is not a matmul, so only the
    head of the ``2 V D`` counts), plus causal attention: QK^T and PV
    are ``2 * 2 * T * H * Dh`` per token per layer forward over the
    full square, half of it under the causal mask, times 3 for
    forward + backward."""
    D, V = m["hidden_size"], m["vocab_size"]
    L, H, Dh = (m["num_hidden_layers"], m["num_attention_heads"],
                m["head_dim"])
    dense = 6.0 * (matmul_params(m) - V * D)
    attn = 3.0 * L * (4.0 * seq_len * H * Dh) / 2.0
    return dense + attn


def splash_flops_and_bytes(m: dict, batch: int, seq_len: int,
                           heads: int | None = None,
                           kv_heads: int | None = None,
                           itemsize: int = 2) -> dict:
    """What causal flash attention needs for ONE layer's forward and
    backward at ``[batch, seq_len]``: only the causal half of the
    square. Forward: QK^T and PV (2 matmuls). Backward, as the
    algorithm needs it (flash recomputes the scores by design — that is
    the algorithm, not remat): QK^T again, dP = dO V^T, dV = P^T dO,
    dQ = dS K, dK = dS^T Q (5 matmuls). Bytes: q, k, v, o read or
    written once forward; q, k, v, o, dO read and dq, dk, dv written
    backward (the least traffic; a kernel that re-reads K/V per query
    block moves more)."""
    H = heads if heads is not None else m["num_attention_heads"]
    Hkv = kv_heads if kv_heads is not None else m["num_key_value_heads"]
    Dh = m["head_dim"]
    square = 2.0 * batch * H * seq_len * seq_len * Dh   # one full matmul
    fwd_flops = 2 * square / 2.0
    bwd_flops = 5 * square / 2.0
    q_bytes = batch * seq_len * H * Dh * itemsize
    kv_bytes = batch * seq_len * Hkv * Dh * itemsize
    fwd_bytes = 2 * q_bytes + 2 * kv_bytes
    bwd_bytes = 4 * q_bytes + 4 * kv_bytes
    return {"fwd_flops": fwd_flops, "bwd_flops": bwd_flops,
            "fwd_bytes": fwd_bytes, "bwd_bytes": bwd_bytes}


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """``(least seconds, which bound)``."""
    t_f = flops / peak["bf16_flops"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
