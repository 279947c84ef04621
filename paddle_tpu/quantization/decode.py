"""Post-training weight-only int8 quantization of the decode models.

Reference capability: the PTQ-deploy pipeline (python/paddle/quantization/
ptq.py convert + the int8 weight-only GEMMs it deploys onto). The layer
quantizers in ``paddle_tpu.quantization`` operate on ``nn.Layer`` models;
THIS module is the functional-pytree counterpart for the flagship decode
stacks (models/llama.py, models/qwen2_moe.py), whose params are plain
pytrees consumed by ``lax.scan``.

``quantize_for_decode(params, cfg)`` replaces every matmul projection
that dominates decode's weight stream with an
``ops.fused.int8_matmul.Int8Weight`` (symmetric int8 + per-output-channel
f32 scale, one scale per (layer[, expert], out_channel)):

  llama:     wq wk wv wo w_gate w_up w_down, lm_head
  qwen2_moe: wq wk wv wo, routed experts w_gate/w_up/w_down,
             shared expert w_gate/w_up/w_down, lm_head

Deliberately NOT quantized:
  * embed — consumed by row lookup, not matmul; one row (D·2 bytes) per
    step is already negligible traffic;
  * norms (attn/mlp/final) — O(D) vectors;
  * qwen's router — kept f32 by design for stable top-k softmax (a
    routing flip is a much larger numeric event than a logit wobble),
    and it is O(D·E) — noise traffic;
  * qwen's shared-expert sigmoid gate — O(D·1).

The quantized pytree drops into every decode entry point unchanged —
``generate``, ``generate_paged``, ``serving_tick`` /
``serving_tick_block`` — because the model bodies dispatch
matmuls through ``_mm`` (dense array or Int8Weight).
Training paths are out of scope: quantize AFTER training, for serving.
"""
from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

from ..ops.fused.int8_matmul import Int8Weight

__all__ = ["quantize_for_decode", "dequantize_for_decode",
           "is_quantized_params", "decode_weight_bytes"]

_LLAMA_LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
_QWEN_ATTN_KEYS = ("wq", "wk", "wv", "wo")
_FFN_KEYS = ("w_gate", "w_up", "w_down")


def _is_moe(cfg) -> bool:
    return hasattr(cfg, "num_experts")


def quantize_for_decode(params: Dict[str, Any], cfg, *,
                        quantize_lm_head: bool = True) -> Dict[str, Any]:
    """params (llama- or qwen2_moe-family pytree) -> a new pytree whose
    projection weights are ``Int8Weight``s. Model family comes from the
    config shape (``num_experts`` present = MoE). Idempotent-hostile by
    design: quantizing an already-quantized tree raises (re-quantizing
    int8 through f32 would silently double the error)."""
    if is_quantized_params(params):
        raise ValueError("params are already weight-only quantized")
    layers = dict(params["layers"])
    if _is_moe(cfg):
        for k in _QWEN_ATTN_KEYS:
            layers[k] = Int8Weight.quantize(layers[k])
        experts = dict(layers["experts"])
        for k in _FFN_KEYS:
            # [L, E, D, F]: per-(layer, expert, out-channel) scales
            experts[k] = Int8Weight.quantize(experts[k])
        layers["experts"] = experts
        shared = dict(layers["shared"])
        for k in _FFN_KEYS:
            shared[k] = Int8Weight.quantize(shared[k])
        layers["shared"] = shared
    else:
        for k in _LLAMA_LAYER_KEYS:
            layers[k] = Int8Weight.quantize(layers[k])
    out = dict(params, layers=layers)
    if quantize_lm_head:
        out["lm_head"] = Int8Weight.quantize(params["lm_head"])
    return out


def dequantize_for_decode(params: Dict[str, Any],
                          dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Inverse structural map: every Int8Weight becomes its dense
    ``dtype`` approximation (for A/B numerics, not a bit-exact undo)."""
    def walk(node):
        if isinstance(node, Int8Weight):
            return node.dequant(dtype)
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return node
    return walk(params)


def is_quantized_params(params) -> bool:
    def any_q(node) -> bool:
        if isinstance(node, Int8Weight):
            return True
        if isinstance(node, dict):
            return any(any_q(v) for v in node.values())
        return False
    return any_q(params)


def decode_weight_bytes(params) -> int:
    """HBM bytes the decode step streams for weights: every leaf's
    nbytes (int8 q + f32 scales for quantized, full dtype otherwise),
    EXCEPT the embedding table — decode reads one row per token, so the
    table's size is not per-step traffic (its row is counted instead)."""
    import numpy as np

    def leaf_bytes(node) -> int:
        if isinstance(node, Int8Weight):
            return int(node.q.size) * 1 + int(node.scale.size) * 4
        if isinstance(node, dict):
            return sum(leaf_bytes(v) for v in node.values())
        if hasattr(node, "size") and hasattr(node, "dtype"):
            return int(node.size) * np.dtype(node.dtype).itemsize
        return 0

    total = sum(leaf_bytes(v) for k, v in params.items() if k != "embed")
    emb = params.get("embed")
    if emb is not None:
        # one row lookup per decode step
        total += int(emb.shape[-1]) * np.dtype(emb.dtype).itemsize
    return total
