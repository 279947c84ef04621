"""Family ``qwen2_moe``: Qwen2-MoE-shaped decoders (GQA attention, a
softmax router over all experts, top-k routed SwiGLU experts, a shared
expert behind a sigmoid gate), served through the program's
``models/qwen2_moe.py``.

``make_params`` is the benchmark's own recipe: normal(0, 1/sqrt(fan_in))
matrices in the served dtype, the router float32 normal x 0.02 (as the
program keeps it), one jitted call from the seed, in the pytree layout
``models/qwen2_moe.py`` documents.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench_family_dense_decoder import (CONTROL_ROUND_TO,  # noqa: F401
                                        _make, dtype_of, seed_key)

REFERENCE_KIND = "moe"


def param_shapes(m: dict) -> dict:
    D, V = m["hidden_size"], m["vocab_size"]
    H, Hkv, Dh = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    L, E = m["num_hidden_layers"], m["num_experts"]
    Fm, Fs = m["moe_intermediate_size"], m["shared_expert_intermediate_size"]
    return {
        "embed": ((V, D), D), "lm_head": ((D, V), D),
        "layers.wq": ((L, D, H * Dh), D), "layers.wk": ((L, D, Hkv * Dh), D),
        "layers.wv": ((L, D, Hkv * Dh), D),
        "layers.wo": ((L, H * Dh, D), H * Dh),
        "layers.experts.w_gate": ((L, E, D, Fm), D),
        "layers.experts.w_up": ((L, E, D, Fm), D),
        "layers.experts.w_down": ((L, E, Fm, D), Fm),
        "layers.shared.w_gate": ((L, D, Fs), D),
        "layers.shared.w_up": ((L, D, Fs), D),
        "layers.shared.w_down": ((L, Fs, D), Fs),
        "layers.shared.gate": ((L, D, 1), D),
        # float32, std 0.02: fan = 1 / 0.02^2
        "layers.router": ((L, D, E), 2500.0),
    }


def param_count(m: dict) -> int:
    return int(sum(np.prod(s) for s, _ in param_shapes(m).values())
               + m["hidden_size"] * (2 * m["num_hidden_layers"] + 1))


def make_params(model: dict, seed: int) -> dict:
    import jax
    sh, dt = param_shapes(model), dtype_of(model)
    router = sh.pop("layers.router")
    made = _make(seed_key(seed), shapes=tuple(sh.items()), dtype=dt)
    made.update(_make(jax.random.fold_in(seed_key(seed), 1),
                      shapes=(("layers.router", router),),
                      dtype=jnp.float32))
    L, D = model["num_hidden_layers"], model["hidden_size"]
    out = {"final_norm": jnp.ones((D,), dt),
           "layers": {"attn_norm": jnp.ones((L, D), dt),
                      "mlp_norm": jnp.ones((L, D), dt)}}
    for name, arr in made.items():
        node = out
        *path, leaf = name.split(".")
        for k in path:
            node = node.setdefault(k, {})
        node[leaf] = arr
    return out


def program_config(model: dict, **kw):
    from paddle_tpu.models import qwen2_moe as Q
    if model["head_dim"] * model["num_attention_heads"] != model[
            "hidden_size"]:
        raise SystemExit("models/qwen2_moe.py derives head_dim as "
                         "hidden_size / num_attention_heads")
    if model.get("norm_topk_prob"):
        raise SystemExit("models/qwen2_moe.py serves with unnormalised "
                         "top-k weights only")
    cfg = Q.Qwen2MoeConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        num_hidden_layers=model["num_hidden_layers"],
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        rms_norm_eps=model["rms_norm_eps"], rope_theta=model["rope_theta"],
        num_experts=model["num_experts"],
        num_experts_per_tok=model["num_experts_per_tok"],
        moe_intermediate_size=model["moe_intermediate_size"],
        shared_expert_intermediate_size=model[
            "shared_expert_intermediate_size"],
        dtype=dtype_of(model), **kw)
    return cfg, Q
