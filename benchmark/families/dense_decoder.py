"""Family ``dense_decoder``: Llama/Mistral-shaped decoders, served and
trained through the program's ``models/llama.py``.

``make_params`` is the benchmark's own weight recipe (the reference may
take nothing the program made): normal(0, 1/sqrt(fan_in)) from the
seed, one jitted call, born in the served dtype, in the pytree layout
``models/llama.py`` documents (per-layer tensors stacked on a leading
layer axis).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

REFERENCE_KIND = "dense"
CONTROL_ROUND_TO = 3    # mantissa bits of float8 e4m3: the step below bfloat16


def seed_key(seed: int):
    """--seed may exceed 31 bits: fold the high part in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def dtype_of(model: dict):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        model["torch_dtype"]]


def param_shapes(m: dict) -> dict:
    D, F, V = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    H, Hkv, Dh = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    L = m["num_hidden_layers"]
    return {
        "embed": ((V, D), D), "lm_head": ((D, V), D),
        "layers": {
            "wq": ((L, D, H * Dh), D), "wk": ((L, D, Hkv * Dh), D),
            "wv": ((L, D, Hkv * Dh), D), "wo": ((L, H * Dh, D), H * Dh),
            "w_gate": ((L, D, F), D), "w_up": ((L, D, F), D),
            "w_down": ((L, F, D), F)},
        "norms": {"final_norm": (D,),
                  "layers": {"attn_norm": (L, D), "mlp_norm": (L, D)}},
    }


def param_count(m: dict) -> int:
    sh = param_shapes(m)
    mats = [sh["embed"], sh["lm_head"], *sh["layers"].values()]
    return int(sum(np.prod(s) for s, _ in mats)
               + np.prod(sh["norms"]["final_norm"])
               + sum(np.prod(s) for s in sh["norms"]["layers"].values()))


@partial(jax.jit, static_argnames=("shapes", "dtype"))
def _make(key, *, shapes, dtype):
    names, specs = zip(*shapes)
    keys = jax.random.split(key, len(names))
    return {n: (jax.random.normal(k, s, jnp.float32)
                / np.sqrt(fan)).astype(dtype)
            for n, (s, fan), k in zip(names, specs, keys)}


def make_params(model: dict, seed: int) -> dict:
    sh, dt = param_shapes(model), dtype_of(model)
    flat = [("embed", sh["embed"]), ("lm_head", sh["lm_head"])] + [
        (f"layers.{k}", v) for k, v in sh["layers"].items()]
    made = _make(seed_key(seed), shapes=tuple(flat), dtype=dt)
    layers = {k.split(".", 1)[1]: v for k, v in made.items()
              if k.startswith("layers.")}
    for k, s in sh["norms"]["layers"].items():
        layers[k] = jnp.ones(s, dt)
    return {"embed": made["embed"], "layers": layers,
            "final_norm": jnp.ones(sh["norms"]["final_norm"], dt),
            "lm_head": made["lm_head"]}


def program_config(model: dict, **kw):
    """The program's config object and model module for these published
    keys. Policy knobs stay at the program's defaults unless a mode's
    own rules (``kw``) set them."""
    from paddle_tpu.models import llama as L
    if model["head_dim"] * model["num_attention_heads"] != model[
            "hidden_size"]:
        raise SystemExit("models/llama.py derives head_dim as "
                         "hidden_size / num_attention_heads")
    if model.get("sliding_window") is not None:
        raise SystemExit("models/llama.py has no sliding window")
    cfg = L.LlamaConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_hidden_layers=model["num_hidden_layers"],
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        rms_norm_eps=model["rms_norm_eps"], rope_theta=model["rope_theta"],
        dtype=dtype_of(model), **kw)
    return cfg, L


def kv_bytes_per_token(model: dict) -> int:
    return (2 * model["num_key_value_heads"] * model["head_dim"]
            * model["num_hidden_layers"] * jnp.dtype(dtype_of(model)).itemsize)
