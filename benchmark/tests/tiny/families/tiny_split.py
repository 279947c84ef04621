"""Family ``tiny_split``: the proof that a family made only of added
files brings its own layers. It serves through ``models/llama.py`` as
``dense_decoder`` does (weights, the program's config: that family's),
but its reference is its own: the dense block written out here, walked
as TWO groups of layers (the leading one, then the rest) as a model with
a leading layer of another shape would be, and a scope and a kernel that
only this file names. It has no ``REFERENCE_KIND``.
"""
from __future__ import annotations

import jax

from bench_family_dense_decoder import (CONTROL_ROUND_TO,  # noqa: F401
                                        make_params, param_count,
                                        program_config, seed_key)
from harness.reference import (_a, _w, causal_attention, rms_norm, rotary,
                               swiglu)

SCOPES = ("attn.split_latent",)
KERNELS = {"attn.split_latent.kernel": r"^split_latent_attention"}


def split_layer(lp, h, positions, m, round_to=None):
    """Pre-norm GQA attention with half-split rotary embedding, then
    pre-norm SwiGLU, both residual."""
    T = h.shape[0]
    H, Hkv, Dh = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    x = _a(rms_norm(h, _w(lp["attn_norm"]), m["rms_norm_eps"]), round_to)
    q = rotary((x @ _w(lp["wq"], round_to)).reshape(T, H, Dh), positions,
               m["rope_theta"])
    k = rotary((x @ _w(lp["wk"], round_to)).reshape(T, Hkv, Dh), positions,
               m["rope_theta"])
    v = (x @ _w(lp["wv"], round_to)).reshape(T, Hkv, Dh)
    h = h + _a(causal_attention(q, k, v), round_to) @ _w(lp["wo"], round_to)
    x = rms_norm(h, _w(lp["mlp_norm"]), m["rms_norm_eps"])
    return h + swiglu(x, _w(lp["w_gate"], round_to), _w(lp["w_up"], round_to),
                      _w(lp["w_down"], round_to), round_to)


def reference_layers(params, model):
    def rows(lo, hi):
        return jax.tree_util.tree_map(lambda a: a[lo:hi], params["layers"])
    return [(split_layer, rows(0, 1)), (split_layer, rows(1, None))]
