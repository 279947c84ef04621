"""A family may re-lay its weights for serving, once (PR 49).

``llama`` and ``qwen2_moe`` bring ``serving_params``: the engine's tree
holds ``layers.wq`` / ``.wk`` / ``.wv`` output-major (``[L, O, D]``,
under ``wq_om`` ...), and the block contracts over the last axis of
what it finds there (``models/llama.py: _proj``). The caller's tree
keeps ``[L, D, O]`` and is not touched; an int8 tree and a family
without the function pass through. At the tiny widths ``wq`` is square
(64 x 64: a shape could not tell the layouts apart) and ``wk`` / ``wv``
are not (64 x 32).
"""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models.llama import OUTPUT_MAJOR
from paddle_tpu.models.serving_tick import serving_tick, serving_tick_block
from paddle_tpu.serving import ServingEngine

FAMILIES = ("llama", "qwen2_moe")
QKV = ("wq", "wk", "wv")
S, PS, PPS = 3, 4, 4


def _family(name):
    mod = importlib.import_module(f"paddle_tpu.models.{name}")
    cls = {"llama": "LlamaConfig", "qwen2_moe": "Qwen2MoeConfig"}[name]
    cfg = getattr(mod, cls).tiny(dtype=jnp.float32,
                                 use_flash_attention=False, remat=False)
    params = mod.init_params(cfg, jax.random.PRNGKey(5))
    D, Dh = cfg.hidden_size, cfg.head_dim
    assert params["layers"]["wq"].shape[1:] == (D, D)
    assert params["layers"]["wk"].shape[1:] == (D, cfg.num_key_value_heads
                                                * Dh) != (D, D)
    return mod, cfg, params


def _engine(params, cfg, **kw):
    return ServingEngine(params, cfg, max_batch=S, page_size=PS,
                         max_prompt_len=12, max_new_tokens_cap=8,
                         prefill_chunk=6, **kw)


def _tick_meta():
    """Slot 0 prefills a whole prompt of 5 (so its tail is live), slot 1
    decodes at position 3, slot 2 is dead; a padding row in between."""
    width = S + 6
    tables = 1 + np.arange(S * PPS, dtype=np.int32).reshape(S, PPS)
    slot = np.full((width,), S, np.int32)
    pos = np.zeros((width,), np.int32)
    qoff = np.zeros((width,), np.int32)
    slot[0:5], pos[0:5], qoff[0:5] = 0, np.arange(5), np.arange(5)
    slot[6], pos[6] = 1, 3
    real = slot < S
    page = np.where(real, tables[np.minimum(slot, S - 1), pos // PS], 0)
    meta = dict(tok_slot=slot, tok_pos=pos, tok_page=page,
                tok_off=np.where(real, pos % PS, 0), tok_qoff=qoff,
                q_len=[5, 1, 0], kv_len=[5, 4, 0], last=[4, 6, 0],
                tables=tables, cur_tok=[0, 7, 0])
    meta = {k: jnp.asarray(v, jnp.int32) for k, v in meta.items()}
    meta["tail_live"] = jnp.asarray([True, True, False])
    tok = np.where(real, 1 + np.arange(width), 0).astype(np.int32)
    return jnp.asarray(tok), meta, width


def _run(mod, cfg, params, program):
    cache = mod.init_serving_pages(cfg, 1 + S * PPS, PS, max_batch=S)
    if program == "block":
        lengths = jnp.asarray([5, 3, 0], jnp.int32)
        tables = 1 + jnp.arange(S * PPS, dtype=jnp.int32).reshape(S, PPS)
        return serving_tick_block(
            params, jnp.asarray([3, 7, 0], jnp.int32), lengths, tables,
            cache, cfg, mod.SERVING, num_steps=3, attn_impl="dense")
    tok, meta, width = _tick_meta()
    return serving_tick(
        params, tok, meta, cache, cfg, mod.SERVING, tq=width - S,
        attn_impl="dense",
        decode_tail={"plain": 0, "tail": 2}[program])


@pytest.mark.parametrize("program", ["plain", "tail", "block"])
@pytest.mark.parametrize("family", FAMILIES)
def test_a_tick_on_the_serving_tree_is_the_callers_tick(family, program):
    """Tokens, logits, counts and the pools written: the same products
    over the same weights, the operand laid the other way round."""
    mod, cfg, params = _family(family)
    served = mod.serving_params(params, cfg)
    for name in QKV:
        assert name not in served["layers"]
        assert served["layers"][name + OUTPUT_MAJOR].shape == tuple(
            np.asarray(params["layers"][name].shape)[[0, 2, 1]])
    want = _run(mod, cfg, params, program)
    got = _run(mod, cfg, served, program)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if jnp.issubdtype(w.dtype, jnp.floating):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-5, atol=2e-5)
        else:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _serve(eng, cfg):
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in (9, 4, 11, 6)]
    with eng:
        handles = [eng.submit(p, m) for p, m in zip(prompts, (6, 8, 3, 7))]
        return [np.asarray(h.result(timeout=300)) for h in handles]


@pytest.mark.parametrize("family", FAMILIES)
def test_an_engines_tokens_are_those_of_the_given_tree(family, monkeypatch):
    """Greedy tokens with the family's function and with it patched out;
    the caller's tree is the same objects with the same shapes after."""
    mod, cfg, params = _family(family)
    before = [(id(x), x.shape) for x in jax.tree.leaves(params)]
    keys = set(params["layers"])
    eng = _engine(params, cfg)
    for name in QKV:
        assert name + OUTPUT_MAJOR in eng._params["layers"]
    got = _serve(eng, cfg)
    assert [(id(x), x.shape) for x in jax.tree.leaves(params)] == before
    assert set(params["layers"]) == keys
    monkeypatch.setattr(mod, "SERVING", mod.SERVING._replace(params=None))
    plain = _engine(params, cfg)
    assert plain._params is params
    for g, w in zip(got, _serve(plain, cfg)):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("family", FAMILIES)
def test_the_serving_tree_shares_every_other_leaf(family):
    mod, cfg, params = _family(family)
    eng = _engine(params, cfg)
    try:
        tree = eng._params
        assert tree is not params and tree["layers"] is not params["layers"]
        flat = dict(jax.tree_util.tree_leaves_with_path(params))
        relaid = 0
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            key = jax.tree_util.keystr(path)
            if key.endswith(OUTPUT_MAJOR + "']"):
                relaid += 1
                continue
            assert leaf is flat[path], key
        assert relaid == 3 and len(flat) == len(jax.tree.leaves(tree))
        for name in QKV:
            np.testing.assert_array_equal(
                np.asarray(tree["layers"][name + OUTPUT_MAJOR]),
                np.swapaxes(np.asarray(params["layers"][name]), 1, 2))
    finally:
        eng.close()


@pytest.mark.parametrize("family", FAMILIES)
def test_stats_carry_the_bytes_re_laid(family):
    """The gauge that says the mechanism is on: the three stacks' bytes,
    the seconds, and the span inside ``serving.setup.init``."""
    mod, cfg, params = _family(family)
    eng = _engine(params, cfg)
    try:
        setup = eng.stats()["setup"]
    finally:
        eng.close()
    assert setup["weights_relaid_bytes"] == sum(
        int(params["layers"][name].nbytes) for name in QKV) > 0
    assert setup["weights_relay_s"] > 0
    span = [s for s in setup["spans"]
            if s["name"] == "serving.setup.init.relay"]
    assert len(span) == 1 and span[0]["parent"] == "serving.setup.init"
    assert span[0]["dur_s"] <= setup["weights_relay_s"]


@pytest.mark.parametrize("family", FAMILIES)
def test_an_int8_tree_passes_through(family, monkeypatch):
    """``quantization="int8"``: no leaf re-laid (an ``Int8Weight`` is no
    dense array), the block falls to ``_mm``, and the engine serves what
    it served before."""
    from paddle_tpu.ops.fused.int8_matmul import Int8Weight
    from paddle_tpu.quantization.decode import quantize_for_decode
    mod, cfg, params = _family(family)
    quant = quantize_for_decode(params, cfg)
    assert mod.serving_params(quant, cfg)["layers"].keys() == \
        quant["layers"].keys()
    eng = _engine(params, cfg, quantization="int8")
    layers = eng._params["layers"]
    for name in QKV:
        assert isinstance(layers[name], Int8Weight)
        assert name + OUTPUT_MAJOR not in layers
    assert eng.stats()["setup"]["weights_relaid_bytes"] == 0
    got = _serve(eng, cfg)
    monkeypatch.setattr(mod, "SERVING", mod.SERVING._replace(params=None))
    for g, w in zip(got, _serve(_engine(quant, cfg), cfg)):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("family", ["lfm2_moe", "granite_hybrid"])
def test_a_family_without_the_function_serves_the_given_tree(family):
    """LFM2's two and granite's four attention layers keep ``[D, O]``
    (``PERF.md`` section 7): the engine's tree IS the caller's."""
    mod = importlib.import_module(f"paddle_tpu.models.{family}")
    assert mod.SERVING.params is None
    cls = {"lfm2_moe": "Lfm2MoeConfig",
           "granite_hybrid": "GraniteHybridConfig"}[family]
    cfg = getattr(mod, cls).tiny()
    params = mod.init_params(cfg, jax.random.PRNGKey(2))
    eng = ServingEngine(params, cfg, max_batch=2, page_size=4,
                        max_prompt_len=8, max_new_tokens_cap=4,
                        prefill_chunk=4)
    try:
        assert eng._params is params
        setup = eng.stats()["setup"]
    finally:
        eng.close()
    assert setup["weights_relaid_bytes"] == 0
    assert setup["weights_relay_s"] == 0.0
