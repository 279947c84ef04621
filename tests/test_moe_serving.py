"""A serving tick's routed experts (``incubate/moe/functional.py:
moe_ffn_share`` at the share ``(0, E)`` over ``ops/pallas/
grouped_matmul.py: held_experts_swiglu``) in ``models/qwen2_moe.py``:
the kernel with experts nobody chose, its column blocks, the router's
choice as the capacity path and the serving path share it, and the
family's ticks against its own whole-sequence ``forward`` with padding
rows and dead slots in the tick. Float32 on the CPU under conftest's
"highest" matmul precision; the kernel runs in interpret mode.
"""
import functools
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.incubate.moe import functional as MF
from paddle_tpu.models.llama import rms_norm
from paddle_tpu.models.serving_tick import serving_tick
from paddle_tpu.ops.pallas import grouped_matmul as G
from paddle_tpu.serving import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- the kernel ----

E, ROWS, K_TOP, WIDTH = 6, 7, 2, 88     # 88 = 11 column blocks of 8

# which of the E experts no row chooses
_UNCHOSEN = {"none": (), "first": (0,), "middle": (2, 3), "last": (5,),
             "all-but-one": (0, 1, 2, 4, 5), "every": tuple(range(E))}


def _dense_swiglu(x, ids, w, wg, wu, wd):
    """``sum_j w[n, j] swiglu(x[n], expert ids[n, j])``, an expert at a
    time; an id of ``E`` lands nowhere."""
    y = np.zeros(x.shape, np.float32)
    for n in range(x.shape[0]):
        for j in range(ids.shape[1]):
            e = ids[n, j]
            if e < wg.shape[0]:
                g = x[n] @ wg[e]
                h = g / (1 + np.exp(-g)) * (x[n] @ wu[e])
                y[n] += w[n, j] * (h @ wd[e])
    return y


@pytest.mark.parametrize("blocks", [1, 2, 11])
@pytest.mark.parametrize("unchosen", list(_UNCHOSEN))
def test_held_experts_with_unchosen_experts_equal_the_dense_reference(
        monkeypatch, blocks, unchosen):
    """An expert nobody chose holds the step before it in BOTH block
    coordinates: whatever the number of column blocks, and wherever the
    unchosen experts lie, the chosen ones' rows are the dense
    reference's and the counts say who took a row."""
    monkeypatch.setattr(G, "held_tile_n", lambda K, N, *a: N // blocks)
    rng = np.random.default_rng(blocks)
    idle = _UNCHOSEN[unchosen]
    live = [e for e in range(E) if e not in idle]
    ids = (rng.choice(live, size=(ROWS, K_TOP)) if live
           else np.full((ROWS, K_TOP), E))
    ids[0, 0] = E                       # one pair that lands elsewhere
    x = rng.normal(size=(ROWS, WIDTH)).astype(np.float32)
    w = rng.uniform(size=(ROWS, K_TOP)).astype(np.float32)
    wg, wu, wd = (rng.normal(size=(2, E, WIDTH, WIDTH)).astype(np.float32)
                  / 8 for _ in range(3))
    y, counts = G.held_experts_swiglu(
        jnp.asarray(x), jnp.asarray(ids, jnp.int32), jnp.asarray(w),
        jnp.asarray(wg), jnp.asarray(wu), jnp.asarray(wd),
        layer=jnp.int32(1), tile_m=8)
    want = _dense_swiglu(x, ids, w, wg[1], wu[1], wd[1])
    assert np.abs(np.asarray(y) - want).max() <= 1e-4 * max(
        1.0, np.abs(want).max())
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(ids.ravel(), minlength=E + 1)[:E])


@pytest.mark.parametrize("blocks", [1, 2, 11])
@pytest.mark.parametrize("unchosen", list(_UNCHOSEN))
def test_an_unchosen_expert_names_the_block_before_it(blocks, unchosen):
    """The weight block a grid step names changes only where a chosen
    expert's column block begins: the pipeline fetches ``touched x
    column blocks`` blocks a launch (one more where the first chosen
    expert is not the walk's first step and has several column
    blocks), however many experts the chip holds."""
    tiles = np.asarray([0 if e in _UNCHOSEN[unchosen] else 1
                        for e in range(E)], np.int32)
    chosen = tiles > 0
    blk = np.maximum.accumulate(np.where(chosen, np.arange(E), -1))
    blk = np.where(blk < 0, chosen.argmax(), blk)
    walk = [tuple(int(v) for v in G._held_w_index(
        e, j, np.asarray([3]), blk, None, tiles, last_j=blocks - 1))
        for e in range(E) for j in range(blocks)]
    assert all(step[0] == 3 and step[2] == 0 for step in walk)
    fetches = 1 + sum(a != b for a, b in zip(walk, walk[1:]))
    touched = int(chosen.sum())
    leading = touched and not chosen[0] and blocks > 1
    assert fetches == max(1, touched * blocks + bool(leading))
    # a chosen expert's steps walk its own column blocks in order
    for e in np.flatnonzero(chosen):
        assert walk[e * blocks:(e + 1) * blocks] == [
            (3, e, 0, j) for j in range(blocks)]


_WIDTHS = {  # (K, N) of the gate / up and the down matmul
    "qwen1.5-moe-a2.7b": ("hidden_size", "moe_intermediate_size"),
    "lfm2-24b-a2b": ("hidden_size", "moe_intermediate_size"),
    "longcat-flash-chat": ("hidden_size", "expert_ffn_hidden_size"),
}


@pytest.mark.parametrize("config", list(_WIDTHS))
@pytest.mark.parametrize("matmul", ["gate_up", "down"])
def test_held_tile_n_is_a_legal_column_block(config, matmul):
    """A multiple of 128 lanes that divides ``N``, or ``N`` (Mosaic
    refuses any other block: 704 columns of Qwen's 1408 were one)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        model = json.load(f)
    K, N = (model[k] for k in _WIDTHS[config])
    if matmul == "down":
        K, N = N, K
    tn = G.held_tile_n(K, N)
    assert N % tn == 0 and (tn % 128 == 0 or tn == N)
    # two buffers a stack, two stacks: the call states what it needs
    assert 4 * K * tn * 2 + G.HELD_VMEM_SLACK <= 64 << 20


# ------------------------------------------------------------- the router ----

@pytest.mark.parametrize("score_fn", ["softmax", "sigmoid"])
@pytest.mark.parametrize("bias", [False, True], ids=["nobias", "bias"])
@pytest.mark.parametrize("norm", [False, True], ids=["raw", "normalised"])
def test_top_k_choice_is_what_top_k_gating_dispatches(score_fn, bias, norm):
    """``top_k_gating`` at a capacity that drops nothing scatters exactly
    the choices and weights ``top_k_choice`` returns."""
    rng = np.random.default_rng(5)
    S, n_e, k = 13, 8, 3
    logits = jnp.asarray(rng.normal(size=(S, n_e)), jnp.float32)
    sel = (jnp.asarray(rng.normal(size=(n_e,)), jnp.float32) if bias
           else None)
    kw = dict(score_fn=score_fn, select_bias=sel, normalize_topk=norm)
    idx, w, gates = MF.top_k_choice(logits, k, **kw)
    idx, w = np.asarray(idx), np.asarray(w)
    assert idx.shape == w.shape == (S, k) and idx.dtype == np.int32
    assert all(len(set(row)) == k for row in idx.tolist())
    # the choice: the k best biased scores, best first
    scores = np.asarray(gates) + (0 if sel is None else np.asarray(sel))
    np.testing.assert_array_equal(idx, np.argsort(-scores, axis=1)[:, :k])
    raw = np.take_along_axis(np.asarray(gates), idx, axis=1)
    np.testing.assert_allclose(
        w, raw / raw.sum(1, keepdims=True) if norm else raw, rtol=1e-6)
    dispatch, combine, _ = MF.top_k_gating(logits, k, S, **kw)
    want_d = np.zeros((S, n_e), np.float32)
    want_c = np.zeros((S, n_e), np.float32)
    np.put_along_axis(want_d, idx, 1.0, axis=1)
    np.put_along_axis(want_c, idx, w, axis=1)
    np.testing.assert_array_equal(np.asarray(dispatch).sum(-1), want_d)
    np.testing.assert_array_equal(np.asarray(combine).sum(-1), want_c)


# ------------------------------------------------------------- the ticks ----

S_SLOTS, PS, PPS = 4, 4, 8


def _qwen():
    from paddle_tpu.models import qwen2_moe as M
    cfg = M.Qwen2MoeConfig.tiny(dtype=jnp.float32, remat=False)
    return M, cfg, M.init_params(cfg, jax.random.PRNGKey(3))


def _stream(cfg, spans, width):
    """``spans {slot: tokens}`` from position 0, packed with a padding
    row in front, one between the spans and the rest behind; the slots
    without a span are dead (``q_len`` 0)."""
    tok = np.zeros((width,), np.int32)
    slot_of = np.full((width,), S_SLOTS, np.int32)
    pos, qoff = np.zeros((width,), np.int32), np.zeros((width,), np.int32)
    q_len, last = np.zeros((S_SLOTS,), np.int32), np.zeros((S_SLOTS,),
                                                           np.int32)
    tables = 1 + np.arange(S_SLOTS * PPS, dtype=np.int32).reshape(
        S_SLOTS, PPS)
    i = 1
    for s, toks in spans.items():
        n = len(toks)
        tok[i:i + n], slot_of[i:i + n] = toks, s
        pos[i:i + n] = qoff[i:i + n] = np.arange(n)
        q_len[s], last[s] = n, i + n - 1
        i += n + 1
    real = slot_of < S_SLOTS
    page = np.where(real, tables[np.minimum(slot_of, S_SLOTS - 1),
                                 pos // PS], 0)
    meta = dict(tok_slot=slot_of, tok_pos=pos, tok_page=page,
                tok_off=np.where(real, pos % PS, 0), tok_qoff=qoff,
                q_len=q_len, kv_len=q_len, last=last, tables=tables)
    return (jnp.asarray(tok),
            {k: jnp.asarray(v, jnp.int32) for k, v in meta.items()}, real)


@pytest.mark.parametrize("impl", ["dense", "pallas"])
def test_a_tick_equals_forward_at_every_real_row(monkeypatch, impl):
    """Two prompts prefilled in ONE tick with padding rows around them
    and two dead slots: the walk's hidden state at EVERY real row, through
    the final norm and the head, is the whole-sequence ``forward``'s
    logits at that position (the C = N einsum), by the masked dense form
    and by the grouped matmul in interpret mode; the tick's counts are
    ``top_k`` pairs a real row a layer and ``E`` experts a launch."""
    M, cfg, params = _qwen()
    monkeypatch.setattr(M, "moe_ffn_share",
                        functools.partial(MF.moe_ffn_share, impl=impl))
    rng = np.random.default_rng(7)
    spans = {2: rng.integers(1, cfg.vocab_size, 9),
             0: rng.integers(1, cfg.vocab_size, 5)}
    width = 20
    tok, meta, real = _stream(cfg, spans, width)
    cache = M.init_serving_pages(cfg, 1 + S_SLOTS * PPS, PS,
                                 max_batch=S_SLOTS)
    h = params["embed"].astype(cfg.dtype)[tok[None]]
    h, _ = M._walk(params, h, cache, meta, cfg, width, "dense")
    logits = np.asarray(rms_norm(h[0], params["final_norm"],
                                 cfg.rms_norm_eps) @ params["lm_head"])
    i = 1
    for s, toks in spans.items():
        want = np.asarray(M.forward(params, jnp.asarray(toks)[None],
                                    cfg)[0])[0]
        got = logits[i:i + len(toks)]
        assert np.abs(got - want).max() < 2e-4 * max(1, np.abs(want).max())
        i += len(toks) + 1
    toks, _, counts, _ = serving_tick(
        params, tok, {**meta, "tail_live": jnp.zeros((S_SLOTS,), bool)},
        cache, cfg, M.SERVING, tq=width)
    layers, n_e = cfg.num_hidden_layers, cfg.num_experts
    pairs, touched, held = np.asarray(counts)
    assert pairs == cfg.num_experts_per_tok * int(real.sum()) * layers
    assert held == n_e * layers and 0 < touched <= held
    for s, span in spans.items():
        assert int(toks[s]) == logits[int(meta["last"][s])].argmax()


def test_the_engine_adds_the_ticks_counts():
    """The counts come back beside the tokens and land in the engine's
    counters when a tick completes: ``top_k`` pairs a real row an expert
    layer, and at most every held expert touched."""
    M, cfg, params = _qwen()
    eng = ServingEngine(params, cfg, model="qwen2_moe", max_batch=3,
                        page_size=4,
                        max_prompt_len=16, max_new_tokens_cap=8,
                        prefill_chunk=8, decode_block_size=2)
    try:
        prompt = np.arange(1, 12, dtype=np.int32)
        out = eng.submit(prompt, 6).result(timeout=300)
        c = eng.metrics.snapshot()["counters"]
    finally:
        eng.close()
    want = np.asarray(M.generate(params, jnp.asarray(prompt[None]), cfg,
                                 6))[0, 11:]
    np.testing.assert_array_equal(np.asarray(out), want)
    layers = cfg.num_hidden_layers
    assert c["moe_pairs_held"] == (cfg.num_experts_per_tok * layers
                                   * c["tick_rows_real"])
    assert c["moe_experts_held"] % (cfg.num_experts * layers) == 0
    assert 0 < c["moe_experts_touched"] <= c["moe_experts_held"]


def test_int8_experts_keep_the_capacity_einsum():
    """Weight-only int8 expert leaves are a fact of the input: the tick
    keeps the C = N einsum (the grouped matmul reads bfloat16 stacks)
    and counts every expert as read."""
    from paddle_tpu.quantization.decode import quantize_for_decode
    M, cfg, params = _qwen()
    q = quantize_for_decode(params, cfg)
    tok, meta, real = _stream(cfg, {1: np.arange(1, 7)}, 12)
    cache = M.init_serving_pages(cfg, 1 + S_SLOTS * PPS, PS)
    meta = {**meta, "tail_live": jnp.zeros((S_SLOTS,), bool)}
    jaxpr = str(jax.make_jaxpr(lambda p, c: serving_tick(
        p, tok, meta, c, cfg, M.SERVING, tq=12))(q, cache))
    assert "held_experts_matmul" not in jaxpr
    _, _, counts, _ = serving_tick(q, tok, meta, cache, cfg, M.SERVING,
                                   tq=12)
    layers = cfg.num_hidden_layers
    np.testing.assert_array_equal(
        np.asarray(counts),
        [cfg.num_experts_per_tok * int(real.sum()) * layers,
         cfg.num_experts * layers, cfg.num_experts * layers])
