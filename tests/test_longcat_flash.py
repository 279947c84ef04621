"""``models/longcat_flash.py`` (LongCat-Flash-Chat: latent attention, a
shortcut-connected mixture of experts, identity experts, ONE CHIP'S SHARE
of the routed experts) against the benchmark's plain float32 reference
(``benchmark/families/longcat_flash.py``), tiny, on the CPU: the whole
forward, prefill and decode through the latent pages, ragged ticks, the
fused block, the kernel against its dense reference, the expert share
against the uncut layer, and the engine over a cache whose one page pool
is not ``k_pages`` / ``v_pages``.
"""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import manifest, reference  # noqa: E402

from paddle_tpu.incubate.moe.functional import moe_ffn_share  # noqa: E402
from paddle_tpu.models import layer_walk  # noqa: E402
from paddle_tpu.models import longcat_flash as M  # noqa: E402
from paddle_tpu.models.serving_tick import (  # noqa: E402
    serving_tick, serving_tick_block)
from paddle_tpu.ops.pallas import mla_paged_attention as K  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402

TOL = 2e-4
FAMILY = manifest.load_family("longcat_flash")
TINY = json.load(open(os.path.join(
    BENCH, "tests", "tiny", "configs", "tiny-longcat.json")))


def built(seed=11, **kw):
    model = {**TINY, **kw}
    cfg, mod = FAMILY.program_config(model)
    assert mod is M
    return model, cfg, FAMILY.make_params(model, seed)


def ref_logits(params, model, tokens, rows=None):
    tokens = np.asarray(tokens, np.int32)
    h = reference.hidden_states(params, tokens, model, FAMILY)
    rows = np.arange(tokens.size) if rows is None else np.asarray(rows)
    return np.asarray(reference.logits_at(params, h, rows, model))


def seq(n, mul=7, add=3):
    return (np.arange(n) * mul + add) % TINY["vocab_size"]


# ------------------------------------------------------------ the stack ----

def test_the_configuration_is_one_chip_s_share():
    model, cfg, params = built()
    # chip 1 of 4: experts 8..15 of the 32 the router scores, beside 8
    # identity experts
    assert FAMILY.deployment(model) == (4, 1)
    assert cfg.experts_held == (8, 8) and cfg.n_routed_experts == 32
    assert params["moe"]["router"].shape == (2, 64, 40)
    assert params["moe"]["experts"]["w_gate"].shape == (2, 8, 64, 32)
    assert params["mla"]["wq_a"].shape[0] == 4      # two sublayers a layer
    abstract = M.abstract_params(cfg)
    assert jax.tree.map(lambda a: a.shape, abstract) == jax.tree.map(
        lambda a: a.shape, params)
    with pytest.raises(ValueError):
        M.LongcatFlashConfig.tiny(experts_held=(12, 8))


def test_cache_is_one_latent_pool_declared_by_name():
    _, cfg, _ = built()
    cache = M.init_serving_pages(cfg, 9, 4, max_batch=3)
    assert set(cache) == {M.POOL}
    # 48 + 8 values a token a sublayer, padded to the chip's 128 lanes
    assert cfg.latent_width == 56 and cfg.row_width == 128
    assert cache[M.POOL].shape == (4, 9, 4, 128)
    assert M.cache_page_pools(cfg) == (
        layer_walk.PagePoolSpec(M.POOL, 1),)
    kinds = M.serving_cache_kinds(cfg)
    assert len(kinds) == 4 and all(k.cache == "pages" for k in kinds)
    assert K.latent_row_width(512, 64) == 640


def test_forward_against_the_reference():
    model, cfg, params = built()
    toks = seq(25)
    got = np.asarray(M.forward(params, jnp.asarray(toks[None]), cfg))[0]
    assert np.abs(got - ref_logits(params, model, toks)).max() < TOL


def test_generate_follows_the_reference_greedily():
    model, cfg, params = built()
    out = np.asarray(M.generate(params, jnp.asarray(seq(9)[None]), cfg, 6))[0]
    want = ref_logits(params, model, out[:-1], rows=np.arange(8, 14))
    assert (want.argmax(-1) == out[9:]).all()


def test_prefill_in_unequal_chunks_through_the_dense_latent_cache():
    """``forward_with_cache`` (the EXPANDED form over a dense cache of
    latents): chunks of 4, 7 and 1, then the whole sequence's logits."""
    model, cfg, params = built()
    toks = seq(12)
    cache = M.init_kv_cache(cfg, 1, 16)
    at = 0
    for n in (4, 7, 1):
        logits, cache = M.forward_with_cache(
            params, jnp.asarray(toks[None, at:at + n]), cache, at, cfg)
        at += n
        want = ref_logits(params, model, toks[:at], rows=[at - 1])
        assert np.abs(np.asarray(logits)[0] - want[0]).max() < TOL


# ----------------------------------------------------------------- ticks ----

S, PS, PPS = 3, 4, 12


class Ticks:
    """A hand-driven serving cache: ``S`` slots of ``PPS`` pages, slot
    ``s`` owning pages ``1 + s*PPS ..``. ``run`` packs the given spans
    ``{slot: tokens}`` at each slot's current length into ONE tick of
    ``S + width`` rows, a padding token in front and one between the
    spans."""

    def __init__(self, cfg, params, attn_impl="auto"):
        self.cfg, self.params, self.impl = cfg, params, attn_impl
        self.cache = M.init_serving_pages(cfg, 1 + S * PPS, PS, max_batch=S)
        self.tables = 1 + np.arange(S * PPS, dtype=np.int32).reshape(S, PPS)
        self.lens = np.zeros((S,), np.int32)

    def run(self, spans, width=16, decode_tail=0, tail_live=()):
        T = S + width
        tok = np.zeros((T,), np.int32)
        tok_slot = np.full((T,), S, np.int32)
        tok_pos, tok_qoff = np.zeros((T,), np.int32), np.zeros((T,), np.int32)
        q_len, kv_len = np.zeros((S,), np.int32), np.zeros((S,), np.int32)
        last = np.zeros((S,), np.int32)
        i = 1
        for s, toks in spans.items():
            n = len(toks)
            tok[i:i + n], tok_slot[i:i + n] = toks, s
            tok_pos[i:i + n] = self.lens[s] + np.arange(n)
            tok_qoff[i:i + n] = np.arange(n)
            q_len[s], kv_len[s], last[s] = n, self.lens[s] + n, i + n - 1
            self.lens[s] += n
            i += n + 1
        real = tok_slot < S
        page = np.where(real, self.tables[np.minimum(tok_slot, S - 1),
                                          np.minimum(tok_pos // PS, PPS - 1)],
                        0)
        live = np.zeros((S,), bool)
        live[list(tail_live)] = True
        meta = dict(tok_slot=tok_slot, tok_pos=tok_pos,
                    tok_page=page.astype(np.int32),
                    tok_off=np.where(real, tok_pos % PS, 0).astype(np.int32),
                    tok_qoff=tok_qoff, q_len=q_len, kv_len=kv_len, last=last,
                    tables=self.tables, tail_live=live)
        meta = {k: jnp.asarray(v) for k, v in meta.items()}
        toks, logits, counts, self.cache = serving_tick(
            self.params, jnp.asarray(tok), meta, self.cache, self.cfg,
            M.SERVING, tq=width, decode_tail=decode_tail,
            attn_impl=self.impl)
        self.lens[list(tail_live)] += decode_tail
        return np.asarray(toks), np.asarray(logits), np.asarray(counts)


@pytest.mark.parametrize("impl", ["auto", "pallas"])
def test_chunked_prefill_then_decode_through_the_latent_pages(impl):
    """Chunks that do not divide the prompt (1, 2, 3, 5 and 11 tokens,
    the last across three pages), then three decode ticks: the ABSORBED
    form over the latent pages against the reference's expanded one;
    ``pallas``: the kernel itself, in interpret mode."""
    model, cfg, params = built()
    toks = seq(25)
    want = ref_logits(params, model, toks)
    t, at = Ticks(cfg, params, impl), 0
    for n in (1, 2, 3, 5, 11, 1, 1, 1):
        _, logits, counts = t.run({1: toks[at:at + n]})
        at += n
        assert np.abs(logits[1] - want[at - 1]).max() < TOL, (n, at)
        # every real row's choices land somewhere, once: 4 a row a layer
        assert counts[:3].sum() == cfg.moe_topk * n * cfg.num_layers


def test_mixed_tick_one_slot_prefilling_others_decoding():
    model, cfg, params = built()
    a, b, c = seq(13), seq(9, 5, 1), seq(7, 11, 2)
    wa, wb, wc = (ref_logits(params, model, x) for x in (a, b, c))
    t = Ticks(cfg, params)
    t.run({1: b[:6], 2: c[:4]})
    for step in range(3):       # slot 0 prefills 4 a tick, 1 and 2 decode
        _, logits, _ = t.run({0: a[4 * step:4 * step + 4],
                              1: b[6 + step:7 + step],
                              2: c[4 + step:5 + step]})
        assert np.abs(logits[0] - wa[4 * step + 3]).max() < TOL
        assert np.abs(logits[1] - wb[6 + step]).max() < TOL
        assert np.abs(logits[2] - wc[4 + step]).max() < TOL


def test_fused_tail_counts_its_steps_and_leaves_a_mid_prefill_slot_alone():
    model, cfg, params = built()
    a, b = seq(12), seq(6, 5, 1)
    plain, tailed = Ticks(cfg, params), Ticks(cfg, params)
    for t in (plain, tailed):
        t.run({1: b[:5]})
    _, _, c0 = plain.run({0: a[:5], 1: b[5:6]})
    toks, _, c1 = tailed.run({0: a[:5], 1: b[5:6]}, decode_tail=2,
                             tail_live=(1,))
    assert toks.shape == (S, 3)
    cont = np.concatenate([b, toks[1]])
    want = ref_logits(params, model, cont[:-1], rows=np.arange(5, 8))
    assert (want.argmax(-1) == toks[1]).all()
    # two more launches of one live row each
    assert (c1[:3].sum() - c0[:3].sum()
            == 2 * cfg.moe_topk * cfg.num_layers)
    # slot 0's pages (1..12) are what the tick without a tail wrote
    np.testing.assert_array_equal(
        np.asarray(plain.cache[M.POOL])[:, 1:1 + PPS],
        np.asarray(tailed.cache[M.POOL])[:, 1:1 + PPS])
    _, logits, _ = tailed.run({0: a[5:12]})
    assert np.abs(logits[0] - ref_logits(params, model, a)[11]).max() < TOL


def test_fused_block_against_the_reference():
    model, cfg, params = built()
    b = seq(6, 5, 1)
    t = Ticks(cfg, params)
    t.run({1: b[:5]})
    lengths = np.array([0, 5, 0], np.int32)
    tok = jnp.asarray(np.array([0, b[5], 0], np.int32))
    before = np.asarray(t.cache[M.POOL])
    toks, counts, nxt, cache = serving_tick_block(
        params, tok, jnp.asarray(lengths), jnp.asarray(t.tables), t.cache,
        cfg, M.SERVING, num_steps=3)
    toks = np.asarray(toks)
    cont = np.concatenate([b, toks[1]])
    want = ref_logits(params, model, cont[:-1], rows=np.arange(5, 8))
    assert (want.argmax(-1) == toks[1]).all()
    assert int(nxt[1]) == toks[1, -1] and int(nxt[0]) == 0
    assert np.asarray(counts)[:3].sum() == 3 * cfg.moe_topk * cfg.num_layers
    # the free slots' pages stay bitwise
    after = np.asarray(cache[M.POOL])
    for s in (0, 2):
        np.testing.assert_array_equal(
            after[:, 1 + s * PPS:1 + (s + 1) * PPS],
            before[:, 1 + s * PPS:1 + (s + 1) * PPS])


# ---------------------------------------------------------------- kernel ----

def _launch(seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    H, dk, dv, S_, pps, ps = 4, 256, 128, 5, 6, 16
    T = S_ + 40
    pages = jnp.asarray(rng.normal(size=(3, S_ * pps + 1, ps, dk)), dtype)
    tables = 1 + rng.permutation(S_ * pps).reshape(S_, pps).astype(np.int32)
    # a decode row, a dead slot, a 19-token span over 30 of context, a
    # 21-token span from position 0, a decode row whose kv_len lies past
    # the table (6 x 16 = 96)
    q_len = np.array([1, 0, 19, 21, 1], np.int32)
    kv_len = np.array([37, 0, 49, 21, 200], np.int32)
    start = np.array([0, 1, S_, S_ + 19, 4], np.int32)
    q = jnp.asarray(rng.normal(size=(T, H, dk)), dtype)
    return (q, pages, start, q_len, kv_len, tables), dict(
        dv=dv, sm_scale=0.1, layer=1)


@pytest.mark.parametrize("tile,block", [(2, 8), (1, 4), (0, 8)])
def test_kernel_in_interpret_mode_against_the_dense_reference(tile, block):
    """Decode rows and spans in one launch, partial last blocks, a dead
    slot, a ``kv_len`` past the table, tiles that do and do not divide
    the context, the whole table in one tile."""
    args, kw = _launch()
    ref = K.mla_paged_attention(*args, impl="dense", **kw)
    got = K.mla_paged_attention(*args, impl="pallas", kv_tile_pages=tile,
                                block_tokens=block, **kw)
    scale = float(jnp.abs(ref).max())
    assert float(jnp.abs(got - ref).max()) < 1e-5 * scale
    own = np.zeros(args[0].shape[0], bool)
    for s, n in zip(args[2], args[3]):
        own[s:s + n] = True
    assert not np.asarray(got)[~own].any()      # rows nobody owns: zero


def test_kernel_in_bfloat16_keeps_float32_scores():
    args, kw = _launch(dtype=jnp.bfloat16)
    ref = K.mla_paged_attention(*args, impl="dense", **kw).astype(jnp.float32)
    got = K.mla_paged_attention(*args, impl="pallas", **kw).astype(
        jnp.float32)
    assert float(jnp.abs(got - ref).max()) <= 2 ** -7 * float(
        jnp.abs(ref).max())


def test_absorbed_attention_equals_expanded():
    """One sublayer, a 10-token sequence: the absorbed form over a
    latent page pool against the expanded form over the same latents."""
    _, cfg, params = built()
    lp = layer_walk._layer_params(params["mla"], 2)
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 10, cfg.hidden_size))
    pos = jnp.arange(10)[None]
    q_n, q_r, c_kv, k_r = M._mla_qkv(lp, h, pos, cfg)
    lat = jnp.concatenate([c_kv, k_r], -1)
    want = M._expanded_attention(lp, q_n, q_r, lat, 0, cfg)[0]
    pad = cfg.row_width - cfg.latent_width
    pages = jnp.zeros((4, 4, cfg.row_width)).at[1:4].set(
        jnp.pad(lat[0], ((0, 2), (0, pad))).reshape(3, 4, -1))
    q = jnp.concatenate([jnp.einsum("thn,hnc->thc", q_n[0], lp["w_uk"]),
                         q_r[0], jnp.zeros((10, 4, pad))], -1)
    o_lat = K.mla_paged_attention(
        q, pages, [0], [10], [10], [[1, 2, 3]], dv=cfg.kv_lora_rank,
        sm_scale=cfg.sm_scale, impl="dense")
    got = jnp.einsum("thc,hcv->thv", o_lat, lp["w_uv"])
    assert float(jnp.abs(got - want).max()) < 1e-5


def test_kernel_is_registered_with_the_auditor():
    from paddle_tpu.analysis import kernel_audit as ka
    assert K.AUDIT_KIND == "mla_paged_attention"
    for geom in K.AUDIT_GEOMETRIES:
        verdict = ka.audit_config("mla_paged_attention", geom, None)
        assert verdict["ok"], verdict
        ((label, fn, args),) = K.audit_launches(geom)
        assert label.startswith("walk[kv_tile_pages=8,block_tokens=16")


def test_mla_cells_reads_the_cell_s_geometry():
    from tools.kernel_bench import mla_cells, ragged_cells
    cells = mla_cells()
    c = cells["longprompt"]
    assert (c["slots"], c["span"], c["heads"], c["row_width"], c["dv"],
            c["layers"]) == (48, 512, 64, 640, 512, 8)
    assert c["pps"] == -(-(16384 + 1024 - 1) // c["page_size"])
    # and the ragged kernel's table keeps to the cells that launch it
    assert "longprompt" not in ragged_cells()


# ------------------------------------------------------- the expert share ----

def _uncut(x, router, bias, w, k, scale, n_routed):
    """The whole layer, every routed expert held, token by token."""
    p = jax.nn.softmax(x @ router, -1)
    _, idx = jax.lax.top_k(p + bias, k)
    out = np.zeros(x.shape, np.float32)
    for n in range(x.shape[0]):
        for e in np.asarray(idx[n]):
            wt = scale * float(p[n, e])
            if e >= n_routed:
                out[n] += wt * np.asarray(x[n])
            else:
                g, u, d = (w[key][e] for key in ("w_gate", "w_up", "w_down"))
                out[n] += wt * np.asarray(
                    (jax.nn.silu(x[n] @ g) * (x[n] @ u)) @ d)
    return out


@pytest.mark.parametrize("impl", ["dense", "pallas"])
def test_the_shares_held_parts_and_the_identity_part_once_are_the_uncut_layer(
        impl):
    """4 shares of 8 of 32 routed experts + 8 identity experts, top 6:
    every share's held part, plus the identity part counted ONCE, is the
    layer with every expert held; each share's pairs add up to ``top_k x
    rows`` and the held pairs over the shares to every routed pair."""
    rng = np.random.default_rng(0)
    N, D, F, R, Z, k = 37, 64, 32, 32, 8, 6
    router = jnp.asarray(rng.normal(size=(D, R + Z)), jnp.float32) * 0.3
    bias = jnp.asarray(rng.normal(size=(R + Z,)), jnp.float32) * 0.01
    x = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    w = {"w_gate": jnp.asarray(rng.normal(size=(R, D, F)), jnp.float32) / 8,
         "w_up": jnp.asarray(rng.normal(size=(R, D, F)), jnp.float32) / 8,
         "w_down": jnp.asarray(rng.normal(size=(R, F, D)), jnp.float32) / 6}
    want = _uncut(x, router, bias, w, k, 6.0, R)
    kw = dict(num_routed=R, zero_experts=Z, top_k=k, scale=6.0, impl=impl,
              tile_m=8)
    # the identity part alone: a share that holds one expert nobody can
    # be routed to does not exist, so take it as a share's result less
    # its held part computed without identity experts' weights
    total = np.zeros((N, D), np.float32)
    held_pairs = 0
    for share in range(4):
        ex = {key: v[share * 8:(share + 1) * 8] for key, v in w.items()}
        y, c = moe_ffn_share(x, router, bias, ex, held=(share * 8, 8), **kw)
        c = np.asarray(c)
        assert c[:3].sum() == k * N
        held_pairs += c[0]
        zero_pairs = c[1]
        total += np.asarray(y)
    p = jax.nn.softmax(x @ router, -1)
    _, idx = jax.lax.top_k(p + bias, k)
    z = np.asarray((jnp.where(idx >= R, jnp.take_along_axis(p, idx, 1), 0)
                    * 6.0).sum(1))
    total -= 3 * z[:, None] * np.asarray(x)     # counted 4 times, wanted once
    assert held_pairs + zero_pairs == k * N
    assert np.abs(total - want).max() < 2e-5 * np.abs(want).max()


def test_a_masked_row_routes_nowhere_and_counts_nowhere():
    rng = np.random.default_rng(1)
    N, D, F = 9, 64, 32
    router = jnp.asarray(rng.normal(size=(D, 12)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    ex = {"w_gate": jnp.asarray(rng.normal(size=(2, 4, D, F)), jnp.float32),
          "w_up": jnp.asarray(rng.normal(size=(2, 4, D, F)), jnp.float32),
          "w_down": jnp.asarray(rng.normal(size=(2, 4, F, D)), jnp.float32)}
    mask = jnp.asarray([True] * 5 + [False] * 4)
    kw = dict(held=(2, 4), num_routed=8, zero_experts=4, top_k=3, scale=2.0,
              layer=jnp.int32(1))
    y, c = moe_ffn_share(x, router, None, ex, row_mask=mask, **kw)
    assert np.asarray(c)[:3].sum() == 3 * 5
    assert not np.asarray(y)[5:].any()
    y5, c5 = moe_ffn_share(x[:5], router, None, ex, **kw)
    np.testing.assert_allclose(np.asarray(y)[:5], np.asarray(y5), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c5))


# ---------------------------------------------------------------- engine ----

def engine(cfg, params, **kw):
    geo = dict(max_batch=3, page_size=4, max_prompt_len=32,
               max_new_tokens_cap=16, prefill_chunk=8, check_invariants=True)
    return ServingEngine(params, cfg, model="longcat_flash", **{**geo, **kw})


@pytest.mark.parametrize("block", [1, 4])
def test_engine_is_greedy_generate_and_a_prefix_hit_equals_no_cache(block):
    _, cfg, params = built()
    prompt = seq(19).astype(np.int32)
    want = np.asarray(M.generate(params, jnp.asarray(prompt[None]), cfg,
                                 8))[0, 19:]
    eng = engine(cfg, params, decode_block_size=block)
    try:
        cold = eng.submit(prompt, 8).result(timeout=300)
        warm = eng.submit(prompt, 8).result(timeout=300)    # 4 pages attach
        c = eng.metrics.snapshot()["counters"]
    finally:
        eng.close()
    assert (np.asarray(cold) == want).all() and (np.asarray(warm) == want).all()
    assert c["prefix_hits"] == 1 and c["prefix_hit_tokens"] == 16
    # the share's pairs come back beside the tokens and add up
    assert (c["moe_pairs_held"] + c["moe_pairs_zero"] + c["moe_pairs_absent"]
            == cfg.moe_topk * cfg.num_layers * c["tick_rows_real"])
    assert 0 < c["moe_experts_touched"] <= 8 * cfg.num_layers * (
        c["decode_steps"] + c["prefill_chunks"])
    # a 19-token prompt in chunks of 8, 8, 3 and seven decode rows
    first = 8 * 9 // 2 + (8 * 8 + 36) + (3 * 16 + 6)
    assert c["attn_score_pairs"] >= first + sum(range(20, 27))


def test_engine_defrags_and_counts_copies_on_a_latent_pool():
    _, cfg, params = built()
    a, b = seq(13).astype(np.int32), seq(11, 5, 1).astype(np.int32)
    want = np.asarray(M.generate(params, jnp.asarray(b[None]), cfg,
                                 6))[0, 11:]
    eng = engine(cfg, params, prefix_cache=False)
    try:
        assert [p.name for p in eng._pools] == [M.POOL]
        assert eng._copies_a_page(1) == eng._copies_a_page(8) == 4
        assert eng._slot_state_bytes == 0
        ha = eng.submit(a, 12)
        hb = eng.submit(b, 6)
        first = next(iter(hb))          # b is mid-generation
        ha.result(timeout=300)          # a's pages free: holes below b's
        moved = eng.defragment()
        rest = list(hb)
    finally:
        eng.close()
    assert (np.asarray([first] + rest) == want).all()
    assert moved >= 0


@pytest.mark.parametrize("call", ["export_chain", "export_chain_begin",
                                  "adopt_chain"])
def test_engine_refuses_chain_migration_for_a_latent_pool_by_name(call):
    _, cfg, params = built()
    eng = engine(cfg, params)
    try:
        with pytest.raises(RuntimeError, match="latent_pages"):
            getattr(eng, call)({"page_size": 4} if call == "adopt_chain"
                               else 0)
        assert eng.metrics.snapshot()["labeled"]["chain_refused"]
    finally:
        eng.close()


def test_engine_leaves_the_cold_tier_off_for_a_latent_pool_and_says_so():
    _, cfg, params = built()
    eng = engine(cfg, params, cold_tier_bytes=1 << 20)
    try:
        assert eng._cold is None
        assert eng.metrics.snapshot()["labeled"]["cold_tier_refused"]
    finally:
        eng.close()


@pytest.mark.parametrize("family", ["llama", "qwen2_moe", "lfm2_moe",
                                    "granite_hybrid"])
def test_the_older_families_keep_their_two_pools(family):
    """They declare no pool: ``k_pages`` / ``v_pages``, page axis 2;
    ``qwen2_moe`` hands three counts back beside the tokens (its routed
    experts go through the share ``(0, E)``), the others none."""
    import importlib
    mod = importlib.import_module(f"paddle_tpu.models.{family}")
    assert mod.SERVING.page_pools(None) == layer_walk.KV_POOLS
    assert mod.SERVING.counters == (
        ("moe_pairs_held", "moe_experts_touched", "moe_experts_held")
        if family == "qwen2_moe" else ())


def test_engine_resolves_the_model_by_name_and_by_config():
    from paddle_tpu.models import SERVING_FAMILIES, resolve_family
    assert SERVING_FAMILIES["longcat_flash"] == "LongcatFlashConfig"
    _, cfg, _ = built()
    assert resolve_family(None, cfg) is M
    assert resolve_family("longcat_flash") is M


def test_defrag_pools_moves_every_pool_along_its_own_axis():
    from paddle_tpu.inference.paged_kv import apply_defrag, defrag_pools
    lat = jnp.arange(2 * 5 * 3).reshape(2, 5, 3)
    kv = jnp.arange(2 * 2 * 5 * 3).reshape(2, 2, 5, 3)
    tables = np.array([[4, 2], [0, 0]])
    plan = {4: 1}
    (a, b), t = defrag_pools(plan, [(lat, 1), (kv, 2)], tables)
    np.testing.assert_array_equal(np.asarray(a)[:, 1], np.asarray(lat)[:, 4])
    np.testing.assert_array_equal(np.asarray(b)[:, :, 1],
                                  np.asarray(kv)[:, :, 4])
    np.testing.assert_array_equal(np.asarray(t), [[1, 2], [0, 0]])
    k2, v2, t2 = apply_defrag(plan, kv, kv, tables, page_axis=2)
    np.testing.assert_array_equal(np.asarray(k2), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(t2), np.asarray(t))
