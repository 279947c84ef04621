"""``models/mimo_v2_flash.py`` (MiMo-V2-Flash: window layers with a
learned sink in a ring of pages a slot beside full layers in the paged
pool, q / k heads of one size and v heads of another, rotary on part of a
head at two bases, ONE CHIP'S SHARE of the routed experts) against the
benchmark's plain float32 reference (``benchmark/families/
mimo_v2_flash.py``), tiny, on the CPU: the whole forward, chunked
prefill and decode through both kinds of pool with the ring wrapped
several times, the window's edge, the sink, the kernel's new arguments
against its references, the expert shares against the uncut layer, and
the engine over a cache of two kinds of pool.
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
from paddle_tpu.models import mimo_v2_flash as M  # noqa: E402
from paddle_tpu.models.serving_tick import (  # noqa: E402
    serving_tick, serving_tick_block)
from paddle_tpu.ops.pallas import ragged_paged_attention as R  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402

TOL = 2e-4
FAMILY = manifest.load_family("mimo_v2_flash")
TINY = json.load(open(os.path.join(
    BENCH, "tests", "tiny", "configs", "tiny-mimo.json")))


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
    # chip 1 of 4: experts 8..15 of the 32 the router scores
    assert FAMILY.deployment(model) == (4, 1)
    assert cfg.experts_held == (8, 8) and cfg.n_routed_experts == 32
    assert cfg.rotary_dim == 8 and cfg.key_row_width == 128
    # the cut's walk: layer 0, then window x 4 + full, then a window
    assert [(len(g.layers), g.repeats) for g in M.layer_groups(cfg)] == [
        (1, 1), (5, 1), (1, 1)]
    assert params["moe"]["router"].shape == (6, 64, 32)
    assert params["moe"]["experts"]["w_gate"].shape == (6, 8, 64, 32)
    # two kinds of attention, their own stacks: 2 and 4 KV heads
    assert params["full"]["wk"].shape == (2, 2 * 24, 64)
    assert params["window"]["wk"].shape == (5, 4 * 24, 64)
    assert params["window"]["wv"].shape == (5, 4 * 16, 64)
    assert params["window"]["sinks"].shape == (5, 8)
    assert "sinks" not in params["full"]
    abstract = M.abstract_params(cfg)
    assert jax.tree.map(lambda a: a.shape, abstract) == jax.tree.map(
        lambda a: a.shape, params)
    with pytest.raises(ValueError):
        M.MimoV2FlashConfig.tiny(experts_held=(12, 8))
    # the published stack: layer 0 and every sixth from 5 are full, the
    # walk a leading layer, seven scanned periods and five layers more
    whole = M.MimoV2FlashConfig()
    assert sum(whole.hybrid_layer_pattern) == 39
    groups = M.layer_groups(whole)
    assert [(len(g.layers), g.repeats) for g in groups] == [
        (1, 1), (6, 7), (5, 1)]


def test_the_cache_is_two_kinds_of_pool():
    _, cfg, _ = built()
    cache = M.init_serving_pages(cfg, 9, 4, max_batch=3, max_span=6)
    # a ring: ceil((8 - 1 + 6) / 4) + 1 = 5 pages a slot, one trash page
    assert M.window_ring_pages(cfg, 4, 6) == 5
    assert cache[M.K_FULL].shape == (2, 2, 9, 4, 128)
    assert cache[M.V_FULL].shape == (2, 2, 9, 4, 16)
    assert cache[M.K_WINDOW].shape == (5, 4, 3 * 5 + 1, 4, 128)
    assert cache[M.V_WINDOW].shape == (5, 4, 3 * 5 + 1, 4, 16)
    # the window pool's bytes follow the slots, the window and the
    # chunk: not the paged pool's pages
    more = M.init_serving_pages(cfg, 900, 4, max_batch=3, max_span=6)
    assert more[M.K_WINDOW].shape == cache[M.K_WINDOW].shape
    assert [p.name for p in M.cache_page_pools(cfg)] == [M.K_FULL, M.V_FULL]
    kinds = M.serving_cache_kinds(cfg)
    assert [k.cache for k in kinds] == [
        "pages", "window_pages", "window_pages", "window_pages",
        "window_pages", "pages", "window_pages"]


def test_forward_against_the_reference():
    model, cfg, params = built()
    toks = seq(40)
    got = np.asarray(M.forward(params, jnp.asarray(toks)[None], cfg))[0]
    assert np.abs(got - ref_logits(params, model, toks)).max() < TOL


def test_generate_follows_the_reference_greedily():
    model, cfg, params = built()
    out = np.asarray(M.generate(params, jnp.asarray(seq(14))[None], cfg,
                                6))[0]
    want = ref_logits(params, model, out[:-1], rows=np.arange(13, 19))
    assert (want.argmax(-1) == out[14:]).all()


def test_the_sink_and_the_window_are_in_the_result():
    """The reference with the sinks far below every score, and with the
    window one token wider: both move the logits by much more than the
    tolerance the program is held to."""
    model, cfg, params = built()
    toks = seq(40)
    want = ref_logits(params, model, toks)
    no_sink = {**params, "window": {
        **params["window"],
        "sinks": jnp.full_like(params["window"]["sinks"], -1e4)}}
    assert np.abs(ref_logits(no_sink, model, toks) - want).max() > 100 * TOL
    wider = {**model, "sliding_window": model["sliding_window"] + 1}
    assert np.abs(ref_logits(params, wider, toks) - want).max() > 100 * TOL
    # and the program follows its configuration's window
    cfg9, _ = FAMILY.program_config(wider)
    got = np.asarray(M.forward(params, jnp.asarray(toks)[None], cfg9))[0]
    assert np.abs(got - ref_logits(params, wider, toks)).max() < TOL


# ----------------------------------------------------------------- ticks ----

S, PS, PPS, SPAN = 3, 4, 24, 16


class Ticks:
    """A hand-driven serving cache: ``S`` slots of ``PPS`` pages of the
    full layers' pool, slot ``s`` owning pages ``1 + s*PPS ..``, and the
    window layers' rings sized for spans of ``SPAN`` rows. ``run`` packs
    the given spans ``{slot: tokens}`` at each slot's current length
    into ONE tick of ``S + width`` rows, a padding token in front and
    one between the spans."""

    def __init__(self, cfg, params, attn_impl="auto"):
        self.cfg, self.params, self.impl = cfg, params, attn_impl
        self.cache = M.init_serving_pages(cfg, 1 + S * PPS, PS, max_batch=S,
                                          max_span=SPAN)
        self.tables = 1 + np.arange(S * PPS, dtype=np.int32).reshape(S, PPS)
        self.lens = np.zeros((S,), np.int32)

    def run(self, spans, width=SPAN, decode_tail=0, tail_live=()):
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
def test_chunked_prefill_then_decode_wraps_the_ring(impl, monkeypatch):
    """Chunks that do not divide the prompt, then decode ticks, 90
    tokens in all: the ring of a slot holds (8 - 1 + 16) / 4 -> 6 + 1 =
    7 pages = 28 tokens, so it wraps three times and more; every
    tick's logits against the reference's whole forward. ``pallas``: the
    kernel itself, in interpret mode, a span cut into blocks of 4."""
    model, cfg, params = built()
    monkeypatch.setattr(M, "BLOCK_TOKENS", 4)
    n_tok = 90 if impl == "auto" else 60
    toks = seq(n_tok)
    want = ref_logits(params, model, toks)
    t, at = Ticks(cfg, params, impl), 0
    ring = (t.cache[M.K_WINDOW].shape[2] - 1) // S * PS
    assert ring == 28
    chunks = [1, 2, 3, 5, 11, 16, 16, 13, 1, 1, 1, 7, 9, 1, 1, 1, 1]
    for n in chunks:
        if at + n > n_tok:
            break
        _, logits, counts = t.run({1: toks[at:at + n]})
        at += n
        assert np.abs(logits[1] - want[at - 1]).max() < TOL, (n, at)
        # every real row's choices land somewhere, once: 4 a row a layer
        assert counts[:3].sum() == cfg.num_experts_per_tok * n * 6
    assert at >= (3 * ring if impl == "auto" else ring + 16)


def test_a_short_and_a_long_request_in_one_batch():
    """Slot 0 prefills a long prompt 8 a tick while slots 1 and 2 (a
    short prompt each) decode beside it, past the ring's wrap."""
    model, cfg, params = built()
    a, b, c = seq(64), seq(9, 5, 1), seq(40, 11, 2)
    wa = ref_logits(params, model, a)
    t = Ticks(cfg, params)
    t.run({1: b[:6], 2: c[:9]})
    t.run({2: c[9:24]})
    t.run({2: c[24:30]})
    cb, cc = list(b[:6]), list(c[:30])
    for step in range(8):
        _, logits, _ = t.run({0: a[8 * step:8 * step + 8],
                              1: [b[6 + step] if step < 3 else 5 + step],
                              2: [c[30 + step]]})
        cb.append(b[6 + step] if step < 3 else 5 + step)
        cc.append(c[30 + step])
        assert np.abs(logits[0] - wa[8 * step + 7]).max() < TOL
        assert np.abs(logits[1] - ref_logits(params, model, cb)[-1]
                      ).max() < TOL
        assert np.abs(logits[2] - ref_logits(params, model, cc)[-1]
                      ).max() < TOL


def test_fused_tail_and_block_against_the_reference():
    model, cfg, params = built()
    a, b = seq(12), seq(30, 5, 1)
    t = Ticks(cfg, params)
    t.run({1: b[:16]})
    t.run({1: b[16:29]})
    toks, _, _ = t.run({0: a[:5], 1: b[29:30]}, decode_tail=2,
                       tail_live=(1,))
    assert toks.shape == (S, 3)
    cont = np.concatenate([b, toks[1]])
    want = ref_logits(params, model, cont[:-1], rows=np.arange(29, 32))
    assert (want.argmax(-1) == toks[1]).all()
    # the mid-prefill slot goes on where it was
    _, logits, _ = t.run({0: a[5:12]})
    assert np.abs(logits[0] - ref_logits(params, model, a)[11]).max() < TOL
    # the fused block from where slot 1 stands
    lengths = np.array([0, t.lens[1], 0], np.int32)
    tok = jnp.asarray(np.array([0, toks[1, -1], 0], np.int32))
    blk, counts, nxt, _ = serving_tick_block(
        params, tok, jnp.asarray(lengths), jnp.asarray(t.tables), t.cache,
        cfg, M.SERVING, num_steps=3)
    blk = np.asarray(blk)
    cont = np.concatenate([cont, blk[1]])
    want = ref_logits(params, model, cont[:-1], rows=np.arange(32, 35))
    assert (want.argmax(-1) == blk[1]).all()
    assert int(nxt[1]) == blk[1, -1] and int(nxt[0]) == 0
    assert np.asarray(counts)[:3].sum() == 3 * cfg.num_experts_per_tok * 6


def test_a_span_wider_than_the_ring_was_sized_for_is_refused():
    _, cfg, params = built()
    t = Ticks(cfg, params)
    with pytest.raises(ValueError, match="ring"):
        t.run({0: seq(20)}, width=32)
    with pytest.raises(ValueError, match="specul"):
        serving_tick(params, None, {}, t.cache, cfg, M.SERVING, spec_k=2)


# ---------------------------------------------------------------- kernel ----

def _batch(seed=0, dtype=jnp.float32):
    """A ragged batch: a decode row deep in its context, a dead slot, a
    span over some context, a span from position 0."""
    rng = np.random.default_rng(seed)
    S_, Tq, H, Hkv, Dk, Dv, ps, pps = 4, 8, 8, 2, 24, 16, 4, 16
    P = S_ * pps + 1
    q = jnp.asarray(rng.normal(size=(S_, Tq, H, Dk)), dtype)
    kp = jnp.asarray(rng.normal(size=(Hkv, P, ps, Dk)), dtype)
    vp = jnp.asarray(rng.normal(size=(Hkv, P, ps, Dv)), dtype)
    q_len = jnp.asarray([8, 1, 0, 5], jnp.int32)
    kv_len = jnp.asarray([40, 23, 0, 5], jnp.int32)
    tables = jnp.asarray(1 + rng.permutation(S_ * pps).reshape(S_, pps),
                         jnp.int32)
    sinks = jnp.asarray(rng.normal(size=(H,)), jnp.float32)
    return (q, kp, vp, q_len, kv_len, tables), sinks


def _brute(args, window, sinks):
    """Attention row by row from the equations, in numpy."""
    q, kp, vp, q_len, kv_len, tables = (np.asarray(a) for a in args)
    S_, Tq, H, Dk = q.shape
    Hkv, Dv = kp.shape[0], vp.shape[-1]
    G = H // Hkv
    out = np.zeros((S_, Tq, H, Dv), np.float32)
    for s in range(S_):
        ks = kp[:, tables[s]].reshape(Hkv, -1, Dk)
        vs = vp[:, tables[s]].reshape(Hkv, -1, Dv)
        for t in range(q_len[s]):
            pos = kv_len[s] - q_len[s] + t
            lo = max(0, pos - window + 1) if window else 0
            for h in range(H):
                z = (q[s, t, h] / np.sqrt(Dk)) @ ks[h // G, lo:pos + 1].T
                if sinks is not None:
                    z = np.concatenate([z, [float(sinks[h])]])
                p = np.exp(z - z.max())
                p /= p.sum()
                if sinks is not None:
                    p = p[:-1]
                out[s, t, h] = p @ vs[h // G, lo:pos + 1]
    return out


@pytest.mark.parametrize("window", [0, 6])
@pytest.mark.parametrize("sink", [False, True])
def test_the_kernels_new_arguments_against_its_references(window, sink):
    """A value head size other than the keys', a window, sinks: the
    one-shot reference, the dense twin and the interpreted kernel at two
    tiles against attention worked row by row; the twin bitwise the
    kernel."""
    args, sinks = _batch()
    sinks = sinks if sink else None
    want = _brute(args, window, sinks)
    kw = dict(window=window, sinks=sinks)
    got = R.ragged_paged_attention_reference(*args, **kw)
    assert got.shape == want.shape
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    for tile in (2, 3):
        twin = R.ragged_paged_attention(*args, impl="dense",
                                        kv_tile_pages=tile, **kw)
        kern = R.ragged_paged_attention(*args, impl="pallas",
                                        kv_tile_pages=tile, **kw)
        assert np.abs(np.asarray(kern) - want).max() < 1e-5
        np.testing.assert_array_equal(np.asarray(twin), np.asarray(kern))


def test_the_windows_edge_is_exact():
    """Key ``i - window + 1`` is seen, key ``i - window`` is not: a
    value planted on each changes, and does not change, the result."""
    args, _ = _batch()
    q, kp, vp, q_len, kv_len, tables = args
    W, s = 6, 1                     # the decode row at position 22
    pos = int(kv_len[s]) - 1

    def planted(key_pos):
        page, off = int(tables[s, key_pos // 4]), key_pos % 4
        return vp.at[:, page, off].add(100.0)

    base = R.ragged_paged_attention(q, kp, vp, q_len, kv_len, tables,
                                    impl="pallas", kv_tile_pages=2, window=W)
    seen = R.ragged_paged_attention(q, kp, planted(pos - W + 1), q_len,
                                    kv_len, tables, impl="pallas",
                                    kv_tile_pages=2, window=W)
    unseen = R.ragged_paged_attention(q, kp, planted(pos - W), q_len, kv_len,
                                      tables, impl="pallas", kv_tile_pages=2,
                                      window=W)
    assert np.abs(np.asarray(seen - base)[s, 0]).max() > 1e-3
    np.testing.assert_array_equal(np.asarray(unseen)[s], np.asarray(base)[s])


def test_the_old_arguments_trace_the_program_they_always_did():
    """Without the new arguments a launch lowers to the text it lowered
    to before they existed (``tools/program_hashes.py`` holds the cells'
    whole programs to that): the kernel's jaxpr names no sink operand,
    no window term."""
    args, _ = _batch()
    old = jax.make_jaxpr(lambda *a: R.ragged_paged_attention(
        *a, impl="pallas", kv_tile_pages=2))(*args)
    same = jax.make_jaxpr(lambda *a: R.ragged_paged_attention(
        *a, impl="pallas", kv_tile_pages=2, window=0, sinks=None))(*args)
    assert str(old) == str(same)
    new = jax.make_jaxpr(lambda *a: R.ragged_paged_attention(
        *a, impl="pallas", kv_tile_pages=2, window=6))(*args)
    assert str(new) != str(old)


@pytest.mark.parametrize("impl,bt", [("packed", 0), ("pallas", 0),
                                     ("pallas", 3), ("dense", 3)])
def test_the_packed_entry_cuts_a_span_into_blocks(impl, bt):
    """The tick's packed stream through the formulation a CPU tick
    takes, the slot-major kernel, and the kernel over virtual slots of 3
    tokens (``_span_blocks``): the same rows, padding rows zero."""
    args, sinks = _batch()
    q, kp, vp, q_len, kv_len, tables = args
    S_, Tq = q.shape[:2]
    T = 20
    tok_slot = np.full((T,), S_, np.int32)
    tok_qoff = np.zeros((T,), np.int32)
    start = np.zeros((S_,), np.int32)
    i = 2
    for s in range(S_):
        n = int(q_len[s])
        start[s] = i
        tok_slot[i:i + n], tok_qoff[i:i + n] = s, np.arange(n)
        i += n + (s == 0)
    qp = np.zeros((T,) + q.shape[2:], np.float32)
    real = tok_slot < S_
    qp[real] = np.asarray(q)[tok_slot[real], tok_qoff[real]]
    want = _brute(args, 6, sinks)
    meta = (jnp.asarray(tok_slot), jnp.asarray(tok_qoff), q_len, kv_len,
            tables)
    plan = R.stream_plan(*meta, Tq, q.shape[2], kp, start=jnp.asarray(start),
                         block_tokens=bt)
    got = np.asarray(R.ragged_paged_attention_packed(
        jnp.asarray(qp), kp, vp, *meta, tq=Tq, impl=impl,
        kv_tile_pages=None if impl == "packed" else 2, window=6,
        sinks=sinks, plan=plan))
    assert np.abs(got[real] - want[tok_slot[real], tok_qoff[real]]
                  ).max() < 1e-5
    assert np.abs(got[~real]).max() == 0


# ---------------------------------------------------------------- experts ----

def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Every chip's ``moe_ffn_share`` part of one layer (here 4 chips of
    8 experts) sums to what the uncut reference layer gives: the
    reference's ``routed`` with all 32 experts held."""
    model, cfg, _ = built()
    uncut = {**model, "n_routed_experts": 32, "ep_this_chip": 0}
    whole = FAMILY.make_params(uncut, 5)["moe"]
    lp = jax.tree.map(lambda a: a[2], whole)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = FAMILY.routed(lp, x, reference._static_model(uncut) and {
            k: v for k, v in uncut.items()
            if isinstance(v, (int, float, bool))}, None)
        total = jnp.zeros_like(x)
        pairs = 0
        for chip in range(4):
            share = jax.tree.map(lambda a: a[chip * 8:(chip + 1) * 8],
                                 lp["experts"])
            y, counts = moe_ffn_share(
                x, lp["router"], lp["router_bias"], share,
                held=(chip * 8, 8), num_routed=32, top_k=4,
                score_fn="sigmoid", normalize_topk=True)
            total = total + y
            pairs += int(counts[0])
            assert int(counts[0]) + int(counts[2]) == 4 * 24
    assert pairs == 4 * 24          # every choice is held exactly once
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < TOL


# ----------------------------------------------------------------- engine ----

def engine(cfg, params, **kw):
    return ServingEngine(params, cfg, model="mimo_v2_flash", **{**dict(
        max_batch=3, page_size=4, max_prompt_len=96, max_new_tokens_cap=16,
        prompt_buckets=(16, 96), prefill_chunk=16), **kw})


@pytest.mark.parametrize("block", [1, 4])
def test_engine_serves_greedily_what_the_reference_puts_first(block):
    """A short and a long request at once, the long one's ring wrapped
    three times (80 + 12 tokens over a ring of 28): every served token
    is the reference's first choice after the tokens before it."""
    model, cfg, params = built()
    eng = engine(cfg, params, decode_block_size=block)
    try:
        prompts = [seq(80), seq(9, 5, 1)]
        hs = [eng.submit(p, 12) for p in prompts]
        outs = [np.asarray(h.result(timeout=300)) for h in hs]
        for p, out in zip(prompts, outs):
            cont = np.concatenate([p, out])
            want = ref_logits(params, model, cont[:-1],
                              rows=np.arange(p.size - 1, cont.size - 1))
            assert (want.argmax(-1) == out).all()
        snap = eng.metrics.snapshot()
        c = snap["counters"]
        assert c["prefix_bypassed_window"] == 2
        assert c["window_kv_tokens"] > 0
        assert 0 < c["window_attn_pairs"] < c["attn_score_pairs"]
        assert c["moe_pairs_held"] + c["moe_pairs_absent"] > 0
        g = eng.gauges()
        ring = M.window_ring_pages(cfg, 4, 16)
        assert g["window_pool_bytes"] == sum(
            int(eng._cache[k].nbytes) for k in (M.K_WINDOW, M.V_WINDOW))
        assert eng._cache[M.K_WINDOW].shape[2] == 3 * ring + 1
        # admission counts the full layers' pages alone
        assert eng.pool.total_pages == eng._cache[M.K_FULL].shape[2]
        assert eng.prefix_cache is None
    finally:
        eng.close()


def test_engine_counts_the_window_layers_launches():
    """``window_kv_tokens`` / ``window_attn_pairs`` of hand-made
    launches: one layer's worth."""
    _, cfg, params = built()
    eng = engine(cfg, params)
    try:
        q = np.array([1, 5, 16])
        kv = np.array([40, 5, 20])
        got = eng._window_counts([(q, kv)])
        # W = 8: a decode row reads 8 keys, a span from 0 its 5 keys, a
        # 16-row span at 4..19 the 7 before it and its own
        assert got["window_kv_tokens"] == 8 + 5 + 20
        assert got["window_attn_pairs"] == 8 + (1 + 2 + 3 + 4 + 5) + (
            5 + 6 + 7 + 13 * 8)
    finally:
        eng.close()


def test_engine_defrags_the_paged_pool_and_leaves_the_rings():
    model, cfg, params = built()
    eng = engine(cfg, params, total_pages=120)
    try:
        a = eng.submit(seq(30), 4)
        b = eng.submit(seq(20, 5, 1), 16)
        a.result(timeout=300)
        moved = eng.defragment()
        out = np.asarray(b.result(timeout=300))
        cont = np.concatenate([seq(20, 5, 1), out])
        want = ref_logits(params, model, cont[:-1],
                          rows=np.arange(19, cont.size - 1))
        assert (want.argmax(-1) == out).all()
        assert moved >= 0
    finally:
        eng.close()


@pytest.mark.parametrize("call", ["export_chain", "adopt_chain"])
def test_engine_refuses_chain_migration_for_window_rings_by_name(call):
    _, cfg, params = built()
    eng = engine(cfg, params)
    try:
        with pytest.raises(RuntimeError, match="window rings"):
            if call == "export_chain":
                eng.export_chain(seq(8))
            else:
                eng.adopt_chain(b"")
        labeled = eng.metrics.snapshot()["labeled"]["chain_refused"]
        assert any("window_pages" in str(k) for k in labeled)
        with pytest.raises(ValueError, match="speculative"):
            engine(cfg, params, speculative=object())
    finally:
        eng.close()
