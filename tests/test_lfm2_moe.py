"""``models/lfm2_moe.py`` against the benchmark's plain float32 reference
(``benchmark/families/lfm2_moe.py``, which imports nothing of
``paddle_tpu``): whole sequences, serving ticks (chunked prefill, mixed
ticks, fused tails, slot reuse), the sigmoid-and-bias router, the engine
with the features a stateful model turns off, and the cut in depth.

Float32 on the CPU under conftest's "highest" matmul precision: program
and reference differ by the order of float32 sums only, so logits of
order 1 agree to 2e-4 (measured gaps are under 2e-5); the router's
choice is discrete, and a flip needs two sigmoid-plus-bias scores within
that rounding of each other, which the seeds here do not have.
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

from paddle_tpu.incubate.moe.functional import top_k_gating  # noqa: E402
from paddle_tpu.models import lfm2_moe as M  # noqa: E402
from paddle_tpu.models.serving_tick import (  # noqa: E402
    serving_tick, serving_tick_block)
from paddle_tpu.ops.pallas import ragged_paged_attention as R  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402

TOL = 2e-4
FAMILY = manifest.load_family("lfm2_moe")
TINY = json.load(open(os.path.join(
    BENCH, "tests", "tiny", "configs", "tiny-lfm2.json")))
# conv-dense, then attention / conv / conv / conv with experts: a whole
# period and a trailing part of one (attention, conv)
SEVEN = ("conv", "full_attention", "conv", "conv", "conv", "full_attention",
         "conv")


def model_of(layer_types, num_dense=1):
    return {**TINY, "layer_types": list(layer_types),
            "num_hidden_layers": len(layer_types),
            "num_dense_layers": num_dense}


def built(layer_types=SEVEN, num_dense=1, seed=11):
    model = model_of(layer_types, num_dense)
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

def test_layer_groups_of_the_published_stack():
    """40 layers compile as a handful of loops: the two dense conv
    layers walked once, nine whole periods scanned, the trailing
    (attention, conv) walked once."""
    g = M.layer_groups(M.Lfm2MoeConfig())
    assert [(len(x.layers), x.repeats) for x in g] == [(2, 1), (4, 9),
                                                       (2, 1)]
    assert g[0].layers == (("conv", "dense", 0, 0), ("conv", "dense", 1, 1))
    # the first period: attention 0 and conv layers 2-4, experts 0-3;
    # a repeat on, attention 1, conv 5-7, experts 4-7
    assert g[1].layers == (("full_attention", "moe", 0, 0),
                           ("conv", "moe", 2, 1), ("conv", "moe", 3, 2),
                           ("conv", "moe", 4, 3))
    assert g[1].stride == {"full_attention": 1, "conv": 3, "moe": 4}
    assert g[2].layers == (("full_attention", "moe", 9, 36),
                           ("conv", "moe", 29, 37))
    kinds = M.layer_kinds(M.Lfm2MoeConfig())
    assert sum(op == "full_attention" for op, *_ in kinds) == 10
    assert [i for i, k in enumerate(kinds) if k[0] == "full_attention"] == \
        list(range(2, 40, 4))


def test_cache_pytree_is_built_from_the_kinds():
    _, cfg, _ = built()
    cache = M.init_serving_pages(cfg, total_pages=9, page_size=4,
                                 max_batch=3)
    # 2 attention layers; 2 KV heads of 16 share a row; 5 conv layers,
    # 3 slots and the trash row, K - 1 = 2 earlier values
    assert cache["k_pages"].shape == (2, 1, 9, 4, 32)
    assert cache["conv_state"].shape == (5, 4, 2, 64)
    kinds = M.serving_cache_kinds(cfg)
    assert [k.cache for k in kinds].count("slot_rows") == 5
    full = M.Lfm2MoeConfig()
    shapes = jax.eval_shape(lambda: M.init_serving_pages(full, 8193, 16, 64))
    assert shapes["k_pages"].shape == (10, 4, 8193, 16, 128)
    assert shapes["conv_state"].shape == (30, 65, 2, 2048)


def test_forward_against_the_reference():
    """Every kind of layer and a trailing part of a period."""
    model, cfg, params = built()
    toks = seq(24)
    got = np.asarray(M.forward(params, jnp.asarray(toks)[None], cfg))[0]
    want = ref_logits(params, model, toks)
    assert np.abs(got - want).max() < TOL


def test_generate_follows_the_reference_greedily():
    model, cfg, params = built()
    out = np.asarray(M.generate(params, jnp.asarray(seq(9))[None], cfg, 6))[0]
    want = ref_logits(params, model, out[:-1], rows=np.arange(8, 14))
    assert (want.argmax(-1) == out[9:]).all()


# ----------------------------------------------------------------- ticks ----

S, PS, PPS = 3, 4, 8


class Ticks:
    """A hand-driven serving cache: ``S`` slots of ``PPS`` pages, slot
    ``s`` owning pages ``1 + s*PPS ..``. ``run`` packs the given spans
    ``{slot: tokens}`` at each slot's current length into ONE tick."""

    def __init__(self, cfg, params):
        self.cfg, self.params = cfg, params
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
        i = 1                               # a padding token in front
        for s, toks in spans.items():
            n = len(toks)
            tok[i:i + n], tok_slot[i:i + n] = toks, s
            tok_pos[i:i + n] = self.lens[s] + np.arange(n)
            tok_qoff[i:i + n] = np.arange(n)
            q_len[s], kv_len[s], last[s] = n, self.lens[s] + n, i + n - 1
            self.lens[s] += n
            i += n + 1                      # and one between the spans
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
        toks, logits, self.cache = serving_tick(
            self.params, jnp.asarray(tok), meta, self.cache, self.cfg,
            M.SERVING, tq=width, decode_tail=decode_tail)
        self.lens[list(tail_live)] += decode_tail
        return np.asarray(toks), np.asarray(logits)


def test_chunked_prefill_then_decode_against_the_reference():
    """Chunks that do not divide the prompt — spans of 1, 2, 3 and 5
    tokens: every branch of the state update (a span shorter than the
    row, as long, longer) — then three decode ticks. LOGITS at every
    span's last token against the reference's full forward."""
    model, cfg, params = built()
    toks = seq(14)
    want = ref_logits(params, model, toks)
    t, at = Ticks(cfg, params), 0
    for n in (1, 2, 3, 5, 1, 1, 1):
        _, logits = t.run({1: toks[at:at + n]})
        at += n
        assert np.abs(logits[1] - want[at - 1]).max() < TOL, (n, at)


def test_mixed_tick_one_slot_prefilling_others_decoding():
    model, cfg, params = built()
    a, b, c = seq(13), seq(9, 5, 1), seq(7, 11, 2)
    wa, wb, wc = (ref_logits(params, model, x) for x in (a, b, c))
    t = Ticks(cfg, params)
    t.run({1: b[:6], 2: c[:4]})
    for step in range(3):       # slot 0 prefills 4 a tick, 1 and 2 decode
        _, logits = t.run({0: a[4 * step:4 * step + 4],
                           1: b[6 + step:7 + step], 2: c[4 + step:5 + step]})
        assert np.abs(logits[0] - wa[4 * step + 3]).max() < TOL
        assert np.abs(logits[1] - wb[6 + step]).max() < TOL
        assert np.abs(logits[2] - wc[4 + step]).max() < TOL


def test_fused_tail_leaves_a_mid_prefill_slot_alone():
    """``decode_tail`` 2 with slot 0 mid-prefill (tail-dead): slot 1's
    tail tokens are the reference's greedy continuation, slot 0's conv
    rows are what the tick without a tail leaves, and its prefill goes
    on to the reference's logits."""
    model, cfg, params = built()
    a, b = seq(12), seq(6, 5, 1)
    plain, tailed = Ticks(cfg, params), Ticks(cfg, params)
    for t in (plain, tailed):
        t.run({1: b[:5]})
    plain.run({0: a[:5], 1: b[5:6]})
    toks, _ = tailed.run({0: a[:5], 1: b[5:6]}, decode_tail=2,
                         tail_live=(1,))
    assert toks.shape == (S, 3)
    cont = np.concatenate([b, toks[1]])
    want = ref_logits(params, model, cont[:-1], rows=np.arange(5, 8))
    assert (want.argmax(-1) == toks[1]).all()
    np.testing.assert_array_equal(
        np.asarray(plain.cache["conv_state"])[:, 0],
        np.asarray(tailed.cache["conv_state"])[:, 0])
    assert not np.array_equal(np.asarray(plain.cache["conv_state"])[:, 1],
                              np.asarray(tailed.cache["conv_state"])[:, 1])
    _, logits = tailed.run({0: a[5:12]})
    assert np.abs(logits[0] - ref_logits(params, model, a)[11]).max() < TOL


def test_fused_block_against_the_reference():
    """``serving_tick_block``: every slot decodes ``num_steps`` tokens."""
    model, cfg, params = built()
    b = seq(6, 5, 1)
    t = Ticks(cfg, params)
    first, _ = t.run({1: b})
    toks, _, t.cache = serving_tick_block(
        params, jnp.asarray(np.array([0, first[1], 0], np.int32)),
        jnp.asarray(t.lens), jnp.asarray(t.tables), t.cache, cfg, M.SERVING,
        3)
    cont = np.concatenate([b, first[1:2], np.asarray(toks)[1]])
    want = ref_logits(params, model, cont[:-1], rows=np.arange(5, 9))
    assert (want.argmax(-1) == cont[6:]).all()


def test_a_reused_slot_serves_as_a_fresh_one():
    """A slot that served A then serves B: zero BY POSITION, no reset."""
    model, cfg, params = built()
    a, b = seq(11), seq(10, 13, 5)
    used, fresh = Ticks(cfg, params), Ticks(cfg, params)
    used.run({0: a[:8]})
    used.run({0: a[8:]})
    used.lens[0] = 0                # retired: B starts at position 0
    for t in (used, fresh):
        t.run({0: b[:1]})
        t.run({0: b[1:3]})
    (_, got), (_, want) = used.run({0: b[3:]}), fresh.run({0: b[3:]})
    np.testing.assert_array_equal(got[0], want[0])
    assert np.abs(got[0] - ref_logits(params, model, b)[-1]).max() < TOL


# ---------------------------------------------------------------- router ----

def test_router_bias_enters_the_choice_only():
    """On data where the bias changes the chosen set (asserted), the
    combine weights are the UNBIASED sigmoids of the chosen experts,
    renormalised: fails if the bias leaks into the weights or is ignored
    in the choice."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(0, 0.5, (32, 8)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.3, (8,)), jnp.float32)
    s = np.asarray(jax.nn.sigmoid(logits))
    chosen = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :2]
    unbiased = np.argsort(-s, axis=-1)[:, :2]
    changed = [set(a) != set(b) for a, b in zip(chosen, unbiased)]
    assert 8 <= sum(changed) < 32
    _, combine, _ = top_k_gating(logits, 2, 32, score_fn="sigmoid",
                                 select_bias=bias, normalize_topk=True)
    got = np.asarray(combine.sum(-1))                           # [S, E]
    want = np.zeros_like(s)
    picked = np.take_along_axis(s, chosen, -1)
    np.put_along_axis(want, chosen,
                      picked / picked.sum(-1, keepdims=True), -1)
    # the published 1e-6 in the denominator is 5e-7 of a weight: the
    # program leaves it out (the configuration file's `departures`)
    np.testing.assert_allclose(got, want, atol=1e-6)
    # a NEGATIVE biased score is still never picked twice
    _, low, _ = top_k_gating(logits, 2, 32, score_fn="sigmoid",
                             select_bias=bias - 5.0)
    assert (np.asarray(low.sum(-1) > 0).sum(-1) == 2).all()


def test_default_router_is_bitwise_the_softmax_one():
    """The arguments the new router brings default to the program the
    softmax router always traced: the outputs are bitwise those of the
    algorithm written out here (softmax, peel by zeroing, guarded
    renormalisation)."""
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(0, 1.0, (16, 6)), jnp.float32)
    for norm in (False, True):
        d, c, aux = top_k_gating(logits, 2, 16, normalize_topk=norm)
        raw = jax.nn.softmax(logits, axis=-1)
        g, masks, vals = raw, [], []
        for _ in range(2):
            m = jax.nn.one_hot(jnp.argmax(g, -1), 6, dtype=jnp.float32)
            g = g * (1.0 - m)
            masks.append(m)
            vals.append(jnp.sum(raw * m, -1))
        if norm:
            den = sum(vals)
            vals = [v / jnp.where(den > 0, den, 1.0) for v in vals]
        want = sum(v[:, None] * m for v, m in zip(vals, masks))
        np.testing.assert_array_equal(np.asarray(c.sum(-1)),
                                      np.asarray(want))
        np.testing.assert_array_equal(np.asarray(d.sum(-1)),
                                      np.asarray(sum(masks)))
    explicit = jax.make_jaxpr(lambda x: top_k_gating(
        x, 2, 16, score_fn="softmax", select_bias=None))(logits)
    assert str(jax.make_jaxpr(lambda x: top_k_gating(x, 2, 16))(logits)) \
        == str(explicit)


def test_lane_packed_pool_reads_as_the_plain_one():
    """Two KV heads of 16 in one row of the pool, queries widened with
    zeros: the packed entry gives what it gives over the plain pool."""
    rng = np.random.default_rng(2)
    T, H, Hkv, Dh, P = 7, 4, 2, 16, 5
    q = jnp.asarray(rng.normal(size=(T, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, Hkv, P, PS, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, Hkv, P, PS, Dh)), jnp.float32)
    f = R.lane_pack_factor(Dh, Hkv)
    assert f == 2 and R.lane_pack_factor(64, 8) == 2
    assert R.lane_pack_factor(128, 8) == 1 == R.lane_pack_factor(48, 8)

    def packed(x):      # [1, Hkv, P, ps, Dh] -> [1, Hkv/f, P, ps, f*Dh]
        return R.lane_pack_heads(x.transpose(0, 2, 3, 1, 4), f).transpose(
            0, 3, 1, 2, 4)

    args = (jnp.asarray([0, 0, 0, 1, 1, 2, 2], jnp.int32),
            jnp.asarray([0, 1, 2, 0, 1, 0, 0], jnp.int32),
            jnp.asarray([3, 2, 1], jnp.int32), jnp.asarray([7, 2, 9],
                                                           jnp.int32),
            jnp.asarray([[1, 2, 0], [3, 0, 0], [4, 2, 1]], jnp.int32))
    args = (args[0].at[6].set(3), *args[1:])        # one padding token
    for impl in ("packed", "dense"):
        want = R.ragged_paged_attention_packed(q, k, v, *args, tq=3,
                                               impl=impl, layer=0)
        got = R.ragged_paged_attention_packed(q, packed(k), packed(v), *args,
                                              tq=3, impl=impl, layer=0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6)


# ---------------------------------------------------------------- engine ----

def engine(cfg, params, **kw):
    return ServingEngine(params, cfg, max_batch=3, page_size=4,
                         max_prompt_len=24, max_new_tokens_cap=8,
                         prefill_chunk=5, **kw)


@pytest.mark.parametrize("block", [1, 3])
def test_engine_is_greedy_generate(block):
    """Geometry alone: ``submit``, chunked prefill (chunks of 5 over
    prompts of 12, 7 and 23), mixed ticks, fused tails and blocks,
    retirement and slot reuse (5 requests on 3 slots)."""
    _, cfg, params = built()
    prompts = [seq(12), seq(7, 5, 1), seq(23, 11, 2), seq(3, 3, 3),
               seq(16, 13, 4)]
    with engine(cfg, params, decode_block_size=block) as eng:
        hs = [eng.submit(p, 6) for p in prompts]
        got = [h.result(timeout=600) for h in hs]
        assert eng.audit() == []
    for p, g in zip(prompts, got):
        want = np.asarray(M.generate(params, jnp.asarray(p)[None], cfg, 6))
        assert list(np.asarray(g)) == list(want[0, len(p):])


def test_engine_bypasses_the_prefix_cache_for_a_stateful_model():
    """One prompt twice (the harness's warm-up): the same tokens, no
    hit, two bypasses; the state's bytes are gauged."""
    _, cfg, params = built()
    prompt = seq(17)
    with engine(cfg, params) as eng:
        a = eng.submit(prompt, 4).result(timeout=600)
        b = eng.submit(prompt, 4).result(timeout=600)
        assert list(np.asarray(a)) == list(np.asarray(b))
        snap = eng.snapshot()
        assert snap["counters"]["prefix_hits"] == 0
        assert snap["counters"]["prefix_bypassed_stateful"] == 2
        assert eng.prefix_cache is None and eng._cold is None
        assert eng.gauges()["slot_state_bytes"] == 5 * 4 * 2 * 64 * 4
        assert eng.warm_programs() > 0
        # pages move under a live cache pytree; the slots' rows stay
        h = eng.submit(seq(9, 5, 1), 6)
        eng.defragment()
        c = h.result(timeout=600)
    want = np.asarray(M.generate(params, jnp.asarray(seq(9, 5, 1))[None],
                                 cfg, 6))[0, 9:]
    assert list(np.asarray(c)) == list(want)


def test_engine_refuses_speculation_for_a_stateful_model():
    _, cfg, params = built()
    with pytest.raises(ValueError, match="per-slot state"):
        engine(cfg, params, speculative="ngram")


@pytest.mark.parametrize("call", ["export_chain", "export_chain_begin",
                                  "adopt_chain", "adopt_chain_begin"])
def test_engine_refuses_chain_migration_for_a_stateful_model(call):
    _, cfg, params = built()
    with engine(cfg, params) as eng:
        with pytest.raises(RuntimeError, match="per-slot state"):
            getattr(eng, call)(1 if call.startswith("export") else {})


def test_engine_resolves_the_model_by_name_and_by_config():
    from paddle_tpu.models import resolve_family
    assert resolve_family("lfm2_moe") is M
    assert resolve_family(None, M.Lfm2MoeConfig.tiny()) is M
    assert resolve_family(M) is M
    with pytest.raises(ValueError, match="exposing SERVING"):
        resolve_family("no_such_family")


# ------------------------------------------------------------------- cut ----

def test_the_cut_is_a_slice_of_the_stack():
    """Layers 1-9 of a 12-layer model of the published pattern (two
    dense conv layers, attention the third of each four): the cut
    configuration run through the PROGRAM on the slice of the weights
    agrees with the reference's layers 1-9 of the whole model."""
    types = tuple("full_attention" if i % 4 == 2 else "conv"
                  for i in range(12))
    whole = model_of(types, num_dense=2)
    params = FAMILY.make_params(whole, 5)
    cut = model_of(types[1:10], num_dense=1)
    cfg, _ = FAMILY.program_config(cut)
    assert [(len(g.layers), g.repeats) for g in M.layer_groups(cfg)] == [
        (1, 1), (4, 2)]
    # by kind: the conv layers but the first and the last, the first two
    # attention layers, the second dense layer, the first eight of the
    # ten expert layers
    rows = {"conv": slice(1, 8), "attn": slice(0, 2), "dense": slice(1, 2),
            "moe": slice(0, 8)}
    sliced = {k: (jax.tree_util.tree_map(lambda a, r=rows[k]: a[r], v)
                  if k in rows else v) for k, v in params.items()}
    toks = seq(20)
    got = np.asarray(M.forward(sliced, jnp.asarray(toks)[None], cfg))[0]
    layers = list(reference._unstacked(
        reference.layer_groups(params, whole, FAMILY)))[1:10]
    h = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
    pos = jnp.arange(20, dtype=jnp.int32)
    static = reference._static_model(whole)
    for layer_fn, _, lp in layers:
        h = reference._layer_jit(lp, h, pos, layer_fn=layer_fn, model=static,
                                 round_to=None)
    want = np.asarray(reference.logits_at(params, h, np.arange(20), whole))
    assert np.abs(got - want).max() < TOL
    # and the cut's own reference walks the same nine layers
    assert np.abs(ref_logits(sliced, cut, toks) - want).max() < 1e-5
