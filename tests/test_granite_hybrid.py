"""``models/granite_hybrid.py`` against the benchmark's plain float32
reference (``benchmark/families/granite_hybrid.py``, which imports
nothing of ``paddle_tpu`` and scans the recurrence token by token):
whole sequences with all four multipliers, the chunked (SSD) form
against the sequential recurrence, serving ticks (chunked prefill, mixed
ticks, fused tails and blocks, a slot changing hands, idle slots), what
the state is worth at these weights, the kernel against its XLA twin,
and the engine with the features a stateful model turns off.

Float32 on the CPU under conftest's "highest" matmul precision: program
and reference differ by the order of float32 sums only (a chunk's masked
matmuls against a token-by-token scan), so logits of order 1 agree to
2e-4, the tolerance ``tests/test_lfm2_moe.py`` uses (measured gaps are
under 3e-6). The harness's head has no divisor, so the reference's logits
are divided by ``logits_scaling`` here.
"""
import dataclasses
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

from paddle_tpu.models import granite_hybrid as M  # noqa: E402
from paddle_tpu.models import layer_walk  # noqa: E402
from paddle_tpu.models.serving_tick import (  # noqa: E402
    serving_tick, serving_tick_block)
from paddle_tpu.ops.pallas import ssd_update as K  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402

TOL = 2e-4
FAMILY = manifest.load_family("granite_hybrid")
TINY = json.load(open(os.path.join(
    BENCH, "tests", "tiny", "configs", "tiny-granite.json")))
# a whole period (mamba x 2, attention, mamba) twice and a trailing
# part of one: a scanned group whose body has two runs of Mamba layers
NINE = ("mamba", "mamba", "attention", "mamba",
        "mamba", "mamba", "attention", "mamba", "mamba")


def model_of(layer_types):
    return {**TINY, "layer_types": list(layer_types),
            "num_hidden_layers": len(layer_types)}


def built(layer_types=NINE, seed=11, **kw):
    model = {**model_of(layer_types), **kw}
    cfg, mod = FAMILY.program_config(model)
    assert mod is M
    return model, cfg, FAMILY.make_params(model, seed)


def ref_logits(params, model, tokens, rows=None):
    """The reference's logits at the PUBLISHED scale."""
    tokens = np.asarray(tokens, np.int32)
    h = reference.hidden_states(params, tokens, model, FAMILY)
    rows = np.arange(tokens.size) if rows is None else np.asarray(rows)
    return np.asarray(reference.logits_at(params, h, rows, model)) / model[
        "logits_scaling"]


def seq(n, mul=7, add=3):
    return (np.arange(n) * mul + add) % TINY["vocab_size"]


# ------------------------------------------------------------ the stack ----

def test_layer_groups_of_the_published_stack():
    """40 layers = 4 periods of 10, walked as runs of 5 Mamba layers,
    the attention layer, 4 Mamba layers: two Mamba bodies a program."""
    cfg = M.GraniteHybridConfig()
    assert [i for i, t in enumerate(cfg.layer_types) if t == "attention"] \
        == [5, 15, 25, 35]
    (g,) = M.layer_groups(cfg)
    assert (len(g.layers), g.repeats) == (10, 4)
    assert g.stride == {"mamba": 9, "attention": 1, "mlp": 10}
    assert [(layer[0], n) for layer, n in layer_walk.runs(g.layers)] == [
        ("mamba", 5), ("attention", 1), ("mamba", 4)]
    # the walk pieces exist once: LFM2's names are these
    from paddle_tpu.models import lfm2_moe
    assert lfm2_moe.Group is layer_walk.Group
    assert lfm2_moe.LayerKind is layer_walk.LayerKind is M.LayerKind
    assert lfm2_moe._layer_params is layer_walk._layer_params


def test_cache_pytree_is_built_from_the_kinds():
    cfg = M.GraniteHybridConfig()
    kinds = M.serving_cache_kinds(cfg)
    assert [k.cache for k in kinds].count("pages") == 4
    assert [k.cache for k in kinds].count("slot_rows") == 36
    cache = jax.eval_shape(
        lambda: M.init_serving_pages(cfg, 8193, 16, max_batch=64))
    # head size 64: two KV heads a 128-lane row
    assert cache["k_pages"].shape == (4, 4, 8193, 16, 128)
    assert cache["conv_state"].shape == (36, 65, 3, 4352)
    assert cache["conv_state"].dtype == jnp.bfloat16
    # state-major: [N, H * P], 2 MiB a slot a layer, float32
    assert cache["ssm_state"].shape == (36, 65, 128, 4096)
    assert cache["ssm_state"].dtype == jnp.float32
    assert 128 * 4096 * 4 == 2 * 2 ** 20


def test_parameter_count_is_the_published_model_s():
    published = json.load(open(os.path.join(
        BENCH, "configs", "granite-4.0-h-micro.json")))
    assert published["reduced"] == {}
    assert FAMILY.param_count(published) == 3191396096
    assert published["sizes"]["params_whole_model_tied"] == 3191396096
    shapes = jax.eval_shape(lambda: M.init_params(
        FAMILY.program_config(published)[0], jax.random.PRNGKey(0)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == FAMILY.param_count(published, tied=False)


# ------------------------------------------------------ whole sequences ----

def test_forward_against_the_reference():
    """All four multipliers (12, 1/16 on the scores, 0.22, the logits
    over 8), 40 tokens through chunks of 16."""
    model, cfg, params = built()
    toks = seq(40)
    got = np.asarray(M.forward(params, jnp.asarray(toks)[None], cfg))[0]
    want = ref_logits(params, model, toks)
    assert np.abs(want).max() > 0.3          # logits of order 1
    assert np.abs(got - want).max() < TOL


def test_each_multiplier_enters():
    """A multiplier that the program dropped would pass no comparison:
    changing each one in the PROGRAM's config alone moves the logits."""
    model, cfg, params = built()
    toks = jnp.asarray(seq(24))[None]
    base = np.asarray(M.forward(params, toks, cfg))
    for field in ("embedding_multiplier", "attention_multiplier",
                  "residual_multiplier", "logits_scaling"):
        other = dataclasses.replace(cfg, **{field: getattr(cfg, field) * 2})
        moved = np.abs(np.asarray(M.forward(params, toks, other)) - base)
        assert moved.max() > 50 * TOL, field


def test_generate_follows_the_reference_greedily():
    model, cfg, params = built()
    out = np.asarray(M.generate(params, jnp.asarray(seq(9))[None], cfg, 6))[0]
    want = ref_logits(params, model, out[:-1], rows=np.arange(8, 14))
    assert (want.argmax(-1) == out[9:]).all()


def test_prefill_in_unequal_chunks_then_decode_through_the_dense_cache():
    model, cfg, params = built()
    toks = seq(30)
    want = ref_logits(params, model, toks)
    cache, at = M.init_kv_cache(cfg, 1, 32), 0
    for n in (7, 1, 13, 3, 1, 1, 1, 1, 1, 1):
        logits, cache = M.forward_with_cache(
            params, jnp.asarray(toks[at:at + n])[None], cache, at, cfg)
        at += n
        assert np.abs(np.asarray(logits)[0] - want[at - 1]).max() < TOL, at


# ----------------------------------- the chunked form, without a model ----

def sequential(x, dt, a_neg, bm, cm, s0):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t
    C_t``, float64, one sequence; ``s0 [H, P, N]``."""
    s, ys = np.array(s0, np.float64), []
    for t in range(x.shape[0]):
        s = (np.exp(dt[t] * a_neg)[:, None, None] * s
             + (dt[t][:, None] * x[t])[:, :, None] * bm[t][None, None])
        ys.append(s @ cm[t])
    return np.stack(ys), s


@pytest.mark.parametrize("span,initial,chunk", [
    (1, False, 256), (7, False, 256), (128, False, 256), (1, True, 256),
    (7, True, 256), (128, True, 256), (128, True, 48)],
    ids=lambda v: str(v))
def test_chunked_form_against_the_sequential_recurrence(span, initial, chunk):
    """One span in slot 1 of 3 beside a decode row in slot 2, from a
    zero or a non-zero state (the span then starts past position 0), as
    one chunk or several (``chunk`` 48: three)."""
    H, P, N, S = 4, 8, 16, 3
    rng = np.random.default_rng(span + 7 * initial)
    T = span + 3
    x = rng.normal(size=(T, H, P)).astype(np.float32)
    dt = rng.uniform(1e-3, 0.3, size=(T, H)).astype(np.float32)
    a_neg = -rng.uniform(1, 16, size=(H,)).astype(np.float32)
    bm, cm = (rng.normal(size=(T, N)).astype(np.float32) for _ in range(2))
    s0 = rng.normal(size=(S, H, P, N)).astype(np.float32)
    tok_slot = np.array([S] + [1] * span + [2, S], np.int32)
    pos0 = 5 if initial else 0
    tok_pos = np.array([0] + list(pos0 + np.arange(span)) + [9, 0], np.int32)
    state = np.zeros((2, S + 1, N, H * P), np.float32)
    state[1, :S] = s0.reshape(S, H * P, N).transpose(0, 2, 1)
    plans = M.ssd_plan(jnp.asarray(tok_slot), jnp.asarray(tok_pos), S, chunk)
    assert len(plans) == -(-T // chunk)
    y, new = M.ssd_rows(*map(jnp.asarray, (x, dt, a_neg, bm, cm)), plans,
                        jnp.asarray(state), 1, impl="dense")
    y, new = np.asarray(y), np.asarray(new)
    for slot, rows in ((1, slice(1, 1 + span)), (2, slice(1 + span, T - 1))):
        first = s0[slot] if (slot == 2 or initial) else 0 * s0[slot]
        want_y, want_s = sequential(x[rows], dt[rows], a_neg, bm[rows],
                                    cm[rows], first)
        scale = max(np.abs(want_y).max(), 1.0)
        assert np.abs(y[rows] - want_y).max() < 2e-5 * scale
        got_s = new[1, slot].T.reshape(H, P, N)
        assert np.abs(got_s - want_s).max() < 2e-5 * max(
            np.abs(want_s).max(), 1.0)
    # slot 0 had no row, layer 0 is another layer's: bitwise as they were
    np.testing.assert_array_equal(new[1, 0], state[1, 0])
    np.testing.assert_array_equal(new[1, S], state[1, S])
    np.testing.assert_array_equal(new[0], state[0])
    assert (y[[0, T - 1]] == 0).all()           # padding rows


# ----------------------------------------------------------------- ticks ----

S, PS, PPS = 3, 4, 12


class Ticks:
    """A hand-driven serving cache: ``S`` slots of ``PPS`` pages, slot
    ``s`` owning pages ``1 + s*PPS ..``. ``run`` packs the given spans
    ``{slot: tokens}`` at each slot's current length into ONE tick of
    ``S + width`` rows (19 at the default: more than the tiny model's
    ``mamba_chunk_size`` 16, so every tick is two chunks in turn)."""

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
    """Chunks that do not divide the prompt — spans of 1, 2, 3, 5 and
    11 tokens (shorter than the conv's window, as long, longer; the last
    crossing the tick's chunk boundary) — then three decode ticks."""
    model, cfg, params = built()
    toks = seq(25)
    want = ref_logits(params, model, toks)
    t, at = Ticks(cfg, params), 0
    for n in (1, 2, 3, 5, 11, 1, 1, 1):
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
    tail tokens are the reference's greedy continuation, slot 0's two
    states are what the tick without a tail leaves, BITWISE, and its
    prefill goes on to the reference's logits."""
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
    for name in ("conv_state", "ssm_state"):
        np.testing.assert_array_equal(
            np.asarray(plain.cache[name])[:, 0],
            np.asarray(tailed.cache[name])[:, 0])
        assert not np.array_equal(np.asarray(plain.cache[name])[:, 1],
                                  np.asarray(tailed.cache[name])[:, 1])
    _, logits = tailed.run({0: a[5:12]})
    assert np.abs(logits[0] - ref_logits(params, model, a)[11]).max() < TOL


def test_fused_block_against_the_reference():
    """``serving_tick_block``: a live slot decodes ``num_steps`` tokens;
    the free slots' state stays bitwise."""
    model, cfg, params = built()
    b = seq(6, 5, 1)
    t = Ticks(cfg, params)
    first, _ = t.run({1: b})
    before = np.asarray(t.cache["ssm_state"])
    toks, _, t.cache = serving_tick_block(
        params, jnp.asarray(np.array([0, first[1], 0], np.int32)),
        jnp.asarray(t.lens), jnp.asarray(t.tables), t.cache, cfg, M.SERVING,
        3)
    cont = np.concatenate([b, first[1:2], np.asarray(toks)[1]])
    want = ref_logits(params, model, cont[:-1], rows=np.arange(5, 9))
    assert (want.argmax(-1) == cont[6:]).all()
    after = np.asarray(t.cache["ssm_state"])
    np.testing.assert_array_equal(after[:, [0, 2, 3]], before[:, [0, 2, 3]])
    assert not np.array_equal(after[:, 1], before[:, 1])


def test_a_slot_changes_hands_without_a_reset():
    """A slot that served A then serves B: its state counts as zero BY
    POSITION, whatever A left in it."""
    model, cfg, params = built()
    a, b = seq(11), seq(10, 13, 5)
    used, fresh = Ticks(cfg, params), Ticks(cfg, params)
    used.run({0: a[:8]})
    used.run({0: a[8:]})
    assert np.abs(np.asarray(used.cache["ssm_state"])[:, 0]).max() > 1e-3
    used.lens[0] = 0                # retired: B starts at position 0
    for t in (used, fresh):
        t.run({0: b[:1]})
        t.run({0: b[1:3]})
    (_, got), (_, want) = used.run({0: b[3:]}), fresh.run({0: b[3:]})
    np.testing.assert_array_equal(got[0], want[0])
    assert np.abs(got[0] - ref_logits(params, model, b)[-1]).max() < TOL


def test_idle_slots_and_the_trash_row_are_bitwise_untouched():
    _, cfg, params = built()
    t = Ticks(cfg, params)
    t.run({0: seq(6), 2: seq(5, 5, 1)})
    before = {k: np.asarray(v) for k, v in t.cache.items()}
    t.run({2: seq(1, 3, 9)})        # slot 0 idle, slot 1 never used
    for name in ("conv_state", "ssm_state"):
        after = np.asarray(t.cache[name])
        np.testing.assert_array_equal(after[:, [0, 1, S]],
                                      before[name][:, [0, 1, S]])
        assert not np.array_equal(after[:, 2], before[name][:, 2])
        assert (after[:, S] == 0).all()         # nothing ever wrote it


def test_the_state_matters_at_these_weights():
    """A slot's state zeroed mid-sequence moves the next logits by far
    more than the tolerance: a state that leaked, was reset or was lost
    cannot pass a comparison at 2e-4."""
    model, cfg, params = built()
    toks = seq(21)
    want = ref_logits(params, model, toks)
    t = Ticks(cfg, params)
    t.run({1: toks[:12]})
    t.run({1: toks[12:20]})
    t.cache = {**t.cache,
               "ssm_state": jnp.zeros_like(t.cache["ssm_state"])}
    _, logits = t.run({1: toks[20:]})
    assert np.abs(logits[1] - want[20]).max() > 25 * TOL    # measured 53 x


def test_bfloat16_stored_state_gap():
    """What storing the state in bfloat16 between steps would cost
    (``ssm_state_dtype`` is a field of the CONFIG, float32 in the
    published configuration's file, which quotes this measurement): a
    32-token prompt, then 96 decode steps, the state re-rounded after
    each."""
    model, cfg, params = built()
    _, low, _ = built(ssm_state_dtype="bfloat16")
    assert cfg.ssm_state_dtype == jnp.float32
    assert low.ssm_state_dtype == jnp.bfloat16
    toks = seq(128, 11, 5)
    want = ref_logits(params, model, toks)
    gaps = {}
    for name, c in (("float32", cfg), ("bfloat16", low)):
        cache = M.init_kv_cache(c, 1, 128)
        assert cache["ssm"].dtype == c.ssm_state_dtype
        logits, cache = M.forward_with_cache(
            params, jnp.asarray(toks[:32])[None], cache, 0, c)
        step = jax.jit(lambda tok, cache, at, c=c: M.forward_with_cache(
            params, tok, cache, at, c))
        worst = np.abs(np.asarray(logits)[0] - want[31]).max()
        for at in range(32, 128):
            logits, cache = step(jnp.asarray(toks[at:at + 1])[None], cache,
                                 jnp.int32(at))
            worst = max(worst, np.abs(np.asarray(logits)[0] - want[at]).max())
        gaps[name] = float(worst)
    print("stored-state gaps", gaps)
    # measured (PR 38): float32 2.4e-7, bfloat16 1.3e-4, 550 x as wide
    assert gaps["float32"] < TOL / 100
    assert gaps["bfloat16"] > 100 * gaps["float32"]
    published = json.load(open(os.path.join(
        BENCH, "configs", "granite-4.0-h-micro.json")))
    assert published["ssm_state_dtype"] == "float32"
    assert "test_bfloat16_stored_state_gap" in published["assumed"][
        "ssm_state_dtype"]


# ------------------------------------------- the kernel and its XLA twin ----

def _launch(tok_slot, S=6, N=16, HP=256, seed=0, poison=()):
    rng = np.random.default_rng(seed)
    T = len(tok_slot)
    state = rng.normal(size=(2, S + 1, N, HP)).astype(np.float32)
    for s in poison:
        state[1, s] = np.nan
    args = (rng.normal(size=(T, N)), rng.normal(size=(T, N)),
            rng.normal(size=(T, HP)), rng.uniform(size=(S, HP)))
    return (jnp.asarray(state), 1) + tuple(
        jnp.asarray(a, jnp.float32) for a in args) + (
            jnp.asarray(tok_slot, jnp.int32),)


@pytest.mark.parametrize("tok_slot,head_blocks", [
    ([0, 1, 2, 3, 4, 5], 1),                            # decode rows
    ([6, 0] + [2] * 11 + [3, 5, 5, 5, 6, 6], 2),        # spans across blocks
    ([6] * 8, 2),                                       # no live slot
    ([4] * 24, 1)],                                     # one span, 3 blocks
    ids=["decode", "mixed", "none", "span"])
def test_kernel_in_interpret_mode_against_its_twin(tok_slot, head_blocks):
    args = _launch(tok_slot)
    y0, s0 = K.ssd_update(*args, impl="dense")
    y1, s1 = K.ssd_update(*args, impl="pallas", head_blocks=head_blocks)
    assert np.abs(np.asarray(y0 - y1)).max() < 1e-5
    assert np.abs(np.asarray(s0 - s1)).max() < 1e-5
    dead = [s for s in range(7) if s not in set(tok_slot)]
    for new in (s0, s1):
        np.testing.assert_array_equal(np.asarray(new)[1, dead],
                                      np.asarray(args[0])[1, dead])
        np.testing.assert_array_equal(np.asarray(new)[0],
                                      np.asarray(args[0])[0])


def test_kernel_neither_reads_nor_writes_a_dead_slot():
    """Dead slots and the trash row hold NaN: the live slots' results
    are finite and equal the clean launch's, and the NaN rows come back
    as they were (a walk that visited them would spread them)."""
    tok_slot = [6, 0, 0, 0, 3, 6, 5, 5]
    clean = _launch(tok_slot)
    dirty = _launch(tok_slot, poison=(1, 2, 4, 6))
    for impl in ("dense", "pallas"):
        y0, s0 = K.ssd_update(*clean, impl=impl)
        y1, s1 = K.ssd_update(*dirty, impl=impl)
        np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
        live = [0, 3, 5]
        np.testing.assert_array_equal(np.asarray(s0)[1, live],
                                      np.asarray(s1)[1, live])
        assert np.isnan(np.asarray(s1)[1, [1, 2, 4, 6]]).all()


def test_kernel_is_registered_with_the_auditor():
    from paddle_tpu.analysis import kernel_audit as ka
    assert K.AUDIT_KIND == "ssd_update"
    for geom in K.AUDIT_GEOMETRIES:
        verdict = ka.audit_config("ssd_update", geom, None)
        assert verdict["ok"], verdict
        ((label, fn, args),) = K.audit_launches(geom)
        assert label.startswith("state_pass[head_blocks=")
    # the cell's tick fits the audit's VMEM budget at two head blocks
    assert K.default_head_blocks(192, 64, 4096, 128) == 2
    assert K.block_bytes(192, 64, 2048, 128) <= K.VMEM_BLOCK_BUDGET


# ---------------------------------------------------------------- engine ----

def engine(cfg, params, **kw):
    return ServingEngine(params, cfg, max_batch=3, page_size=4,
                         max_prompt_len=24, max_new_tokens_cap=8,
                         prompt_buckets=(8, 24), prefill_chunk=5, **kw)


@pytest.mark.parametrize("block", [1, 3])
def test_engine_is_greedy_generate(block):
    """Geometry alone: ``submit``, chunked prefill (chunks of 5 over
    prompts of 12, 7 and 23), mixed ticks, fused tails and blocks,
    retirement and slots changing hands (5 requests on 3 slots), the
    config resolved to its family by ``models.resolve_family``."""
    _, cfg, params = built()
    prompts = [seq(12), seq(7, 5, 1), seq(23, 11, 2), seq(3, 3, 3),
               seq(16, 13, 4)]
    with engine(cfg, params, decode_block_size=block) as eng:
        assert eng._mod is M
        hs = [eng.submit(p, 6) for p in prompts]
        got = [h.result(timeout=600) for h in hs]
        assert eng.audit() == []
    for p, g in zip(prompts, got):
        want = np.asarray(M.generate(params, jnp.asarray(p)[None], cfg, 6))
        assert list(np.asarray(g)) == list(want[0, len(p):])


def test_engine_bypasses_the_prefix_cache_and_counts_the_state():
    """One prompt twice (the harness's warm-up): the same tokens, no
    hit, two bypasses; the state's bytes are gauged, and what the ticks
    had to move of it is counted from their live slots."""
    _, cfg, params = built()
    prompt = seq(17)
    with engine(cfg, params) as eng:
        a = eng.submit(prompt, 4).result(timeout=600)
        b = eng.submit(prompt, 4).result(timeout=600)
        assert list(np.asarray(a)) == list(np.asarray(b))
        snap = eng.snapshot()
        c = snap["counters"]
        assert c["prefix_hits"] == 0
        assert c["prefix_bypassed_stateful"] == 2
        assert eng.prefix_cache is None and eng._cold is None
        # 7 Mamba layers x 4 rows x (conv 3 x 160 f32 + state 16 x 128 f32)
        a_slot = 7 * (3 * 160 + 16 * 128) * 4
        assert eng.gauges()["slot_state_bytes"] == 4 * a_slot
        assert c["slot_state_bytes_moved"] == 2 * c["tick_live_slots"] * a_slot
        assert c["tick_live_slots"] > 0
        assert eng.warm_programs() > 0


def test_engine_refuses_speculation_for_a_stateful_model():
    _, cfg, params = built()
    with pytest.raises(ValueError, match=r"per-slot state \(\['mamba'"):
        engine(cfg, params, speculative="ngram")
    with pytest.raises(ValueError, match="rolled back"):
        serving_tick(params, None, {}, {}, cfg, M.SERVING, spec_k=2)


@pytest.mark.parametrize("call", ["export_chain", "export_chain_begin",
                                  "adopt_chain", "adopt_chain_begin"])
def test_engine_refuses_chain_migration_for_a_stateful_model(call):
    _, cfg, params = built()
    with engine(cfg, params) as eng:
        with pytest.raises(RuntimeError, match="per-slot state"):
            getattr(eng, call)(1 if call.startswith("export") else {})


def test_engine_resolves_the_model_by_name_and_by_config():
    from paddle_tpu.models import SERVING_FAMILIES, resolve_family
    assert SERVING_FAMILIES["granite_hybrid"] == "GraniteHybridConfig"
    assert resolve_family("granite_hybrid") is M
    assert resolve_family(None, M.GraniteHybridConfig.tiny()) is M


def test_serving_targets_trace_the_family_s_three_functions():
    from paddle_tpu.analysis import serving_graphs
    names = [t.name for t in serving_graphs.serving_targets("granite_hybrid")]
    assert any("serving_tick" in n for n in names)
    assert any("serving_tick_block" in n for n in names)
    assert not any("verify" in n or "spec" in n for n in names)


# ------------------------------------------------------------- the tools ----

def test_ragged_cells_keep_both_generate_cells_apart():
    """Two cells share the traffic ``generate``: the first keeps the
    traffic's name (what the LFM2 sweeps and compiles read), the second
    is keyed by its own and carries the state pass's geometry."""
    from tools.kernel_bench import ragged_cells
    cells = ragged_cells()
    assert cells["generate"]["layers"] == 2 and "ssm" not in cells["generate"]
    g = cells["granite4h-serve-generate"]
    assert (g["slots"], g["span"], g["pps"], g["pages"]) == (64, 128, 128,
                                                             8193)
    assert (g["kv_heads"], g["group"], g["head_dim"], g["layers"]) == (
        4, 8, 128, 4)
    assert g["ssm"] == dict(layers=36, heads=64, head_dim=64, state=128)


def test_ssd_sweep_runs_off_the_chip(tmp_path):
    """The kernel's sweep at a tiny size in interpret mode: six rows
    (decode rows only and with a span, 0 / 25 / 100 % of the slots
    live), each with the auditor's verdict."""
    from tools.kernel_bench import ssd_sweep
    out = tmp_path / "sweep.jsonl"
    rows = ssd_sweep(out=str(out), iters=1)
    assert len(rows) == 6 == len(out.read_text().splitlines())
    assert {r["tick"] for r in rows} == {"decode", "span"}
    assert {r["slots_live"] for r in rows} == {0.0, 0.25, 1.0}
    assert all(r["audit"] == "ok" and not r["timing_honest"] for r in rows)
    live = [r for r in rows if r["live_slots"]]
    assert all(r["state_bytes"] == 2 * r["live_slots"] * 16 * 128 * 4
               for r in live)
