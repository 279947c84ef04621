"""Ragged paged-attention kernel + one-program serving tick (ISSUE r12).

Verification story, bottom up:

* the Pallas kernel (interpret mode off-TPU) is BITWISE-equal to the
  dense-gather reference on seeded ragged batches — mixed prefill and
  decode spans, empty slots, partial tail pages, post-defrag
  (scattered, non-monotone) page lists;
* the packed (work-proportional) formulation the engine's CPU ticks
  route through is bitwise-equal to the slot-major reference, padding
  rows exactly zero;
* the engine built on the tick keeps greedy outputs bitwise-equal to
  ``generate()`` in every cache state — cold, warm full-prefix hit,
  partial-prefix hit, chunked prefill, post-defrag;
* the paged-KV invariant checker stays clean through a ragged-tick
  bench-shaped run (mixed admissions, chunked prefill, prefix sharing,
  mid-stream defrag).
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import llama as L
from paddle_tpu.models import serving_tick as T
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    ragged_paged_attention, ragged_paged_attention_packed, tiled_ulp_error)
from paddle_tpu.serving import ServingEngine

CFG = L.LlamaConfig.tiny(dtype=jnp.float32, use_flash_attention=False,
                         remat=False)


@pytest.fixture(scope="module")
def params():
    return L.init_params(CFG, jax.random.PRNGKey(0))


import functools


@functools.lru_cache(maxsize=None)
def _gen_jit(n):
    return jax.jit(lambda p, t: L.generate(p, t, CFG, max_new_tokens=n))


def _ref(params, prompt, n):
    out = _gen_jit(n)(params, jnp.asarray(prompt)[None])
    return np.asarray(out)[0, len(prompt):]


# ---------------------------------------------------------------------------
# kernel vs its dense twin: bitwise on seeded ragged batches; vs the
# one-shot reference: the ulp-at-row-scale contract
# ---------------------------------------------------------------------------

def _ragged_case(seed, S=4, Tq=6, H=4, Hkv=2, Dh=8, ps=4, P=24, pps=5,
                 scatter_tables=False, layers=None, q_len=None,
                 kv_len=None):
    """One seeded ragged batch: mixed prefill spans (q_len>1), decode
    steps (q_len=1), an empty slot (q_len=0), partial tail pages
    (kv_len % page_size != 0), TRASH entries past the covered range.
    ``layers`` makes the pools the serving tick's STACKED ones,
    ``[layers, Hkv, P, ps, Dh]``, every layer its own values;
    ``q_len`` / ``kv_len`` replace the drawn lengths."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(S, Tq, H, Dh).astype(np.float32))
    pool = (Hkv, P, ps, Dh) if layers is None else (layers, Hkv, P, ps, Dh)
    kp = jnp.asarray(rng.randn(*pool).astype(np.float32))
    vp = jnp.asarray(rng.randn(*pool).astype(np.float32))
    kv_max = pps * ps
    ql = np.zeros((S,), np.int32)
    kl = np.zeros((S,), np.int32)
    for s in range(S):
        kind = s % 3          # 0: prefill span, 1: decode, 2: empty
        if kind == 0:
            ql[s] = rng.randint(2, Tq + 1)
            kl[s] = rng.randint(ql[s], kv_max + 1)
        elif kind == 1:
            ql[s] = 1
            kl[s] = rng.randint(1, kv_max + 1)
    if q_len is not None:
        ql, kl = np.asarray(q_len, np.int32), np.asarray(kv_len, np.int32)
    if scatter_tables:
        # post-defrag shape: page ids scattered anywhere in the pool,
        # non-monotone per row (defrag remaps rows entry-by-entry)
        ids = rng.permutation(P - 1)[: S * pps] + 1
    else:
        ids = np.arange(1, S * pps + 1)
    tables = ids.reshape(S, pps).astype(np.int32)
    for s in range(S):
        covered = -(-int(kl[s]) // ps)
        tables[s, covered:] = 0              # TRASH past the span
    return (q, kp, vp, jnp.asarray(ql), jnp.asarray(kl),
            jnp.asarray(tables))


from paddle_tpu.ops.pallas.ragged_paged_attention import (  # noqa: E402
    ROW_BLOCK, TILED_ULP_BOUND, default_kv_tile_pages, vmem_scratch_bytes)


def _twin_and_contract(case, tile, **kw):
    """The kernel (interpret off-TPU) at ``tile`` against its dense
    twin, BITWISE, and against the one-shot reference under the
    ulp-at-row-scale contract. ``tile`` None: the walk a default call
    selects."""
    out_k = ragged_paged_attention(*case, impl="pallas",
                                   kv_tile_pages=tile, **kw)
    if tile is None:
        pps, ps, dh = case[5].shape[1], case[1].shape[-2], case[1].shape[-1]
        tile = default_kv_tile_pages(pps, ps, dh, case[1].dtype)
    out_r = ragged_paged_attention(*case, impl="dense", kv_tile_pages=tile,
                                   **kw)
    assert out_k.dtype == out_r.dtype
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
    one = ragged_paged_attention(*case, impl="dense", **kw)
    err = tiled_ulp_error(out_k, one)
    assert err <= TILED_ULP_BOUND, err
    return np.asarray(out_k)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("tile", [None, 1, 3, 5])
def test_kernel_matches_twin_bitwise(seed, tile):
    """Pallas kernel (double-buffered page-copy loops, interpret
    off-TPU) vs its dense twin — the same ``_flash_tile`` at two call
    sites: BITWISE on mixed prefill+decode batches with empty slots
    and partial tail pages. tile=3 does not divide pps=5 (ragged last
    tile); tile=5 is the whole table in one tile, which is also what
    the default selects at this width."""
    _twin_and_contract(_ragged_case(seed), tile)


LAYERS = 3


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("tile", [0, 3], ids=["one_tile", "tiled"])
def test_layer_indexed_kernel_matches_layer_slice_bitwise(tile, layer):
    """The serving tick's way in: the kernel handed the STACKED pools
    and a layer index (its DMAs read ``pages[layer, h, page]``) against
    the kernel, and its dense twin, handed that layer's pages sliced
    out: BITWISE, one tile and several, every layer; the index may be
    a traced value (the layer scan's)."""
    q, kps, vps, *geom = _ragged_case(layer, layers=LAYERS,
                                      scatter_tables=True)
    twin = tile or geom[2].shape[1]
    got = jax.jit(lambda l: ragged_paged_attention(
        q, kps, vps, *geom, impl="pallas", kv_tile_pages=tile,
        layer=l))(jnp.int32(layer))
    for impl, t in (("pallas", tile), ("dense", twin)):
        want = ragged_paged_attention(q, kps[layer], vps[layer], *geom,
                                      impl=impl, kv_tile_pages=t)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # off the TPU the layer is sliced out in front of the same code
    np.testing.assert_array_equal(
        np.asarray(ragged_paged_attention(q, kps, vps, *geom, impl="dense",
                                          kv_tile_pages=twin, layer=layer)),
        np.asarray(got))


# the walk's three trip counts, one case each way: (name, case kwargs,
# tile). Row blocks are ROW_BLOCK rows, so the launches that exercise
# them carry that many rows a slot and more.
_WIDE = dict(S=3, Tq=ROW_BLOCK, H=4, Hkv=2, pps=36, P=110)  # G=2: 2 blocks
_WALKS = [
    # slots: dead ones between live ones, first and last
    ("dead_between_live", dict(S=5, q_len=[0, 3, 0, 1, 0],
                               kv_len=[0, 9, 0, 20, 0]), 2),
    # query rows: a decoding slot inside a launch of ROW_BLOCK-row spans
    # costs one block; the span beside it all of them
    ("decode_in_span_launch", dict(_WIDE, q_len=[1, ROW_BLOCK, 0],
                                   kv_len=[17, ROW_BLOCK + 5, 0]), 3),
    # G x q_len one row past a block's edge, G = 3 (rows padded to
    # whole blocks; token = row // 3)
    ("rows_off_block_edge", dict(S=2, Tq=50, H=6, Hkv=2, pps=14, P=30,
                                 q_len=[43, 1], kv_len=[50, 3]), 4),
    ("rows_at_block_edge", dict(_WIDE, q_len=[ROW_BLOCK // 2, 2, 1],
                                kv_len=[ROW_BLOCK // 2, 31, 32]), 8),
    # pages: nothing, one key, a tile's edge and one past it, the table
    ("kv_len_0_1", dict(q_len=[1, 1, 0, 1], kv_len=[0, 1, 0, 2]), 2),
    ("kv_len_at_tile_edge", dict(q_len=[1, 2, 6, 1],
                                 kv_len=[8, 9, 16, 7]), 2),
    ("kv_len_full_table", dict(q_len=[6, 1, 1, 0],
                               kv_len=[20, 20, 19, 0]), 2),
    # a retiring slot's overrun (the fused decode tail steps past a
    # retirement): kv_len past the table, on the LAST slot, is read as
    # the table's width: no tile, page or key past it (tile 2 does not
    # divide the 5 pages: the last tile's sixth page stays masked)
    ("kv_len_past_table_last_slot", dict(q_len=[1, 2, 0, 1],
                                         kv_len=[5, 9, 0, 23]), 2),
    ("kv_len_past_table_one_tile", dict(q_len=[1, 0, 1, 1],
                                        kv_len=[21, 0, 20, 21]), 5),
    ("scattered_pages", dict(scatter_tables=True), 2),
    ("scattered_pages_tile_1", dict(scatter_tables=True), 1),
    ("stacked_pool", dict(layers=2, scatter_tables=True), 3),
    # a grid step holds a slot's KV heads: 1, 4, 8 and 16 of them over a
    # table five tiles wide, dead, decoding and span slots side by side
    *[(f"kv_heads_{n}", dict(S=5, H=2 * n, Hkv=n, pps=9, P=50,
                             scatter_tables=True, q_len=[0, 1, 5, 1, 0],
                             kv_len=[0, 33, 30, 7, 0]), 2)
      for n in (1, 4, 8, 16)],
]


@pytest.mark.parametrize("name,kw,tile", _WALKS,
                         ids=[w[0] for w in _WALKS])
def test_walk_trip_counts_follow_the_data(name, kw, tile):
    """Every way the kernel's loops can run short or long against the
    twin, which takes every trip: dead slots, one row block of many,
    rows off a block's edge, no key / one key / a tile's edge / the
    whole table, scattered page lists, the stacked pool. Bitwise, and
    the contract against the one-shot reference; dead slots and rows
    past ``q_len`` come out zero."""
    case = _ragged_case(len(name), **kw)
    layer = {"layer": 1} if kw.get("layers") else {}
    out = _twin_and_contract(case, tile, **layer)
    q_len = np.asarray(case[3])
    for s, n in enumerate(q_len):
        assert not out[s, n:].any(), (name, s)


def test_lane_packed_head_size_64_matches_the_plain_pool():
    """Head size 64 enters the kernel lane-packed, two KV heads a
    128-lane row and the queries widened with zeros: the kernel over
    the packed pool against its twin (bitwise) and against the dense
    one-shot reference over the PLAIN pool (the contract's bound at
    float32's eps)."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as R
    rng = np.random.RandomState(3)
    T, H, Hkv, Dh, P, ps, S = 9, 8, 4, 64, 7, 4, 3
    f = R.lane_pack_factor(Dh, Hkv)
    assert f == 2
    q = jnp.asarray(rng.randn(T, H, Dh).astype(np.float32))
    k = jnp.asarray(rng.randn(2, Hkv, P, ps, Dh).astype(np.float32))
    v = jnp.asarray(rng.randn(2, Hkv, P, ps, Dh).astype(np.float32))

    def packed(x):      # [L, Hkv, P, ps, Dh] -> [L, Hkv/f, P, ps, f*Dh]
        return R.lane_pack_heads(x.transpose(0, 2, 3, 1, 4), f).transpose(
            0, 3, 1, 2, 4)

    meta = (jnp.asarray([0, 0, 0, 0, 1, 2, 2, S, S], jnp.int32),
            jnp.asarray([0, 1, 2, 3, 0, 0, 1, 0, 0], jnp.int32),
            jnp.asarray([4, 1, 2], jnp.int32),
            jnp.asarray([9, 5, 2], jnp.int32),
            jnp.asarray([[1, 2, 3], [4, 5, 0], [6, 0, 0]], jnp.int32))
    run = functools.partial(ragged_paged_attention_packed, tq=4, layer=1)
    got = run(q, packed(k), packed(v), *meta, impl="pallas",
              kv_tile_pages=2)
    twin = run(q, packed(k), packed(v), *meta, impl="dense",
               kv_tile_pages=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(twin))
    want = run(q, k, v, *meta, impl="dense")
    assert tiled_ulp_error(got, want) <= TILED_ULP_BOUND
    assert not np.asarray(got)[7:].any()        # padding tokens: zero


def test_kernel_matches_reference_post_defrag_page_lists():
    """Scattered, non-monotone page tables (the shape defrag remaps
    produce) change nothing: the kernel walks the table, not an
    arithmetic page layout."""
    _twin_and_contract(_ragged_case(7, scatter_tables=True), None)


def test_empty_batch_and_full_pages():
    """Degenerate geometries: every slot empty (all-zero output), and a
    span exactly filling its last page (no partial tail)."""
    q, kp, vp, _, _, tables = _ragged_case(3)
    zeros = jnp.zeros((4,), jnp.int32)
    out = ragged_paged_attention(q, kp, vp, zeros, zeros, tables,
                                 impl="pallas")
    assert not np.asarray(out).any()
    q_len = jnp.asarray([4, 1, 2, 1], jnp.int32)
    kv_len = jnp.asarray([8, 4, 20, 12], jnp.int32)   # all % ps == 0
    _twin_and_contract((q, kp, vp, q_len, kv_len, tables), None)


def test_packed_matches_slot_major():
    """The work-proportional packed formulation (the engine's off-TPU
    tick path) against the slot-major reference. The same math, masks
    and reduction axes, but two contractions XLA is free to order
    differently (a batched einsum over the token stream against
    ``_attend``'s per-slot dots): on this XLA 90 of 224 elements differ
    in the last place. So it is held to the module's contract for
    differing reduction orders — error in ulp AT THE ROW'S SCALE
    (``tiled_ulp_error``; measured 1.0003, the bound here 2 where the
    tiled walk's is ``TILED_ULP_BOUND``) — and padding rows (slot
    sentinel S) exactly zero. With ``layer`` the packed entry slices
    the layer out in front of the same code: bitwise."""
    rng = np.random.RandomState(11)
    _, kp, vp, _, _, tables = _ragged_case(11, scatter_tables=True)
    S, Tq, H, Dh = 4, 3, 4, 8
    q_len = jnp.asarray([3, 1, 0, 2], jnp.int32)
    kv_len = jnp.asarray([9, 6, 0, 2], jnp.int32)
    # packed stream: slot 0's 3-token span, slot 1's decode token, one
    # padding token (sentinel S), slot 3's 2-token span
    tok_slot = jnp.asarray([0, 0, 0, 1, S, 3, 3], jnp.int32)
    tok_qoff = jnp.asarray([0, 1, 2, 0, 0, 0, 1], jnp.int32)
    qpk = jnp.asarray(rng.randn(7, H, Dh).astype(np.float32))
    run = functools.partial(
        ragged_paged_attention_packed, qpk, tok_slot=tok_slot,
        tok_qoff=tok_qoff, q_len=q_len, kv_len=kv_len, tables=tables,
        tq=Tq)
    out_p = run(kp, vp, impl="packed")
    out_d = run(kp, vp, impl="dense")
    assert tiled_ulp_error(out_p, out_d) <= 2
    assert not np.asarray(out_p)[4].any()    # padding row is zero
    assert not np.asarray(out_d)[4].any()
    stack = lambda x: jnp.stack([x + 1, x])  # noqa: E731
    out_l = run(stack(kp), stack(vp), impl="packed", layer=1)
    np.testing.assert_array_equal(np.asarray(out_l), np.asarray(out_p))


def _mixed_tick(seed, S, tq, H, Hkv, Dh, Dv=None, layers=None, ps=4,
                pps=6):
    """A tick's packed stream as the engine packs it, every kind of row
    in it: spans of 1, 7 and ``tq`` rows (slots 0, 1, 2), a second
    decode row in the LAST slot, every other slot dead, and padding
    rows in front of the first slot, between slots and behind the last.
    ``(q [T, H, Dh], kp, vp, tok_slot, tok_qoff, q_len, kv_len, tables,
    start)``; ``layers``: stacked pools."""
    rng = np.random.RandomState(seed)
    ql = np.zeros((S,), np.int32)
    ql[[0, 1, 2, S - 1]] = 1, 7, tq, 1
    kl = np.where(ql > 0, ql + rng.randint(0, pps * ps - tq, (S,)), 0)
    tok_slot, tok_qoff, start = [S], [0], np.zeros((S,), np.int32)
    for s in range(S):
        start[s] = len(tok_slot)
        tok_slot += [s] * int(ql[s]) + [S] * (s in (0, 2))
        tok_qoff += list(range(int(ql[s]))) + [0] * (s in (0, 2))
    tok_slot, tok_qoff = tok_slot + [S, S], tok_qoff + [0, 0]
    P = S * pps + 1
    tables = rng.permutation(P - 1)[: S * pps].reshape(S, pps) + 1
    for s in range(S):
        tables[s, -(-int(kl[s]) // ps):] = 0            # TRASH past the span
    lead = () if layers is None else (layers,)
    draw = lambda *shape: jnp.asarray(  # noqa: E731
        rng.randn(*shape).astype(np.float32))
    i32 = lambda a: jnp.asarray(np.asarray(a, np.int32))  # noqa: E731
    return (draw(len(tok_slot), H, Dh), draw(*lead, Hkv, P, ps, Dh),
            draw(*lead, Hkv, P, ps, Dv or Dh), i32(tok_slot), i32(tok_qoff),
            i32(ql), i32(kl), i32(tables), i32(start))


_STREAM_CASES = {
    # G = 1 and 4; 5 slots: a buffer about the stream's size; 24 slots,
    # 20 of them dead: a buffer that is mostly padding
    "g1": dict(S=5, H=2, Hkv=2),
    "g4": dict(S=5, H=8, Hkv=2),
    "g1-mostly-padding": dict(S=24, H=2, Hkv=2),
    "g4-mostly-padding": dict(S=24, H=8, Hkv=2),
    # the serving tick's way in: stacked pools and a layer index
    "stacked-layer": dict(S=5, H=8, Hkv=2, layers=3, kw=dict(layer=2)),
    # a window, sinks and values of another head size than the keys'
    "window-sinks-dv": dict(S=5, H=8, Hkv=2, Dv=16, sinks=True,
                            kw=dict(window=5)),
    "window-sinks-dv-mostly-padding": dict(S=24, H=8, Hkv=2, Dv=16,
                                           sinks=True, kw=dict(window=5)),
    # no plan from the caller: the entry makes it from the metadata,
    # each slot's first row read from ``tok_slot``
    "no-plan": dict(S=5, H=8, Hkv=2, plan=None),
    "no-plan-mostly-padding": dict(S=24, H=8, Hkv=2, plan=None),
    # a span cut into virtual slots of 3 tokens, with and without the
    # slots' first rows from the caller
    "blocks-of-3": dict(S=5, H=8, Hkv=2, plan=dict(block_tokens=3)),
    "blocks-of-3-no-start": dict(S=5, H=8, Hkv=2,
                                 plan=dict(block_tokens=3, start=None)),
    "blocks-of-3-mostly-padding": dict(S=24, H=8, Hkv=2,
                                       plan=dict(block_tokens=3)),
    # head size 64 over a LANE-PACKED pool (two KV heads a 128-lane
    # row, the queries widened with zeros), stacked
    "lane-packed": dict(S=5, H=8, Hkv=4, Dh=64, layers=2, kw=dict(layer=1)),
    "lane-packed-mostly-padding": dict(S=24, H=8, Hkv=4, Dh=64, layers=2,
                                       kw=dict(layer=1)),
}


@pytest.mark.parametrize("impl", ["pallas", "dense"])
@pytest.mark.parametrize("name", list(_STREAM_CASES))
def test_stream_launch_matches_the_packed_formulation(name, impl):
    """The packed entry's way to the kernel (``_stream_launch``: a
    slot's rows copied straight into the kernel's head-major block, the
    results gathered straight back) against the packed formulation a
    CPU tick takes, on a tick with every kind of row (``_mixed_tick``):
    the contract's bound at the row's scale for two orders of the same
    reductions, padding rows exactly zero; the kernel and its dense
    twin over the same boundary BITWISE."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as R
    c = dict(_STREAM_CASES[name])
    kw, sinks = dict(c.pop("kw", {})), c.pop("sinks", False)
    # the packing's plan made by the caller, as a walk of many layers
    # makes it once a tick
    plan = c.pop("plan", {})
    tq, Dh = 9, c.pop("Dh", 8)
    *args, start = _mixed_tick(3, tq=tq, Dh=Dh, **c)
    q, tok_slot = args[0], np.asarray(args[3])
    T, G, S = q.shape[0], c["H"] // c["Hkv"], c["S"]
    if name.startswith("lane-packed"):
        # [L, Hkv, P, ps, Dh] -> [L, Hkv/f, P, ps, f*Dh]
        f = R.lane_pack_factor(Dh, c["Hkv"])
        assert f == 2
        args[1:3] = [R.lane_pack_heads(x.transpose(0, 2, 3, 1, 4),
                                       f).transpose(0, 3, 1, 2, 4)
                     for x in args[1:3]]
        G *= f
    if sinks:
        kw["sinks"] = jnp.asarray(
            np.random.RandomState(4).randn(c["H"]).astype(np.float32))
    if plan is not None:
        kw["plan"] = R.stream_plan(*args[3:], tq, c["H"], args[1],
                                   **{"start": start, **plan})
    run = functools.partial(ragged_paged_attention_packed, *args, tq=tq,
                            **kw)
    want = run(impl="packed")
    got = run(impl=impl, kv_tile_pages=2)
    assert got.shape == want.shape == (T, c["H"], c.get("Dv", Dh))
    assert tiled_ulp_error(got, want) <= TILED_ULP_BOUND
    assert not np.asarray(got)[tok_slot == S].any()     # padding: zero
    assert np.asarray(got)[tok_slot < S].all()
    if impl == "pallas":
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(run(impl="dense", kv_tile_pages=2)))


def test_a_plan_for_other_heads_is_refused():
    """A plan places the rows of ONE head geometry: handed a stream of
    another, the entry raises at trace time where a gather would have
    read other rows in silence."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as R
    *args, start = _mixed_tick(3, S=24, tq=9, H=8, Hkv=2, Dh=8)
    plan = R.stream_plan(*args[3:], 9, 4, args[1], start=start)
    with pytest.raises(ValueError, match="cannot place"):
        ragged_paged_attention_packed(*args, tq=9, impl="dense", plan=plan)


def test_packed_sweep_times_the_one_entry_off_the_chip():
    """``tools/kernel_bench.py --packed-sweep`` off the chip: a decode,
    a span and a verify tick of the tiny cells (whole slots; a window
    launch whose span is cut into virtual slots) through the packed
    entry's kernel path, a quarter and all of the slots live, wall-clock
    rows that say they are no device timing."""
    from tools.kernel_bench import VERIFY_ROWS, packed_sweep
    rows = packed_sweep(iters=1)
    assert [(r["cell"], r["block_tokens"]) for r in rows[::6]] == [
        ("tiny", 0), ("tiny.blocked", 2)]
    assert [(r["tick"], r["slots_live"]) for r in rows] == 2 * [
        ("decode", 0.25), ("decode", 1.0), ("span", 0.25), ("span", 1.0),
        ("verify", 0.25), ("verify", 1.0)]
    assert all(r["busy_ms"] > 0 and not r["timing_honest"] for r in rows)
    assert all(r["stream_rows"] == (
        r["slots"] * VERIFY_ROWS if r["tick"] == "verify"
        else r["slots"] + (r["tq"] > 1) * r["tq"]) for r in rows)


def test_bottom_right_causal_prefill_equals_whole():
    """Chunked-prefill exactness at the kernel level: running a prompt
    as two ragged spans (KV written first, bottom-right causal) gives
    the SAME bits for the second span's rows as one whole-prompt span —
    the property the engine's chunked prefill rests on."""
    rng = np.random.RandomState(5)
    Hkv, Dh, ps, P, pps = 2, 8, 4, 10, 4
    H, n, split = 4, 10, 6
    kp0 = jnp.zeros((Hkv, P, ps, Dh), jnp.float32)
    vp0 = jnp.zeros((Hkv, P, ps, Dh), jnp.float32)
    k_new = rng.randn(n, Hkv, Dh).astype(np.float32)
    v_new = rng.randn(n, Hkv, Dh).astype(np.float32)
    q = rng.randn(n, H, Dh).astype(np.float32)
    table = np.zeros((1, pps), np.int32)
    table[0, : -(-n // ps)] = np.arange(1, -(-n // ps) + 1)
    tab = jnp.asarray(table)

    def write(kp, vp, lo, hi):
        pos = np.arange(lo, hi)
        pages = table[0, pos // ps]
        kp = kp.at[:, pages, pos % ps].set(
            np.moveaxis(k_new[lo:hi], 1, 0))
        vp = vp.at[:, pages, pos % ps].set(
            np.moveaxis(v_new[lo:hi], 1, 0))
        return kp, vp

    # whole prompt: one span of n rows
    kp, vp = write(kp0, vp0, 0, n)
    whole = ragged_paged_attention(
        jnp.asarray(q)[None], kp, vp, jnp.asarray([n], jnp.int32),
        jnp.asarray([n], jnp.int32), tab, impl="pallas")
    # two chunks: rows split.. attend over written prefix + own span
    kp, vp = write(kp0, vp0, 0, split)
    kp, vp = write(kp, vp, split, n)
    part = ragged_paged_attention(
        jnp.asarray(q[split:])[None], kp, vp,
        jnp.asarray([n - split], jnp.int32), jnp.asarray([n], jnp.int32),
        tab, impl="pallas")
    np.testing.assert_array_equal(np.asarray(whole)[0, split:],
                                  np.asarray(part)[0, : n - split])


def test_tiled_kernel_post_defrag_and_degenerate_slots():
    """Scattered page tables, kv_len=0 (dead slot -> exact zeros),
    kv_len=1 and single-page slots through a two-page tile."""
    _twin_and_contract(_ragged_case(7, scatter_tables=True), 2)
    q, kp, vp, _, _, tables = _ragged_case(3)
    zeros = jnp.zeros((4,), jnp.int32)
    out = ragged_paged_attention(q, kp, vp, zeros, zeros, tables,
                                 impl="pallas", kv_tile_pages=2)
    assert not np.asarray(out).any()
    # kv_len 1 and single-page (kv_len <= page_size) slots
    q_len = jnp.asarray([1, 1, 1, 1], jnp.int32)
    kv_len = jnp.asarray([1, 4, 2, 3], jnp.int32)
    _twin_and_contract((q, kp, vp, q_len, kv_len, tables), 2)


@pytest.mark.parametrize("seed", [0, 1, 2, 5])
@pytest.mark.parametrize("pps,ps", [(5, 4), (32, 8)])
def test_tiled_vs_oneshot_ulp_contract(seed, pps, ps):
    """The walk's exactness contract vs the one-shot reference
    (TILED_ULP_BOUND — ulp measured at the slot's output scale; a raw
    per-element ulp bound cannot survive the flash combine's
    reassociation at cancellation-small components, see the kernel
    module). Mixed prefill+decode spans, empty slots, partial tail
    pages, tiles that do not divide the live page count."""
    case = _ragged_case(seed, pps=pps, ps=ps)
    one = np.asarray(ragged_paged_attention(*case, impl="dense",
                                            kv_tile_pages=0))
    for tile in (1, 3, max(pps // 2, 1), pps):
        tiled = np.asarray(ragged_paged_attention(
            *case, impl="pallas", kv_tile_pages=tile))
        err = tiled_ulp_error(tiled, one)
        assert err <= TILED_ULP_BOUND, (seed, pps, ps, tile, err)


def test_scratch_independent_of_table_width_and_rows_of_the_launch():
    """The acceptance property in numbers, straight from the scratch
    shapes: past one tile the K+V scratch does not grow with
    pages_per_slot — a 100k-token table pins the same VMEM as a 2k one
    — a table under one tile pins its own width, the flash state grows
    with the launch's query rows alone, and no serving geometry of the
    benchmark selects a tile as wide as its table."""
    ps, dh = 16, 128
    walks = [vmem_scratch_bytes(pps, ps, dh, jnp.bfloat16)
             for pps in (88, 128, 160, 512, 6250)]
    assert len(set(walks)) == 1 and walks[0] == 512 * 2 ** 10
    assert default_kv_tile_pages(6250, ps, dh, jnp.bfloat16) == 32
    for pps in (88, 128, 160):          # batch, generate, chat
        assert default_kv_tile_pages(pps, ps, dh, jnp.bfloat16) < pps
    # a table under one tile is one trip over its own width
    assert default_kv_tile_pages(16, ps, dh, jnp.bfloat16) == 16
    assert vmem_scratch_bytes(16, ps, dh, jnp.bfloat16) == walks[0] // 2
    assert vmem_scratch_bytes(16, ps, dh, jnp.bfloat16,
                              kv_tile_pages=0) == walks[0] // 2
    # the tile is bytes, not tokens: float32 rows halve its pages
    assert default_kv_tile_pages(6250, ps, dh, jnp.float32) == 16
    # flash state: (max, denominator, accumulator) a query row
    assert (vmem_scratch_bytes(160, ps, dh, rows=512) - walks[0]
            == 512 * (dh + 2) * 4)


def test_a_step_that_cannot_hold_every_head_takes_them_in_blocks(
        monkeypatch):
    """Where the VMEM budget does not hold a slot's KV heads in one
    grid step, the step holds the largest divisor of them that fits and
    a slot takes several steps: the same walk, bitwise against the
    twin, and the copies a page rise by the steps."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as R
    case = _ragged_case(4, S=5, H=16, Hkv=8, pps=9, P=50,
                        scatter_tables=True, q_len=[0, 1, 5, 1, 0],
                        kv_len=[0, 33, 30, 7, 0])
    geom = dict(kv_heads=8, pages_per_slot=9, page_size=4, head_dim=8,
                dtype=jnp.float32, rows=12, kv_tile_pages=2)
    assert R.page_copies(**geom) == 2
    whole = R._step_vmem_bytes(8, 2, 4, 8, 12, 4)
    monkeypatch.setattr(R, "STEP_VMEM_BUDGET", whole // 3)
    assert R.page_copies(**geom) == 2 * 4       # two heads a step
    R._pallas_impl.clear_cache()
    try:
        _twin_and_contract(case, 2)
    finally:
        R._pallas_impl.clear_cache()


def test_geometry_chosen_tile_fits_the_cells_scoped_vmem():
    """The four serving cells' launches (``ragged_cells()``: a decode
    tick, the cell's chunk, twice the chunk): at the geometry's tile
    (512 KV tokens, never given up) the heads a step the geometry
    selects keep the scratch, and the whole step with its
    double-buffered blocks as the chip's compiler counts them, under
    the budget inside the 16 MiB of scoped VMEM; at the cell's own
    launches a step holds every KV head, so a page moves with ONE copy
    a pool — but the batch cell's 16 heads under its 256-row chunk,
    which take two steps a slot."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as R
    from tools.kernel_bench import ragged_cells
    cells = ragged_cells()
    assert len(cells) == 4
    assert R.STEP_VMEM_BUDGET < 16 * 2 ** 20
    for name, c in cells.items():
        kv, ps, dh, pps = (c["kv_heads"], c["page_size"], c["head_dim"],
                           c["pps"])
        tile = default_kv_tile_pages(pps, ps, dh, jnp.bfloat16)
        assert tile == 32
        for tq in (1, c["span"], 2 * c["span"]):
            rows = tq * c["group"]
            heads = R.heads_per_step(kv, tile, ps, dh, jnp.bfloat16, rows)
            assert kv % heads == 0
            scratch = vmem_scratch_bytes(pps, ps, dh, jnp.bfloat16,
                                         rows=rows, kv_heads=kv)
            step = R._step_vmem_bytes(heads, tile, ps, dh, rows, 2)
            assert scratch < step <= R.STEP_VMEM_BUDGET, (name, tq)
            assert scratch == heads * vmem_scratch_bytes(
                pps, ps, dh, jnp.bfloat16, rows=rows)
            copies = R.page_copies(kv, pps, ps, dh, jnp.bfloat16, rows)
            assert copies == 2 * kv // heads
            if tq <= c["span"]:
                assert copies == (4 if (name, tq) == ("batch", 256) else 2)


# ---------------------------------------------------------------------------
# engine exactness: greedy == generate() in every cache state
# ---------------------------------------------------------------------------

def _engine(params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_prompt_len", 16)
    kw.setdefault("max_new_tokens_cap", 16)
    return ServingEngine(params, CFG, **kw)


@pytest.mark.parametrize("attn_impl", ["auto", "pallas"])
def test_block_tick_free_slots_are_dead_and_live_ones_decode(
        params, monkeypatch, span_tick, attn_impl):
    """The fused decode block with FREE slots (length 0) between live
    ones: the free slots enter the tick dead (``q_len == lengths > 0``,
    slot sentinel, ``tail_live`` false: the kernel's walk skips them)
    and every live slot's tokens equal single-step greedy decode,
    through the packed formulation and through the kernel."""
    S, ps, pps, K = 5, 4, 6, 3
    rng = np.random.RandomState(4)
    cache = L.init_serving_pages(CFG, 1 + S * pps, ps)
    tables = np.zeros((S, pps), np.int32)
    lengths = np.zeros((S,), np.int32)
    tok = np.zeros((S,), np.int32)
    for s, n in ((1, 7), (3, 4)):          # slots 0, 2 and 4 stay free
        tables[s] = 1 + s * pps + np.arange(pps)
        prompt = rng.randint(0, CFG.vocab_size, (n,))
        toks, _, cache = span_tick(L, params, CFG, cache, tables, s,
                                   prompt, 0, 8)
        lengths[s], tok[s] = n, int(toks[s])
    live = lengths > 0
    # single-step greedy decode: K blocks of one step each
    want, cur, lens, c = [], jnp.asarray(tok), lengths.copy(), cache
    for _ in range(K):
        t, nxt, c = T.serving_tick_block(
            params, cur, jnp.asarray(lens), jnp.asarray(tables), c, CFG,
            L.SERVING, 1)
        want.append(np.asarray(t)[:, 0])
        # the successor of the slots' current tokens: a live slot's new
        # token, a dead slot's old value
        np.testing.assert_array_equal(
            np.asarray(nxt), np.where(live, np.asarray(t)[:, 0],
                                      np.asarray(cur)))
        cur, lens = nxt, lens + live
    want = np.stack(want, axis=1)
    seen = {}
    tick = T.serving_tick

    def spy(params, tokens, meta, *a, **kw):
        if not seen:            # the block's own call, not the tail's
            seen.update(meta)
        return tick(params, tokens, meta, *a, **kw)

    monkeypatch.setattr(T, "serving_tick", spy)
    got, nxt, _ = T.serving_tick_block(
        params, jnp.asarray(tok), jnp.asarray(lengths),
        jnp.asarray(tables), cache, CFG, L.SERVING, K, attn_impl=attn_impl)
    np.testing.assert_array_equal(np.asarray(got)[live], want[live])
    np.testing.assert_array_equal(np.asarray(nxt),
                                  np.where(live, want[:, -1], tok))
    np.testing.assert_array_equal(np.asarray(seen["q_len"]), live)
    np.testing.assert_array_equal(np.asarray(seen["tail_live"]), live)
    np.testing.assert_array_equal(np.asarray(seen["tok_slot"]),
                                  np.where(live, np.arange(S), S))


def test_engine_matches_generate_cold_warm_partial(params):
    """The one-program tick keeps greedy outputs byte-identical to
    ``generate()`` whether the prompt's prefix was cold, fully cached
    (EXACT attach — any page count), or partially cached."""
    rng = np.random.RandomState(2)
    base = rng.randint(0, CFG.vocab_size, (13,)).astype(np.int32)
    partial = np.concatenate(
        [base[:9], rng.randint(0, CFG.vocab_size, (5,)).astype(np.int32)])
    with _engine(params) as eng:
        cold = eng.submit(base, 6).result(timeout=300)
        warm = eng.submit(base, 6).result(timeout=300)
        part = eng.submit(partial, 6).result(timeout=300)
        snap = eng.stats()
    np.testing.assert_array_equal(cold, _ref(params, base, 6))
    np.testing.assert_array_equal(warm, _ref(params, base, 6))
    np.testing.assert_array_equal(part, _ref(params, partial, 6))
    assert snap["counters"]["prefix_hits"] >= 2   # warm + partial

    # cache states actually differed: the warm run attached pages
    assert snap["counters"]["prefix_hit_tokens"] > 0


def test_engine_matches_generate_chunked_prefill(params):
    """Chunked prefill (prefill_chunk budget < prompt length) is purely
    a scheduling knob: outputs still match generate() bitwise, for
    aligned and unaligned chunk sizes."""
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, CFG.vocab_size, (n,)).astype(np.int32)
               for n in (15, 9, 13)]
    for chunk in (4, 5):
        with _engine(params, prefill_chunk=chunk) as eng:
            handles = [eng.submit(p, 5) for p in prompts]
            outs = [h.result(timeout=300) for h in handles]
        for p, out in zip(prompts, outs):
            np.testing.assert_array_equal(out, _ref(params, p, 5))


@pytest.mark.parametrize("mode,kw", [
    ("plain", {}),
    ("decode_tail", dict(decode_block_size=4)),
    ("spec_k", dict(speculative="ngram", spec_k=3)),
])
def test_engine_tick_modes_match_generate(params, mode, kw):
    """The three modes of the one tick program — plain, the fused
    greedy tail, the speculative verify — all run the layer scan that
    carries the stacked pools: under chunked prefill with requests
    overlapping, each keeps greedy outputs byte-identical to
    ``generate()``, and each mode's program did run."""
    rng = np.random.RandomState(8)
    prompts = [rng.randint(0, CFG.vocab_size, (n,)).astype(np.int32)
               for n in (14, 6, 11)]
    # a repetitive prompt, so the n-gram drafter has something to offer
    prompts.append(np.tile(prompts[1][:3], 5)[:13])
    modes = set()
    with _engine(params, prefill_chunk=5, **kw) as eng:
        tick = eng._tick_jit

        def spy(*a, decode_tail=0, spec_k=0, **k):
            modes.add("spec_k" if spec_k else
                      "decode_tail" if decode_tail else "plain")
            return tick(*a, decode_tail=decode_tail, spec_k=spec_k, **k)

        eng._tick_jit = spy
        handles = [eng.submit(p, 9) for p in prompts]
        outs = [h.result(timeout=300) for h in handles]
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _ref(params, p, 9))
    assert mode in modes


def test_engine_matches_generate_after_defrag(params):
    """Mid-stream defrag scatters every live page list; the ragged tick
    reads the remapped tables as data, so continuations stay bitwise
    equal to generate()."""
    rng = np.random.RandomState(6)
    p1 = rng.randint(0, CFG.vocab_size, (11,)).astype(np.int32)
    p2 = rng.randint(0, CFG.vocab_size, (7,)).astype(np.int32)
    with _engine(params, check_invariants=True) as eng:
        # stagger: retire a short request first so the pool fragments
        eng.submit(p2, 2).result(timeout=300)
        h1 = eng.submit(p1, 8)
        it = iter(h1)
        next(it)
        moved = eng.defragment()
        h2 = eng.submit(p2, 6)
        out1 = h1.result(timeout=300)
        out2 = h2.result(timeout=300)
        assert eng.audit() == []
    assert moved >= 0   # plan may be empty; the point is the remap path
    np.testing.assert_array_equal(out1, _ref(params, p1, 8))
    np.testing.assert_array_equal(out2, _ref(params, p2, 6))


def test_invariant_checker_clean_through_ragged_bench_run(params):
    """A bench-shaped mixed run — staggered admissions, shared
    prefixes, chunked prefill, mid-run defrag — with per-tick invariant
    checking ON: zero violations, every output exact."""
    rng = np.random.RandomState(8)
    header = rng.randint(0, CFG.vocab_size, (8,)).astype(np.int32)
    specs = []
    for i in range(8):
        tail = rng.randint(0, CFG.vocab_size,
                           (int(rng.randint(2, 8)),)).astype(np.int32)
        prompt = (np.concatenate([header, tail]) if i % 2
                  else tail)
        specs.append((prompt, int(rng.randint(2, 7))))
    with _engine(params, check_invariants=True, prefill_chunk=4,
                 max_batch=3) as eng:
        handles = []
        for i, (prompt, mnt) in enumerate(specs):
            handles.append(eng.submit(prompt, mnt))
            if i == 4:
                eng.defragment()
            time.sleep(0.002)
        outs = [h.result(timeout=300) for h in handles]
        assert eng.audit() == []
        snap = eng.stats()
    assert snap["counters"].get("invariant_violations", 0) == 0
    assert snap["counters"]["completed"] == len(specs)
    for (prompt, mnt), out in zip(specs, outs):
        np.testing.assert_array_equal(out, _ref(params, prompt, mnt))


def test_sampling_prefill_does_not_throttle_greedy_tail(params):
    """A parked SAMPLING request must not disable the fused greedy
    decode tail for in-flight greedy streams: mid-prefill spans sit
    the tail out on the trash page regardless of temperature, so only
    live decoders and COMPLETING spans gate it. Pins (a) greedy
    exactness with a sampling span sharing the tick — the tail>0 +
    sampling-span program path — and (b) that fused steps actually
    ran (steps > ticks would be equal if every tick were single-step)."""
    rng = np.random.RandomState(9)
    victim_p = rng.randint(0, CFG.vocab_size, (3,)).astype(np.int32)
    intruder_p = rng.randint(0, CFG.vocab_size, (16,)).astype(np.int32)
    with _engine(params, max_batch=2, decode_block_size=4,
                 prefill_chunk=3, prefix_cache=False) as eng:
        h_v = eng.submit(victim_p, 20)
        it = iter(h_v)
        next(it)                      # victim is mid-decode
        h_i = eng.submit(intruder_p, 4, temperature=0.7, seed=1)
        out_v = h_v.result(timeout=300)
        out_i = h_i.result(timeout=300)
        snap = eng.stats()
    np.testing.assert_array_equal(out_v, _ref(params, victim_p, 20))
    assert len(out_i) == 4            # sampling request completed
    steps = snap["counters"]["decode_steps"]
    ticks = snap["histograms"]["decode_step_s"]["count"]
    assert steps > ticks, (
        f"no fused tail/block ever ran: {steps} steps in {ticks} ticks")


@pytest.mark.slow
def test_100k_token_page_table_serves_end_to_end(params):
    """The r16 acceptance scenario: a page table spanning ~100k tokens
    serves through the engine end-to-end, bitwise-equal to
    ``generate()`` — the geometry the one-shot walk cannot hold
    on-chip (its K+V scratch would be ~100 MB at serving dims; the
    auto-selection proves it flips to the tiled walk there), kept out
    of tier-1 for runtime.

    Three layers of evidence:
    * kernel: tiled == one-shot at kv_len = 100_000 under the
      ulp-at-row-scale contract (dense formulations — off-TPU there
      is no VMEM, the formulation is what's under test), and the
      tiled PALLAS walk (interpret) bitwise == the tiled reference at
      an 8k-token table (512 pages, 32 double-buffered tiles);
    * geometry: ``default_kv_tile_pages`` picks the tiled walk at the
      100k table and its scratch equals the 2k table's;
    * engine: a request decodes against the 100k-capacity table
      (pages_per_slot=6253) bitwise-equal to ``generate()``
      (attn_impl='dense' — the slot-major gather; the packed CPU
      formulation gathers per TOKEN and would thrash, which is
      exactly the work-scaling story docs/PERF.md records)."""
    # --- kernel at kv = 100_000 --------------------------------------
    rng = np.random.RandomState(0)
    Hkv, Dh, ps = 2, 16, 16
    pps = -(-100_000 // ps)                      # 6250 pages
    P = pps + 2
    q = jnp.asarray(rng.randn(1, 1, 4, Dh).astype(np.float32))
    kp = jnp.asarray(rng.randn(Hkv, P, ps, Dh).astype(np.float32))
    vp = jnp.asarray(rng.randn(Hkv, P, ps, Dh).astype(np.float32))
    ql = jnp.ones((1,), jnp.int32)
    kl = jnp.full((1,), 100_000, jnp.int32)
    tabs = jnp.asarray(1 + np.arange(pps, dtype=np.int32)[None])
    tile = default_kv_tile_pages(pps, ps, Dh, jnp.float32)
    assert 0 < tile < pps                        # O(tile), not O(table)
    assert vmem_scratch_bytes(pps, ps, Dh, jnp.float32) == \
        vmem_scratch_bytes(2 * tile, ps, Dh, jnp.float32)
    one = np.asarray(ragged_paged_attention(
        q, kp, vp, ql, kl, tabs, impl="dense", kv_tile_pages=0))
    tiled = np.asarray(ragged_paged_attention(
        q, kp, vp, ql, kl, tabs, impl="dense", kv_tile_pages=tile))
    assert tiled_ulp_error(tiled, one) <= TILED_ULP_BOUND
    # tiled PALLAS (interpret) at an 8k table: the real kernel's
    # double-buffered DMA walk, bitwise vs the tiled reference
    kl8 = jnp.full((1,), 8000, jnp.int32)
    a = ragged_paged_attention(q, kp[:, :514], vp[:, :514], ql, kl8,
                               tabs[:, :512], impl="pallas",
                               kv_tile_pages=16)
    b = ragged_paged_attention(q, kp[:, :514], vp[:, :514], ql, kl8,
                               tabs[:, :512], impl="dense",
                               kv_tile_pages=16)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # --- engine over the 100k-capacity table -------------------------
    prompt = np.random.RandomState(1).randint(
        0, CFG.vocab_size, (12,)).astype(np.int32)
    with ServingEngine(params, CFG, max_batch=1, page_size=ps,
                       max_prompt_len=32, max_new_tokens_cap=100_000,
                       attn_impl="dense", decode_block_size=8,
                       prefix_cache=False) as eng:
        assert eng.scheduler.pages_per_slot >= 6250
        out = eng.submit(prompt, 24).result(timeout=600)
        assert eng.audit() == []
    np.testing.assert_array_equal(out, _ref(params, prompt, 24))
