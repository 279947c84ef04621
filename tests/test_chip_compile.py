"""Described-chip compiles: the kernels of chip_smoke.py's two phases,
at the smoke's widths, handed to the TPU's own compiler for a v5e that
is described, not attached (the ``on-chip-measurement`` guide, section
2). Nothing runs; what the chip's compiler would refuse — a 16-bit
matmul accumulator, a slice off the tiling, too much VMEM — fails here,
at no chip time. Interpret mode never objects to any of those.

This is the ONE file of such compiles in tier-1. The topology is
described inside the module-scoped fixture below, never at import, in
a ``skipif``, a ``parametrize`` argument or ``conftest.py``: only one
process may load the TPU library, and every xdist worker imports every
test file. Compiles happen in the test's own process for the same
reason.
"""
import functools
import math
import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# Llama-3-8B widths (chip_smoke.smoke_config) and the smoke's geometry
H, HKV, DH, D = 32, 8, 128, 4096
G = H // HKV
BATCH, SEQ = 2, 2048
SLOTS, PAGE, PPS = 8, 16, 82           # serve: 8 slots, 82 pages/slot


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """Compile ``fn`` for one described chip at the given shapes. The
    persistent cache is off around it (an entry written for a described
    device cannot be read back without a chip and only warns), and so
    is conftest's ``jax_default_matmul_precision="highest"``: programs
    on the chip run at the default precision, and under "highest"
    Mosaic refuses every bf16 matmul, upstream kernels included."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    one = SingleDeviceSharding(topo.devices[0])
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_default_matmul_precision)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    cc.reset_cache()

    def compile_for_chip(fn, *shapes, donate=()):
        """``shapes``: ShapeDtypeStructs, or pytrees of them."""
        args = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            shapes)
        lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
        compiled = lowered.compile()
        text = types.SimpleNamespace(lowered=lowered.as_text(),
                                     compiled=compiled.as_text(),
                                     memory=compiled.memory_analysis())
        assert "tpu_custom_call" in text.compiled, "no Mosaic kernel"
        return text

    yield compile_for_chip
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_default_matmul_precision", was[1])
    cc.reset_cache()


def sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _ragged_args(tq, pps, slots=SLOTS, layers=1, pages=None, kv_heads=HKV,
                 group=G):
    """The kernel's operands: the STACKED pools and the layer index in
    front of the geometry (a 4-D pool enters as a one-layer stack);
    ``group * tq`` (token, group)-ordered query rows a (slot, kv
    head)."""
    pages = sds((layers, kv_heads, pages or slots * pps + 1, PAGE, DH))
    i32 = functools.partial(sds, dtype=jnp.int32)
    return (sds((slots, kv_heads, group * tq, DH)), pages, pages, i32((1,)),
            i32((slots,)), i32((slots,)), i32((slots, pps)))


def _ragged_walk(pps, group=G):
    """The kernel as a default call launches it at this table width
    (the heads a grid step holds follow from the launch's shapes)."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as R
    return functools.partial(
        R._pallas_impl, g=group, interpret=False,
        tile_pages=R.default_kv_tile_pages(pps, PAGE, DH))


# the engine's packed widths in the smoke: the fused block (tq=1), a
# decode-heavy tick (32) and a full prefill chunk (256)
@pytest.mark.parametrize("tq", [1, 32, 256])
def test_ragged_smoke_geometry(chip, tq):
    text = chip(_ragged_walk(PPS), *_ragged_args(tq, PPS))
    # chip_smoke.py finds the kernel by name in the train step's
    # compiled text and in the serving programs' lowered text
    from chip_smoke import kernels_in
    assert kernels_in(text.compiled)["ragged_paged_attention"] == 1
    assert kernels_in(text.lowered)["ragged_paged_attention"] == 1


@pytest.mark.parametrize("tq", [1, 256])
def test_ragged_long_context(chip, tq):
    """A 16k-token table: 32 tiles of the same walk, the same VMEM."""
    chip(_ragged_walk(1024), *_ragged_args(tq, 1024))


# the benchmark's serving cells, what ONE layer's launch looks like
# there, read from the files the cells run from (tools/kernel_bench.py:
# ragged_cells; today chat: 32 slots of up to 160 pages over a pool of
# 3584, 16 layers, 128-row chunks; batch: 16 slots of 88 pages, 16 KV
# heads of one query head, 256-row chunks; generate: below).
# ``refused``: twice the cell's chunk, which the one-shot walk this
# kernel replaced could not hold in VMEM (23 MiB of scoped VMEM at the
# chat cell's 256 rows, limit 16): the workload files keep their
# chunks, raising them is a benchmark PR's.
from tools.kernel_bench import ragged_cells  # noqa: E402

CELLS = ragged_cells()
CHAT, BATCH_CELL, GENERATE = (CELLS[c] for c in ("chat", "batch", "generate"))
# granite-4.0-h-micro's cell: the second of the traffic ``generate``, so
# keyed by its own name
GRANITE = CELLS["granite4h-serve-generate"]
_CELL_LAUNCHES = [
    ("chat-decode", CHAT, 1), ("chat-chunk", CHAT, CHAT["span"]),
    ("chat-refused", CHAT, 2 * CHAT["span"]),
    ("batch-decode", BATCH_CELL, 1),
    ("batch-chunk", BATCH_CELL, BATCH_CELL["span"]),
    # since a grid step holds a slot's KV heads: the two lane-packed
    # cells' launches as the kernel sees them (4 heads of 8 query rows a
    # token at width 128), and twice the batch cell's chunk
    ("batch-wide", BATCH_CELL, 2 * BATCH_CELL["span"]),
    ("generate-decode", GENERATE, 1),
    ("generate-chunk", GENERATE, GENERATE["span"]),
    ("generate-refused", GENERATE, 2 * GENERATE["span"]),
    ("granite-decode", GRANITE, 1),
    ("granite-chunk", GRANITE, GRANITE["span"]),
]


@pytest.mark.parametrize("name,cell,tq", _CELL_LAUNCHES,
                         ids=[c[0] for c in _CELL_LAUNCHES])
def test_ragged_layer_indexed_cell_geometry(chip, name, cell, tq):
    """What the serving tick launches per layer: the kernel over the
    stacked pool with the scan's layer index, at the four cells'
    geometries, a grid step holding the slot's KV heads (the scratch
    and the q and o blocks times the heads: the compiler's scoped VMEM
    is the judge); no cell's table fits one tile, so none selects a
    walk whose cost follows ``pages_per_slot``; at a cell's own
    launches a page moves with one copy a pool, but for the batch
    cell's 16 heads under a 256-row chunk (two steps a slot)."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as R
    assert (cell["page_size"], cell["head_dim"]) == (PAGE, DH)
    assert R.default_kv_tile_pages(cell["pps"], PAGE, DH) < cell["pps"]
    if tq <= cell["span"]:
        assert R.page_copies(cell["kv_heads"], cell["pps"], PAGE, DH,
                             rows=cell["group"] * tq) == (
            4 if name == "batch-chunk" else 2)
    chip(_ragged_walk(cell["pps"], cell["group"]),
         *_ragged_args(tq, cell["pps"], cell["slots"], cell["layers"],
                       cell["pages"], cell["kv_heads"], cell["group"]))


# the benchmark's generate cell (benchmark/workloads/
# lfm2moe-serve-generate.json): head size 64, 64 slots of 128 pages,
# 2 attention layers, 64-row chunks
@pytest.mark.parametrize("tq", [GENERATE["span"], 2 * GENERATE["span"]],
                         ids=["chunk", "refused"])
def test_ragged_head_size_64_enters_lane_packed(chip, monkeypatch, tq):
    """Head size 64 through the PACKED entry over a lane-packed pool
    (two KV heads a 128-lane row), stacked, with a layer index, at the
    generate cell's geometry: the kernel the chip's compiler is handed
    is the 128-wide one. The same entry over the plain ``[.., 8, P, 16,
    64]`` pool is what it refuses (a 64-lane page DMA), which is why the
    pool is packed. 128 rows a slot (256 a packed KV head) ran out of
    scoped VMEM under the one-shot walk; the cell's chunk stays 64."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as R
    monkeypatch.setattr(R, "_on_tpu", lambda: True)
    g, m = GENERATE, GENERATE["model"]
    S, T = g["slots"], g["slots"] + tq
    f = R.lane_pack_factor(m["head_dim"], m["kv_heads"])
    assert f == 2
    assert (g["kv_heads"], g["head_dim"]) == (m["kv_heads"] // f,
                                              f * m["head_dim"])
    i32 = functools.partial(sds, dtype=jnp.int32)
    meta = (i32((T,)), i32((T,)), i32((S,)), i32((S,)), i32((S, g["pps"])))

    def attend(q, kp, vp, layer, *geom):
        return R.ragged_paged_attention_packed(q, kp, vp, *geom, tq=tq,
                                               layer=layer[0])

    def pool(heads, width):
        return sds((g["layers"], heads, g["pages"], PAGE, width))

    q = sds((T, m["heads"], m["head_dim"]))
    packed = pool(g["kv_heads"], g["head_dim"])
    text = chip(attend, q, packed, packed, i32((1,)), *meta)
    # 2 x 4 query heads x tq rows a slot and packed KV head
    assert (f"bf16[{S},{g['kv_heads']},{g['group'] * tq},{g['head_dim']}]"
            in text.compiled)
    if tq == g["span"]:
        plain = pool(m["kv_heads"], m["head_dim"])
        with pytest.raises(Exception, match="aligned to tiling"):
            chip(attend, q, plain, plain, i32((1,)), *meta)


def _mistral_tick_shapes(tq, layers, pages):
    """``serving_tick``'s operands at Mistral-7B-v0.3 widths (the
    Llama-3-8B ones above but for the vocabulary) and the chat cell's
    slots and table width, cut to ``layers`` layers and ``pages``
    pages."""
    from paddle_tpu.models import llama as L
    cfg = L.LlamaConfig(
        vocab_size=32768, hidden_size=D, intermediate_size=14336,
        num_hidden_layers=layers, num_attention_heads=H,
        num_key_value_heads=HKV, rope_theta=1e6, dtype=jnp.bfloat16)
    # the tree an ENGINE holds (``serving_params``: the q / k / v stacks
    # output-major), not ``init_params``' own
    params = jax.eval_shape(lambda: L.serving_params(
        L.init_params(cfg, jax.random.PRNGKey(0)), cfg))
    params = jax.tree.map(lambda a: sds(a.shape), params)
    S, T = CHAT["slots"], CHAT["slots"] + (tq if tq > 1 else 0)
    i32 = functools.partial(sds, dtype=jnp.int32)
    f32 = functools.partial(sds, dtype=jnp.float32)
    meta = dict(tok_slot=i32((T,)), tok_pos=i32((T,)), tok_page=i32((T,)),
                tok_off=i32((T,)), tok_qoff=i32((T,)), q_len=i32((S,)),
                kv_len=i32((S,)), last=i32((S,)),
                tables=i32((S, CHAT["pps"])), temp=f32((S,)),
                top_p=f32((S,)), top_k=i32((S,)),
                key=sds((S, 2), jnp.uint32), produced=i32((S,)),
                # the slots' current tokens, kept on the device
                tail_live=sds((S,), jnp.bool_), cur_tok=i32((S,)))
    pool = sds((layers, HKV, pages, PAGE, DH))
    return cfg, (params, i32((T,)), meta,
                 {"k_pages": pool, "v_pages": pool})


_HLO_RESULT = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
_HLO_ARRAY = re.compile(r"\b(bf16|f16|f32|s32|u32|s8|u8|pred)\[([\d,]+)\]")
_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
             "u8": 1, "pred": 1}


def _page_results(compiled_text, nbytes):
    """(name, opcode, line) of every instruction of the compiled
    program, fused computations included, whose result holds an array
    of pages (rows of ``DH``, which no weight has) of ``nbytes`` or
    more."""
    out = []
    for line in compiled_text.splitlines():
        m = _HLO_RESULT.match(line)
        if not m:
            continue
        for dtype, dims in _HLO_ARRAY.findall(m.group(2)):
            dims = [int(d) for d in dims.split(",")]
            if (dims[-1] == DH
                    and _ITEMSIZE[dtype] * math.prod(dims) >= nbytes):
                out.append((m.group(1), m.group(3), line))
                break
    return out


def _scatters_in_place(compiled_text, line):
    """Whether a ``fusion`` is the span's scatter taken in place: its
    computation scatters, and its result shares its operand's buffer."""
    called = re.search(r"calls=%([\w.\-]+)", line).group(1)
    body = compiled_text.split(f"\n%{called} (", 1)[1].split("\n}", 1)[0]
    return (" scatter(" in body
            and '"aliasing_operands":{"lists":[]}' not in line)


_TICK_TEXTS = {}


def _mistral_tick_text(chip, monkeypatch, tq):
    """The chat cell's whole tick at ``tq`` query rows a slot (two
    layers, the cell's own pool), compiled for the described chip once
    a module."""
    from paddle_tpu.models import llama as L
    from paddle_tpu.models import serving_tick as T
    from paddle_tpu.ops.pallas import ragged_paged_attention as R
    # the packed entry asks the backend, and sees the CPU here
    monkeypatch.setattr(R, "_on_tpu", lambda: True)
    if tq not in _TICK_TEXTS:
        # the cell's own pool: a layer's pages (112 MiB) are then larger
        # than the kernel's query rows (32 x 128 tokens, 32 MiB)
        cfg, shapes = _mistral_tick_shapes(tq, 2, CHAT["pages"])

        # the pool as ONE donated pytree, as the engine's jitted wrapper
        # has it
        def serving_tick(params, tokens, meta, cache):
            return T.serving_tick(params, tokens, meta, cache, cfg,
                                  L.SERVING, tq=tq)

        _TICK_TEXTS[tq] = chip(serving_tick, *shapes, donate=(3,))
    return _TICK_TEXTS[tq]


@pytest.mark.parametrize("tq", [1, 128])
def test_serving_tick_holds_the_pool_once(chip, monkeypatch, tq):
    """The whole tick, compiled for the described chip: the KV pool is
    ONE buffer from the program's parameter to its result. Nothing but
    the parameters, the layer loop and its carry (and the span's rows
    scattered into that carry in place) has a result as large as one
    layer's K pages: no ``copy``, ``dynamic-slice`` or
    ``dynamic-update-slice`` of a layer's pages, no relayout in front
    of the kernel, no whole-pool copy into the donated buffers at the
    end; and the program's temporaries stay under one pool."""
    layers, pages = 2, CHAT["pages"]
    text = _mistral_tick_text(chip, monkeypatch, tq)
    from chip_smoke import kernels_in
    assert kernels_in(text.compiled)["ragged_paged_attention"] == 1
    layer_pages = HKV * pages * PAGE * DH * 2     # bytes of one layer's K
    carried = {"parameter", "while", "tuple", "get-tuple-element",
               "bitcast", "scatter"}
    found = _page_results(text.compiled, layer_pages)
    moved = [f"{opcode} {name}" for name, opcode, line in found
             if opcode not in carried
             and not (opcode == "fusion"
                      and _scatters_in_place(text.compiled, line))]
    assert {"parameter", "while"} <= {opcode for _, opcode, _ in found}
    assert not moved, f"the tick moves a layer's pages or more: {moved}"
    assert text.memory.alias_size_in_bytes >= 2 * layers * layer_pages
    assert text.memory.temp_size_in_bytes < layers * layer_pages


def _written_results(compiled_text, nbytes, scope):
    """``opcode name`` of every instruction OUTSIDE fused computations
    and outside the Mosaic kernels whose ``op_name`` holds ``scope`` and
    whose result holds an array of ``nbytes`` or more that it WRITES (a
    tuple, its elements and a bitcast move nothing; a gather the
    compiler expands into a loop of in-place updates is that ``while``,
    one pass)."""
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", compiled_text))
    out, skip = [], False
    for line in compiled_text.splitlines():
        if line and not line[0].isspace():      # a computation's header
            skip = line.split(" ", 1)[0].lstrip("%") in fused
        m = _HLO_RESULT.match(line)
        if (skip or not m or scope not in line
                or m.group(3) in ("custom-call", "tuple", "bitcast",
                                  "get-tuple-element", "parameter")):
            continue
        if any(_ITEMSIZE[d] * math.prod(int(x) for x in dims.split(","))
               >= nbytes for d, dims in _HLO_ARRAY.findall(m.group(2))):
            out.append(f"{m.group(3)} {m.group(1)}")
    return out


def test_span_tick_passes_over_the_query_buffer_at_most_three_times(
        chip, monkeypatch):
    """The chat cell's span tick (``T = 32 + 128``, ``tq = 128``): the
    kernel's query buffer (32 slots x 128 tokens x 32 heads x 128, 33.6
    MB for a stream of 1.3) is written ONCE a layer in front of the
    kernel (every slot's first token, padded out to its block), a
    span's rows copied into it in place (the update and the loop that
    holds it are the other two names), and nothing of its size is
    written behind it. Until PR 48 eight results of that size a layer
    stood under ``ragged_attn`` outside the kernel (a zero fill, a
    scatter, the scale, four transposing copies and a pad: 8 of a span
    tick's ~21 ms); three is where this guard stands."""
    tq = CHAT["span"]
    text = _mistral_tick_text(chip, monkeypatch, tq)
    nbytes = CHAT["slots"] * tq * H * DH * 2
    passes = _written_results(text.compiled, nbytes, "ragged_attn")
    assert 1 <= len(passes) <= 3, passes
    # the yardstick finds what it is to find: the kernel's own result is
    # of that size, under that scope, and not counted
    assert re.search(r"custom-call\(.*ragged_attn", text.compiled)


def _layer_weight_results(compiled_text, layers, opcode):
    """``(name, dims)`` of every ``opcode`` instruction of the compiled
    program, fused computations included, whose result is ONE LAYER of a
    stacked matrix of ``layers`` (``[1, a, b]`` or ``[a, b]``, either
    way round)."""
    mats = {tuple(sorted(a.shape[1:])) for a in jax.tree.leaves(layers)
            if len(a.shape) == 3}
    out = []
    for line in compiled_text.splitlines():
        m = _HLO_RESULT.match(line)
        if not m or m.group(3) != opcode:
            continue
        for _, dims in _HLO_ARRAY.findall(m.group(2)):
            dims = tuple(int(d) for d in dims.split(","))
            if tuple(sorted(dims[-2:])) in mats and set(dims[:-2]) <= {1}:
                out.append((m.group(1), dims))
    return out


@pytest.mark.parametrize("tq", [1, 128])
def test_serving_tick_reads_the_qkv_stacks_as_they_lie(chip, monkeypatch,
                                                       tq):
    """The chat cell's tick on the tree an engine holds (``llama.
    serving_params``: ``wq`` / ``wk`` / ``wv`` output-major, contracted
    over their last axis): a layer's three slices leave their stacks
    (``[1, 4096, 4096]`` and two ``[1, 1024, 4096]``) and NO ``copy``
    re-lays a layer of any weight in front of its product. Held
    ``[L, D, O]`` the compiler put one behind each of the three slices
    (``copy.64`` / ``.66`` / ``.67``, ``{2,1,0}`` to ``{1,2,0}``: ~0.75
    ms of every chat tick's 12.4; ``PERF.md`` section 6, PR 49)."""
    text = _mistral_tick_text(chip, monkeypatch, tq)
    cfg, (params, *_) = _mistral_tick_shapes(tq, 2, CHAT["pages"])
    layers = params["layers"]
    assert layers["wq_om"].shape == (2, H * DH, D)
    assert layers["wk_om"].shape == layers["wv_om"].shape == (2, HKV * DH, D)
    copies = _layer_weight_results(text.compiled, layers, "copy")
    assert not copies, f"a layer's weight is re-laid: {copies}"
    sliced = [dims for _, dims in _layer_weight_results(
        text.compiled, layers, "dynamic-slice")]
    assert sliced.count((1, HKV * DH, D)) == 2          # wk, wv
    assert sliced.count((1, H * DH, D)) >= 1            # wq (wo is square too)
    assert (1, D, HKV * DH) not in sliced


# the three serving cells' tick programs as the ENGINE jits them
# (``serving/engine.py: _jit_step_fns``: the family's own walk, the
# slots' current tokens in and their successor out, the cache donated),
# at the cell's slots, table, pool and chunk, the model at its published
# widths cut to two layers (LFM2: one of each kind)
_TWO_LAYERS = {
    "dense_decoder": dict(num_hidden_layers=2),
    "qwen2_moe": dict(num_hidden_layers=2),
    "lfm2_moe": dict(num_hidden_layers=2, num_dense_layers=1,
                     layer_types=["conv", "full_attention"]),
}
_CELL_PROGRAMS = [("chat", "tick"), ("batch", "tick"), ("generate", "tick"),
                  ("chat", "block"), ("batch", "block"),
                  # twice the cell's chunk (chat 256 rows a slot, generate
                  # 128): the kernel's step then holds its heads' flash
                  # state and blocks at a narrower tile, or fewer heads
                  ("chat", "tick-wide"), ("generate", "tick-wide")]


def _cell_program_args(traffic):
    """``(mod, cfg, S, pps, chunk, params, cache)`` of the serving cell
    with that traffic, abstractly, read from the files it runs from."""
    import json
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if os.path.join(root, "benchmark") not in sys.path:
        sys.path.insert(0, os.path.join(root, "benchmark"))
    from harness import manifest

    def read(*path):
        with open(os.path.join(root, *path)) as f:
            return json.load(f)

    bench = manifest.load_manifest()
    cell = next(w for w in bench["workloads"] if w["traffic"] == traffic
                and w["name"].split("-")[1] == "serve")
    work = read("benchmark", "workloads", cell["name"] + ".json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    model = {**read(conf["file"]), **work.get("overrides", {})}
    model.update(_TWO_LAYERS[model["family"]])
    cfg, mod = manifest.load_family(model["family"]).program_config(model)
    g = CELLS[traffic]
    # the tree the engine holds: the family's own where it brings one
    serving = getattr(mod, "serving_params", lambda p, cfg: p)
    params = jax.eval_shape(lambda: serving(
        mod.init_params(cfg, jax.random.PRNGKey(0)), cfg))
    cache = jax.eval_shape(lambda: mod.init_serving_pages(
        cfg, g["pages"], g["page_size"], max_batch=g["slots"]))
    return mod, cfg, g["slots"], g["pps"], g["span"], params, cache


@pytest.mark.parametrize("traffic,program", _CELL_PROGRAMS,
                         ids=["-".join(c) for c in _CELL_PROGRAMS])
def test_cell_tick_programs_keep_the_slots_tokens_on_the_device(
        topo, chip, monkeypatch, traffic, program):
    """The engine's jitted tick (at the cell's chunk width, and at
    twice it) and fused
    block, with the slots' current tokens as an operand and their
    successor as a result, compile for the described chip at every
    serving cell's geometry; the successor is one more ``s32[S]`` result
    in front of the donated cache, and a decode row's token is gathered
    from it in the program (no host upload of the slots' tokens). A
    family with routed experts hands its counts back in front of the
    successor, runs them through the held-experts grouped matmul, and
    copies no layer's experts in front of it."""
    from paddle_tpu.ops.pallas import grouped_matmul as G
    from paddle_tpu.ops.pallas import ragged_paged_attention as R
    from paddle_tpu.serving import engine as E
    monkeypatch.setattr(R, "_on_tpu", lambda: True)
    monkeypatch.setattr(G, "_on_tpu", lambda: True)
    mod, cfg, S, pps, chunk, params, cache = _cell_program_args(traffic)
    counters = mod.SERVING.counters
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    i32 = functools.partial(sds, dtype=jnp.int32)
    f32 = functools.partial(sds, dtype=jnp.float32)
    samp = dict(temp=f32((S,)), top_p=f32((S,)), top_k=i32((S,)),
                key=sds((S, 2), jnp.uint32), produced=i32((S,)))
    E._JIT_CACHE.clear()        # jit objects of THIS precision context
    tick, block = E._jit_step_fns(mod, cfg, "auto")
    if program.startswith("tick"):
        chunk *= 2 if program == "tick-wide" else 1
        T = S + chunk
        meta = dict(tok_slot=i32((T,)), tok_pos=i32((T,)),
                    tok_page=i32((T,)), tok_off=i32((T,)),
                    tok_qoff=i32((T,)), q_len=i32((S,)), kv_len=i32((S,)),
                    last=i32((S,)), tables=i32((S, pps)),
                    tail_live=sds((S,), jnp.bool_), cur_tok=i32((S,)),
                    **samp)
        lowered = tick.lower(*on_chip((params, i32((T,)), meta, cache)),
                             tq=chunk, decode_tail=0)
        results = 3             # toks, logits, cur_tok'
    else:
        lowered = block.lower(
            *on_chip((params, i32((S,)), i32((S,)), i32((S, pps)), cache)),
            num_steps=1, sampling=on_chip(samp))
        results = 2             # toks, cur_tok'
    results += bool(counters)   # the family's counts, before cur_tok'
    compiled = lowered.compile()
    E._JIT_CACHE.clear()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel"
    if hasattr(mod, "serving_params"):
        # q / k / v read as their stacks hold them (the batch cell's
        # three are ``[1, 2048, 2048]``): no layer of them re-laid
        qkv = [a for name, a in params["layers"].items()
               if name.endswith("_om")]
        assert len(qkv) == 3
        assert not _layer_weight_results(text, qkv, "copy")
    outs = jax.tree.leaves(compiled.out_info)
    leaves = jax.tree.leaves(cache)
    assert len(outs) == results + len(leaves)
    nxt = outs[results - 1]
    assert (nxt.shape, nxt.dtype) == ((S,), jnp.int32)
    if counters:
        counts = outs[results - 2]
        assert (counts.shape, counts.dtype) == ((len(counters),), jnp.int32)
        assert "held_experts_matmul" in text
        ex = params["layers"]["experts"]
        one_layer = sum(math.prod(a.shape[1:]) * a.dtype.itemsize
                        for a in jax.tree.leaves(ex))
        assert compiled.memory_analysis().temp_size_in_bytes < one_layer // 4
    assert [(o.shape, o.dtype) for o in outs[results:]] == [
        (a.shape, a.dtype) for a in leaves]
    # the whole cache is donated: the pools are held once
    pools = sum(math.prod(a.shape) * a.dtype.itemsize for a in leaves)
    assert compiled.memory_analysis().alias_size_in_bytes >= pools


# granite-4.0-h-micro's serving cell (``granite4h-serve-generate``): the
# WHOLE model at its published widths and depth, 64 slots, the state-
# space state 4.57 GiB beside the KV pool


@pytest.mark.parametrize("rows", [GRANITE["slots"],
                                  GRANITE["slots"] + GRANITE["span"]],
                         ids=["decode", "span"])
def test_ssd_update_at_the_cell_s_geometry(chip, monkeypatch, rows):
    """The state pass over the stacked state with a layer index, in a
    loop that carries the state as the tick's layer loops do: the loop's
    temporaries are a sliver of ONE layer's state (the kernel updates
    the donated buffer in place) and the whole state is aliased."""
    from paddle_tpu.ops.pallas import ssd_update as K
    monkeypatch.setattr(K, "_on_tpu", lambda: True)
    m, S = GRANITE["ssm"], GRANITE["slots"]
    L, N, HP = m["layers"], m["state"], m["heads"] * m["head_dim"]
    f32 = functools.partial(sds, dtype=jnp.float32)

    def fn(state, c, b, w, dec, tok_slot):
        def body(i, carry):
            ys, state = carry
            y, state = K.ssd_update(state, i, c, b, w, dec, tok_slot)
            return ys + y, state
        return jax.lax.fori_loop(
            0, L, body, (jnp.zeros((rows, HP), jnp.float32), state))

    text = chip(fn, f32((L, S + 1, N, HP)), f32((rows, N)), f32((rows, N)),
                f32((rows, HP)), f32((S, HP)), sds((rows,), jnp.int32),
                donate=(0,))
    assert "ssd_update" in text.compiled
    state = L * (S + 1) * N * HP * 4
    assert text.memory.alias_size_in_bytes >= state
    assert text.memory.temp_size_in_bytes < N * HP * 4 * S // 8


def test_granite_cell_tick_updates_the_state_in_place(topo, chip,
                                                      monkeypatch):
    """The engine's jitted tick at the cell's geometry (64 slots + one
    128-row span), the published model WHOLE (40 layers, 100 352 rows of
    vocabulary): it compiles for the described chip, holds parameters,
    cache and temporaries within the chip's 15.75 GiB, aliases the whole
    cache, and its temporaries stay far under one copy of the state
    (4.57 GiB): no operation copies it."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as R
    from paddle_tpu.ops.pallas import ssd_update as K
    from paddle_tpu.serving import engine as E
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if os.path.join(root, "benchmark") not in sys.path:
        sys.path.insert(0, os.path.join(root, "benchmark"))
    from harness import manifest
    monkeypatch.setattr(R, "_on_tpu", lambda: True)
    monkeypatch.setattr(K, "_on_tpu", lambda: True)
    cell = manifest.Cell(manifest.load_manifest(),
                         "granite4h-serve-generate")
    family = cell.family
    cfg, mod = family.program_config(cell.model)
    g = GRANITE
    S, pps, chunk = g["slots"], g["pps"], g["span"]
    params = jax.eval_shape(lambda: family.make_params(cell.model, 0))
    cache = jax.eval_shape(lambda: mod.init_serving_pages(
        cfg, g["pages"], g["page_size"], max_batch=S))
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    i32 = functools.partial(sds, dtype=jnp.int32)
    f32 = functools.partial(sds, dtype=jnp.float32)
    T = S + chunk
    meta = dict(tok_slot=i32((T,)), tok_pos=i32((T,)), tok_page=i32((T,)),
                tok_off=i32((T,)), tok_qoff=i32((T,)), q_len=i32((S,)),
                kv_len=i32((S,)), last=i32((S,)), tables=i32((S, pps)),
                tail_live=sds((S,), jnp.bool_), cur_tok=i32((S,)),
                temp=f32((S,)), top_p=f32((S,)), top_k=i32((S,)),
                key=sds((S, 2), jnp.uint32), produced=i32((S,)))
    E._JIT_CACHE.clear()        # jit objects of THIS precision context
    tick, _ = E._jit_step_fns(mod, cfg, "auto")
    compiled = tick.lower(*on_chip((params, i32((T,)), meta, cache)),
                          tq=chunk, decode_tail=0).compile()
    E._JIT_CACHE.clear()
    text = compiled.as_text()
    assert "ssd_update" in text and "ragged_paged_attention" in text
    mem = compiled.memory_analysis()
    held = sum(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree.leaves(cache))
    state = math.prod(cache["ssm_state"].shape) * 4
    assert state == 36 * 65 * 2 * 2 ** 20
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < state // 8
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert live < 15.75 * 2 ** 30
    # the stacked weights enter the loops as they lie: no re-laid-out
    # copy of a whole stack (a stack 8512 columns wide was one: 1.2 GiB)
    assert not re.search(r"= bf16\[36,2048,(4096|4352)\]\S* copy\(", text)

# longcat-flash-chat's serving cell (``longcat-serve-longprompt``): one
# chip's share of an EP-32 deployment at published widths, 4 layers, a
# latent pool, 48 slots, 512-row chunks


def _longcat():
    from tools.kernel_bench import mla_cells
    return mla_cells()["longprompt"]


@pytest.mark.parametrize("kind", ["decode", "span"])
def test_mla_kernel_at_the_cell_s_geometry(chip, kind):
    """Attention over the latent pages as ONE sublayer's launch: the
    stacked pool with a layer index, 64 heads over 640-lane rows, the
    cell's table of 17 408 tokens; a decode launch and one that carries
    the 512-row chunk. What interpret mode cannot refuse (a page DMA off
    the tiling, the VMEM of a 1024-row block) fails here."""
    from paddle_tpu.ops.pallas import mla_paged_attention as K
    c = _longcat()
    S, pps, ps, H = c["slots"], c["pps"], c["page_size"], c["heads"]
    T = S + (c["span"] if kind == "span" else 0)
    i32 = functools.partial(sds, dtype=jnp.int32)
    fn = functools.partial(
        K._pallas_impl, heads=H, dv=c["dv"], tb=K.BLOCK_TOKENS,
        tile_pages=K.default_kv_tile_pages(pps, ps), interpret=False)
    text = chip(fn, sds(((T + K.BLOCK_TOKENS) * H, c["row_width"])),
                sds((c["layers"], c["pages"], ps, c["row_width"])),
                i32((1,)), i32((S,)), i32((S,)), i32((S,)), i32((S, pps)))
    assert "mla_paged_attention" in text.compiled
    # the kernel holds a block and two tiles, never the table
    assert text.memory.temp_size_in_bytes < 2 ** 20


def test_held_experts_matmul_at_the_cell_s_geometry(chip, monkeypatch):
    """The expert share at the cell's widths: 560 rows routed over 768
    outputs, 16 held experts of 6144 x 2048 read from the model's stacks
    at a layer index; the grouped matmul's weight blocks fit VMEM and no
    layer's experts (1.2 GB) are copied in front of the kernel."""
    from paddle_tpu.incubate.moe.functional import moe_ffn_share
    from paddle_tpu.ops.pallas import grouped_matmul as G
    monkeypatch.setattr(G, "_on_tpu", lambda: True)
    N, D, F, E, L = 560, 6144, 2048, 16, 4

    def fn(x, router, bias, wg, wu, wd, layer, mask):
        return moe_ffn_share(
            x, router, bias, {"w_gate": wg, "w_up": wu, "w_down": wd},
            held=(0, E), num_routed=512, zero_experts=256, top_k=12,
            scale=6.0, layer=layer, row_mask=mask)

    f32 = functools.partial(sds, dtype=jnp.float32)
    text = chip(fn, sds((N, D)), f32((D, 768)), f32((768,)),
                sds((L, E, D, F)), sds((L, E, D, F)), sds((L, E, F, D)),
                sds((), jnp.int32), sds((N,), jnp.bool_))
    assert text.compiled.count("held_experts_matmul") >= 2
    assert text.memory.temp_size_in_bytes < E * 3 * D * F * 2 // 4


@pytest.mark.parametrize("traffic,kind", [
    ("batch", "decode"), ("batch", "span"), ("generate", "decode"),
    ("generate", "span")], ids=lambda v: v)
def test_every_expert_held_at_the_cells_geometry(chip, monkeypatch, traffic,
                                                 kind):
    """An older family's expert block as a tick launches it, at the
    share ``(0, E)``: the batch cell's 16 and 272 rows over 60 experts
    of 2048 x 1408 (a column block of 704 was refused: 1408 = 11 x 128)
    and, for the day the generate cell's ticks take it (PERF.md §6, PR
    43: its roofline reader has to count touched experts first), its 64
    and 128 rows over 64 of 2048 x 1536, read
    from the model's stacks at a layer index. Both grouped matmuls
    compile with a whole expert a step (their VMEM stated), and no
    layer's experts (1.04 / 1.21 GB) are copied in front of them."""
    from paddle_tpu.incubate.moe.functional import moe_ffn_share
    from paddle_tpu.ops.pallas import grouped_matmul as G
    from tools.kernel_bench import moe_cells
    monkeypatch.setattr(G, "_on_tpu", lambda: True)
    c = moe_cells()[traffic]
    N = c["slots"] + (c["span"] if kind == "span" else 0)
    D, F, n_e, L = c["hidden"], c["width"], c["held"], c["layers"]
    assert G.held_tile_n(D, F) % 128 == 0 and G.held_tile_n(F, D) % 128 == 0

    def fn(x, router, wg, wu, wd, layer, mask):
        return moe_ffn_share(
            x, router, None, {"w_gate": wg, "w_up": wu, "w_down": wd},
            held=(0, n_e), num_routed=n_e, top_k=c["top_k"], layer=layer,
            row_mask=mask, score_fn="sigmoid", normalize_topk=True)

    text = chip(fn, sds((N, D)), sds((D, n_e), jnp.float32),
                sds((L, n_e, D, F)), sds((L, n_e, D, F)),
                sds((L, n_e, F, D)), sds((), jnp.int32),
                sds((N,), jnp.bool_))
    assert text.compiled.count("held_experts_matmul") >= 2
    assert text.memory.temp_size_in_bytes < n_e * 3 * D * F * 2 // 8


@pytest.mark.parametrize("program", ["tick", "block"])
def test_longcat_cell_programs_hold_the_latent_pool_once(topo, chip,
                                                         monkeypatch,
                                                         program):
    """The engine's jitted tick (48 slots + one 512-row chunk) and fused
    block at the cell's geometry and published widths: they compile for
    the described chip, the latent pool is aliased (held once), the
    tick holds at least 80 % of the chip's 15.75 GiB and fits it, the
    share's counts come back as one more ``s32[4]`` result beside the
    tokens, and no stack of weights is re-laid out in front of the
    layer loop."""
    from paddle_tpu.ops.pallas import grouped_matmul as G
    from paddle_tpu.ops.pallas import mla_paged_attention as K
    from paddle_tpu.serving import engine as E
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if os.path.join(root, "benchmark") not in sys.path:
        sys.path.insert(0, os.path.join(root, "benchmark"))
    from harness import manifest
    monkeypatch.setattr(K, "_on_tpu", lambda: True)
    monkeypatch.setattr(G, "_on_tpu", lambda: True)
    cell = manifest.Cell(manifest.load_manifest(),
                         "longcat-serve-longprompt")
    family = cell.family
    cfg, mod = family.program_config(cell.model)
    g = _longcat()
    S, pps, chunk = g["slots"], g["pps"], g["span"]
    params = jax.eval_shape(lambda: family.make_params(cell.model, 0))
    cache = jax.eval_shape(lambda: mod.init_serving_pages(
        cfg, g["pages"], g["page_size"], max_batch=S))
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    i32 = functools.partial(sds, dtype=jnp.int32)
    f32 = functools.partial(sds, dtype=jnp.float32)
    samp = dict(temp=f32((S,)), top_p=f32((S,)), top_k=i32((S,)),
                key=sds((S, 2), jnp.uint32), produced=i32((S,)))
    E._JIT_CACHE.clear()        # jit objects of THIS precision context
    tick, block = E._jit_step_fns(mod, cfg, "auto")
    if program == "tick":
        T = S + chunk
        meta = dict(tok_slot=i32((T,)), tok_pos=i32((T,)),
                    tok_page=i32((T,)), tok_off=i32((T,)),
                    tok_qoff=i32((T,)), q_len=i32((S,)), kv_len=i32((S,)),
                    last=i32((S,)), tables=i32((S, pps)),
                    tail_live=sds((S,), jnp.bool_), cur_tok=i32((S,)),
                    **samp)
        compiled = tick.lower(*on_chip((params, i32((T,)), meta, cache)),
                              tq=chunk, decode_tail=0).compile()
        results = 4             # toks, logits, counts, cur_tok'
    else:
        compiled = block.lower(
            *on_chip((params, i32((S,)), i32((S,)), i32((S, pps)), cache)),
            num_steps=1, sampling=on_chip(samp)).compile()
        results = 3             # toks, counts, cur_tok'
    E._JIT_CACHE.clear()
    text = compiled.as_text()
    assert "mla_paged_attention" in text and "held_experts_matmul" in text
    outs = jax.tree.leaves(compiled.out_info)
    assert len(outs) == results + 1
    counts, nxt, pool = outs[results - 2:]
    assert (counts.shape, counts.dtype) == ((4,), jnp.int32)
    assert (nxt.shape, nxt.dtype) == ((S,), jnp.int32)
    assert pool.shape == cache[mod.POOL].shape == (
        8, g["pages"], g["page_size"], 640)
    mem = compiled.memory_analysis()
    held = math.prod(pool.shape) * 2
    assert mem.alias_size_in_bytes >= held
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 0.80 * 15.75 * 2 ** 30 <= live < 15.75 * 2 ** 30
    assert mem.temp_size_in_bytes < held // 8
    # the stacked weights enter the layer loop as they lie (held as the
    # published matrices, q_b_proj and kv_b_proj were copied whole in
    # every tick: 436 MB)
    assert not re.search(r"= bf16\[8,(1536|512|64),\d+,?\d*\]\S* copy\(",
                         text)


# the trainer's step (``mistral7b-train-dp2tp2``: Mistral widths, strict
# Pallas kernels, 4 x 2048 tokens a dp replica), 2 of the cell's 5 layers


def _train_step_for(topo, monkeypatch, dp, tp, **step_kw):
    """``make_train_step`` lowered for ``dp x tp`` described chips over
    shapes only: (lowered, mesh)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.models import llama as L
    from paddle_tpu.ops.pallas import flash_attention as FA
    from paddle_tpu.ops.pallas import fused_norm_rope as FN
    from paddle_tpu.parallel import init_hybrid_mesh
    monkeypatch.setattr(FA, "_on_tpu", lambda: True)
    monkeypatch.setattr(FN, "_on_tpu", lambda: True)
    cfg = L.LlamaConfig(
        vocab_size=32768, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=2, num_attention_heads=32, num_key_value_heads=8,
        rms_norm_eps=1e-5, rope_theta=1e6, dtype=jnp.bfloat16,
        max_position_embeddings=2048, use_flash_attention="pallas",
        use_fused_norm_rope="pallas")
    mesh = init_hybrid_mesh(dp=dp, pp=1, tp=tp, set_global=False,
                            devices=topo.devices[:dp * tp]).mesh
    with mesh:
        step, init = L.make_train_step(cfg, mesh, **step_kw)
        specs = L.train_state_specs(cfg, mesh, **step_kw)
        state = jax.tree.map(
            lambda a, sp: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, sp)),
            jax.eval_shape(init, jax.random.PRNGKey(0)), specs)
        rows = NamedSharding(mesh, P("dp", None))
        batch = {k: jax.ShapeDtypeStruct((4 * dp, 2048), jnp.int32,
                                         sharding=rows)
                 for k in ("tokens", "labels")}
        return step.lower(state, batch), mesh


def test_train_step_reduce_scatters_its_gradients_in_the_loop(
        topo, chip, monkeypatch):
    """dp 2 x tp 2, ``zero_stage`` left to the mesh: the body of the
    backward loop holds no all-reduce across dp but the two norms' 16 KB
    (at stage 0 it ends in two tuple all-reduces of the layer's seven
    matrices, 218 MB, with nothing left to run beside them), and each
    of the seven is reduced across dp by a reduce-scatter fused onto
    the matmul that made it, beside the tp ones it already had."""
    from hlo_collectives import backward_loop_collectives
    lowered, mesh = _train_step_for(topo, monkeypatch, 2, 2)
    compiled = lowered.compile()
    got = backward_loop_collectives(compiled.as_text(), mesh.shape)
    assert got.body is not None
    norms = 2 * D * 2
    assert [r for r in got.all_reduces
            if r.crosses and r.bytes > norms] == [], got.all_reduces
    over_dp = [r for r in got.reduce_scatters if r.crosses]
    assert len(over_dp) == 7, got.reduce_scatters
    assert len(got.reduce_scatters) - len(over_dp) >= 5    # tp's, kept
    # a layer's gradients leave the loop halved: wq + wk + wv + wo +
    # w_gate + w_up + w_down over tp 2 x dp 2, padded a little
    layer = 2 * (2 * D * D + 2 * D * HKV * DH + 3 * D * 14336) // 4
    assert layer <= sum(r.bytes for r in over_dp) < 1.02 * layer
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    # splash's out [4, 16, 2048, 128] bf16 + logsumexp f32 a device a
    # layer, kept through the layer's remat since PR 45
    kept = 2 * 4 * 16 * 2048 * (2 * 128 + 4)
    assert live < 4.0 * 2 ** 30, (
        f"{live / 2 ** 30:.2f} GiB live a device (3.75 at PR 45, of it "
        f"{kept / 2 ** 30:.2f} of splash residuals; 3.62 at PR 41; stage "
        f"0: 4.44 at 2 layers)")


def test_train_step_on_one_chip_is_the_stage_0_program(topo, chip,
                                                       monkeypatch):
    """dp 1: nothing to shard over, so "by the mesh" is stage 0 and an
    explicit stage 1 finds no dp either: one lowered text."""
    texts = {str(kw): _train_step_for(topo, monkeypatch, 1, 1,
                                      **kw)[0].as_text()
             for kw in ({}, {"zero_stage": 0}, {"zero_stage": 1})}
    assert len(set(texts.values())) == 1, list(texts)
    assert "sharding_constraint" not in texts["{}"]


def test_splash_fwd(chip):
    from paddle_tpu.ops.pallas.flash_attention import _splash
    fn = functools.partial(_splash, causal=True, sm_scale=DH ** -0.5)
    text = chip(fn, sds((BATCH, SEQ, H, DH)), sds((BATCH, SEQ, HKV, DH)),
                sds((BATCH, SEQ, HKV, DH)))
    assert "splash_mha" in text.compiled


def test_fused_rms_fwd_bwd(chip):
    from paddle_tpu.ops.pallas import fused_norm_rope as F
    geom = {"rows": BATCH * SEQ, "d": D, "dtype": "bfloat16"}
    labels = []
    for label, fn, args in F.audit_launches(geom):
        chip(fn, *args)
        labels.append(label.split("[")[0])
    assert labels == ["rms_fwd", "rms_bwd"]


def test_fused_rope(chip):
    from paddle_tpu.ops.pallas import fused_norm_rope as F
    geom = {"rope_batch": BATCH, "rope_seq": SEQ, "rope_heads": H,
            "rope_kv_heads": HKV, "rope_head_dim": DH, "dtype": "bfloat16"}
    (_, fn, args), = F.audit_launches(geom)
    chip(fn, *args)


# bench.py's batched mixed-length decode: 32 streams, 32-token pages,
# prompts to 2048 tokens
_PK = dict(B=32, page=32, pps=64)


def _paged_kv_args():
    B, page, pps = _PK["B"], _PK["page"], _PK["pps"]
    pages = sds((HKV, B * pps, page, DH))
    return (sds((B, H, DH)), pages, pages, sds((B,), jnp.int32),
            sds((B, pps), jnp.int32))


def test_paged_kv_stats_call(chip):
    """``_stats_call`` re-plumbs a private upstream kernel body; this is
    the compile test that says the plumbing still fits the installed
    JAX."""
    from paddle_tpu.inference.paged_kv import _stats_call
    chip(functools.partial(_stats_call, pages_per_compute_block=4),
         *_paged_kv_args())


def test_int8_matmul(chip):
    """Weight-only int8 decode GEMMs at 8B widths: the MLP up-projection
    for a block of 8 slots."""
    from paddle_tpu.ops.pallas import int8_matmul as I
    (_, fn, args), = I.audit_launches(
        {"M": SLOTS, "K": D, "N": 14336, "dtype": "bfloat16"})
    chip(fn, *args)


# the training cell ``joyai-train-8k-ep8`` (JoyAI-LLM-Flash as one chip
# of an EP-8 job: benchmark/workloads/joyai-train-8k-ep8.json): latent
# attention EXPANDED through splash at q / k 192 and v 128, the held
# experts' grouped matmuls each way, the whole step


def _joyai_cell():
    from tools.kernel_bench import train_gmm_cells
    return train_gmm_cells()["joyai-train-8k-ep8"]


def test_splash_takes_head_size_192_beside_v_128(chip):
    """q and k at one and a half lane tiles, v at one, forward and
    backward at the cell's batch and sequence: the chip's compiler takes
    192 as it is, so ``flash_attention`` pads nothing."""
    from paddle_tpu.ops.pallas.flash_attention import _splash
    c = _joyai_cell()
    assert (c["qk"], c["dv"]) == (192, 128)
    B, T, H = c["batch"], c["seq_len"], c["heads"]

    def loss(q, k, v):
        o = _splash(q, k, v, True, c["qk"] ** -0.5)
        assert o.shape == (B, T, H, c["dv"])
        return (o.astype(jnp.float32) ** 2).sum()

    text = chip(jax.grad(loss, argnums=(0, 1, 2)), sds((B, T, H, c["qk"])),
                sds((B, T, H, c["qk"])), sds((B, T, H, c["dv"])))
    assert text.compiled.count("splash_mha") >= 3       # fwd, dq, dkv


def test_grouped_matmuls_each_way_at_the_train_cell_s_geometry(chip):
    """``[rows, 2048] @ [32, 2048, 768]`` forward, ``dX`` (the same
    kernel over the transposed stack) and ``dW`` (``_tgmm_call``: a whole
    expert's float32 gradient block a step, 6 MiB, which the stated VMEM
    limit allows) over the sorted buffer the step sizes (twice the
    balanced rows, the tail dead)."""
    from paddle_tpu.incubate.moe.functional import (ROW_TILE_M,
                                                    held_pairs_bound)
    from paddle_tpu.ops.pallas import grouped_matmul as G
    c = _joyai_cell()
    D, F, E = c["hidden"], c["width"], c["held"]
    bound = held_pairs_bound(c["rows"], c["top_k"], E, c["routed"])
    assert bound == 2 * c["rows"]
    tile_m = ROW_TILE_M
    tiles = -(-bound // tile_m) + E
    M = tiles * tile_m
    i32 = functools.partial(sds, dtype=jnp.int32)

    def each_way(x, g, w, te, live):
        fwd = G._gmm_call(x, w, te, live, tile_m, F, interpret=False)
        dx = G._gmm_call(g, jnp.swapaxes(w, 1, 2), te, live, tile_m, D,
                         interpret=False)
        dw = G._tgmm_call(x, g, te, live, E, tile_m, F, interpret=False)
        return fwd, dx, dw

    text = chip(each_way, sds((M, D)), sds((M, F)), sds((E, D, F)),
                i32((tiles,)), i32((1,)))
    assert text.compiled.count("grouped_matmul_dw") >= 1
    assert text.compiled.count("grouped_matmul") >= 3


def test_joyai_train_step_fits_the_chip(topo, chip, monkeypatch):
    """The cell's WHOLE step (dense + 5 expert layers, 2 x 8192 tokens,
    strict kernels) for the described chip: splash, the fused norm and
    the grouped matmuls each way are in the compiled text, the expert
    walk is not, and ``memory_analysis`` stays under the chip's 15.75
    GiB (13.9 at PR 44: 5.88 of state, 8.0 of temporaries; since PR 45
    a layer's remat keeps splash's ``out`` and ``logsumexp``, 0.76 GiB
    over the six layers, and the backward loops hold no forward
    kernel)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    import os
    import sys
    bench_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench_dir not in sys.path:
        sys.path.insert(0, bench_dir)
    from harness import manifest
    from paddle_tpu.ops.pallas import flash_attention as FA
    from paddle_tpu.ops.pallas import fused_norm_rope as FN
    from paddle_tpu.ops.pallas import grouped_matmul as G
    from paddle_tpu.parallel import init_hybrid_mesh
    for mod in (FA, FN, G):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    cell = manifest.Cell(manifest.load_manifest(), "joyai-train-8k-ep8")
    tr = cell.workload["trainer"]
    B, T = tr["batch"], tr["seq_len"]
    cfg, L = cell.family.program_config(
        dict(cell.model), max_position_embeddings=T,
        use_flash_attention="pallas", use_fused_norm_rope="pallas")
    mesh = init_hybrid_mesh(dp=1, pp=1, tp=1, set_global=False,
                            devices=topo.devices[:1]).mesh
    with mesh:
        step, init = L.make_train_step(cfg, mesh)
        state = jax.tree.map(
            lambda a, sp: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, sp)),
            jax.eval_shape(init, jax.random.PRNGKey(0)),
            L.train_state_specs(cfg, mesh))
        batch = {k: jax.ShapeDtypeStruct(
            (B, T), jnp.int32, sharding=NamedSharding(mesh, P("dp", None)))
            for k in ("tokens", "labels")}
        compiled = step.lower(state, batch).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    count = lambda mark: sum(mark in ln for ln in calls)
    # two groups x fwd, dq, dkv; the forward in the forward loops alone
    assert count("splash_mha") == 6 and count("splash_mha_fwd") == 2, [
        ln.split(" = ")[0] for ln in calls if "splash_mha" in ln]
    assert count("_rms_fwd_call") and count("_rms_bwd_call")
    assert count("grouped_matmul_dw") >= 3 and count("grouped_matmul") >= 12
    assert count("held_experts_matmul") == 0
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    state_bytes = 6 * cell.family.param_count(cell.model)
    assert abs(mem.argument_size_in_bytes - state_bytes) < 0.01 * state_bytes
    assert mem.alias_size_in_bytes >= 0.99 * mem.argument_size_in_bytes
    H, Dv = cfg.num_attention_heads, cfg.v_head_dim
    kept = cfg.num_hidden_layers * B * H * T * (2 * Dv + 4)
    assert 0.80 * 15.75 * 2 ** 30 < live < 15.75 * 2 ** 30, (
        f"{live / 2 ** 30:.2f} GiB live (arguments "
        f"{mem.argument_size_in_bytes / 2 ** 30:.2f}, temporaries "
        f"{mem.temp_size_in_bytes / 2 ** 30:.2f}) of 15.75; of it the "
        f"splash residuals remat keeps, out [{B}, {H}, {T}, {Dv}] bf16 + "
        f"logsumexp [{B}, {H}, {T}] f32 x {cfg.num_hidden_layers} layers "
        f"= {kept / 2 ** 30:.2f} GiB, are new with PR 45 (13.89 before)")


# MiMo-V2-Flash's serving cell (``mimov2-serve-mixedlen``): window layers
# in a ring a slot beside full layers in the paged pool, k rows of 192
# (held at 256 lanes) beside v rows of 128, the ragged kernel with a
# window, a sink and a span in virtual slots of 16 tokens

def _mimo_cell():
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if os.path.join(root, "benchmark") not in sys.path:
        sys.path.insert(0, os.path.join(root, "benchmark"))
    from harness import manifest
    cell = manifest.Cell(manifest.load_manifest(), "mimov2-serve-mixedlen")
    cfg, mod = cell.family.program_config(cell.model)
    return cell, cfg, mod


def test_mimo_window_pool_has_the_bytes_the_configuration_states():
    """``init_serving_pages``' two kinds of pool at the cell's geometry:
    the rings' bytes follow the slots, the window and the chunk (11
    pages a slot) and are what the configuration file's ``sizes``
    states; so are the paged pool's."""
    cell, cfg, mod = _mimo_cell()
    geo, sizes = cell.workload["engine"], cell.config["sizes"]
    cache = jax.eval_shape(lambda: mod.init_serving_pages(
        cfg, geo["total_pages"], geo["page_size"],
        max_batch=geo["max_batch"], max_span=geo["prefill_chunk"]))
    ring = mod.window_ring_pages(cfg, geo["page_size"], geo["prefill_chunk"])
    assert ring == sizes["window_ring_pages_per_slot"] == 11
    assert cache[mod.K_WINDOW].shape == (5, 8, 48 * 11 + 1, 64, 256)
    assert cache[mod.V_WINDOW].shape == (5, 8, 48 * 11 + 1, 64, 128)
    assert cache[mod.K_FULL].shape == (2, 4, 13057, 64, 256)
    assert cache[mod.V_FULL].shape == (2, 4, 13057, 64, 128)

    def nbytes(*names):
        return sum(math.prod(cache[n].shape) * 2 for n in names)

    assert nbytes(mod.K_WINDOW, mod.V_WINDOW) == sizes[
        "window_pool_bytes_48_slots_in_pool"]
    assert nbytes(mod.K_FULL, mod.V_FULL) == sizes[
        "full_pool_bytes_13057_pages_in_pool"]
    # counted at the published 192-wide key row
    assert sizes["window_pool_bytes_48_slots_published"] == (
        nbytes(mod.K_WINDOW, mod.V_WINDOW) - 2 * math.prod(
            cache[mod.K_WINDOW].shape) // 4 - 5 * 8 * 64 * 320 * 2)


@pytest.mark.parametrize("program", ["tick", "tick_tail3", "block4"])
def test_mimo_cell_programs_hold_both_pools_once(topo, chip, monkeypatch,
                                                 program):
    """The engine's jitted tick (48 slots + one 512-row chunk, plain and
    with a fused tail of 3) and fused block of 4 at the cell's geometry
    and published widths, all seven layers: they compile for the
    described chip (k rows of 192 enter the kernel padded to 256 lanes,
    v rows at 128; the sinks as a float32 scalar-prefetch operand; a
    span in virtual slots of 16 tokens), both kinds of pool are aliased
    (held once), the tick holds at least 60 % of the chip's 15.75 GiB
    and fits it, and the share's counts come back as one more ``s32[4]``
    result beside the tokens."""
    from paddle_tpu.ops.pallas import grouped_matmul as G
    from paddle_tpu.ops.pallas import ragged_paged_attention as R
    from paddle_tpu.serving import engine as E
    monkeypatch.setattr(R, "_on_tpu", lambda: True)
    monkeypatch.setattr(G, "_on_tpu", lambda: True)
    cell, cfg, mod = _mimo_cell()
    geo = cell.workload["engine"]
    S, chunk, ps = geo["max_batch"], geo["prefill_chunk"], geo["page_size"]
    pps = -(-(geo["max_prompt_len"] + geo["max_new_tokens_cap"] - 1) // ps)
    params = jax.eval_shape(lambda: cell.family.make_params(cell.model, 0))
    cache = jax.eval_shape(lambda: mod.init_serving_pages(
        cfg, geo["total_pages"], ps, max_batch=S, max_span=chunk))
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    i32 = functools.partial(sds, dtype=jnp.int32)
    f32 = functools.partial(sds, dtype=jnp.float32)
    samp = dict(temp=f32((S,)), top_p=f32((S,)), top_k=i32((S,)),
                key=sds((S, 2), jnp.uint32), produced=i32((S,)))
    E._JIT_CACHE.clear()        # jit objects of THIS precision context
    tick, block = E._jit_step_fns(mod, cfg, "auto")
    if program.startswith("tick"):
        T = S + chunk
        meta = dict(tok_slot=i32((T,)), tok_pos=i32((T,)),
                    tok_page=i32((T,)), tok_off=i32((T,)),
                    tok_qoff=i32((T,)), q_len=i32((S,)), kv_len=i32((S,)),
                    last=i32((S,)), tables=i32((S, pps)),
                    tail_live=sds((S,), jnp.bool_), cur_tok=i32((S,)),
                    **samp)
        compiled = tick.lower(
            *on_chip((params, i32((T,)), meta, cache)), tq=chunk,
            decode_tail=3 if program == "tick_tail3" else 0).compile()
        results = 4             # toks, logits, counts, cur_tok'
    else:
        compiled = block.lower(
            *on_chip((params, i32((S,)), i32((S,)), i32((S, pps)), cache)),
            num_steps=4, sampling=on_chip(samp)).compile()
        results = 3             # toks, counts, cur_tok'
    E._JIT_CACHE.clear()
    text = compiled.as_text()
    assert "ragged_paged_attention" in text and "held_experts_matmul" in text
    outs = jax.tree.leaves(compiled.out_info)
    leaves = jax.tree.leaves(cache)
    assert len(outs) == results + len(leaves)
    counts, nxt = outs[results - 2:results]
    assert (counts.shape, counts.dtype) == ((4,), jnp.int32)
    assert (nxt.shape, nxt.dtype) == ((S,), jnp.int32)
    assert [(o.shape, o.dtype) for o in outs[results:]] == [
        (a.shape, a.dtype) for a in leaves]
    mem = compiled.memory_analysis()
    pools = sum(math.prod(a.shape) * a.dtype.itemsize for a in leaves)
    assert mem.alias_size_in_bytes >= pools
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"[mimo {program}] arguments {mem.argument_size_in_bytes} "
          f"alias {mem.alias_size_in_bytes} temp {mem.temp_size_in_bytes} "
          f"live {live} = {live / 2 ** 30:.2f} GiB")
    assert 0.60 * 15.75 * 2 ** 30 <= live < 15.75 * 2 ** 30
    # no pool is copied around a layer: temporaries far under one pool
    assert mem.temp_size_in_bytes < math.prod(
        cache[mod.K_WINDOW].shape) * 2
