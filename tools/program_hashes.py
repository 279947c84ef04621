"""Hashes of the serving cells' LOWERED tick programs and the training
cells' LOWERED train steps, for a described v5e (no chip): what a PR
that moves shared code compares at parent and change to show that a
family's programs did not move.

    JAX_PLATFORMS=cpu python tools/program_hashes.py [cell ...] > change.json
    (cd <a checkout of the parent> && JAX_PLATFORMS=cpu python \\
        <this file> [cell ...]) > parent.json

Run from the root of the checkout to hash (the file may lie elsewhere).
For every serving cell of ``BENCHMARK.json`` (or those named), at the
cell's own configuration, slots, table, pool and chunk: the engine's
jitted tick at ``slots + prefill_chunk`` rows (plain and with a fused
tail of 3) and its fused block of 4, lowered to StableHLO text and
hashed; for every training cell, ``make_train_step``'s step at the
cell's widths, depth, batch and mesh (``<cell>.step``). A Mosaic kernel's serialized body carries the Python call stack
of its call site (file names, function names, line numbers), so an edit
to a docstring above the call would change it: each body is replaced by
the hash of its assembly printed WITHOUT debug info first.
"""
import base64
import functools
import hashlib
import importlib
import json
import os
import re
import sys

ROOT = os.getcwd()
for p in (ROOT, os.path.join(ROOT, "benchmark")):
    sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax._src.interpreters import mlir as jmlir  # noqa: E402
from jax._src.lib.mlir import ir  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def strip_kernel_locations(text: str) -> str:
    def sub(m):
        cfg = json.loads(m.group(1).replace("\\22", '"'))
        body = base64.b64decode(cfg["custom_call_config"]["body"])
        ctx = jmlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(body).operation.get_asm(
                enable_debug_info=False)
        cfg["custom_call_config"]["body"] = hashlib.sha256(
            asm.encode()).hexdigest()
        return ('backend_config = "'
                + json.dumps(cfg, sort_keys=True).replace('"', "'") + '"')
    return re.sub(r'backend_config = "(\{.*?\})"', sub, text)


def train_step_text(cell, topo) -> str:
    """The cell's train step lowered over shapes only, built the way
    ``benchmark/harness/train.py: Trainer`` builds it."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.ops.pallas import flash_attention as FA
    from paddle_tpu.ops.pallas import fused_norm_rope as FN
    from paddle_tpu.parallel import init_hybrid_mesh
    FA._on_tpu = FN._on_tpu = lambda: True
    tr = cell.workload["trainer"]
    dp, tp = int(tr.get("dp", 1)), int(tr.get("tp", 1))
    B, T = int(tr["batch"]), int(tr["seq_len"])
    cfg, L = cell.family.program_config(
        dict(cell.model), max_position_embeddings=T,
        use_flash_attention="pallas", use_fused_norm_rope="pallas")
    mesh = init_hybrid_mesh(dp=dp, pp=1, tp=tp, set_global=False,
                            devices=topo.devices[:dp * tp]).mesh
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
        return step.lower(state, batch).as_text()


def main(names) -> dict:
    from harness import manifest
    from paddle_tpu.ops.pallas import ragged_paged_attention as R
    from paddle_tpu.serving import engine as E
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    R._on_tpu = lambda: True
    for kernel in ("ssd_update", "mla_paged_attention", "grouped_matmul"):
        try:
            importlib.import_module(
                f"paddle_tpu.ops.pallas.{kernel}")._on_tpu = lambda: True
        except ImportError:     # a parent from before that kernel
            pass

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    bench = manifest.load_manifest()
    out = {}
    for name in names or [w["name"] for w in bench["workloads"]]:
        cell = manifest.Cell(bench, name)
        if cell.mode == "train":
            text = strip_kernel_locations(train_step_text(cell, topo))
            out[f"{name}.step"] = hashlib.sha256(
                text.encode()).hexdigest()[:16]
            continue
        cfg, mod = cell.family.program_config(dict(cell.model))
        eng = cell.workload["engine"]
        S, ps, chunk = eng["max_batch"], eng["page_size"], eng["prefill_chunk"]
        longest = max(eng.get("prompt_buckets") or [eng["max_prompt_len"]])
        pps = -(-(longest + eng["max_new_tokens_cap"] - 1) // ps)
        pages = eng.get("total_pages") or S * pps + 1
        params = jax.eval_shape(
            lambda: mod.init_params(cfg, jax.random.PRNGKey(0)))
        # the tree an engine holds and the cache its family builds, the
        # rings sized by the chunk: the record's (``mod.SERVING``); for a
        # parent from before the record, the engine's ``init_cache`` and
        # the module's ``serving_params``
        family = getattr(mod, "SERVING", None)
        serving = family.params if family else getattr(
            mod, "serving_params", None)
        if serving is not None:
            params = jax.eval_shape(lambda p: serving(p, cfg), params)
        cache = jax.eval_shape(
            lambda: family.init_pages(cfg, pages, ps, S, chunk) if family
            else E.init_cache(mod, cfg, pages, ps, S, chunk))
        i32 = functools.partial(sds, dtype=jnp.int32)
        f32 = functools.partial(sds, dtype=jnp.float32)
        samp = dict(temp=f32((S,)), top_p=f32((S,)), top_k=i32((S,)),
                    key=sds((S, 2), jnp.uint32), produced=i32((S,)))
        E._JIT_CACHE.clear()
        tick, block = E._jit_step_fns(mod, cfg, "auto")
        T = S + chunk
        meta = dict(tok_slot=i32((T,)), tok_pos=i32((T,)),
                    tok_page=i32((T,)), tok_off=i32((T,)),
                    tok_qoff=i32((T,)), q_len=i32((S,)), kv_len=i32((S,)),
                    last=i32((S,)), tables=i32((S, pps)),
                    tail_live=sds((S,), jnp.bool_), cur_tok=i32((S,)), **samp)
        tick_args = on_chip((params, i32((T,)), meta, cache))
        block_args = on_chip((params, i32((S,)), i32((S,)), i32((S, pps)),
                              cache))
        programs = {
            "tick": lambda: tick.lower(*tick_args, tq=chunk, decode_tail=0),
            "tick_tail3": lambda: tick.lower(*tick_args, tq=chunk,
                                             decode_tail=3),
            "block4": lambda: block.lower(*block_args, num_steps=4,
                                          sampling=on_chip(samp))}
        for label, lower in programs.items():
            text = strip_kernel_locations(lower().as_text())
            out[f"{name}.{label}"] = hashlib.sha256(
                text.encode()).hexdigest()[:16]
    return out


if __name__ == "__main__":
    print(json.dumps(main(sys.argv[1:]), indent=1))
