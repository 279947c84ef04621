"""Mode ``train``: the program's ``make_train_step`` over
``init_hybrid_mesh``, fed seeded batches, steps chained on donated
state with one ``block_until_ready`` at the end of the window.

Set-up builds ONE object — the compiled step with its state — drives it
through its first three steps (the ones the reference follows) by the
window's own call and feed, and hands the same object to the window.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import reference, traffic
from .common import CompileCounter, check, log, start_trace

# how a kernel shows in compiled program text (copied from
# chip_smoke.py KERNEL_MARKS / kernels_in)
KERNEL_MARKS = {
    "splash_attention": ("splash_mha",),
    "fused_rms_norm": ("_rms_fwd_call", "_rms_bwd_call"),
    "fused_rope": ("_rope_call",),
}
TRAINER_KEYS = {"dp", "tp", "batch", "seq_len", "strict_kernels"}
REF_STEPS = 2          # AdamW steps the reference applies (see reference.py)


def kernels_in(text: str) -> dict:
    calls = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    return {k: sum(any(m in ln for m in marks) for ln in calls)
            for k, marks in KERNEL_MARKS.items()}


def host_batch(seed: int, step: int, batch: int, seq: int, vocab: int):
    """Tokens and next-token labels of one step, every row different."""
    toks = traffic.seed_rng(seed, 100 + step).integers(
        0, vocab, (batch, seq + 1), dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


def run_reference(cell, model, family, seed, round_to=None):
    """The float32 reference over the first steps, before any state of
    the program exists. Returns small host numbers only."""
    tr = cell.workload["trainer"]
    B, T, V = tr["batch"], tr["seq_len"], model["vocab_size"]
    params = family.make_params(model, seed)
    ref = reference.TrainReference(params, model, family,
                                   cell.workload["optimizer"], round_to)
    del params
    losses = [ref.step(*host_batch(seed, i, B, T, V))
              for i in range(REF_STEPS)]
    losses.append(ref.loss(*host_batch(seed, REF_STEPS, B, T, V)))
    change = ref.change_norms(family.make_params(model, seed))
    return {"losses": losses, "grad": ref.grad_norms(), "change": change}


def _leaf_names(tree) -> list:
    return [".".join(str(getattr(k, "key", getattr(k, "name", k)))
                     for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _find_mu(opt_state):
    """The first-moment tree inside an optax state."""
    for node in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu")):
        if hasattr(node, "mu"):
            return node.mu
    raise SystemExit("the optimizer state holds no `mu`: the first "
                     "gradient cannot be worked out from it")


def leaf_norms(tree, minus=None, scale: float = 1.0) -> dict:
    """``{leaf name: |leaf - minus| * scale}`` in float32, one jitted
    reduction per tree."""

    def sq(a, b=None):
        d = a.astype(jnp.float32)
        if b is not None:
            d = d - b.astype(jnp.float32)
        return jnp.sum(d * d)
    trees = (tree,) if minus is None else (tree, minus)
    vals = jax.tree_util.tree_leaves(
        jax.jit(lambda *t: jax.tree_util.tree_map(sq, *t))(*trees))
    return {n: float(np.sqrt(float(v))) * scale
            for n, v in zip(_leaf_names(tree), vals)}


class Trainer:
    """The compiled step with its state and its feed."""

    def __init__(self, cell, model, family, seed, devs):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.parallel import init_hybrid_mesh
        tr = cell.workload["trainer"]
        bad = set(tr) - TRAINER_KEYS
        if bad:
            raise SystemExit(f"workload file pins trainer knobs "
                             f"{sorted(bad)}; allowed {sorted(TRAINER_KEYS)}")
        self.B, self.T = int(tr["batch"]), int(tr["seq_len"])
        self.seed, self.model, self.family = seed, model, family
        kw = dict(max_position_embeddings=self.T)
        if tr.get("strict_kernels", True):
            # strict mode: an error instead of a silent dense fallback
            kw.update(use_flash_attention="pallas",
                      use_fused_norm_rope="pallas")
        self.cfg, L = family.program_config(model, **kw)
        self.hm = init_hybrid_mesh(dp=int(tr.get("dp", 1)), pp=1,
                                   tp=int(tr.get("tp", 1)), devices=devs,
                                   set_global=False)
        self.mesh = self.hm.mesh
        self.batch_sharding = NamedSharding(self.mesh, P("dp", None))
        self.timing = {}
        t = time.perf_counter()

        def lap(name):
            nonlocal t
            jax.block_until_ready(getattr(self, "state", None))
            self.timing[name] = time.perf_counter() - t
            t = time.perf_counter()
        with self.mesh:
            step, init = L.make_train_step(self.cfg, self.mesh)
            state = init(family.seed_key(seed))
            self.state = state
            lap("program_init")
            # the benchmark's own seeded weights, placed as the
            # program's are (the reference takes nothing the program made)
            mine = family.make_params(model, seed)
            state["params"] = jax.tree_util.tree_map(
                lambda new, old: jax.device_put(new, old.sharding),
                mine, state["params"])
            del mine
            self.state = state
            self.feed = [self.device_batch(i) for i in range(3)]
            lap("seeded_weights")
            self.compiled = step.lower(self.state, self.feed[0]).compile()
            lap("compile")
        self.text = self.compiled.as_text()
        lap("program_text")
        self.steps_done = 0

    def device_batch(self, i: int) -> dict:
        toks, labels = host_batch(self.seed, i, self.B, self.T,
                                  self.model["vocab_size"])
        return {"tokens": jax.device_put(toks, self.batch_sharding),
                "labels": jax.device_put(labels, self.batch_sharding)}

    def step(self):
        """The window's own call: the next batch of the feed."""
        self.state, loss = self.compiled(self.state,
                                         self.feed[self.steps_done])
        self.steps_done += 1
        return loss

    def extend_feed(self, n: int) -> None:
        while len(self.feed) < n:
            self.feed.append(self.device_batch(len(self.feed)))

    def first_steps(self, opt: dict) -> dict:
        """Steps 1-3 with what the reference is compared on."""
        out = {"losses": [], "step_s": []}
        for i in range(3):
            t0 = time.perf_counter()
            loss = self.step()
            out["losses"].append(float(loss))
            jax.block_until_ready(self.state)
            out["step_s"].append(time.perf_counter() - t0)
            if i == 0:      # mu_1 = (1 - b1) g_1
                out["grad"] = {
                    n.replace("params.", "", 1) if n.startswith("params.")
                    else n: v for n, v in leaf_norms(
                        _find_mu(self.state["opt"]),
                        scale=1.0 / (1.0 - opt["b1"])).items()}
            if i == REF_STEPS - 1:
                p0 = jax.tree_util.tree_map(
                    lambda new, old: jax.device_put(new, old.sharding),
                    self.family.make_params(self.model, self.seed),
                    self.state["params"])
                out["change"] = leaf_norms(self.state["params"], minus=p0)
                del p0
        return out


def compare_training(prog: dict, ref: dict, limits: dict, checks: list,
                     tag: str = "") -> dict:
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        out[f"loss{i + 1}_gap"] = abs(a - b)
        log(f"[correct]{tag} step {i + 1} loss program {a:.6f} "
            f"reference {b:.6f}")
        check(f"loss{i + 1}_gap", out[f"loss{i + 1}_gap"],
              limits["loss_gap"][i], checks)
    for what in ("grad", "change"):
        gaps = reference.leaf_gaps(prog[what], ref[what])
        worst = sorted(gaps.items(), key=lambda kv: -kv[1])[:4]
        log(f"[correct]{tag} {what} norm gaps, worst leaves: "
            + ", ".join(f"{k} {v:.5f}" for k, v in worst)
            + f"; median {float(np.median(list(gaps.values()))):.5f}")
        out[f"{what}_leaf_gaps"] = gaps
    out["grad_norm_gap"] = reference.worst_leaf_gap(prog["grad"],
                                                    ref["grad"])
    check("first_grad_norm_worst_leaf_gap", out["grad_norm_gap"],
          limits["grad_norm_gap"], checks)
    out["change_norm_gap"] = reference.worst_leaf_gap(prog["change"],
                                                      ref["change"])
    check("param_change_norm_worst_leaf_gap", out["change_norm_gap"],
          limits["change_norm_gap"], checks)
    return out


def run_window(trainer: Trainer, seconds: float, step_s: float,
               trace: bool, wl: dict) -> dict:
    """As many whole steps as fit ``seconds`` (decided from the warm
    step time, not by polling), chained, one ``block_until_ready`` at
    the end. Traced: the chain is cut around a few seconds of steps."""
    n = max(int(math.floor(seconds / step_s)), 1)
    trainer.extend_feed(trainer.steps_done + n)
    jax.block_until_ready(trainer.feed)
    compiles = CompileCounter()
    compiles.arm()
    losses, traced = [], None
    t0 = time.perf_counter()
    out_t0 = t0
    if not trace:
        for _ in range(n):
            losses.append(trainer.step())
        jax.block_until_ready((trainer.state, losses))
        t1 = time.perf_counter()
        rate_n, rate_s = n, t1 - t0
    else:
        k = max(min(int(wl.get("trace_seconds", 3.0) / step_s), n // 2), 1)
        a = (n - k) // 2
        for _ in range(a):
            losses.append(trainer.step())
        jax.block_until_ready((trainer.state, losses))
        t_a = time.perf_counter()
        rate_n, rate_s = a, t_a - t0
        start_trace()
        for _ in range(k):
            losses.append(trainer.step())
        jax.block_until_ready((trainer.state, losses))
        jax.profiler.stop_trace()
        traced = k
        for _ in range(n - a - k):
            losses.append(trainer.step())
        jax.block_until_ready((trainer.state, losses))
        t1 = time.perf_counter()
    n_compiles = compiles.disarm()
    vals = [float(x) for x in losses]
    tokens = trainer.B * trainer.T
    return {"steps": n, "losses": vals, "window_s": t1 - t0, "t0": out_t0,
            "tokens_per_step": tokens,
            "tokens_per_s": rate_n * tokens / rate_s if rate_n else None,
            "compiles_in_window": n_compiles, "trace_steps": traced,
            "failed": int(sum(not np.isfinite(v) for v in vals))}
