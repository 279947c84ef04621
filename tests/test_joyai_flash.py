"""``models/joyai_flash.py`` (JoyAI-LLM-Flash: latent attention trained
in its expanded form, a dense and expert layers in one step, a share of
the routed experts, a multi-token-prediction module) against the
benchmark's plain float32 reference (``benchmark/families/
joyai_flash.py``), tiny, on the CPU: logits, the loss and every leaf's
gradient with the module off and on, the shares of an expert layer
adding up to the uncut layer in value and gradient, a skewed batch past
the sorted buffer's bound, and two AdamW steps against the harness's
training reference.

Tolerances: program and reference are float32 here and conftest sets
``jax_default_matmul_precision="highest"``, so what separates them is
the order of float32 sums (a fused norm, a scatter-add, a softmax in
blocks): ``TOL`` = 2e-4 of the largest value compared, some 100 float32
ulps of a sum of a few thousand terms, as ``tests/test_longcat_flash.py``
holds its family to; a bfloat16 path reads 1e-2.
"""
import functools
import json
import os
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import manifest, reference, train  # noqa: E402
from harness.common import Checks  # noqa: E402

from paddle_tpu.incubate.moe import functional as F  # noqa: E402
from paddle_tpu.models import joyai_flash as M  # noqa: E402
from paddle_tpu.models import mla  # noqa: E402
from paddle_tpu.observability import step_counters  # noqa: E402
from paddle_tpu.parallel import init_hybrid_mesh  # noqa: E402

TOL = 2e-4
FAMILY = manifest.load_family("joyai_flash")
TINY = json.load(open(os.path.join(
    BENCH, "tests", "tiny", "configs", "tiny-joyai.json")))
B, T = 2, 64


def built(seed=11, **kw):
    model = {**TINY, **kw}
    cfg, mod = FAMILY.program_config(model)
    assert mod is M
    return model, cfg, FAMILY.make_params(model, seed)


def batch_of(model, seed=5):
    toks = np.random.default_rng(seed).integers(
        0, model["vocab_size"], (B, T + 1), dtype=np.int32)
    return {"tokens": jnp.asarray(toks[:, :-1]),
            "labels": jnp.asarray(toks[:, 1:])}


def close(a, b, tol=TOL, what=""):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-6)
    assert np.abs(a - b).max() <= tol * scale, (
        what, np.abs(a - b).max(), scale)


# ---------------------------------------------------------- the stack ----

def test_the_configuration_is_one_chip_s_share():
    model, cfg, params = built()
    assert cfg.experts_held == (8, 8) and cfg.n_routed_experts == 16
    assert [g.repeats for g in M.layer_groups(cfg)] == [1, 2]
    assert [g.layers[0][1] for g in M.layer_groups(cfg)] == [
        "dense_layers", "layers"]
    mine = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    assert (jax.tree_util.tree_structure(mine)
            == jax.tree_util.tree_structure(params))
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(params)))
    assert "mtp" not in params
    assert "mtp" in built(num_nextn_predict_layers=1)[2]
    assert FAMILY.param_count(model) == sum(
        a.size for a in jax.tree_util.tree_leaves(params))


def test_the_cell_s_arithmetic():
    """The configuration as the cell cuts it: the issue's counts."""
    bench = manifest.load_manifest()
    cell = manifest.Cell(bench, "joyai-train-8k-ep8")
    m = cell.model
    assert (m["num_hidden_layers"], m["n_routed_experts"], m["vocab_size"],
            m["num_nextn_predict_layers"]) == (6, 32, 16160, 0)
    assert FAMILY.held(m) == (0, 32) and FAMILY.routed_experts(m) == 256
    assert FAMILY.param_count(m) == 1_049_533_696
    per_token = FAMILY.train_flops_per_token(m, 8192, 5.0)
    assert abs(per_token / 1e9 - 3.22) < 0.005
    core = 3.0 * 6 * 2.0 * 8192 * 32 * (192 + 128) / 2.0
    assert abs(core / per_token - 0.47) < 0.005


def test_seeded_weights_give_scores_of_unit_deviation():
    """normal(0, 1/sqrt(fan_in)) behind the two latent norms: q, k of
    unit deviation a component, so scores / sqrt(192) of deviation 1
    (PR 40's lesson; within 15 %: 4 heads x 2 x 64 x 64 samples)."""
    model, cfg, params = built(seed=3)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    h = params["embed"][batch_of(model)["tokens"]]
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    q_n, q_r, c_kv, k_r = mla.mla_qkv(lp, h, pos, cfg)
    k_n = jnp.einsum("btc,hnc->bthn", c_kv, lp["w_uk"])
    s = (jnp.einsum("bthn,bshn->bhts", q_n, k_n)
         + jnp.einsum("bthr,bsr->bhts", q_r, k_r)) * cfg.sm_scale
    assert abs(float(jnp.std(s)) - 1.0) < 0.15
    x = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-6)
    logits = x.reshape(-1, x.shape[-1]) @ lp["router"]
    assert abs(float(jnp.std(logits)) - 1.0) < 0.15
    assert 0 < float(jnp.abs(lp["router_bias"]).max()) < 0.02


def test_forward_logits_equal_the_reference():
    model, cfg, params = built()
    batch = batch_of(model)
    got = M.forward(params, batch["tokens"], cfg)
    for b in range(B):
        toks = np.asarray(batch["tokens"][b])
        h = reference.hidden_states(params, toks, model, FAMILY)
        want = reference.logits_at(params, h, np.arange(T), model)
        close(got[b], want, what=f"logits of sequence {b}")


@pytest.mark.parametrize("nextn", [0, 1], ids=["mtp_off", "mtp_on"])
def test_loss_and_every_leafs_gradient_equal_the_reference(nextn):
    """``jax.grad`` of the program's loss against ``jax.grad`` of the
    plain reference's, leaf by leaf: both stacks, the float32 router,
    the top-level leaves and, where it is on, the multi-token-prediction
    module. The selection bias takes no gradient in either."""
    model, cfg, params = built(num_nextn_predict_layers=nextn)
    batch = batch_of(model)
    loss, grads = jax.value_and_grad(M.loss_fn)(params, batch, cfg)
    want, wgrads = jax.value_and_grad(FAMILY.loss_with_mtp)(
        params, batch["tokens"], batch["labels"], model)
    assert abs(float(loss) - float(want)) < TOL * float(want)
    if nextn:
        plain = FAMILY.loss_with_mtp(
            {k: v for k, v in params.items() if k != "mtp"},
            batch["tokens"], batch["labels"], model)
        assert float(want) > float(plain) + 1.0     # 0.3 x ~ln(256)
    names = train._leaf_names(grads)
    assert any(n.startswith("dense_layers.") for n in names)
    assert any(n.startswith("layers.experts.") for n in names)
    assert any(n.startswith("mtp.") for n in names) == bool(nextn)
    for name, g, w in zip(names, jax.tree_util.tree_leaves(grads),
                          jax.tree_util.tree_leaves(wgrads)):
        if name.endswith("router_bias"):
            assert not np.asarray(g).any() and not np.asarray(w).any()
            continue
        assert np.abs(np.asarray(w)).max() > 0, name
        close(g, w, tol=5 * TOL, what=name)


@pytest.mark.parametrize("against", ["plain_remat", "no_remat"])
@pytest.mark.parametrize("attn", ["pallas", "dense"])
def test_keeping_splash_s_residuals_changes_no_gradient(
        attn, against, splash_interpreted, monkeypatch):
    """A dense and an expert layer with the module on, rematerialised
    but for splash's ``out`` and ``logsumexp`` (``remat_layer``), against
    plain ``jax.checkpoint`` and against no remat: the loss and every
    leaf's gradient EXACTLY, through the kernel (interpret mode; the
    backward reads what the forward pass wrote instead of a second
    evaluation) and on the dense path (nothing carries the name)."""
    def value_and_grads(remat):
        cfg = M.JoyAIFlashConfig.tiny(
            num_hidden_layers=2, num_nextn_predict_layers=1,
            use_flash_attention=attn, remat=remat)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        return jax.jit(jax.value_and_grad(
            lambda p: M.loss_fn(p, batch, cfg)))(
                M.init_params(cfg, jax.random.PRNGKey(0)))

    kept = value_and_grads(True)
    if against == "plain_remat":
        monkeypatch.setattr(M, "remat_layer", jax.checkpoint)
    other = value_and_grads(against == "plain_remat")
    assert np.abs(np.asarray(jax.tree.leaves(kept[1])[0])).max() > 0
    for name, a, b in zip(train._leaf_names(kept), jax.tree.leaves(kept),
                          jax.tree.leaves(other)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)


# ------------------------------------------------------- the expert share ----

def _layer_inputs(seed=2, n_rows=96):
    model, cfg, params = built(seed=seed)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(seed), (n_rows, cfg.hidden_size))
    return model, cfg, lp, x


def _share(lp, x, cfg, held, experts, **kw):
    return F.moe_ffn_share(
        x, lp["router"], lp["router_bias"], experts, held=held,
        num_routed=cfg.n_routed_experts, top_k=cfg.num_experts_per_tok,
        scale=cfg.routed_scaling_factor, score_fn="sigmoid",
        normalize_topk=True, **kw)


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight shares ``(2k, 2)`` of one expert layer's 16 routed experts,
    the shared expert and the residual counted once, sum to the uncut
    layer's output AND to its input-gradient and router-gradient (each
    is linear in the experts' parts)."""
    model, cfg, lp, x = _layer_inputs()
    whole = {**model, "n_routed_experts": 16, "ep_this_chip": 0}
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    D, Fm = cfg.hidden_size, cfg.moe_intermediate_size
    ex = {"w_gate": jax.random.normal(ks[0], (16, D, Fm)) / np.sqrt(D),
          "w_up": jax.random.normal(ks[1], (16, D, Fm)) / np.sqrt(D),
          "w_down": jax.random.normal(ks[2], (16, Fm, D)) / np.sqrt(Fm)}
    ct = jax.random.normal(jax.random.PRNGKey(8), x.shape)

    def part(held):
        sub = {k: v[held[0]:held[0] + held[1]] for k, v in ex.items()}

        def f(x, router):
            y, counts = _share({**lp, "router": router}, x, cfg, held, sub)
            return jnp.vdot(y, ct), (y, counts)
        (_, (y, counts)), g = jax.value_and_grad(
            f, argnums=(0, 1), has_aux=True)(x, lp["router"])
        return y, counts, g

    y_all, c_all, g_all = part((0, 16))
    parts = [part((2 * k, 2)) for k in range(8)]
    close(sum(p[0] for p in parts), y_all, what="the shares' outputs")
    close(sum(p[2][0] for p in parts), g_all[0], what="input gradient")
    close(sum(p[2][1] for p in parts), g_all[1], what="router gradient")
    assert sum(int(p[1][0]) for p in parts) == int(c_all[0]) == x.shape[0] * 4
    # and the uncut layer is the reference's
    want = FAMILY.routed({**lp, "experts": ex}, x, whole, None)
    close(y_all, want, what="the uncut layer against the reference")


@pytest.mark.parametrize("held_first,pairs", [(4, 1024), (10, 0)],
                         ids=["twice_the_bound", "none_held"])
def test_a_skewed_batch_drops_nothing(monkeypatch, held_first, pairs):
    """Every row chooses the SAME four experts (4..7, by the selection
    bias). With experts 4, 5 held the 1024 held pairs are twice the
    sorted buffer's bound (512): the row-tile walk takes further passes
    over the same buffer, equals the dense form and the reference in
    value and gradient, and says so; with none of the four held it
    computes nothing and says that."""
    monkeypatch.setattr(F, "HELD_PAIRS_UNBOUNDED_TO", 64)
    model, cfg, lp, x = _layer_inputs(seed=4, n_rows=512)
    assert F.held_pairs_bound(512, 4, 2, 16) == 512
    bias = jnp.zeros((16,)).at[4:8].set(10.0)
    lp = {**lp, "router_bias": bias}
    held = (held_first, 2)
    ex = jax.tree_util.tree_map(lambda a: a[:2], lp["experts"])
    ct = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def run(impl):
        def f(x, router, ex):
            y, counts = _share({**lp, "router": router}, x, cfg, held, ex,
                               impl=impl, row_stats=True)
            return jnp.vdot(y, ct), (y, counts)
        (_, (y, counts)), g = jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True)(x, lp["router"], ex)
        return y, np.asarray(counts), g

    y_d, c_d, g_d = run("dense")
    y_p, c_p, g_p = run("pallas")
    assert c_p[0] == c_d[0] == pairs and c_p[0] + c_p[2] == 512 * 4
    assert c_p[5] == (1 if pairs > 512 else 0)      # the fall-back, counted
    close(y_p, y_d, what="row-tile walk against the dense form")
    for a, b in zip(jax.tree_util.tree_leaves(g_p),
                    jax.tree_util.tree_leaves(g_d)):
        close(a, b, tol=5 * TOL)
    m = {**model, "n_routed_experts": 2, "ep_this_chip": held_first // 2}
    want = FAMILY.routed({**lp, "experts": ex}, x, m, None)
    close(y_p, want, what="row-tile walk against the reference")
    if not pairs:
        assert not np.asarray(y_p).any()


def test_a_serving_tick_s_rows_keep_the_expert_walk(monkeypatch):
    """What chooses the walk is what the call observes: a tick's handful
    of rows an expert stays on the expert walk (forward only), a step's
    hundreds go by row tiles."""
    from paddle_tpu.ops.pallas import grouped_matmul as G
    calls = []
    monkeypatch.setattr(G, "held_experts_swiglu", lambda *a, **k: (
        calls.append("experts"), (jnp.zeros(a[0].shape, jnp.float32),
                                  jnp.zeros((2,), jnp.int32)))[1])
    monkeypatch.setattr(G, "grouped_experts_swiglu", lambda *a, **k: (
        calls.append("row_tiles"), (jnp.zeros(a[0].shape, jnp.float32),
                                    jnp.zeros((2,), jnp.int32),
                                    jnp.zeros((2,), jnp.int32)))[1])
    model, cfg, lp, _ = _layer_inputs()
    ex = jax.tree_util.tree_map(lambda a: a[:2], lp["experts"])
    for rows in (64, 512):
        _share(lp, jnp.ones((rows, cfg.hidden_size)), cfg, (4, 2), ex,
               impl="pallas")
    assert calls == ["experts", "row_tiles"]


# ------------------------------------------------------ the train step ----
OPT = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}
SEED = 21


@functools.lru_cache(maxsize=None)
def reference_run(round_to=None):
    """The harness's training reference at the tiny size (once a
    process: two tests read the float32 one)."""
    cell = types.SimpleNamespace(workload={
        "trainer": {"batch": B, "seq_len": T}, "optimizer": OPT})
    return train.run_reference(cell, built()[0], FAMILY, SEED,
                               round_to=round_to)


def test_two_adamw_steps_reproduce_the_training_reference():
    """``make_train_step`` entered as ``harness/train.py`` enters it
    (the benchmark's seeded weights over the program's state, batches
    from ``host_batch``) against ``harness.reference.TrainReference``:
    three losses, the first gradient's norm and the parameters' change
    after two steps, every leaf. Float32 on both sides: the gaps are
    summation order, limits 20 x under the bfloat16 cell's."""
    model, cfg, _ = built()
    seed, opt = SEED, OPT
    ref = reference_run()
    mesh = init_hybrid_mesh(dp=1, pp=1, tp=1, devices=jax.devices()[:1],
                            set_global=False).mesh
    step_counters().clear()
    with mesh:
        step, init = M.make_train_step(cfg, mesh)
        state = init(FAMILY.seed_key(seed))
        state["params"] = FAMILY.make_params(model, seed)
        prog = {"losses": []}
        for i in range(3):
            toks, labels = train.host_batch(seed, i, B, T,
                                            model["vocab_size"])
            state, loss = step(state, {"tokens": jnp.asarray(toks),
                                       "labels": jnp.asarray(labels)})
            prog["losses"].append(float(loss))
            if i == 0:
                prog["grad"] = {
                    n.replace("params.", "", 1): v
                    for n, v in train.leaf_norms(
                        train._find_mu(state["opt"]),
                        scale=1.0 / (1.0 - opt["b1"])).items()}
            if i == 1:
                prog["change"] = train.leaf_norms(
                    state["params"], minus=FAMILY.make_params(model, seed))
    assert set(prog["grad"]) == set(ref["grad"])
    assert {n.split(".")[0] for n in ref["grad"]} == {
        "embed", "final_norm", "lm_head", "dense_layers", "layers"}
    checks = Checks()
    got = train.compare_training(
        prog, ref, {"loss_gap": [2e-5, 2e-5, 2e-5], "grad_norm_gap": 1e-3,
                    "change_norm_gap": 5e-3}, checks)
    assert all(checks), (checks.compared, got["grad_leaf_gaps"])
    # one record a step, the pairs of two expert layers
    jax.effects_barrier()
    counts = step_counters().totals("train")
    assert counts["steps"] == 3
    assert (counts["train_moe_pairs_held"] + counts["train_moe_pairs_absent"]
            == 3 * 2 * B * T * model["num_experts_per_tok"])
    assert counts["train_moe_bound_fallbacks"] == 0


def test_a_lower_precision_control_fails_the_tiny_limits():
    """The reference with both operands of every linear layer at 3
    mantissa bits (the control) is not the float32 reference: the first
    loss alone moves by more than the tiny limits allow."""
    ref = reference_run()
    low = reference_run(FAMILY.CONTROL_ROUND_TO)
    assert abs(low["losses"][0] - ref["losses"][0]) > 1e-3
    assert reference.worst_leaf_gap(low["grad"], ref["grad"]) > 1e-2


def test_the_step_is_one_chip_s():
    cfg = M.JoyAIFlashConfig.tiny()
    mesh = init_hybrid_mesh(dp=2, pp=1, tp=1, devices=jax.devices()[:2],
                            set_global=False).mesh
    with pytest.raises(NotImplementedError, match="one chip's share"):
        M.make_train_step(cfg, mesh)
