"""Verified jaxpr rewrite passes (analysis/rewrite.py).

Mutation-test discipline, mirroring the lint passes: every rewrite has
a seeded graph it MUST fire on, mutated graphs it must NOT fire on
(wrong quantization scheme, non-exclusive intermediates, wrong
reduction), and an idempotence check (re-running the rewriter on
rewritten output is a no-op). The verifier itself is mutation-tested —
a deliberately wrong replacement must be rejected. Exactness pins:
greedy outputs through a ``ServingEngine(rewrites=True)`` are
byte-identical to the unrewritten engine, and a differentiated
(train-step-shaped) loss through ``rewrite_callable`` matches lockstep
numerics within the declared tolerance.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.analysis.framework import (ExactnessContract,
                                           REWRITE_REGISTRY, Severity)
from paddle_tpu.analysis.rewrite import (DecodeTailFusePass,
                                         FusedRmsNormPass,
                                         Int8EpilogueFusePass,
                                         count_matches, rewrite_jaxpr,
                                         rewrite_callable,
                                         run_rewrite_suite,
                                         verify_rewrite)
from paddle_tpu.analysis.rewrite_conv import (ConvBnFoldPass,
                                              ConvNhwcLayoutPass,
                                              StemSpaceToDepthPass)
from paddle_tpu.models import llama as L


# ---------------------------------------------------------------------------
# seeded graphs
# ---------------------------------------------------------------------------

def _unfused_int8(x, q, scale):
    """The naive dequantize-then-matmul idiom the epilogue rewrite
    exists to eliminate."""
    w = (q.astype(jnp.float32) * scale[None, :]).astype(x.dtype)
    return jnp.matmul(x, w)


def _int8_args(m=4, k=16, n=8, dtype=jnp.bfloat16):
    r = np.random.RandomState(0)
    x = jnp.asarray(r.standard_normal((m, k)), dtype)
    q = jnp.asarray(r.randint(-127, 128, (k, n)), jnp.int8)
    s = jnp.asarray(np.abs(r.standard_normal(n)) * 0.02 + 1e-3,
                    jnp.float32)
    return x, q, s


def _rms(x, w, eps=1e-5):
    """The jnp rmsnorm formulation (models/llama.py rms_norm)."""
    return L.rms_norm(x, w, eps)


def _rms_args(rows=8, d=16, dtype=jnp.bfloat16):
    r = np.random.RandomState(1)
    x = jnp.asarray(r.standard_normal((rows, d)), dtype)
    w = jnp.asarray(r.standard_normal(d), jnp.float32)
    return x, w


# ---------------------------------------------------------------------------
# int8-epilogue-fuse: fire / no-fire / idempotence / contract
# ---------------------------------------------------------------------------

def test_int8_fires_on_seeded_unfused_graph():
    x, q, s = _int8_args()
    closed = jax.make_jaxpr(_unfused_int8)(x, q, s)
    res = rewrite_jaxpr(closed, retrace=True)
    assert res.fired.get("int8-epilogue-fuse") == 1
    assert res.idempotent is True
    out = verify_rewrite(res)
    assert out.ok, out
    assert out.sites == 1


def test_int8_rewritten_matches_fused_impl_exactly():
    # the replacement IS the hand-fused path: the rewriter reproduces
    # ops/fused/int8_matmul.int8_weight_matmul bit for bit
    from paddle_tpu.ops.fused.int8_matmul import int8_weight_matmul
    x, q, s = _int8_args()
    res = rewrite_jaxpr(jax.make_jaxpr(_unfused_int8)(x, q, s))
    (got,) = res.fn_flat(x, q, s)
    want = int8_weight_matmul(x, q, s, impl="jnp")
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


def test_int8_must_not_fire_per_input_channel_scale():
    # a [in]-scale broadcast over the CONTRACTING dim is a different
    # quantization scheme — the epilogue cannot represent it. Square
    # weight so the 1-D shape check alone cannot distinguish.
    def per_input(x, q, scale):
        w = (q.astype(jnp.float32) * scale[:, None]).astype(x.dtype)
        return jnp.matmul(x, w)

    r = np.random.RandomState(0)
    x = jnp.asarray(r.standard_normal((4, 16)), jnp.bfloat16)
    q = jnp.asarray(r.randint(-127, 128, (16, 16)), jnp.int8)
    s = jnp.asarray(np.abs(r.standard_normal(16)) + 0.01, jnp.float32)
    fired = count_matches(jax.make_jaxpr(per_input)(x, q, s))
    assert not fired.get("int8-epilogue-fuse")


def test_int8_must_not_fire_when_dense_weight_escapes():
    # the dequantized weight is ALSO a graph output: deleting its
    # producer would break the other consumer (exclusivity)
    def leaky(x, q, scale):
        w = (q.astype(jnp.float32) * scale[None, :]).astype(x.dtype)
        return jnp.matmul(x, w), w

    x, q, s = _int8_args()
    fired = count_matches(jax.make_jaxpr(leaky)(x, q, s))
    assert not fired.get("int8-epilogue-fuse")


def test_int8_must_not_fire_on_non_int8_weight():
    x, q, s = _int8_args()
    q16 = q.astype(jnp.int16)
    fired = count_matches(jax.make_jaxpr(_unfused_int8)(x, q16, s))
    assert not fired.get("int8-epilogue-fuse")


def test_int8_must_not_fire_on_batched_dot():
    # 3-D stacked weights (layer-scanned): per-call-site 2-D only
    def batched(x, q, scale):
        w = (q.astype(jnp.float32) * scale[None, None, :]).astype(x.dtype)
        return jnp.einsum("bik,bkn->bin", x, w)

    r = np.random.RandomState(0)
    x = jnp.asarray(r.standard_normal((2, 4, 16)), jnp.bfloat16)
    q = jnp.asarray(r.randint(-127, 128, (2, 16, 8)), jnp.int8)
    s = jnp.asarray(np.abs(r.standard_normal(8)) + 0.01, jnp.float32)
    fired = count_matches(jax.make_jaxpr(batched)(x, q, s))
    assert not fired.get("int8-epilogue-fuse")


# ---------------------------------------------------------------------------
# fused-rmsnorm: fire / no-fire / idempotence / contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rms_fires_on_both_spellings(dtype):
    x, w = _rms_args(dtype=dtype)
    res = rewrite_jaxpr(jax.make_jaxpr(_rms)(x, w), retrace=True)
    assert res.fired.get("fused-rmsnorm") == 1
    assert res.idempotent is True


def test_rms_within_declared_ulp_on_seeded_graph():
    # the kernel performs the same f32 reductions in the same
    # association; only compiler clustering (FMA contraction, reduction
    # tiling) across the fused body can round differently — the
    # declared contract is ulp<=4 (measured worst case over a
    # 420-config sweep; flagship shapes measure 2), and the verifier
    # enforces it per matched site
    x, w = _rms_args(dtype=jnp.bfloat16)
    res = rewrite_jaxpr(jax.make_jaxpr(_rms)(x, w))
    out = verify_rewrite(res)
    assert out.ok and out.mode == "ulp<=4", out


def test_rms_must_not_fire_wrong_denominator():
    # dividing the square-sum by anything but the normalized axis size
    # is not an rmsnorm
    def not_mean(x, w, eps=1e-5):
        xf = x.astype(jnp.float32)
        v = jnp.sum(xf * xf, axis=-1, keepdims=True) / (x.shape[-1] + 1)
        y = xf * jax.lax.rsqrt(v + eps)
        return (y * w.astype(jnp.float32)).astype(x.dtype)

    x, w = _rms_args()
    fired = count_matches(jax.make_jaxpr(not_mean)(x, w))
    assert not fired.get("fused-rmsnorm")


def test_rms_must_not_fire_on_cross_product():
    # mean(x*y) is not a square — the same-value constraint on the
    # mul's operands must hold
    def crossed(x, y, w, eps=1e-5):
        xf = x.astype(jnp.float32)
        yf = y.astype(jnp.float32)
        v = jnp.mean(xf * yf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(v + eps)
        return (out * w.astype(jnp.float32)).astype(x.dtype)

    x, w = _rms_args()
    y = x + 1
    fired = count_matches(jax.make_jaxpr(crossed)(x, y, w))
    assert not fired.get("fused-rmsnorm")


def test_rms_must_not_fire_when_rstd_escapes():
    def leaky(x, w, eps=1e-5):
        xf = x.astype(jnp.float32)
        rstd = jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (xf * rstd * w.astype(jnp.float32)).astype(x.dtype), rstd

    x, w = _rms_args()
    fired = count_matches(jax.make_jaxpr(leaky)(x, w))
    assert not fired.get("fused-rmsnorm")


def test_rms_fires_inside_scan_body():
    def scanned(x, w):
        def body(c, _):
            return _rms(c, w), None
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    x, w = _rms_args(dtype=jnp.float32)
    closed = jax.make_jaxpr(scanned)(x, w)
    assert count_matches(closed).get("fused-rmsnorm") == 1
    res = rewrite_jaxpr(closed)
    (got,) = res.fn_flat(x, w)
    (want,) = [scanned(x, w)]
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64),
                               rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# machinery: the verifier and the match gate are themselves tested
# ---------------------------------------------------------------------------

class _WrongEpsRms(FusedRmsNormPass):
    """Seeded defect: same pattern, numerically wrong replacement."""

    def build(self, statics):
        from paddle_tpu.ops.pallas.fused_norm_rope import fused_rms_norm
        return lambda x, w: fused_rms_norm(x, w, 0.25)  # wrong eps


class _WrongDtypeRms(FusedRmsNormPass):
    """Seeded defect: replacement changes the anchor's dtype."""

    def build(self, statics):
        inner = FusedRmsNormPass.build(self, statics)
        # f16, not f64: x64 is disabled suite-wide, a float64 astype
        # silently truncates back to f32 and would not change the aval
        return lambda x, w: inner(x, w).astype(jnp.float16)


def test_verifier_rejects_numerically_wrong_replacement():
    x, w = _rms_args(dtype=jnp.bfloat16)
    bad = _WrongEpsRms()
    res = rewrite_jaxpr(jax.make_jaxpr(_rms)(x, w), rules=[bad])
    assert res.fired.get("fused-rmsnorm") == 1
    out = verify_rewrite(res, rules=[bad])
    assert not out.ok
    assert "ulp" in out.mode


def test_aval_changing_replacement_cannot_match():
    x, w = _rms_args(dtype=jnp.bfloat16)
    fired = count_matches(jax.make_jaxpr(_rms)(x, w),
                          rules=[_WrongDtypeRms()])
    assert not fired.get("fused-rmsnorm")


def test_contracts_are_declared():
    # registry sanity: both concrete rewrites exist with the documented
    # contracts (ulp-pinned kernel substitution vs pinned-tolerance
    # reassociation)
    assert REWRITE_REGISTRY["fused-rmsnorm"] is FusedRmsNormPass
    assert REWRITE_REGISTRY["int8-epilogue-fuse"] is Int8EpilogueFusePass
    assert FusedRmsNormPass.contract.ulp == 4
    c = Int8EpilogueFusePass.contract
    assert not c.bitwise and c.rtol > 0 and c.atol > 0
    assert ExactnessContract(bitwise=True).describe() == "bitwise"
    assert ExactnessContract(ulp=1).describe() == "ulp<=1"


def test_suite_errors_when_expected_rewrite_missing():
    # the vacuous-pass guard: a target whose meta expects a rewrite
    # that cannot fire must produce an ERROR finding
    from paddle_tpu.analysis.framework import GraphTarget
    x, w = _rms_args()
    target = GraphTarget(name="seeded.no-int8",
                         jaxpr=jax.make_jaxpr(_rms)(x, w),
                         meta={"expect_rewrites": ("int8-epilogue-fuse",)})
    findings, _ = run_rewrite_suite(targets=[target], verify=False)
    errs = [f for f in findings if f.severity == Severity.ERROR]
    assert errs and "int8-epilogue-fuse" in errs[0].message


# ---------------------------------------------------------------------------
# flagship suite (what graph_lint --suite rewrite runs)
# ---------------------------------------------------------------------------

def test_flagship_rewrite_suite_clean():
    findings, table = run_rewrite_suite(models=("llama",))
    errs = [f for f in findings if f.severity == Severity.ERROR]
    assert not errs, [str(f) for f in errs]
    by_graph = {row["graph"]: row for row in table}
    int8 = by_graph["llama.serving_tick[int8-unfused]"]
    # every projection in the 2-layer tick dequantizes unfused: q/k/v/o
    # + gate/up/down per layer land on the stacked per-layer weights
    # (scan body counts once) + lm_head
    assert int8["fired"]["int8-epilogue-fuse"] >= 2
    assert int8["fired"]["fused-rmsnorm"] >= 1
    assert int8["idempotent"] is True
    assert int8["verify"]["ok"] is True
    for row in table:
        assert row["verify"]["ok"], row
        assert row["idempotent"] is True, row


# ---------------------------------------------------------------------------
# exactness pins
# ---------------------------------------------------------------------------

def test_engine_rewrites_greedy_outputs_bitwise_equal():
    """ServingEngine(rewrites=True) greedy outputs are byte-identical
    to the unrewritten engine AND to generate()."""
    from paddle_tpu.serving.engine import ServingEngine

    cfg = L.LlamaConfig.tiny(dtype=jnp.float32,
                             use_flash_attention=False, remat=False)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 9, 12)]

    def run(**kw):
        with ServingEngine(params, cfg, max_batch=4, page_size=4,
                           max_prompt_len=16, max_new_tokens_cap=8,
                           **kw) as eng:
            hs = [eng.submit(p, 8) for p in prompts]
            return [tuple(np.asarray(h.result(timeout=300)).tolist())
                    for h in hs]

    base = run(rewrites=False)
    rewritten = run(rewrites=True)
    assert base == rewritten
    ref = [tuple(np.asarray(L.generate(
        params, p[None, :], cfg, max_new_tokens=8))[0, len(p):].tolist())
        for p in prompts]
    assert rewritten == ref


def test_rewritten_train_numerics_within_declared_tolerance():
    """A differentiated loss through rewrite_callable (fused-rmsnorm
    substituted, custom-VJP backward) matches the unrewritten lockstep
    numerics within the declared tolerance over 3 SGD steps."""
    cfg = L.LlamaConfig.tiny(dtype=jnp.float32,
                             use_flash_attention=False, remat=False)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size, dtype=jnp.int32)

    def loss_fn(params, tokens):
        logits = L.forward(params, tokens, cfg).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits[:, :-1])
        tgt = tokens[:, 1:]
        return -jnp.mean(jnp.take_along_axis(lp, tgt[..., None], -1))

    assert count_matches(
        jax.make_jaxpr(loss_fn)(params, toks)).get("fused-rmsnorm")

    vg_base = jax.jit(jax.value_and_grad(loss_fn))
    vg_rw = jax.jit(jax.value_and_grad(rewrite_callable(loss_fn)))

    def steps(vg, params, n=3, lr=0.1):
        losses = []
        for _ in range(n):
            loss, g = vg(params, toks)
            params = jax.tree_util.tree_map(
                lambda p, gg: p - lr * gg, params, g)
            losses.append(float(loss))
        return losses, params

    base_losses, base_params = steps(vg_base, params)
    rw_losses, rw_params = steps(vg_rw, params)
    # declared tolerance: the substituted kernel's backward is the
    # analytic rmsnorm VJP (same math, different association than jax
    # AD of the jnp formulation) — f32 lockstep agreement to ~1e-5
    np.testing.assert_allclose(rw_losses, base_losses, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(base_params),
                    jax.tree_util.tree_leaves(rw_params)):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64),
                                   rtol=1e-3, atol=1e-5)


# ---------------------------------------------------------------------------
# conv passes (rewrite_conv.py): fire / no-fire / idempotence / contracts
# ---------------------------------------------------------------------------

def _conv(x, w, strides=(1, 1), padding=((1, 1), (1, 1))):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=strides, padding=padding,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _bn_infer(y, g, b, m, v, eps=1e-5, shape=(1, -1, 1, 1)):
    """The inference-BN eqn chain the fold pattern targets (what
    nn.BatchNorm2D traces to in eval mode)."""
    return ((y - m.reshape(shape)) * jax.lax.rsqrt(v.reshape(shape) + eps)
            * g.reshape(shape) + b.reshape(shape))


def _conv_bn_args(cout=4, cin=3, image=6, k=3):
    r = np.random.RandomState(2)
    x = jnp.asarray(r.standard_normal((2, cin, image, image)), jnp.float32)
    w = jnp.asarray(r.standard_normal((cout, cin, k, k)) * 0.1,
                    jnp.float32)
    g, b, m = (jnp.asarray(r.standard_normal(cout), jnp.float32)
               for _ in range(3))
    v = jnp.asarray(np.abs(r.standard_normal(cout)) + 0.5, jnp.float32)
    return x, w, g, b, m, v


def test_conv_bn_fold_fires_verifies_idempotent():
    rules = [ConvBnFoldPass()]
    for relu in (True, False):   # both anchor spellings
        def f(x, w, g, b, m, v):
            out = _bn_infer(_conv(x, w), g, b, m, v)
            return jax.nn.relu(out) if relu else out
        cj = jax.make_jaxpr(f)(*_conv_bn_args())
        res = rewrite_jaxpr(cj, rules=rules, retrace=True)
        assert res.fired.get("conv-bn-fold") == 1, relu
        assert res.idempotent, res.residual
        vo = verify_rewrite(res, rules=rules)
        assert vo.ok, vo


def test_conv_bn_fold_must_not_fire_when_conv_escapes():
    # the conv output is also a graph output — folding would change it
    def f(x, w, g, b, m, v):
        y = _conv(x, w)
        return jax.nn.relu(_bn_infer(y, g, b, m, v)), y
    assert not count_matches(jax.make_jaxpr(f)(*_conv_bn_args()),
                             rules=[ConvBnFoldPass()])


def test_conv_bn_fold_must_not_fire_wrong_axis_bn():
    # channels-LAST stats ([1,1,1,C]) on a channels-first conv: it
    # broadcasts (image == cout) but normalises the wrong axis
    def f(x, w, g, b, m, v):
        return _bn_infer(_conv(x, w), g, b, m, v, shape=(1, 1, 1, 4))
    assert not count_matches(jax.make_jaxpr(f)(*_conv_bn_args(image=4)),
                             rules=[ConvBnFoldPass()])


def test_conv_bn_fold_must_not_fire_on_batch_stats():
    # train-mode BN: the stats are reductions OF the conv output, which
    # therefore escapes the match — the no-fire is structural
    def f(x, w, g, b, m, v):
        y = _conv(x, w)
        return jax.nn.relu(_bn_infer(y, g, b, y.mean(axis=(0, 2, 3)),
                                     y.var(axis=(0, 2, 3))))
    assert not count_matches(jax.make_jaxpr(f)(*_conv_bn_args()),
                             rules=[ConvBnFoldPass()])


def _stem_args(cin=3, image=8):
    r = np.random.RandomState(3)
    x = jnp.asarray(r.standard_normal((1, cin, image, image)),
                    jnp.float32)
    w = jnp.asarray(r.standard_normal((4, cin, 7, 7)) * 0.1, jnp.float32)
    return x, w


def test_stem_s2d_fires_verifies_idempotent():
    def f(x, w):
        return _conv(x, w, strides=(2, 2), padding=((3, 3), (3, 3)))
    rules = [StemSpaceToDepthPass()]
    cj = jax.make_jaxpr(f)(*_stem_args())
    res = rewrite_jaxpr(cj, rules=rules, retrace=True)
    assert res.fired.get("stem-space-to-depth") == 1
    assert res.idempotent, res.residual
    assert verify_rewrite(res, rules=rules).ok


def test_stem_s2d_must_not_fire_off_stem_shapes():
    def f(x, w):
        return _conv(x, w, strides=(2, 2), padding=((3, 3), (3, 3)))
    rules = [StemSpaceToDepthPass()]
    # 4 input channels: not the RGB stem
    assert not count_matches(jax.make_jaxpr(f)(*_stem_args(cin=4)),
                             rules=rules)
    # odd image: the 2x2 phase split does not exist
    assert not count_matches(jax.make_jaxpr(f)(*_stem_args(image=7)),
                             rules=rules)


def test_layout_pass_fires_on_any_nchw_conv():
    rules = [ConvNhwcLayoutPass()]
    cj = jax.make_jaxpr(_conv)(*_conv_bn_args()[:2])
    res = rewrite_jaxpr(cj, rules=rules, retrace=True)
    assert res.fired.get("conv-nhwc-layout") == 1
    # the rewritten conv is NHWC — the NCHW pattern can never re-fire
    assert res.idempotent, res.residual
    assert verify_rewrite(res, rules=rules).ok


# ---------------------------------------------------------------------------
# decode-tail-fuse: fire / no-fire / exactness
# ---------------------------------------------------------------------------

def _tail_args(rows=6, d=16, vocab=32):
    r = np.random.RandomState(4)
    x = jnp.asarray(r.standard_normal((rows, d)), jnp.bfloat16)
    w = jnp.asarray(r.standard_normal(d), jnp.float32)
    idx = jnp.asarray([1, 4], jnp.int32)
    head = jnp.asarray(r.standard_normal((d, vocab)), jnp.bfloat16)
    return x, w, idx, head


def test_decode_tail_fires_and_is_exact_on_seeded_graph():
    def f(x, w, idx, head):
        h = L.rms_norm(x, w, 1e-5)
        return (h[idx] @ head).astype(jnp.float32)
    rules = [DecodeTailFusePass()]
    cj = jax.make_jaxpr(f)(*_tail_args())
    res = rewrite_jaxpr(cj, rules=rules, retrace=True)
    assert res.fired.get("decode-tail-fuse") == 1
    assert res.idempotent, res.residual
    vo = verify_rewrite(res, rules=rules)
    # dtype mirroring (dot in head.dtype, like the matched graph) makes
    # the substitution drift-free on the seeded sites — not just within
    # the 1e-3 pin
    assert vo.ok and vo.max_abs == 0.0, vo


def test_decode_tail_must_not_fire_when_rows_escape():
    def f(x, w, idx, head):
        h = L.rms_norm(x, w, 1e-5)
        rows = h[idx]
        return (rows @ head).astype(jnp.float32), rows
    assert not count_matches(jax.make_jaxpr(f)(*_tail_args()),
                             rules=[DecodeTailFusePass()])


def test_decode_tail_must_not_fire_on_column_gather():
    def f(x, w, idx, head):
        h = L.rms_norm(x, w, 1e-5)
        return (h[:, idx].T @ head).astype(jnp.float32)
    x, w, idx, _ = _tail_args()
    r = np.random.RandomState(5)
    head = jnp.asarray(r.standard_normal((x.shape[0], 8)), jnp.bfloat16)
    assert not count_matches(jax.make_jaxpr(f)(x, w, idx, head),
                             rules=[DecodeTailFusePass()])


def test_new_pass_contracts_pinned():
    # the measured pins documented in each pass docstring — a contract
    # loosened (or tightened past the measurement) without re-measuring
    # should fail here
    assert REWRITE_REGISTRY["conv-bn-fold"] is ConvBnFoldPass
    assert REWRITE_REGISTRY["stem-space-to-depth"] is StemSpaceToDepthPass
    assert REWRITE_REGISTRY["conv-nhwc-layout"] is ConvNhwcLayoutPass
    assert REWRITE_REGISTRY["decode-tail-fuse"] is DecodeTailFusePass
    c = ConvBnFoldPass.contract
    assert (c.rtol, c.atol) == (5e-2, 1e-3) and not c.bitwise
    for cls in (StemSpaceToDepthPass, ConvNhwcLayoutPass):
        assert (cls.contract.rtol, cls.contract.atol) == (5e-2, 2e-2)
    c = DecodeTailFusePass.contract
    assert (c.rtol, c.atol) == (1e-3, 1e-3)
    # the tail swallows the rms core, so it must outrank the plain
    # substitution — and the fold must outrank stem/layout
    assert DecodeTailFusePass.priority < FusedRmsNormPass.priority
    assert (ConvBnFoldPass.priority < StemSpaceToDepthPass.priority
            < ConvNhwcLayoutPass.priority)


# ---------------------------------------------------------------------------
# source_lint host-sync rules (the satellite's own mutation tests)
# ---------------------------------------------------------------------------

def test_source_lint_host_sync_rules_fire():
    from paddle_tpu.analysis.source_lint import lint_file
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n\n\n"
        "def f(x):\n"
        "    y = jax.device_get(x)\n"
        "    x.block_until_ready()\n"
        "    return y, float(jnp.max(x)), bool(jnp.isfinite(x).all())\n"
    )
    rules = sorted(r for r, _, _ in lint_file("fake.py", src=src,
                                              host_sync_scope=True))
    assert rules == ["PT001", "PT002", "PT003", "PT003"]
    # tools/tests scope: the same source is clean
    assert not [r for r, _, _ in lint_file("fake.py", src=src,
                                           host_sync_scope=False)
                if r.startswith("PT")]


def test_source_lint_host_sync_noqa_suppresses():
    from paddle_tpu.analysis.source_lint import lint_file
    src = (
        "import jax.numpy as jnp\n\n\n"
        "def sync():\n"
        "    jnp.zeros(()).block_until_ready()  # noqa: PT002 — api\n"
        "    return float(jnp.zeros(()))  # noqa: PT003\n"
    )
    assert not [r for r, _, _ in lint_file("fake.py", src=src,
                                           host_sync_scope=True)
                if r.startswith("PT")]


def test_source_lint_pt004_table_width_vmem_scratch():
    """PT004 (r16): a Pallas kernel allocating VMEM scratch that
    scales with pages_per_slot flags — the CI guard that the
    long-context ceiling cannot silently regress — while noqa'd
    (explicitly one-shot) and O(tile) shapes stay clean, and the rule
    only runs in pallas scope."""
    from paddle_tpu.analysis.source_lint import lint_file
    bad = (
        "from jax.experimental.pallas import tpu as pltpu\n\n\n"
        "def shapes(pps, page_size, dh, tile, dt):\n"
        "    return [pltpu.VMEM((pps, page_size, dh), dt),\n"
        "            pltpu.VMEM((2, tile, page_size, dh), dt)]\n"
    )
    hits = [r for r, _, _ in lint_file("fake.py", src=bad,
                                       pallas_scope=True)
            if r == "PT004"]
    assert hits == ["PT004"]        # the O(tile) shape did not flag
    assert not [r for r, _, _ in lint_file("fake.py", src=bad)
                if r == "PT004"]    # non-pallas scope: rule off
    ok = (
        "from jax.experimental.pallas import tpu as pltpu\n\n\n"
        "def shapes(pps, page_size, dh, dt):\n"
        "    return pltpu.VMEM((pps, page_size, dh), dt)"
        "  # noqa: PT004 — one-shot by design\n"
    )
    assert not [r for r, _, _ in lint_file("fake.py", src=ok,
                                           pallas_scope=True)
                if r == "PT004"]


def test_source_lint_pt005_serving_host_sync():
    """PT005 (ISSUE 13 satellite): host-sync idioms inside the serving
    hot paths flag — `.item()` and the bare single-arg `np.asarray`
    device-pull shape — while dtype'd container conversions, noqa'd
    sanctioned pull sites, and non-serving scope stay clean."""
    from paddle_tpu.analysis.source_lint import lint_file
    src = (
        "import numpy as np\n\n\n"
        "def tick(toks_d, host_list):\n"
        "    n = toks_d.sum().item()\n"
        "    toks = np.asarray(toks_d)\n"
        "    also = np.array(toks_d)\n"
        "    ok = np.asarray(host_list, np.int32)\n"
        "    ok2 = np.array(host_list, np.int32)\n"
        "    return n, toks, also, ok, ok2\n"
    )
    hits = [r for r, _, _ in lint_file("fake.py", src=src,
                                       serving_scope=True)
            if r == "PT005"]
    assert hits == ["PT005"] * 3  # dtype'd conversions did not flag
    assert not [r for r, _, _ in lint_file("fake.py", src=src)
                if r == "PT005"]       # non-serving scope: rule off
    noqa = (
        "import numpy as np\n\n\n"
        "def tick(toks_d):\n"
        "    return np.asarray(toks_d)"
        "  # noqa: PT005 - the sanctioned pull\n"
    )
    assert not [r for r, _, _ in lint_file("fake.py", src=noqa,
                                           serving_scope=True)
                if r == "PT005"]
    # the live serving tree staying clean (engine read-backs noqa'd
    # with justifications) is covered by
    # test_library_tree_is_clean_of_host_syncs below


def test_source_lint_conservative_on_locals():
    # coercions of locals it cannot prove jax-rooted do not flag
    from paddle_tpu.analysis.source_lint import lint_file
    src = (
        "import numpy as np\n\n\n"
        "def f(diff, eps):\n"
        "    return float(np.max(diff)), float(eps), bool(diff.any())\n"
    )
    assert not [r for r, _, _ in lint_file("fake.py", src=src,
                                           host_sync_scope=True)
                if r.startswith("PT")]


def test_library_tree_is_clean_of_host_syncs():
    import os
    from paddle_tpu.analysis.source_lint import lint_tree
    root = os.path.join(os.path.dirname(__file__), "..")
    hits = [h for h in lint_tree(root) if h[1].startswith("PT")]
    assert not hits, hits
