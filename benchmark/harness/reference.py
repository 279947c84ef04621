"""The plain reference: float32 ``jax.numpy``, written from the
published descriptions, no kernel, no cache, no batching.

It imports nothing of the program. Weights come from the benchmark's own
``families/<family>.py:make_params`` (made from ``--seed``); a layer's
weights are cast to float32 one layer at a time, so the reference fits
beside what is being measured. Every matmul runs under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul
is otherwise done in bfloat16 passes.

``round_to`` is the CONTROL: the same mathematics with both operands of
every linear layer's matmul (weights and the activations that meet
them) rounded to ``round_to`` mantissa bits first — 3 for a bfloat16
configuration, float8 e4m3's, with the exponent left wide, which is
scaled fp8 at its best and so the mildest control. It is the step that
would tempt a later PR. ``lax.reduce_precision`` does the rounding,
explicitly: a control built on a round trip through ``astype(float8)``
read 0.0000 in the serving cell on the chip (my chip run, PR 24), most
likely folded away by the compiler as excess precision. It is never
part of a benchmark run's ``correct``; ``control.py`` and the tests
under ``tests/`` read it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rp(x, round_to):
    return jax.lax.reduce_precision(x.astype(F32), exponent_bits=8,
                                    mantissa_bits=int(round_to))


def _w(x, round_to=None):
    """A weight as float32; a matrix optionally at fewer mantissa bits."""
    if round_to is not None and x.ndim >= 2:
        return _rp(x, round_to)
    return x.astype(F32)


def _a(x, round_to=None):
    """The activation operand of a linear layer's matmul."""
    return x if round_to is None else _rp(x, round_to)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotary(x, positions, theta):
    """Half-split rotary embedding on ``[T, H, Dh]`` (the published
    ``rotate_half`` form)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = positions.astype(F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v):
    """``q [T, H, Dh]``, ``k/v [T, Hkv, Dh]`` -> ``[T, H*Dh]``; grouped
    queries share a key/value head; scores and softmax in float32. One
    key/value head at a time (and rematerialised under a gradient), so
    that a 2048-square of scores for 32 heads is never held at once."""
    T, H, Dh = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    mask = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def one(args):
        qh, kh, vh = args                      # [T, g, Dh], [T, Dh], [T, Dh]
        s = jnp.einsum("tgd,sd->gts", qh, kh) / np.sqrt(Dh)
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sd->tgd", p, vh)

    out = jax.lax.map(one, (q.reshape(T, Hkv, g, Dh).transpose(1, 0, 2, 3),
                            k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3).reshape(T, H * Dh)


def attention_block(lp, h, positions, m, round_to):
    T = h.shape[0]
    H, Hkv, Dh = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    x = _a(rms_norm(h, _w(lp["attn_norm"]), m["rms_norm_eps"]), round_to)
    q = (x @ _w(lp["wq"], round_to)).reshape(T, H, Dh)
    k = (x @ _w(lp["wk"], round_to)).reshape(T, Hkv, Dh)
    v = (x @ _w(lp["wv"], round_to)).reshape(T, Hkv, Dh)
    q = rotary(q, positions, m["rope_theta"])
    k = rotary(k, positions, m["rope_theta"])
    return h + _a(causal_attention(q, k, v), round_to) @ _w(lp["wo"],
                                                             round_to)


def swiglu(x, gate, up, down, round_to=None):
    x = _a(x, round_to)
    return _a(jax.nn.silu(x @ gate) * (x @ up), round_to) @ down


def dense_layer(lp, h, positions, m, round_to=None):
    h = attention_block(lp, h, positions, m, round_to)
    x = rms_norm(h, _w(lp["mlp_norm"]), m["rms_norm_eps"])
    return h + swiglu(x, _w(lp["w_gate"], round_to),
                      _w(lp["w_up"], round_to), _w(lp["w_down"], round_to),
                      round_to)


def moe_layer(lp, h, positions, m, round_to=None):
    """Qwen2-MoE block as published: softmax router over all experts,
    the top-k experts' SwiGLU outputs weighted by their softmax
    probabilities (renormalised only where ``norm_topk_prob``), DROPLESS
    (every token reaches its k experts), plus the shared expert scaled
    by a sigmoid gate. One expert at a time over all rows, masked: slow
    and plain."""
    h = attention_block(lp, h, positions, m, round_to)
    x = rms_norm(h, _w(lp["mlp_norm"]), m["rms_norm_eps"])
    E, k = m["num_experts"], m["num_experts_per_tok"]
    probs = jax.nn.softmax(x @ lp["router"].astype(F32), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    if m.get("norm_topk_prob"):
        top_p = top_p / top_p.sum(-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], top_i].set(top_p)    # [T, E]
    ex = lp["experts"]

    def one(acc, xs):
        g, u, d, w_e = xs
        y = swiglu(x, _w(g, round_to), _w(u, round_to), _w(d, round_to),
                   round_to)
        return acc + y * w_e[:, None], None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (ex["w_gate"], ex["w_up"], ex["w_down"], weight.T))
    sh = lp["shared"]
    shared = swiglu(x, _w(sh["w_gate"], round_to), _w(sh["w_up"], round_to),
                    _w(sh["w_down"], round_to), round_to)
    shared = shared * jax.nn.sigmoid(x @ _w(sh["gate"], round_to))
    return h + routed + shared


LAYER_FNS = {"dense": dense_layer, "moe": moe_layer}


def layer_groups(params, model: dict, family) -> list:
    """The model's layers as the reference walks them: ``[(layer_fn,
    stacked_params, prefix)]`` in order. A family that brings its own
    layers defines ``reference_layers(params, model)`` returning
    ``[(layer_fn, stacked_params), ...]``, each ``layer_fn(lp, h,
    positions, m, round_to) -> h`` in plain float32 ``jax.numpy`` and
    each stack on a leading layer axis; a third element names where the
    stack sits in the program's pytree when that is not ``layers`` (two
    stacks of different shapes cannot share one). A family without it
    is one stack of the kind its ``REFERENCE_KIND`` names."""
    own = getattr(family, "reference_layers", None)
    groups = (own(params, model) if own else
              [(LAYER_FNS[family.REFERENCE_KIND], params["layers"])])
    return [(g[0], g[1], g[2] if len(g) > 2 else "layers") for g in groups]


def _unstacked(groups, cast=None):
    """``(layer_fn, prefix, one layer's params)`` for every layer of
    every group, in order."""
    for layer_fn, stack, prefix in groups:
        for i in range(jax.tree_util.tree_leaves(stack)[0].shape[0]):
            yield layer_fn, prefix, jax.tree_util.tree_map(
                lambda a: a[i] if cast is None else a[i].astype(cast), stack)


def _leaf_name(prefix, path) -> str:
    return prefix + "." + ".".join(str(getattr(k, "key", k)) for k in path)


@partial(jax.jit, static_argnames=("layer_fn", "model", "round_to"))
def _layer_jit(lp, h, positions, *, layer_fn, model, round_to):
    with jax.default_matmul_precision("highest"):
        return layer_fn(lp, h, positions, dict(model), round_to)


@partial(jax.jit, static_argnames=("eps", "round_to"))
def _head_jit(h, final_norm, lm_head, *, eps, round_to):
    with jax.default_matmul_precision("highest"):
        x = _a(rms_norm(h, _w(final_norm), eps), round_to)
        return x @ _w(lm_head, round_to)


def _static_model(model: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float, bool))))


def hidden_states(params, tokens, model: dict, family, round_to=None):
    """Final-layer hidden states ``[T, D]`` (before the last norm) of
    one sequence, layer by layer over the family's ``layer_groups``."""
    tokens = jnp.asarray(tokens, jnp.int32)
    h = params["embed"][tokens].astype(F32)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    static = _static_model(model)
    for layer_fn, _, lp in _unstacked(layer_groups(params, model, family)):
        h = _layer_jit(lp, h, positions, layer_fn=layer_fn, model=static,
                       round_to=round_to)
    return h


def logits_at(params, h, rows, model: dict, round_to=None):
    """Float32 logits ``[len(rows), V]`` at the given rows of ``h``."""
    return _head_jit(h[jnp.asarray(rows)], params["final_norm"],
                     params["lm_head"], eps=model["rms_norm_eps"],
                     round_to=round_to)


def pad_to(n: int, quantum: int = 256) -> int:
    return -(-n // quantum) * quantum


def served_gaps(params, prompt, served, model: dict, family,
                control_round_to=None, pad_len: int | None = None):
    """Teacher-forced over ``prompt + served``: at every served position
    the gap by which the served token's reference logit lies below the
    reference's best. Returns ``(gaps [n], control_gaps [n] | None)``;
    the control's gaps are those of the token a reference computed with
    weights rounded to ``control_round_to`` would put first.

    The sequence is right-padded to ``pad_len`` (the cell's longest
    sequence: ONE program serves every request and stays in the compile
    cache) or else to a multiple of 256. Causal: padding changes no
    earlier row."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    p, n = prompt.size, served.size
    seq = np.concatenate([prompt, served[:-1]])
    padded = np.zeros((max(pad_len or 0, pad_to(seq.size)),), np.int32)
    padded[:seq.size] = seq
    rows = np.arange(p - 1, p - 1 + n)
    ref = logits_at(params, hidden_states(params, padded, model, family),
                    rows, model)
    best = ref.max(-1)
    gaps = np.asarray(best - ref[jnp.arange(n), jnp.asarray(served)])
    if control_round_to is None:
        return gaps, None
    low = logits_at(params, hidden_states(params, padded, model, family,
                                          control_round_to),
                    rows, model, control_round_to)
    first = jnp.argmax(low, -1)
    return gaps, np.asarray(best - ref[jnp.arange(n), first])


# ------------------------------------------------------------ training ----
# The plain training reference: float32 forward, backward and AdamW, one
# layer at a time so that it fits one chip beside nothing else (it runs
# once the program's state is freed). Two full steps and the third
# step's loss: three steps of float32 AdamW state (params, mu, nu at
# 4 B each) do not fit 16 GB at the depth the program trains, so after
# step 1 only its gradient is kept (mu1 and nu1 follow from it) and the
# second step is the last one applied. PERF.md says so.

def _batched(layer_fn, lp, h, m, round_to):
    pos = jnp.arange(h.shape[1], dtype=jnp.int32)
    # one row of the batch at a time, rematerialised in the backward
    # pass: the same mathematics, a fraction of the memory
    row = jax.checkpoint(lambda x: layer_fn(lp, x, pos, m, round_to))
    return jax.lax.map(row, h)


@partial(jax.jit, static_argnames=("layer_fn", "model", "round_to"))
def _layer_fwd(lp, h, *, layer_fn, model, round_to):
    with jax.default_matmul_precision("highest"):
        return _batched(layer_fn, lp, h, dict(model), round_to)


@partial(jax.jit, static_argnames=("layer_fn", "model", "round_to"))
def _layer_bwd(lp, h, dh_out, *, layer_fn, model, round_to):
    with jax.default_matmul_precision("highest"):
        _, vjp = jax.vjp(
            lambda a, b: _batched(layer_fn, a, b, dict(model), round_to),
            lp, h)
        return vjp(dh_out)


@partial(jax.jit, static_argnames=("eps", "round_to"))
def _head_loss(h, final_norm, lm_head, labels, *, eps, round_to):
    """Mean next-token cross entropy and its gradients w.r.t. the last
    hidden states, the final norm and the head."""
    def f(h, fn, lm):
        def row(args):
            x, y = args
            logits = _a(rms_norm(x, fn, eps), round_to) @ _w(lm, round_to)
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        return jax.lax.map(row, (h, labels)).mean()
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(f, argnums=(0, 1, 2))(h, final_norm,
                                                        lm_head)


@partial(jax.jit, static_argnames=("t", "opt"), donate_argnums=(0,))
def _adamw(p, g, g_prev, *, t, opt):
    """AdamW step ``t`` (1 or 2) on one leaf; at ``t == 2`` the first
    step's moments follow from its gradient ``g_prev``."""
    o = dict(opt)
    b1, b2 = o["b1"], o["b2"]
    mu = (1 - b1) * g
    nu = (1 - b2) * g * g
    if t == 2:
        mu = b1 * (1 - b1) * g_prev + mu
        nu = b2 * (1 - b2) * g_prev * g_prev + nu
    mhat = mu / (1 - b1 ** t)
    vhat = nu / (1 - b2 ** t)
    return p - o["lr"] * (mhat / (jnp.sqrt(vhat) + o["eps"])
                          + o["weight_decay"] * p)


def _sq(x):
    return float(jnp.sum(jnp.square(x.astype(F32))))


class TrainReference:
    """``params``: the benchmark's own seeded weights (bfloat16 values),
    consumed: cast to float32 leaf by leaf. ``step(tokens, labels)``
    applies AdamW steps 1 and 2; ``loss(tokens, labels)`` is a forward
    pass. Walks the family's ``layer_groups`` one layer at a time and
    collects, per stacked leaf name (``layers.wq`` ...: the program's
    pytree paths), the first gradient's squared norm."""

    def __init__(self, params, model: dict, family, opt: dict,
                 round_to=None):
        self.m, self.family, self.round_to = model, family, round_to
        self.static = _static_model(model)
        self.opt = tuple(sorted(opt.items()))
        # [layer_fn, prefix, this layer's float32 params], in order
        self.layers = [list(x) for x in _unstacked(
            layer_groups(params, model, family), F32)]
        self.top = {k: params[k].astype(F32)
                    for k in ("embed", "final_norm", "lm_head")}
        self.t = 0
        self.g1_layers = [None] * len(self.layers)
        self.g1_top = {}
        self.grad_sq = {}

    # ------------------------------------------------------------------
    def _forward(self, tokens):
        h = self.top["embed"][tokens]
        hs = []
        for layer_fn, _, lp in self.layers:
            hs.append(h)
            h = _layer_fwd(lp, h, layer_fn=layer_fn, model=self.static,
                           round_to=self.round_to)
        return h, hs

    def loss(self, tokens, labels) -> float:
        h, _ = self._forward(jnp.asarray(tokens))
        val, _ = _head_loss(h, self.top["final_norm"], self.top["lm_head"],
                            jnp.asarray(labels), eps=self.m["rms_norm_eps"],
                            round_to=self.round_to)
        return float(val)

    def _note(self, name, g):
        if self.t == 1:
            self.grad_sq[name] = self.grad_sq.get(name, 0.0) + _sq(g)

    def _update(self, p, g, g_prev):
        return _adamw(p, g, g if g_prev is None else g_prev, t=self.t,
                      opt=self.opt)

    def step(self, tokens, labels) -> float:
        self.t += 1
        assert self.t <= 2, "the reference applies two steps"
        tokens, labels = jnp.asarray(tokens), jnp.asarray(labels)
        h, hs = self._forward(tokens)
        val, (dh, d_fn, d_lm) = _head_loss(
            h, self.top["final_norm"], self.top["lm_head"], labels,
            eps=self.m["rms_norm_eps"], round_to=self.round_to)
        for name, g in (("final_norm", d_fn), ("lm_head", d_lm)):
            self._note(name, g)
            self.top[name] = self._update(self.top[name], g,
                                          self.g1_top.get(name))
            self.g1_top[name] = g if self.t == 1 else None
        del d_fn, d_lm, h
        for i in reversed(range(len(self.layers))):
            layer_fn, prefix, lp = self.layers[i]
            dlp, dh = _layer_bwd(lp, hs.pop(), dh, layer_fn=layer_fn,
                                 model=self.static, round_to=self.round_to)
            prev = self.g1_layers[i]
            for path, g in jax.tree_util.tree_flatten_with_path(dlp)[0]:
                self._note(_leaf_name(prefix, path), g)
            self.layers[i][2] = jax.tree_util.tree_map(
                lambda p, g, gp: self._update(p, g, gp), lp,
                dlp, dlp if prev is None else prev)
            self.g1_layers[i] = dlp if self.t == 1 else None
        d_emb = jnp.zeros_like(self.top["embed"]).at[tokens].add(dh)
        self._note("embed", d_emb)
        self.top["embed"] = self._update(self.top["embed"], d_emb,
                                         self.g1_top.get("embed"))
        self.g1_top["embed"] = d_emb if self.t == 1 else None
        return float(val)

    def grad_norms(self) -> dict:
        return {k: float(np.sqrt(v)) for k, v in self.grad_sq.items()}

    def change_norms(self, params0) -> dict:
        """``|p_now - p0|`` per stacked leaf name; ``params0`` is the
        seeded weights made anew."""
        out = {k: float(np.sqrt(_sq(self.top[k] - params0[k].astype(F32))))
               for k in self.top}
        tot = {}
        for (_, prefix, lp), (_, _, lp0) in zip(
                self.layers, _unstacked(layer_groups(params0, self.m,
                                                     self.family))):
            for (path, a), b in zip(
                    jax.tree_util.tree_flatten_with_path(lp)[0],
                    jax.tree_util.tree_leaves(lp0)):
                name = _leaf_name(prefix, path)
                tot[name] = tot.get(name, 0.0) + _sq(a - b.astype(F32))
        out.update({k: float(np.sqrt(v)) for k, v in tot.items()})
        return out


def leaf_gaps(prog: dict, ref: dict) -> dict:
    """Per leaf, the gap between the program's norm and the
    reference's, measured against the reference's norm of that leaf or
    of the median leaf, whichever is larger."""
    med = float(np.median(list(ref.values())))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in ref}


def worst_leaf_gap(prog: dict, ref: dict) -> float:
    return max(leaf_gaps(prog, ref).values())
