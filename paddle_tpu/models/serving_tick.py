"""The serving tick every family shares: the embedding, the final norm,
the head, the fused sampler, the verify pass and the fused decode tail,
once, around a family's layer walk. The continuous-batching engine
(``paddle_tpu/serving/``) calls a model through the two functions here,
once per tick against a persistent cache pytree, and learns everything
else about it from ONE record (``models/layer_walk.py: ServingFamily``,
a family module's ``SERVING``): ``family.init_pages`` builds the cache,
``serving_tick`` runs one ragged tick over it and ``serving_tick_block``
a fused block of decode ticks. (``generate_paged``, by contrast, builds
its cache fresh per batch and fuses its decode loop into one scan.)
Pages are allocated per REQUEST by the host-side PagePool
(serving/scheduler.py) and freed the moment a sequence retires, so a
long generation never holds cache capacity hostage for the whole batch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .layer_walk import COUNTS
from .llama import _mm, rms_norm

# why a family whose layers keep more than pages has no verify pass, by
# what the kind keeps (``LayerKind.cache``)
_NO_VERIFY = {
    "slot_rows": "no speculative verify for a model with per-slot state: "
                 "a rejected draft's state cannot be rolled back",
    "window_pages": "no speculative verify for a model with window rings: "
                    "a rejected draft's rows have overwritten the ring"}


def _fused_sample(logits, temp, top_p, top_k, key, idx):
    """In-graph per-row sampling head of the serving tick (r16): the
    generalization of the fused argmax that lets SAMPLING requests
    ride the same programs as greedy ones. Greedy rows (temp == 0)
    take ``jnp.argmax`` — BITWISE the pre-r16 fused path, so every
    greedy==generate() pin survives; sampling rows apply temperature →
    top-k → top-p masking (``sample_logits`` semantics, but per-row
    DATA instead of static kwargs) and draw one gumbel/categorical
    token.

    Determinism discipline: the draw for a slot's token at
    continuation index ``idx[s]`` uses ``fold_in(key[s], idx[s])`` —
    the token INDEX keys the draw, not a split chain advanced per
    device step. A fixed seed therefore emits one token stream
    whatever the batch composition, fused-block boundaries or
    speculation around it: tokens a fused block computed past EOS, or
    drafts a verify rejected, burn no key state — the next launch
    re-draws the same index with the same key.

    logits ``[S, V]`` f32; temp/top_p ``[S]`` f32; top_k ``[S]`` i32
    (0 = filter off); key ``[S, 2]`` u32 raw per-slot PRNG keys; idx
    ``[S]`` i32. Returns ``[S]`` i32.

    Cost discipline: the whole sampling branch (sort, cumsum,
    categorical) sits behind a ``lax.cond`` on ``any(temp > 0)`` —
    still ONE program (the predicate is data), but an all-greedy tick
    executes only the argmax at runtime, so folding sampling into
    every program does not tax greedy traffic (measured: the sort is
    the dominant cost on the CPU mesh)."""
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _draw(_):
        l = logits / jnp.maximum(temp, 1e-6)[:, None]
        # top-k with k as data: cutoff at the k-th largest (k=0/off ->
        # the smallest value, masking nothing; ties at the cutoff
        # survive, matching sample_logits)
        srt = jnp.sort(l, axis=-1)[:, ::-1]
        k_eff = jnp.where(top_k > 0, jnp.minimum(top_k, V), V)
        kth = jnp.take_along_axis(srt, (k_eff - 1)[:, None], axis=-1)
        # top-p over the top-k-masked logits (sample_logits order).
        # ONE sort suffices: the masked row's descending sort is the
        # original sort with sub-cutoff positions replaced (ties at
        # the cutoff survive masking in both views). The top-1 token
        # is always kept so top_p=0 degrades to greedy, and cutoff is
        # the SMALLEST kept logit.
        srt2 = jnp.where(srt >= kth, srt, -1e30)
        probs = jax.nn.softmax(srt2, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < top_p[:, None]
        keep = keep.at[:, 0].set(True)
        cutoff = jnp.min(jnp.where(keep, srt2, jnp.inf), axis=-1)
        masked = jnp.where(l < kth, -1e30, l)
        masked = jnp.where(masked < cutoff[:, None], -1e30, masked)

        def draw(k, n, row):
            return jax.random.categorical(jax.random.fold_in(k, n), row)

        return jax.vmap(draw)(key, idx, masked).astype(jnp.int32)

    sampled = jax.lax.cond(jnp.any(temp > 0.0), _draw,
                           lambda _: greedy, None)
    return jnp.where(temp <= 0.0, greedy, sampled)


def _counted(cache, family):
    """``cache`` with the family's counts at zero under ``COUNTS``."""
    return {**cache, COUNTS: jnp.zeros((len(family.counters),), jnp.int32)}


def serving_tick(params, tokens, meta, cache, cfg, family, *, tq: int = 1,
                 decode_tail: int = 0, spec_k: int = 0,
                 attn_impl: str = "auto"):
    """ONE ragged serving tick over a model's whole cache pytree: any mix
    of chunked prefills, warm-prefix attaches and decode steps as a
    single static program. Sequence geometry rides in ``meta`` as DEVICE
    ARRAYS, so XLA compiles exactly one program per packed width:
    prompt length, chunk position and attached-prefix size are data.

    This is how the engine calls every model: the embedding, the final
    norm, the head, the fused sampler, the verify pass and the fused
    decode tail are here, once; the layers are ``family.walk``'s
    (``family``: the model's ``ServingFamily``). ``cache`` is what
    ``family.init_pages`` built and is DONATED by the engine — its page
    pools (``k_pages`` / ``v_pages`` ``[L_attn, Hkv, P, ps, Dh]``, or
    what the family declares: ``family.tick_pool`` names one whose
    second-to-last axis is the page's tokens, which is all this function
    reads of it), and whatever else its layer kinds keep (a fixed row a
    slot, ...); the new cache is the last result.

    A family with ``counters`` hands their counts over the tick's
    launches back too, ``counts [n]`` i32, as the result just before
    ``cur_tok'`` (before the cache where the meta has no ``cur_tok``):
    they ride the cache under ``COUNTS`` from zeros here, through every
    walk (the fused tail's included), and leave it here; the engine adds
    them to its counters when the tick completes (no pull of their own).

    TWO SCALARS OF THE CONFIG, read with ``getattr`` as trace-time
    facts: ``embedding_multiplier`` (the embedding lookup times it) and
    ``logits_scaling`` (the float32 logits over it, before the sampler
    and the verify pass). A config without them, or with 1.0, emits no
    operation (``models/granite_hybrid.py`` sets both).

    tokens ``[T]`` i32 — the tick's packed token stream: each live
    slot's current decode token and/or a span of some prompt's next
    uncached tokens, concatenated (padding tokens allowed anywhere).
    meta — a dict of device arrays describing the packing:

    * ``tok_slot [T]``: owning slot of each packed token (``S`` = a
      padding token that must touch nothing real);
    * ``tok_pos [T]``: the token's absolute sequence position;
    * ``tok_page [T]`` / ``tok_off [T]``: the page id and in-page
      offset its KV lands at (TRASH page for padding);
    * ``tok_qoff [T]``: offset of the token inside its slot's span;
    * ``q_len [S]``: span length per slot (0 = slot idle this tick);
    * ``kv_len [S]``: keys visible at the END of the span (context +
      the span itself);
    * ``last [S]``: packed index of each slot's LAST span token — its
      hidden state feeds that slot's logits row (idle slots may point
      anywhere; their row is junk the host discards);
    * ``tables [S, pps]``: the page-table rows.

    THE SLOTS' CURRENT TOKENS — optional ``meta['cur_tok'] [S]`` i32 (a
    trace-time fact, like the sampling state; the engine ALWAYS passes
    it): the token each slot produced last, kept on the device from one
    tick to the next, so the host need not read tick N's tokens back
    before it launches tick N+1. A packed token ``< 0`` takes its value
    from ``cur_tok[tok_slot]`` in-graph (a decode row, the first token
    of a drafted span; prompt-span tokens come from the host as they
    are), and the successor is returned just before the cache: for
    every slot that produced a token this tick (``meta['tail_live']``:
    a decode row, a span completing its prompt) its LAST token (the
    last fused tail step's; with ``spec_k`` the bonus/correction token
    ``toks[s, accept[s]]``), for every other slot the old value.

    FUSED SAMPLING — five more optional meta arrays, all DATA, turn
    every token selection in the tick (last-position pick, fused tail
    steps, speculative verify) into a per-slot temperature/top-k/top-p
    gumbel draw via ``_fused_sample``: ``temp [S]`` f32 / ``top_p [S]``
    f32 / ``top_k [S]`` i32 (0 = off) / ``key [S, 2]`` u32 raw per-slot
    PRNG keys / ``produced [S]`` i32 — the continuation index of the
    token this launch emits; token ``n`` is always drawn with
    ``fold_in(key, n)``, so a fixed seed yields one stream whatever the
    batch composition, block fusion or speculation (see
    ``_fused_sample``). Greedy rows (temp == 0) keep the bitwise
    argmax. The engine ALWAYS passes these (presence is a trace-time
    fact): sampling slots ride the same programs as greedy ones.

    THREE MODES, chosen by two STATIC arguments (one compile per value):

    * plain (``decode_tail == spec_k == 0``): the ragged pass alone;
    * ``decode_tail`` fuses that many extra decode steps after the
      ragged pass — the multi-step scheduling lever that keeps an
      admission tick producing a full decode block for in-flight
      streams, in the SAME program. ``meta['tail_live'] [S]`` bool
      gates it: only tail-live slots (decoding slots, plus spans that
      complete their prompt this tick) advance — mid-prefill slots stay
      dead through the tail (q_len 0, KV writes to the trash page);
    * ``spec_k`` (the engine's draft-length cap; a speculative engine
      uses exactly one) turns the tick into the speculative VERIFY
      program: speculating slots submitted their current token plus up
      to ``spec_k`` draft tokens as an ordinary ragged span (the same
      packed stream, mixed with prefill spans and plain decode slots),
      and the tick additionally computes the target model's token at
      EVERY span position plus the in-graph longest-prefix acceptance
      against the drafts. Three extra ``meta`` arrays carry the
      (per-slot, DATA-not-shape) speculation geometry: ``ver_idx [S,
      1+spec_k]``, the packed index of each slot's span token ``j``
      (position ``j``'s hidden state predicts span position ``j+1``;
      non-speculating slots point every entry at their ``last`` token,
      so their row 0 reproduces the plain tick's logits exactly), and
      ``draft_tok [S, spec_k]`` / ``draft_len [S]``, the draft tokens
      and each slot's actual draft count ``k_s <= spec_k`` (0 for
      non-speculating slots — adaptive k is data, the cap is the only
      shape). ``spec_k`` and ``decode_tail`` are mutually exclusive
      (speculation IS the multi-token lever on a speculative engine).

    ``tq`` (STATIC; the engine passes the span width of the tick's entry
    in its width grid) is the maximum span length, sizing the kernel's
    slot-major query layout.

    Returns ``(toks, logits [S, V] f32, cache')`` (with ``cur_tok``:
    ``(toks, logits, cur_tok', cache')``; with ``spec_k`` too:
    ``(toks, accept, logits, cur_tok', cache')``): ``toks`` is each
    slot's in-graph token pick at its last position (argmax, or the
    fused sampler's draw) — ``[S]`` i32 when ``decode_tail == 0``, else
    ``[S, 1+decode_tail]`` (the host pulls only these ints); ``logits``
    is the RAGGED pass's (first step's) logits, kept for callers that
    sample their own way — the engine never reads it, it stays on
    device and is dropped. With ``spec_k > 0`` the return is ``(toks
    [S, 1+spec_k], accept [S], logits [S, V] f32, cache')``: ``toks[s,
    j]`` is the target's token after consuming span tokens ``0..j``,
    ``accept[s]`` the number of leading drafts matching it (``toks[s,
    :accept[s]]`` equal the drafts token-for-token and ``toks[s,
    accept[s]]`` is the bonus/correction token — ``1 + accept`` emitted
    tokens from ONE target launch), and ``logits`` is row 0's logits.
    Rejected draft KV needs no device-side rollback: the stale rows sit
    past the slot's advanced length, masked by ``kv_len`` until the
    sequence's real tokens overwrite them positionally — the same
    trash-row discipline retiring overruns already rely on.

    Exactness: the span's KV is scattered into the pages FIRST, then
    the ragged kernel attends over pages only, bottom-right causal —
    so a prefix's KV is a function of the prefix tokens alone and
    chunked/whole/warm prefills all produce the bits a single
    whole-prompt pass would (tests pin greedy equality to
    ``generate()`` in every cache state).
    """
    tq = int(tq)
    spec_k = int(spec_k)
    decode_tail = int(decode_tail)
    if spec_k and decode_tail:
        raise ValueError("spec_k and decode_tail are mutually "
                         "exclusive (speculation replaces the "
                         "fused greedy tail)")
    if spec_k:
        for kind in family.kinds(cfg):
            if kind.cache != "pages":
                raise ValueError(_NO_VERIFY[kind.cache])
    # the counts are this call's to start and to hand back, unless a
    # caller (the block, a tail step) already carries them
    counted = bool(family.counters) and COUNTS not in cache
    if counted:
        cache = _counted(cache, family)
    S = meta["q_len"].shape[0]
    cur = meta.get("cur_tok")
    with jax.named_scope("embed"):
        if cur is not None:
            tokens = jnp.where(
                tokens < 0, cur[jnp.minimum(meta["tok_slot"], S - 1)],
                tokens)
        h = params["embed"].astype(cfg.dtype)[tokens[None]]    # [1, T, D]
        # a family that scales its embedding (trace-time facts of the
        # config, like the divisor of the logits below: 1.0 emits
        # nothing)
        if getattr(cfg, "embedding_multiplier", 1.0) != 1.0:
            h = h * jnp.asarray(cfg.embedding_multiplier, h.dtype)
    logits_scaling = float(getattr(cfg, "logits_scaling", 1.0))
    h, cache_new = family.walk(params, h, cache, meta, cfg, tq, attn_impl)
    with jax.named_scope("lm_head"):
        h = rms_norm(h[0], params["final_norm"], cfg.rms_norm_eps)  # [T, D]
    # fused sampling (r16): when the meta carries per-slot sampling
    # state — temp/top_p [S] f32, top_k [S] i32, key [S, 2] u32 raw
    # PRNG keys, produced [S] i32 (the continuation index of the token
    # this launch emits) — every token selection below goes through
    # _fused_sample instead of bare argmax, so SAMPLING slots ride the
    # same program as greedy ones (the engine always passes the
    # fields; presence is a trace-time fact, not a per-tick branch).
    # Greedy rows still take the bitwise argmax path inside.
    samp = "temp" in meta

    def pick(logits, idx):
        if samp:
            return _fused_sample(logits, meta["temp"], meta["top_p"],
                                 meta["top_k"], meta["key"], idx)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def verify(logits_ver):
        """The verify pass's token at every span position and the
        longest accepted draft prefix, ``(toks [S, 1+spec_k],
        accept [S])``."""
        if samp:
            # SAMPLED acceptance (spec_k is no longer greedy-only):
            # span position j draws the token for continuation index
            # produced+j — the same fold_in key a plain tick would
            # use at that index, and conditioning over the accepted
            # prefix is exact by construction, so the emitted stream
            # is bitwise the non-speculative engine's whatever the
            # drafter proposed. Greedy slots still argmax (temp==0).
            kk = 1 + spec_k
            idx = (meta["produced"][:, None]
                   + jnp.arange(kk, dtype=jnp.int32)[None]).reshape(-1)
            toks = _fused_sample(
                logits_ver.reshape(S * kk, -1),
                jnp.repeat(meta["temp"], kk),
                jnp.repeat(meta["top_p"], kk),
                jnp.repeat(meta["top_k"], kk),
                jnp.repeat(meta["key"], kk, axis=0),
                idx).reshape(S, kk)
        else:
            toks = jnp.argmax(logits_ver, axis=-1).astype(jnp.int32)
        # longest-prefix acceptance: draft j is accepted iff every
        # draft 0..j matched the target's token (sampled or argmax) at
        # its span position (cumprod zeroes everything after the first
        # mismatch) and j is a real draft (j < draft_len — adaptive k
        # is data)
        j = jnp.arange(spec_k)
        match = ((toks[:, :spec_k] == meta["draft_tok"])
                 & (j[None, :] < meta["draft_len"][:, None]))
        accept = jnp.cumprod(match.astype(jnp.int32), axis=1) \
                    .sum(axis=1).astype(jnp.int32)
        return toks, accept

    def result(last, *rest):
        """The tick's results; with ``cur_tok`` its successor (``last``
        ``[S]`` where the slot produced a token) before the cache, the
        family's counts before either."""
        new = cache_new
        if counted:
            new = dict(new)
            rest = (*rest, new.pop(COUNTS))
        if cur is None:
            return (*rest, new)
        with jax.named_scope("sampler"):
            nxt = jnp.where(meta["tail_live"], last, cur)
        return (*rest, nxt, new)

    if spec_k:
        # logits at EVERY span position of every slot — the verify
        # pass's whole point: one launch prices 1+spec_k predictions
        with jax.named_scope("lm_head"):
            h_ver = h[meta["ver_idx"]]              # [S, 1+spec_k, D]
            logits_ver = _mm(h_ver, params["lm_head"]).astype(jnp.float32)
            if logits_scaling != 1.0:
                logits_ver = logits_ver / logits_scaling
        with jax.named_scope("sampler"):
            toks, accept = verify(logits_ver)
        # row 0 == the plain tick's logits for every non-speculating
        # slot (ver_idx[:, 0] = last there)
        return result(toks[jnp.arange(S), accept], toks, accept,
                      logits_ver[:, 0])
    with jax.named_scope("lm_head"):
        h_last = h[meta["last"]]                                # [S, D]
        logits = _mm(h_last, params["lm_head"]).astype(jnp.float32)
        if logits_scaling != 1.0:
            logits = logits / logits_scaling
    with jax.named_scope("sampler"):
        toks = pick(logits, meta["produced"] if samp else None)
    if not decode_tail:
        return result(toks, toks, logits)

    ps = cache[family.tick_pool].shape[-2]
    pps = meta["tables"].shape[1]
    b_idx = jnp.arange(S, dtype=jnp.int32)
    zeros = jnp.zeros((S,), jnp.int32)
    live = meta["tail_live"].astype(jnp.bool_)

    def step(carry, _):
        tok, lens, idx, cache_t = carry
        slot = lens // ps
        # rows out of pages (retiring overruns), dead all-TRASH rows
        # and tail-dead (mid-prefill) slots land on the trash page
        # (page 0, offset 0), which nothing reads
        ok = live & (slot < pps)
        page = jnp.where(
            ok, meta["tables"][b_idx, jnp.minimum(slot, pps - 1)], 0)
        m = dict(tok_slot=jnp.where(live, b_idx, S).astype(jnp.int32),
                 tok_pos=lens, tok_page=page.astype(jnp.int32),
                 tok_off=jnp.where(ok, lens % ps, 0).astype(jnp.int32),
                 tok_qoff=zeros, q_len=live.astype(jnp.int32),
                 kv_len=lens + 1, last=b_idx, tables=meta["tables"])
        if samp:
            # step j of the tail samples continuation index
            # produced + j: the fold_in discipline, not a split chain
            m.update(temp=meta["temp"], top_p=meta["top_p"],
                     top_k=meta["top_k"], key=meta["key"],
                     produced=idx)
        nxt, _, cache_t = serving_tick(
            params, tok, m, cache_t, cfg, family, tq=1, attn_impl=attn_impl)
        return (nxt, lens + 1, idx + 1, cache_t), nxt

    idx0 = (meta["produced"] + 1) if samp else zeros
    (_, _, _, cache_new), tail = lax.scan(
        step, (toks, meta["kv_len"], idx0, cache_new), None,
        length=decode_tail)
    toks = jnp.concatenate([toks[:, None], jnp.moveaxis(tail, 0, 1)],
                           axis=1)                    # [S, 1+tail]
    return result(toks[:, -1], toks, logits)


def serving_tick_block(params, tok, lengths, tables, cache, cfg, family,
                       num_steps: int, *, attn_impl: str = "auto",
                       sampling=None):
    """``num_steps`` fused decode ticks built on the ragged tick (the
    multi-step scheduling lever: per-call dispatch + host bookkeeping
    amortize over the block) over a model's whole cache pytree and its
    ``family``'s walk (see ``serving_tick``). Greedy slots are
    in-graph argmax and match single-step decode exactly. tok/lengths
    ``[S]`` i32, tables ``[S, pps]``. ``tok`` is the slots' current
    tokens as the engine keeps them on the device (``meta['cur_tok']``
    of the tick), and its successor is returned: a live slot's last
    token of the block, a dead slot's old value.

    A slot with ``lengths == 0`` (free, or admitted and not yet
    prefilled) is DEAD to the block: it enters the tick with no query
    row (``q_len`` 0, the slot sentinel for its token), attends
    nothing, writes to the trash page, and its returned tokens mean
    nothing. The host truncates a sequence's tokens at
    EOS/max_new_tokens; positions a retiring sequence wrote past its
    pages land on the trash page, so neighbours never see them.

    ``sampling``: a dict of the fused-sampling meta arrays —
    ``temp``/``top_p`` f32 [S], ``top_k`` i32 [S], ``key`` u32 [S, 2],
    ``produced`` i32 [S] — letting SAMPLING slots ride the fused block
    too (step ``j`` draws continuation index ``produced + j`` via the
    fold_in discipline); None keeps the all-greedy block. Returns
    ``(toks [S, num_steps] i32, tok' [S] i32, cache')``, a family with
    ``counters`` ``(toks, counts [n] i32, tok', cache')``."""
    if family.counters:
        cache = _counted(cache, family)
    S = tok.shape[0]
    pps = tables.shape[1]
    ps = cache[family.tick_pool].shape[-2]
    b_idx = jnp.arange(S, dtype=jnp.int32)
    slot = lengths // ps
    # a slot that holds no context (free, or admitted and not yet
    # prefilled: the scheduler keeps its length 0) is DEAD to the step,
    # as a tail-dead slot is to the tail's: no query row, so the ragged
    # kernel's walk skips it, where a row of its own would walk the
    # trash page in every layer
    live = lengths > 0
    # rows out of pages (retiring overruns) and dead all-TRASH rows
    # land on the trash page (page 0, offset 0), which nothing reads
    ok = live & (slot < pps)
    page = jnp.where(ok, tables[b_idx, jnp.minimum(slot, pps - 1)], 0)
    meta = dict(tok_slot=jnp.where(live, b_idx, S).astype(jnp.int32),
                tok_pos=lengths, tok_page=page,
                tok_off=jnp.where(ok, lengths % ps, 0),
                tok_qoff=jnp.zeros((S,), jnp.int32),
                q_len=live.astype(jnp.int32), kv_len=lengths + 1,
                last=b_idx, tables=tables, tail_live=live, cur_tok=tok)
    if sampling is not None:
        meta.update(temp=sampling["temp"], top_p=sampling["top_p"],
                    top_k=sampling["top_k"], key=sampling["key"],
                    produced=sampling["produced"])
    toks, _, nxt, cache = serving_tick(
        params, tok, meta, cache, cfg, family, tq=1,
        decode_tail=num_steps - 1, attn_impl=attn_impl)
    if num_steps == 1:
        toks = toks[:, None]
    if not family.counters:
        return toks, nxt, cache
    cache = dict(cache)
    return toks, cache.pop(COUNTS), nxt, cache
