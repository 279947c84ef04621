"""Kernels: the least time the chip could take for the latent attention
of the whole ticks in the traced span, over the device's self time under
``attn.mla.core`` there: the absorb and up-project einsums AND the
kernel (labels ``attn.mla.core`` + ``attn.mla.core.kernel``), so that a
program that expands the context and one that absorbs the up-projection
are read against the same work.

The least time of a tick is the longer of two, summed over the ticks:

* BYTES: every context token its launches attend (``kv_tokens`` of the
  tick's ``serving.tick`` annotation, fused steps counted) x attention
  sublayers x the PUBLISHED latent row (``kv_lora_rank +
  qk_rope_head_dim`` values x 2 B: 1152 B, whatever the pool pads it
  to), over the peak HBM bandwidth;
* FLOPs of the LESSER form for the tick's (query token, key) pairs
  (``attn_pairs``: ``q_len x (kv_len - (q_len - 1) / 2)`` a slot):
  ABSORBED, every pair a ``kv_lora_rank + rope`` score and a
  ``kv_lora_rank`` value dot a head; or EXPANDED, ``kv_b`` applied to
  every context token once a sublayer (``kv_lora_rank x heads x (nope +
  v)``) and then ``nope + rope`` and ``v`` a pair a head; x sublayers,
  over the peak bf16 rate. Decode rows are cheaper absorbed, a long
  span over a short context expanded: the lesser is taken a TICK.

Returns None where the annotations carry no ``attn_pairs`` (a program
from before PR 40) or the configuration has no latent attention."""
from harness import hostspans as H
from harness import trace as T
from harness.common import trace_dir
from harness.readers import peaks

LABELS = ("attn.mla.core", "attn.mla.core.kernel")


def sublayers(model: dict) -> int:
    """Attention sublayers: two a layer."""
    return 2 * model["num_layers"]


def row_bytes(model: dict, itemsize: int = 2) -> int:
    """The published latent row a token leaves a sublayer."""
    return (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * itemsize


def absorbed_flops(model: dict, pairs: float) -> float:
    H_, c, r = (model["num_attention_heads"], model["kv_lora_rank"],
                model["qk_rope_head_dim"])
    return pairs * H_ * (2 * c + r) * 2


def expanded_flops(model: dict, pairs: float, kv_tokens: float) -> float:
    H_, c = model["num_attention_heads"], model["kv_lora_rank"]
    nope, r, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    return (kv_tokens * c * H_ * (nope + v) * 2
            + pairs * H_ * (nope + r + v) * 2)


def least_seconds(model: dict, ticks, peak: dict) -> float:
    """Over ``ticks`` (``[(start, end, stats)]``): per tick the longer
    of the bytes' time and the lesser form's FLOPs' time."""
    n = sublayers(model)
    total = 0.0
    for _, _, st in ticks:
        kv, pairs = float(st.get("kv_tokens", 0)), float(st["attn_pairs"])
        flops = min(absorbed_flops(model, pairs),
                    expanded_flops(model, pairs, kv))
        total += n * max(kv * row_bytes(model) / peak["hbm_bytes_per_s"],
                         flops / peak["bf16_flops"])
    return total


def whole_ticks(ctx):
    """The whole ticks of the run's trace with their stats, read once a
    run; None where there is no trace."""
    if "mla_ticks" not in ctx:
        try:
            annotations, device, _ = H.read_xplane(
                T.find_xplane(trace_dir()))
        except (FileNotFoundError, OSError):
            annotations, device = [], []
        ticks = None
        if device:
            ticks = H.whole_ticks(annotations, (
                min(s for _, s, _, _ in device),
                max(e for _, _, e, _ in device)))
        ctx["mla_ticks"] = ticks
    return ctx["mla_ticks"]


def read(ctx):
    hs = H.load(ctx)
    model = ctx["model"]
    if not hs or "kv_lora_rank" not in model:
        return None
    ticks = whole_ticks(ctx)
    if not ticks or any("attn_pairs" not in st for _, _, st in ticks):
        return None
    spent = sum(hs["tick_by_label"].get(k, 0) for k in LABELS) / 1e9
    if not spent:
        return None
    peak = peaks(ctx["devices"][0].device_kind)
    return 100.0 * least_seconds(model, ticks, peak) / spent
