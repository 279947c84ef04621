"""Kernels: the least time the chip could take for the FULL attention
layers' launches of the whole ticks in the traced span, over the
kernel's self time under ``attn.full`` there (label
``attn.full.kernel``).

The least time of a tick is the longer of two, summed over the ticks:

* BYTES: every context token its launches attend (``kv_tokens`` of the
  tick's ``serving.tick`` annotation, fused steps counted) x full layers
  x ``num_key_value_heads`` x the PUBLISHED K and V row (``head_dim +
  v_head_dim`` values x 2 B: 640 B a head, whatever the pool pads a key
  row to), over the peak HBM bandwidth;
* FLOPs: the tick's (query token, key) pairs (``attn_pairs``) x full
  layers x ``num_attention_heads`` x (``head_dim`` for the score +
  ``v_head_dim`` for the value) x 2, over the peak bf16 rate.

Decode rows are bound by the bytes, a 512-row span by the arithmetic;
the program walks a span in blocks of 16 tokens and re-reads its context
once a block, which the least time does not count: so a span tick reads
under what its arithmetic alone would give. ``kind_layers`` /
``least_seconds`` are shared with ``window_attn_roofline_pct``. Returns
None where the annotations carry no ``attn_pairs`` or the configuration
has no ``hybrid_layer_pattern``."""
from harness import hostspans as H
from harness import trace as T
from harness.common import trace_dir
from harness.readers import peaks

LABEL = "attn.full.kernel"


def kind_layers(model: dict, window: bool) -> int:
    """Layers of one attention kind (``hybrid_layer_pattern``: 1 a
    window layer)."""
    return sum(bool(w) == window for w in model["hybrid_layer_pattern"][
        :model["num_hidden_layers"]])


def kv_heads(model: dict, window: bool) -> int:
    return model["swa_num_key_value_heads" if window
                 else "num_key_value_heads"]


def token_bytes(model: dict, window: bool, itemsize: int = 2) -> int:
    """Published bytes of K and V a token leaves ONE layer of the
    kind."""
    return (kv_heads(model, window)
            * (model["head_dim"] + model["v_head_dim"]) * itemsize)


def pair_flops(model: dict) -> int:
    """FLOPs of one (query token, key) pair over a layer's heads."""
    return (model["num_attention_heads"]
            * (model["head_dim"] + model["v_head_dim"]) * 2)


def least_seconds(model: dict, ticks, peak: dict, window: bool) -> float:
    """Over ``ticks`` (``[(start, end, stats)]``): per tick the longer
    of the bytes' time and the FLOPs' time of the kind's layers."""
    tokens, pairs = (("window_kv_tokens", "window_attn_pairs") if window
                     else ("kv_tokens", "attn_pairs"))
    n = kind_layers(model, window)
    total = 0.0
    for _, _, st in ticks:
        total += n * max(
            float(st.get(tokens, 0)) * token_bytes(model, window)
            / peak["hbm_bytes_per_s"],
            float(st[pairs]) * pair_flops(model) / peak["bf16_flops"])
    return total


def whole_ticks(ctx):
    """The whole ticks of the run's trace with their stats, read once a
    run; None where there is no trace."""
    if "attn_ticks" not in ctx:
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
        ctx["attn_ticks"] = ticks
    return ctx["attn_ticks"]


def read(ctx, window: bool = False, label: str = LABEL):
    hs = H.load(ctx)
    model = ctx["model"]
    if not hs or "hybrid_layer_pattern" not in model:
        return None
    pairs = "window_attn_pairs" if window else "attn_pairs"
    ticks = whole_ticks(ctx)
    if not ticks or any(pairs not in st for _, _, st in ticks):
        return None
    spent = hs["tick_by_label"].get(label, 0) / 1e9
    if not spent:
        return None
    peak = peaks(ctx["devices"][0].device_kind)
    return 100.0 * least_seconds(model, ticks, peak, window) / spent
