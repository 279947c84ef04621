"""Model step: device self time, per tick of the traced span, under the
short convolution's scopes (``models/lfm2_moe.py``): ``shortconv.in``
(norm and ``in_proj``), ``shortconv.mix`` (the gates, the taps, the
gathers from stream and state), ``conv_state.write`` and
``shortconv.out``. A time, with the layer's arithmetic beside it: at 64
decode rows a conv layer reads 33.6 MB of weights (41 us at the chip's
bandwidth) against 3.2 GFLOP (16 us), so the bound is bandwidth."""
from harness.hostspans import load
from harness.readers import per_tick_ms

LABELS = ("shortconv.in", "shortconv.mix", "conv_state.write",
          "shortconv.out")


def conv_layers(model: dict) -> int:
    return sum(t == "conv" for t in
               model["layer_types"][:model["num_hidden_layers"]])


def shortconv_flops(model: dict, rows: int) -> int:
    """One conv layer over ``rows`` tokens: ``in_proj`` (D x 3D) and
    ``out_proj`` (D x D) at 2 FLOP a multiply-add, the K taps and the
    two gates elementwise."""
    D, K = model["hidden_size"], model["conv_L_cache"]
    return rows * (2 * D * 3 * D + 2 * D * D + (2 * K + 2) * D)


def shortconv_bytes(model: dict, rows: int, slots: int,
                    itemsize: int = 2) -> int:
    """One conv layer: its weights read once, the rows' hidden states
    read and written, the slots' ``K - 1`` state rows read and
    written."""
    D, K = model["hidden_size"], model["conv_L_cache"]
    weights = D * 3 * D + D * D + D * K + D
    return itemsize * (weights + 2 * rows * D + 2 * slots * (K - 1) * D)


def read(ctx):
    hs = load(ctx)
    if not hs or not any(k in hs["by_label"] for k in LABELS):
        return None
    return per_tick_ms(
        ctx, sum(hs["by_label"].get(k, 0) for k in LABELS) / 1e9)
