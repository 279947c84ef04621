"""Kernels: the least time the chip could take to read the keys and
values the whole ticks of the traced span attend (their ``kv_tokens``,
counted by the engine where it builds the tick, x layers x K and V x
``num_key_value_heads`` x head size x 2 bytes, over the peak HBM
bandwidth of harness/peaks.json) over the self time of
``ragged_paged_attention*`` in those ticks. Bound: bandwidth (a decode
row does 4 FLOP a byte of cache; the ridge is at 240)."""
from harness.hostspans import load
from harness.readers import peaks


def kv_bytes(model: dict, kv_tokens: int) -> int:
    """Bytes of bf16 cache that ``kv_tokens`` attended tokens hold over
    every layer."""
    return (kv_tokens * model["num_hidden_layers"] * 2
            * model["num_key_value_heads"] * model["head_dim"] * 2)


def read(ctx):
    hs = load(ctx)
    if not hs:
        return None
    tokens = hs["tick_stats"]["kv_tokens"]
    spent = hs["tick_by_label"].get("ragged_attn.kernel", 0) / 1e9
    if not tokens or not spent:
        return None
    bandwidth = peaks(ctx["devices"][0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * kv_bytes(ctx["model"], tokens) / bandwidth / spent
