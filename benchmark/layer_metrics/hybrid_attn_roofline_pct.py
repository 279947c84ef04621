"""Kernels: ``attn_layers_roofline_pct`` for a configuration whose
attending layers are typed ``attention`` (that file counts the type
``full_attention`` and would read 0 of this one): the least time the
chip could take to read the keys and values the whole ticks of the
traced span attend (their ``kv_tokens`` x the layers whose type names
attention x K and V x ``num_key_value_heads`` x head size x 2 bytes, over
the peak HBM bandwidth) over the self time of ``ragged_paged_attention*``
in those ticks. Bound: bandwidth. One reader with that file's but for the
type's name: for a ``benchmark`` PR to fold (PERF.md section 7)."""
from harness.hostspans import load
from harness.readers import peaks


def attention_layers(model: dict) -> int:
    return sum("attention" in t for t in
               model["layer_types"][:model["num_hidden_layers"]])


def kv_bytes(model: dict, kv_tokens: int) -> int:
    """Bytes of bf16 cache that ``kv_tokens`` attended tokens hold over
    the layers that attend."""
    return (kv_tokens * attention_layers(model) * 2
            * model["num_key_value_heads"] * model["head_dim"] * 2)


def read(ctx):
    hs = load(ctx)
    if not hs or "layer_types" not in ctx["model"]:
        return None
    tokens = hs["tick_stats"]["kv_tokens"]
    spent = hs["tick_by_label"].get("ragged_attn.kernel", 0) / 1e9
    if not tokens or not spent:
        return None
    bandwidth = peaks(ctx["devices"][0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * kv_bytes(ctx["model"], tokens) / bandwidth / spent
