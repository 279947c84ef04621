"""Kernels: the least time the chip could take for the causal flash
attention the steps in the traced span need (per layer one forward and
one backward at the cell's static shapes; the larger of FLOP / peak and
bytes / peak bandwidth, harness/flops.py) over the summed device time of
the events named ``splash_mha*`` there. Remat's second forward is time
spent, not work needed, so it lowers the share. Bound: compute (the
causal half of a 2048-square at head size 128 is far above the ridge)."""
from harness import flops
from harness.readers import op_seconds, peaks

PATTERN = r"^splash_mha"


def read(ctx):
    tr = ctx.get("train")
    spent = op_seconds(ctx, PATTERN)
    if not tr or not tr.get("trace_steps") or not spent:
        return None
    t = ctx["cell"].workload["trainer"]
    tp = int(t.get("tp", 1))
    dp = int(t.get("dp", 1))
    m = ctx["model"]
    # one device's share: its rows of the batch, its heads
    need = flops.splash_flops_and_bytes(
        m, t["batch"] // dp, t["seq_len"],
        heads=m["num_attention_heads"] // tp,
        kv_heads=max(m["num_key_value_heads"] // tp, 1))
    peak = peaks(ctx["devices"][0].device_kind)
    fwd, _ = flops.roofline_seconds(need["fwd_flops"], need["fwd_bytes"],
                                    peak)
    bwd, _ = flops.roofline_seconds(need["bwd_flops"], need["bwd_bytes"],
                                    peak)
    least = (fwd + bwd) * m["num_hidden_layers"] * tr["trace_steps"]
    return 100.0 * least / spent
