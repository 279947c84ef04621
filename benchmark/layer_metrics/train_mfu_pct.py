"""Trainer: model FLOP utilisation = tokens per second x model FLOP per
token (forward + backward, no recompute: harness/flops.py) over chips x
the bf16 peak of harness/peaks.json. In a traced run the rate is that
of the chained steps before the trace starts."""
from harness import flops
from harness.readers import peaks


def read(ctx):
    tr = ctx.get("train")
    if not tr or not tr.get("tokens_per_s"):
        return None
    devs = ctx["devices"]
    seq = ctx["cell"].workload["trainer"]["seq_len"]
    per_token = flops.train_flops_per_token(ctx["model"], seq)
    peak = peaks(devs[0].device_kind)["bf16_flops"] * len(devs)
    return 100.0 * tr["tokens_per_s"] * per_token / peak
