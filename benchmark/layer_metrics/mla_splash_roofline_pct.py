"""Kernels, latent attention in training: the least time the chip could
take for the causal attention the traced steps need at the PUBLISHED
head sizes (q / k ``qk_nope_head_dim + qk_rope_head_dim``, v
``v_head_dim``: ``families/<family>.py: splash_least_seconds``, per
layer one forward and one backward, each the larger of FLOP / peak and
bytes / peak bandwidth) over the device self time of the events named
``splash_mha*``. Remat's second forward is time spent, not work needed,
and a head padded past its published size is work the model does not
ask for: both lower the share."""
from harness.readers import op_seconds, peaks

PATTERN = r"^splash_mha"


def read(ctx):
    tr, fam = ctx.get("train"), ctx["cell"].family
    spent = op_seconds(ctx, PATTERN)
    if (not tr or not tr.get("trace_steps") or not spent
            or not hasattr(fam, "splash_least_seconds")):
        return None
    t, m = ctx["cell"].workload["trainer"], ctx["model"]
    least = fam.splash_least_seconds(
        m, t["batch"], t["seq_len"], peaks(ctx["devices"][0].device_kind))
    return 100.0 * least * m["num_hidden_layers"] * tr["trace_steps"] / spent
