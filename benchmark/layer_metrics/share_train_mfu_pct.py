"""Trainer, a chip that holds a SHARE of the experts: the whole step's
share of the chip's bf16 peak = tokens per second x model FLOP a token
over the peak. The FLOP are the family's own (``families/<family>.py:
train_flops_per_token``: forward + backward, no recompute; the routed
experts by the (row, choice) pairs that REACHED the experts held here,
which the program counts on the device and sends out once a step:
``paddle_tpu.observability.step_counters()``, group ``train``, counter
``train_moe_pairs_held``, the window's records). In a traced run the
rate is that of the chained steps before the trace starts."""
import time

from harness.readers import peaks

COUNTER = "train_moe_pairs_held"


def window_counts(ctx):
    """The step counters' sums over the records that arrived since the
    window opened, or None (a program without the registry, a family
    that counts nothing)."""
    try:
        from paddle_tpu.observability import step_counters
    except ImportError:
        return None
    t0 = (ctx.get("train") or {}).get("t0")
    if t0 is None:
        return None
    # the harness stamps time.perf_counter(), the registry
    # time.monotonic(): one offset (0 on Linux: the same clock)
    got = step_counters().since(
        "train", t0 + time.monotonic() - time.perf_counter())
    return got if got.get("steps") and COUNTER in got else None


def read(ctx):
    tr = ctx.get("train")
    fam = ctx["cell"].family
    counts = window_counts(ctx)
    if (not tr or not tr.get("tokens_per_s") or counts is None
            or not hasattr(fam, "train_flops_per_token")):
        return None
    pairs_per_token = counts[COUNTER] / counts["steps"] / tr["tokens_per_step"]
    per_token = fam.train_flops_per_token(
        ctx["model"], ctx["cell"].workload["trainer"]["seq_len"],
        pairs_per_token)
    devs = ctx["devices"]
    peak = peaks(devs[0].device_kind)["bf16_flops"] * len(devs)
    return 100.0 * tr["tokens_per_s"] * per_token / peak
