"""Kernels, the held experts in training: the least time the chip could
take for the grouped matmuls of the traced steps (``families/<family>.
py: gmm_least_seconds``: the longer of the arithmetic of the (row,
choice) pairs that reached the held experts, 18 x D x Fm FLOP a pair,
and the traffic the held experts' weights force: read forward and
backward, their gradient written once) over the device self time of the
grouped-matmul kernels under the scope ``moe.experts``. The pairs are
the program's own count (``train_moe_pairs_held``, the mean a step over
the window's records, times the traced steps: the traced steps' own
records are not told apart). Tile padding and rematerialisation are time
spent, not work needed: both lower the share."""
from harness.hostspans import load
from harness.manifest import load_reader
from harness.readers import peaks

_counts = load_reader("share_train_mfu_pct")
COUNTER, window_counts = _counts.COUNTER, _counts.window_counts


def read(ctx):
    hs, tr, fam = load(ctx), ctx.get("train"), ctx["cell"].family
    counts = window_counts(ctx)
    if (not hs or not tr or not tr.get("trace_steps") or counts is None
            or not hasattr(fam, "gmm_least_seconds")):
        return None
    spent = hs["by_label"].get("moe.experts.kernel")
    if not spent:
        return None
    steps = tr["trace_steps"]
    least = fam.gmm_least_seconds(
        ctx["model"], counts[COUNTER] / counts["steps"] * steps, steps,
        peaks(ctx["devices"][0].device_kind))
    return 100.0 * least / (spent / 1e9)
