"""Model step: of the token rows the whole ticks of the traced span
were launched with (packed width, fused steps included), the share that
carried a live decoder's, a draft's or a prompt span's token — the rest
is padding the program computes and throws away. From the ``rows`` and
``rows_real`` stats of the ``serving.tick`` annotations."""
from harness.hostspans import load


def read(ctx):
    hs = load(ctx)
    if not hs:
        return None
    rows, real = hs["tick_stats"]["rows"], hs["tick_stats"]["rows_real"]
    if not rows or real is None:
        return None
    return 100.0 * real / rows
