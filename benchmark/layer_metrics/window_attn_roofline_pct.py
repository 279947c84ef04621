"""Kernels: the least time the chip could take for the WINDOW attention
layers' launches of the whole ticks in the traced span, over the
kernel's self time under ``attn.window`` there (label
``attn.window.kernel``). ``full_attn_roofline_pct``'s arithmetic with the
window layers' counts of the tick's annotation: ``window_kv_tokens`` (the
keys a launch must read, ``min(kv_len, sliding_window - 1 + q_len)`` a
slot: one layer's worth) x window layers x ``swa_num_key_value_heads`` x
the published 640 B a head, and ``window_attn_pairs`` (``min(position +
1, sliding_window)`` a row) x window layers x heads x 320 x 2 FLOP.

Tiles wholly behind the window cost nothing in the numerator, so a walk
that masks them instead of skipping them reads low. What bounds it at
this cell's shapes is neither peak: a decode row's launch reads 128 keys
(80 KB a layer) and a grid step's fixed cost (its page copies' latency,
the flash step of a mostly masked tile) is several times the time those
bytes take. None where the annotations carry no ``window_attn_pairs`` (a
program from before PR 47, another family)."""
from harness import manifest

LABEL = "attn.window.kernel"


def read(ctx):
    full = manifest.load_reader("full_attn_roofline_pct",
                                ctx["cell"].bench_dir)
    return full.read(ctx, window=True, label=LABEL)
