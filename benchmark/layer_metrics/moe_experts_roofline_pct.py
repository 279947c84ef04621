"""Model step: the least time the chip could take for the expert layers
of the traced span's ticks, over the device's self time under
``moe.experts`` there. The least time is the longer of two: to read the
experts HELD once a tick (expert layers x experts x 3 matrices x 2
bytes: a decode tick of 64 rows x 4 experts a row touches nearly every
one of 64 experts), or to do the DROPLESS FLOPs of the rows that carried
a token (experts per token x rows x 3 matrices x 2 FLOP a multiply-add,
an expert layer). The program's C = N einsums compute ``num_experts``
x rows token-slots where dropless routing needs ``experts per token`` x
rows: that extra work shows here as a LOW share, never as one over 100.

Ticks: the harness's count over the traced span (``trace_ticks``, as
every ``*_per_tick`` reader uses); rows: the ``rows_real`` stat of the
whole ticks in the device's window, which are a few fewer, so the FLOP
term errs low."""
from harness.hostspans import load
from harness.readers import peaks

LABEL = "moe.experts"


def expert_layers(model: dict) -> int:
    L = model["num_hidden_layers"]
    return L - min(model.get("num_dense_layers", 0), L)


def expert_params(model: dict) -> int:
    """One expert's three matrices."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def held_bytes(model: dict, itemsize: int = 2) -> int:
    """The experts the cell holds, over its expert layers."""
    return (expert_layers(model) * model["num_experts"]
            * expert_params(model) * itemsize)


def dropless_flops(model: dict, rows: int) -> int:
    """What routing ``rows`` tokens to their experts needs, over the
    expert layers."""
    return (expert_layers(model) * model["num_experts_per_tok"] * rows
            * 2 * expert_params(model))


def least_seconds(model: dict, ticks: int, rows: int, peak: dict) -> float:
    return max(ticks * held_bytes(model) / peak["hbm_bytes_per_s"],
               dropless_flops(model, rows) / peak["bf16_flops"])


def read(ctx):
    hs = load(ctx)
    ticks = (ctx.get("window") or {}).get("trace_ticks")
    if not hs or not ticks or "moe_intermediate_size" not in ctx["model"]:
        return None
    spent = hs["by_label"].get(LABEL, 0) / 1e9
    rows = hs["tick_stats"]["rows_real"]
    if not spent or rows is None:
        return None
    peak = peaks(ctx["devices"][0].device_kind)
    return 100.0 * least_seconds(ctx["model"], ticks, rows, peak) / spent
