"""Kernels: the least time the chip could take to read once and write
once the state-space state of the slots that had a row in the whole
ticks of the traced span (their ``live_slots`` — counted by the engine
where it builds the tick, summed over the tick's launches, so a fused
tail's steps count as they do in ``kv_tokens`` — x the layers of type
``mamba`` x 2 x ``mamba_n_heads`` x ``mamba_d_head`` x ``mamba_d_state``
x 4 bytes, over the peak HBM bandwidth of harness/peaks.json) over the
self time of ``ssd_update*`` in those ticks. Bound: bandwidth (the pass
does 0.5 FLOP a byte of state: a multiply-add a float32 it reads and
writes). ``hostspans.load`` sums three fixed stats of the ticks, so this
reader takes the ``serving.tick`` annotations from the trace itself."""
from harness import hostspans as H
from harness import trace as T
from harness.common import trace_dir
from harness.readers import peaks

LABEL = "ssm.scan.kernel"


def mamba_layers(model: dict) -> int:
    return sum(t == "mamba" for t in
               model["layer_types"][:model["num_hidden_layers"]])


def state_bytes(model: dict, live_slots: int) -> int:
    """Bytes of float32 state that ``live_slots`` slot-launches read
    once and write once, over the Mamba layers."""
    return (live_slots * mamba_layers(model) * 2 * model["mamba_n_heads"]
            * model["mamba_d_head"] * model["mamba_d_state"] * 4)


def device_and_ticks(ctx):
    """``(device operations, whole ticks)`` of the run's trace, read
    once a run; ``(None, None)`` where there is no trace."""
    if "ssm_trace" not in ctx:
        try:
            annotations, device, _ = H.read_xplane(
                T.find_xplane(trace_dir()))
        except (FileNotFoundError, OSError):
            annotations, device = [], []
        ticks = None
        if device:
            window = (min(s for _, s, _, _ in device),
                      max(e for _, _, e, _ in device))
            ticks = H.whole_ticks(annotations, window)
        ctx["ssm_trace"] = (device or None, ticks)
    return ctx["ssm_trace"]


def read(ctx):
    hs = H.load(ctx)
    model = ctx["model"]
    if not hs or "mamba_d_state" not in model:
        return None
    _, ticks = device_and_ticks(ctx)
    live = H.stat_sum(ticks, "live_slots") if ticks else None
    spent = hs["tick_by_label"].get(LABEL, 0) / 1e9
    if not live or not spent:
        return None
    bandwidth = peaks(ctx["devices"][0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * state_bytes(model, live) / bandwidth / spent
