"""Shared by the metrics of a block-diffusion cell: the flash kernels'
device time under the scope a block-diffusion attention op is lowered under
(`paddle_tpu/ops/attention_ops.py:BLOCK_DIFFUSION_SCOPE`: a kernel's `tf_op`
reads `.../scaled_dot_product_attention/block_diffusion_attention/flash_fwd/
pallas_call`, in the backward inside JAX's `transpose(jvp(...))` brackets),
the least time the step's calls could take (`flops_bd.py`: the visible pairs
T^2 + T L a head, q, k, v, o moved once, the replay counted), and the noise
the step's loss worked on, from the `bd.noise` spans `Executor.run` records
while obs is on (labels `masked_rows`, `rows`, `weight_sum`). The kernels
themselves are found by name, as `_hybrid.FLASH` finds them. Imports nothing
of `paddle_tpu`; where a program has no such scope, kernel or span, every
function returns None."""
from benchmark import flops, flops_bd, trace_reduce
from benchmark.harness import TRACE_WARM_STEPS
from benchmark.layer_metrics import _hybrid, _scopes

SCOPE = "block_diffusion_attention"
SPAN = "bd.noise"


def flash_seconds(record):
    """Device seconds in the traced window (mean over chips) of the flash
    kernels under the scope; None where the trace holds none."""
    def make():
        trace = _scopes.trace_of(record)
        window = _scopes.window_of(trace["host"]) if trace else None
        if window is None:
            return None
        _line, lo, hi = window
        total = 0.0
        for dev in trace["devices"].values():
            for name, start, end, tf_op in dev["ops"]:
                ns = min(end, hi) - max(start, lo)
                if ns > 0 and SCOPE in tf_op \
                        and _hybrid.FLASH.search(trace_reduce.op_kind(name)):
                    total += ns / 1e9 / len(trace["devices"])
        return total or None
    return _scopes._cached(record, "bd_flash_seconds", make)


def least_seconds(record):
    """Least seconds of one step's block-diffusion calls: each call's own
    roofline, the recompute's second forward in `count`."""
    cell = record["cell"]
    calls = getattr(cell.family, "attention_calls", None)
    if calls is None or not record.get("peaks"):
        return None
    itemsize = _hybrid._itemsize(cell)
    total = 0.0
    for call in calls(cell.config, cell.traffic):
        if not isinstance(call, dict) or "block_length" not in call:
            return None
        which = 0 if call["kind"] == "forward" else 1
        seconds, _bound = flops.roofline_seconds(
            flops_bd.attention_call_flops(call)[which],
            flops_bd.attention_call_bytes(call, itemsize)[which],
            record["peaks"])
        total += call["count"] * seconds
    return total or None


def share_pct(record):
    seconds = flash_seconds(record)
    busy = (record.get("traced") or {}).get("busy_s")
    return 100.0 * seconds / busy if seconds and busy else None


def roofline_pct(record):
    seconds = flash_seconds(record)
    steps = (record.get("traced") or {}).get("steps_seen")
    least = least_seconds(record) if seconds and steps else None
    return None if least is None else 100.0 * least * steps / seconds


def masked_rows_pct(record):
    """Of the noisy half's rows, the share that was masked (and so carries
    a loss weight), over the traced steps: the `trace_steps` `bd.noise`
    spans from window step TRACE_WARM_STEPS on (obs is cleared at the
    window's start, so the n-th span is window step n)."""
    seen = [span.get("labels") or {} for span in record.get("obs_spans") or ()
            if span.get("name") == SPAN]
    steps = int(record["cell"].traffic.get("trace_steps", 0)) if seen else 0
    seen = seen[TRACE_WARM_STEPS:TRACE_WARM_STEPS + steps]
    rows = sum(s.get("rows", 0) for s in seen)
    return 100.0 * sum(s["masked_rows"] for s in seen) / rows if rows \
        else None
