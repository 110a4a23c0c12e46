"""Shared by the metrics of the Mamba-2 layers: the device time under the op
types a Mamba-2 mixer lowers besides its two projections (`mamba2_scan`, the
chunked recurrence; `causal_conv1d` in front of it; `mamba2_gate_norm`
behind it), both roles, as `framework/trace.py` scopes them, and the least
time the step's scan calls could take (`flops_ssd`: the RECURRENT form's
required FLOPs and bytes over `peaks.json`, the larger of the two a call;
the family's `scan_calls` says how often the forward and the backward run).
Whatever implements the op, XLA's chunk passes or a kernel, lowers under the
op's scope, so the same readers judge both. Imports nothing of `paddle_tpu`;
where a program has no such scope, every function returns None."""
from benchmark import flops, flops_ssd
from benchmark.layer_metrics import _hybrid

SCAN = ("mamba2_scan",)
OP_TYPES = SCAN + ("causal_conv1d", "mamba2_gate_norm")


def device_ms(record):
    """Device ms a traced step under OP_TYPES (median over steps)."""
    return _hybrid.op_type_ms(record, OP_TYPES)


def share_pct(record):
    """`device_ms` over the step's device ms, in %."""
    ms = device_ms(record)
    step = (record.get("traced") or {}).get("step_busy_ms")
    return None if ms is None or not step else 100.0 * ms / step


def least_seconds(record):
    """Least seconds of one step's `mamba2_scan` calls, each by its own
    roofline."""
    cell = record["cell"]
    calls = getattr(cell.family, "scan_calls", None)
    if calls is None or not record.get("peaks"):
        return None
    itemsize = {"bfloat16": 2, "float32": 4}[cell.config["precision"]]
    total = 0.0
    for call in calls(cell.config, cell.traffic):
        if not isinstance(call, dict):
            return None
        ops = flops_ssd.call_flops(call["batch"], call["seq"],
                                   call["heads"], call["head_dim"],
                                   call["state"])
        moved = flops_ssd.call_bytes(call["batch"], call["seq"],
                                     call["heads"], call["head_dim"],
                                     call["groups"], call["state"], itemsize)
        for i, runs in enumerate((call["fwd"], call["bwd"])):
            total += runs * flops.roofline_seconds(ops[i], moved[i],
                                                   record["peaks"])[0]
    return total or None


def roofline_pct(record):
    """`least_seconds` of one step over the device time a step under the
    `mamba2_scan` op type alone, in %."""
    ms = _hybrid.op_type_ms(record, SCAN)
    least = least_seconds(record) if ms else None
    return None if least is None else 100.0 * least * 1e3 / ms
