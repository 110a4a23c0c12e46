"""Shared by the metrics of the hybrid (state-space + attention) cells:
which device operations are the selective-scan kernels and which the flash
kernels, by the `name=` the program gives its `pallas_call`s
(`trace_reduce.op_kind` turns `%ssm_scan_fwd.12 = ... custom-call(...)` into
`custom-call:ssm_scan_fwd`), the least time their calls could take, and the
device time under given op types. Imports nothing of `paddle_tpu`; where a
program has no such kernel or scope, every function returns None."""
import re
import statistics

from benchmark import flops, flops_hybrid
from benchmark.layer_metrics import _scopes

SCAN = re.compile(r"^custom-call:ssm_scan_")
FLASH = re.compile(r"^custom-call:flash_")


def kernel_seconds(record, pattern):
    """Device seconds in the traced window (mean over chips) of the custom
    calls whose kind matches `pattern`, or None where there are none."""
    traced = record.get("traced")
    if not traced:
        return None
    found = sum(s for kind, s in traced["op_seconds"].items()
                if pattern.search(kind))
    return found or None


def share_pct(record, pattern):
    seconds = kernel_seconds(record, pattern)
    if seconds is None or not record["traced"].get("busy_s"):
        return None
    return 100.0 * seconds / record["traced"]["busy_s"]


def _itemsize(cell):
    return {"bfloat16": 2, "float32": 4}[cell.config["precision"]]


def scan_least_seconds(record):
    """Least seconds of one step's selective-scan calls: the HBM bytes they
    must move over the peak bandwidth (the scan has no MXU work)."""
    cell = record["cell"]
    calls = getattr(cell.family, "scan_calls", None)
    if calls is None or not record.get("peaks"):
        return None
    total = 0
    for batch, seq, channels, state, n_fwd, n_bwd in calls(cell.config,
                                                           cell.traffic):
        fwd, bwd = flops_hybrid.scan_call_bytes(batch, seq, channels, state,
                                                _itemsize(cell))
        total += n_fwd * fwd + n_bwd * bwd
    return total / record["peaks"]["hbm_bytes_per_s"] if total else None


def attention_least_seconds(record):
    """Least seconds of one step's flash calls: each call's own roofline
    (the larger of its FLOPs over peak and its bytes over peak bandwidth),
    by visible area, group size and the two widths; the recompute's second
    forward is in `count`."""
    cell = record["cell"]
    if not record.get("peaks"):
        return None
    total = 0.0
    for call in cell.family.attention_calls(cell.config, cell.traffic):
        if not isinstance(call, dict):
            return None
        which = 0 if call["kind"] == "forward" else 1
        seconds, _bound = flops.roofline_seconds(
            flops_hybrid.attention_call_flops(call)[which],
            flops_hybrid.attention_call_bytes(call, _itemsize(cell))[which],
            record["peaks"])
        total += call["count"] * seconds
    return total or None


def roofline_pct(record, pattern, least_seconds):
    """`least_seconds(record)` of one step over the kernels' device time a
    step, in %; None where the trace holds no such kernel."""
    seconds = kernel_seconds(record, pattern)
    steps = (record.get("traced") or {}).get("steps_seen")
    if seconds is None or not steps:
        return None
    least = least_seconds(record)
    return None if least is None else 100.0 * least * steps / seconds


def op_type_ms(record, op_types):
    """Per traced step, device ms (each instant to the innermost running
    operation, as the role split takes it) of the operations whose op-type
    scope is one of `op_types`, whatever their role; median over steps.
    None where the trace has no device plane, no step, or no such scope."""
    trace = _scopes.trace_of(record)
    if not trace:
        return None
    window = _scopes.window_of(trace["host"])
    if window is None:
        return None
    _line, lo, hi = window
    per_step = []
    for dev in sorted(trace["devices"]):
        ops = [(tf_op, a, b)
               for _n, a, b, tf_op in trace["devices"][dev]["ops"]]
        for s, e in _scopes.step_runs(trace["devices"][dev]["modules"],
                                      lo, hi):
            per_step.append(sum(
                ns for tf_op, ns in _scopes.exclusive_ns(ops, s, e).items()
                if _scopes.parse_scope(tf_op)[1] in op_types))
    if not per_step or not any(per_step):
        return None
    return statistics.median(per_step) / 1e6
