"""Shared by the metrics of a model that mixes window and full attention
layers: the flash kernels' device time split by the layer's kind, and the
least time each kind's calls could take. A windowed
`scaled_dot_product_attention` op is lowered under the inner scope
`window_attention` (`paddle_tpu/ops/attention_ops.py:WINDOW_SCOPE`), so a
flash kernel's `tf_op` reads `.../scaled_dot_product_attention/
window_attention/flash_fwd/pallas_call` (and, in the backward, carries the
same scope inside JAX's `transpose(jvp(...))` brackets) where the call is a
window layer's, and has no such component where it is a full layer's. The
kernels themselves are found by name, as `_hybrid.FLASH` finds them.
Imports nothing of `paddle_tpu`; where a program has no such scope or kernel,
every function returns None."""
from benchmark import flops, flops_hybrid, trace_reduce
from benchmark.layer_metrics import _hybrid, _scopes

SCOPE = "window_attention"


def flash_seconds(record):
    """{"window": s, "full": s}: device seconds in the traced window (mean
    over chips) of the flash kernels under the window scope and of the
    others; None where the trace holds no flash kernel, or none under the
    scope though the family says some calls are windowed (a program from
    before the scope: its split cannot be told)."""
    def make():
        trace = _scopes.trace_of(record)
        window = _scopes.window_of(trace["host"]) if trace else None
        if window is None:
            return None
        _line, lo, hi = window
        out = {"window": 0.0, "full": 0.0}
        for dev in trace["devices"].values():
            for name, start, end, tf_op in dev["ops"]:
                ns = min(end, hi) - max(start, lo)
                if ns > 0 and _hybrid.FLASH.search(trace_reduce.op_kind(name)):
                    out["window" if SCOPE in tf_op else "full"] += \
                        ns / 1e9 / len(trace["devices"])
        if not out["window"] and any(c["window"] for c in _calls(record)):
            return None
        return out if out["window"] or out["full"] else None
    return _scopes._cached(record, "swa_flash_seconds", make)


def _calls(record):
    cell = record["cell"]
    calls = getattr(cell.family, "attention_calls", None)
    return [c for c in (calls(cell.config, cell.traffic) if calls else ())
            if isinstance(c, dict)]


def least_seconds(record, windowed):
    """Least seconds of one step's flash calls of one kind (`windowed`: the
    calls with a window; else those without): each call's own roofline, by
    the pairs a query can see (T W - W (W - 1) / 2 a head under a window),
    the recompute's second forward in `count`."""
    if not record.get("peaks"):
        return None
    itemsize = _hybrid._itemsize(record["cell"])
    total = 0.0
    for call in _calls(record):
        if bool(call["window"]) != windowed:
            continue
        which = 0 if call["kind"] == "forward" else 1
        seconds, _bound = flops.roofline_seconds(
            flops_hybrid.attention_call_flops(call)[which],
            flops_hybrid.attention_call_bytes(call, itemsize)[which],
            record["peaks"])
        total += call["count"] * seconds
    return total or None


def _kind_seconds(record, windowed):
    seconds = flash_seconds(record)
    return seconds["window" if windowed else "full"] if seconds else None


def share_pct(record, windowed):
    """That kind's flash seconds over device-busy time in the traced
    window, in %."""
    seconds = _kind_seconds(record, windowed)
    busy = (record.get("traced") or {}).get("busy_s")
    return 100.0 * seconds / busy if seconds and busy else None


def roofline_pct(record, windowed):
    """`least_seconds` of one step over that kind's flash seconds a step,
    in %."""
    seconds = _kind_seconds(record, windowed)
    steps = (record.get("traced") or {}).get("steps_seen")
    least = least_seconds(record, windowed) if seconds and steps else None
    return None if least is None else 100.0 * least * steps / seconds
