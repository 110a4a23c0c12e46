"""Pallas kernels: the least time the step's flash calls could take (the
larger of causal-half FLOPs over peak FLOP/s and q/k/v/o/do/dq/dk/dv bytes
over peak B/s; the recompute's second forward counted in both the least
time and the kernel time) over the kernels' device time, in %. FLOP-bound at
head size 64 and T >= 1024 on a v5e (FLOPs/byte far above 197e12/819e9)."""
from benchmark.layer_metrics import _flash


def read(record):
    seconds = _flash.kernel_seconds(record)
    least, _bound = _flash.least_seconds(record)
    steps = (record.get("traced") or {}).get("steps_seen")
    if seconds is None or least is None or not steps:
        return None
    return 100.0 * least * steps / seconds
