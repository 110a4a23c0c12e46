"""Pallas kernels: the least time the step's WINDOWED flash calls could take
(the family's `attention_calls` with a window: each call's larger of FLOPs
over peak FLOP/s and bytes over peak B/s, FLOPs by the VISIBLE pairs
T W - W (W - 1) / 2 a head through `flops_hybrid`; recompute's second
forward counted) over the device time of the flash kernels under the
`window_attention` scope, in %."""
from benchmark.layer_metrics import _swa


def read(record):
    return _swa.roofline_pct(record, windowed=True)
