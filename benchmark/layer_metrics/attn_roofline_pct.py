"""Pallas kernels: the least time the step's flash calls could take (each
call's larger of FLOPs over peak FLOP/s and bytes over peak B/s, FLOPs by
the area a query can see — T*W under a window, T^2/2 without —, the group
size and the q/k and value widths; recompute's second forward in both terms)
over the flash kernels' device time, in %."""
from benchmark.layer_metrics import _hybrid


def read(record):
    return _hybrid.roofline_pct(record, _hybrid.FLASH,
                                _hybrid.attention_least_seconds)
