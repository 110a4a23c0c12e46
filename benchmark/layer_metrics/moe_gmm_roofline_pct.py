"""Pallas kernels: the least time the step's grouped-matmul calls could take
(each call's own roofline from `flops_moe.py` and `peaks.json`, at the rows
the step COUNTED in its `moe.load` spans, not the rows even routing would
send: expected rows would read over 100% the day routing leans towards the
held experts) over the `moe_gmm_*` kernels' device time, in %."""
from benchmark.layer_metrics import _hybrid, _moe


def read(record):
    return _hybrid.roofline_pct(record, _moe.GMM, _moe.gmm_least_seconds)
