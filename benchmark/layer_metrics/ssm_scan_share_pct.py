"""Pallas kernels: device time of the selective-scan forward and backward
kernels (`custom-call:ssm_scan_*`) over device-busy time in the traced
window, in %."""
from benchmark.layer_metrics import _hybrid


def read(record):
    return _hybrid.share_pct(record, _hybrid.SCAN)
