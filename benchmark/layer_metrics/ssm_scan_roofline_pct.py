"""Pallas kernels: the least time the step's selective-scan calls could take
(the HBM bytes they must move — forward reads xc, delta, B, C and writes y;
backward reads those and dy and writes dxc, ddelta, dB, dC — over the peak
bandwidth; recompute's second forward in both terms) over the scan kernels'
device time, in %. Bandwidth is the scan's only roofline: it has no MXU
work, and its exp and multiply-adds on the vector units have no published
peak."""
from benchmark.layer_metrics import _hybrid


def read(record):
    return _hybrid.roofline_pct(record, _hybrid.SCAN,
                                _hybrid.scan_least_seconds)
