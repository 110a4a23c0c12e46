"""Pallas kernels: device time of the flash forward and the two backward
kernels, found by their names (`custom-call:flash_*`), over device-busy time
in the traced window, in %. Unlike `flash_share_pct` it does not count every
custom call: the hybrid step has the scan kernels beside them."""
from benchmark.layer_metrics import _hybrid


def read(record):
    return _hybrid.share_pct(record, _hybrid.FLASH)
