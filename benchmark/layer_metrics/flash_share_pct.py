"""Pallas kernels: device time of the flash forward and the two backward
kernels over device-busy time in the traced window, in %."""
from benchmark.layer_metrics import _flash


def read(record):
    seconds = _flash.kernel_seconds(record)
    if seconds is None:
        return None
    return 100.0 * seconds / record["traced"]["busy_s"]
