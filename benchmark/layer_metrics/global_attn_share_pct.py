"""Pallas kernels: device time of the FULL-attention layers' flash kernels
(the `custom-call:flash_*` operations NOT under the `window_attention`
scope) over device-busy time in the traced window, in %."""
from benchmark.layer_metrics import _swa


def read(record):
    return _swa.share_pct(record, windowed=False)
