"""Pallas kernels: device time of the WINDOW layers' flash kernels (the
`custom-call:flash_*` operations lowered under the `window_attention`
scope) over device-busy time in the traced window, in %."""
from benchmark.layer_metrics import _swa


def read(record):
    return _swa.share_pct(record, windowed=True)
