"""Pallas kernels: the least time the step's full causal flash calls could
take (the family's `attention_calls` without a window, FLOPs by the causal
area T (T + 1) / 2 a head; recompute's second forward counted) over the
device time of the flash kernels outside the `window_attention` scope,
in %."""
from benchmark.layer_metrics import _swa


def read(record):
    return _swa.roofline_pct(record, windowed=False)
