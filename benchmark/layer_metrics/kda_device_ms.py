"""Step program: per traced step, the device ms under the delta-rule
layers' own op types (`_kda.OP_TYPES`), both roles; median over steps:
`kda_share_pct`'s numerator, as `ssm_device_ms` is the scan layers'."""
from benchmark.layer_metrics import _kda


def read(record):
    return _kda.device_ms(record)
