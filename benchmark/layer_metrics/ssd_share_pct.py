"""Step program: `ssd_device_ms` over the step's device time, in %. Read
from the `tf_op` of each operation's metadata (`_scopes.py`)."""
from benchmark.layer_metrics import _ssd


def read(record):
    return _ssd.share_pct(record)
