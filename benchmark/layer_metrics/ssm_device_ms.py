"""Step program: per traced step, the device ms under the op types
`selective_scan` and `causal_conv1d`, both roles (the scan kernels, the
layout and broadcast passes XLA puts around them, and the depthwise
convolution); median over steps. Read from the `tf_op` of each operation's
metadata (`_scopes.py`)."""
from benchmark.layer_metrics import _hybrid


def read(record):
    return _hybrid.op_type_ms(record, ("selective_scan", "causal_conv1d"))
