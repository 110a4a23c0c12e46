"""Step program: per traced step, the device time under the expert layer's
four op types (`moe_route`, `moe_dispatch`, `moe_experts`, `moe_combine`,
forward, replayed forward and backward; the grouped-matmul kernels and the
sort, gather and elementwise passes around them) over the step's device
time, in %. Read from the `tf_op` of each operation's metadata
(`_scopes.py`)."""
from benchmark.layer_metrics import _moe


def read(record):
    return _moe.share_pct(record)
