"""Step program: per traced step, the device ms under `moe_route`,
`moe_dispatch` and `moe_combine`, both roles: the router, the sort, the row
gathers and the weighted sum, the bandwidth-bound part of the expert layer
that no grouped matmul hides; median over steps."""
from benchmark.layer_metrics import _hybrid, _moe


def read(record):
    return _hybrid.op_type_ms(record, _moe.AROUND)
