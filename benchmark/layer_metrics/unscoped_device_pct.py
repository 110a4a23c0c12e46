"""Step program: the share of a traced step's device time whose `tf_op`
names no role of the program (copies and layout changes XLA made, async
slices, parameters): how much `fwd_device_ms`, `bwd_device_ms` and
`opt_device_ms` leave out; median over steps, in %."""
from benchmark.layer_metrics import _scopes


def read(record):
    roles = _scopes.roles_of(record)
    return roles["unscoped_pct"] if roles else None
