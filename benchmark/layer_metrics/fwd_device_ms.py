"""Step program: per traced step, the device time (operations clipped to
the step's `XLA Modules` run, as `device_step_ms` takes it) of the
operations whose role scope is `forward`; median over steps, in ms. Read
from the `tf_op` of each operation's metadata (`_scopes.py`)."""
from benchmark.layer_metrics import _scopes


def read(record):
    roles = _scopes.roles_of(record)
    return roles["fwd_ms"] if roles else None
