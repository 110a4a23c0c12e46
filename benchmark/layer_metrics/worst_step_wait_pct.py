"""Executor host path: of the window's longest `exec.step`'s excess over the
median step, the share inside `exec.fetch` (waiting for the device or the
runtime) as against the host's own phases, in %; 0.0 where
`step_wall_max_over_median` is under 1.05 (`_account.py`)."""
from benchmark.layer_metrics import _account


def read(record):
    worst = _account.worst_step_of(record)
    return worst["wait_pct"] if worst else None
