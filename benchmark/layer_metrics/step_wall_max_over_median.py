"""Executor host path: over ALL `exec.step` spans of the traced run's window
(obs is on for the whole of it, not for the profiler's steps alone), the
longest over the median: whether the window's worst step was a stall
(`_account.py`)."""
from benchmark.layer_metrics import _account


def read(record):
    worst = _account.worst_step_of(record)
    return worst["max_over_median"] if worst else None
