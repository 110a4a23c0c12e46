"""Executor host path: per traced step, the device-idle time (gaps of the
first chip's busy union inside `bench.traced`) that falls under no `exec.*`
phase at all, in ms: the harness's loop, `float(loss)`, and whatever the
program still leaves out of `exec.step`. Computed from the gaps directly,
not as a difference (`_account.py`)."""
from benchmark.layer_metrics import _account


def read(record):
    account = _account.account_of(record)
    return account["unnamed_ms"] if account else None
