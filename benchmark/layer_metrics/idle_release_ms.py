"""Executor host path: per traced step, the device-idle time (gaps of the
first chip's busy union inside `bench.traced`) that falls under the
Executor's span `exec.release`, in ms: the step's references to the old
state's handles, the feed and the new state dying after the fetch has
returned. 0.0 for a program that has `exec.*` spans and not this one
(`_account.py`)."""
from benchmark.layer_metrics import _account


def read(record):
    account = _account.account_of(record)
    return account["release_ms"] if account else None
