"""Executor host path: per traced step, the device-idle time (gaps of the
first chip's busy union inside `bench.traced`) that falls under the
Executor's span `exec.records`, in ms: what the traced run's own counters
(`Program.record_step_state`: an expert model's `moe.load`) cost the chip,
which a `--trace 0` run does not pay. 0.0 where no layer registered one
(`_account.py`)."""
from benchmark.layer_metrics import _account


def read(record):
    account = _account.account_of(record)
    return account["records_ms"] if account else None
