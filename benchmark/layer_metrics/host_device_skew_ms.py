"""Executor host path: over the traced steps, the largest violation of the
blocking loop's causality between the host's thread line and the first
chip's `XLA Modules` line: max(0, `exec.execute` start - program start,
program end - `exec.fetch` end), in ms. 0 where the two clocks of the trace
agree to within the dispatch latency; what it reads is the error bar of
every `idle_*_ms` (`_account.py`)."""
from benchmark.layer_metrics import _account


def read(record):
    return _account.skew_of(record)
