"""Executor host path: seconds of set-up from the last backend compile's
end to the return of the step's first call: executable load, donation,
the first dispatch; summed over the step-cache misses of
`executor.miss_log()` (`_setup.py`)."""
from benchmark.layer_metrics import _setup


def read(record):
    return _setup.stage_s(record, "first_execute_s")
