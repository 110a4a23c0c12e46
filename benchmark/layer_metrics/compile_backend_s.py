"""Executor host path: seconds of set-up inside JAX's own
`backend_compile_duration` events: XLA's compile, or the persistent
cache's load in its place; summed over the step-cache misses of
`executor.miss_log()` (`_setup.py`)."""
from benchmark.layer_metrics import _setup


def read(record):
    return _setup.stage_s(record, "compile_backend_s")
