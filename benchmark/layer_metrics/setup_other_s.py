"""Executor host path: `setup_s` minus `compile_trace_s`, `compile_lower_s`,
`compile_backend_s` and `first_execute_s`: attach, the batches, program
build and startup, weights, the check's readings, steps 2-3, the warm-up
and any compile outside `Executor`. The five add up to `setup_s`
(`_setup.py`)."""
from benchmark.layer_metrics import _setup


def read(record):
    return _setup.other_s(record)
