"""Executor host path: seconds of set-up inside JAX's own
`jaxpr_trace_duration` events: the Python trace of the step (every op's
kernel, the backward, the optimizer), a nested jit counted once; summed
over the step-cache misses of `executor.miss_log()` (`_setup.py`)."""
from benchmark.layer_metrics import _setup


def read(record):
    return _setup.stage_s(record, "compile_trace_s")
