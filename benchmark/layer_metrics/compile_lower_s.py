"""Executor host path: seconds of set-up inside JAX's own
`jaxpr_to_mlir_module_duration` events: the lowering of the step's jaxpr
to StableHLO in Python, every Pallas kernel's Mosaic lowering in it;
summed over the step-cache misses of `executor.miss_log()` (`_setup.py`)."""
from benchmark.layer_metrics import _setup


def read(record):
    return _setup.stage_s(record, "compile_lower_s")
