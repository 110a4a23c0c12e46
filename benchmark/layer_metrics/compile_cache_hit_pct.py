"""Executor host path: of the compile requests a step-cache miss made of
JAX's persistent compile cache, the share it answered, in %: 100 where the
step loaded, 0 where it compiled; nothing to read where no cache is placed
(`_setup.py`)."""
from benchmark.layer_metrics import _setup


def read(record):
    return _setup.cache_hit_pct(record)
