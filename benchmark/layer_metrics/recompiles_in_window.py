"""Executor host path: `Executor.cache_misses` after the window minus
before it. Anything but 0 means a step compiled inside the window."""


def read(record):
    return float(record["recompiles"])
