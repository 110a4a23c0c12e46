"""Executor host path: per traced step, the device-idle time (gaps of the
first chip's busy union inside `bench.traced`) that falls under the
Executor's spans `exec.prepare` or `exec.feed`, in ms. The spans are read from
the host plane, where obs mirrors them as `TraceAnnotation`s
(`_scopes.py`)."""
from benchmark.layer_metrics import _scopes


def read(record):
    idle = _scopes.idle_of(record)
    return idle["feed_ms"] if idle else None
