"""Step program: device-busy time (union of the XLA Ops intervals) inside
each traced run of the step program, median over runs and chips, in ms."""


def read(record):
    traced = record.get("traced")
    return traced["step_busy_ms"] if traced else None
