"""Shared by the six metrics that say where `setup_s` went: the step's
compile stages as the program's own miss log holds them, and what is left.

What the program writes (`framework/executor.py`): `executor.miss_log()` is
the process's last step-cache misses, one dict a miss, kept whether obs is
on or off. `trace_s`, `lower_s` and `backend_s` are JAX's own compile
events (`jax.monitoring`) between the miss's `exec.compile` boundary and
the return of the step's first call, each stage the union of its
intervals and an instant in one stage only; `first_run_s` runs from the
last backend compile's end to that return; `cache_requests` and
`cache_hits` count what the persistent compile cache was asked and gave.
`recompiles_in_window` holds the window to no miss, the reference imports
nothing of `paddle_tpu` and compiles outside `Executor`: the log at read
time is set-up's.

The five times add up to `setup_s`: `setup_other_s` is the harness's
`setup_s` minus the four stage sums (attach, the batches, program build
and startup, weights, the check's readings, steps 2-3 and the warm-up, the
builder's own milliseconds, and any compile outside `Executor`).

A program without a miss log (the parent of PR 51) gives every reader
nothing to read, and so does a run off the TPU (`record["peaks"]` is None
there): a CPU compiler's seconds are not written under the names of the
chip's. The tests hand a log in as `record["miss_log"]`.
"""
STAGES = {"compile_trace_s": "trace_s", "compile_lower_s": "lower_s",
          "compile_backend_s": "backend_s", "first_execute_s": "first_run_s"}


def log_of(record):
    """The miss log: the record's own where it carries one, else the
    program's; None off the TPU and where the program keeps none."""
    if "miss_log" in record:
        return record["miss_log"]
    if record.get("peaks") is None:
        return None
    from paddle_tpu.framework import executor
    read = getattr(executor, "miss_log", None)
    return None if read is None else read()


def _sum(log, key):
    return float(sum(miss[key] for miss in log))


def stage_s(record, metric):
    """Sum of one stage over the log's misses; None without a miss."""
    log = log_of(record)
    return _sum(log, STAGES[metric]) if log else None


def cache_hit_pct(record):
    """Of the compile requests that asked the persistent cache, the share
    it answered; None where no miss asked it (the cache is off)."""
    log = log_of(record) or ()
    requests = _sum(log, "cache_requests")
    return 100.0 * _sum(log, "cache_hits") / requests if requests else None


def other_s(record):
    """`setup_s` minus the four stage sums; None without a miss."""
    log = log_of(record)
    if not log:
        return None
    return float(record["setup_s"]) - sum(
        _sum(log, key) for key in STAGES.values())
