"""Shared by the metrics that close the account of the host's time in a
step: every device-idle millisecond of a traced step under the Executor
phase the host was in or, by name, under none (`idle_release_ms`,
`idle_records_ms`, `idle_unnamed_ms` beside `_scopes.idle_split`'s three);
how far the host's and the device's lines of the trace agree
(`host_device_skew_ms`); and the window's worst step against its median
step (`step_wall_max_over_median`, `worst_step_wait_pct`). Imports nothing
of `paddle_tpu`.

What the program writes (`framework/executor.py`): with obs on `exec.step`
covers the whole of a jitted `Executor.run`, and its phases tile it:
`exec.prepare`, `exec.feed`, `exec.prepare`, (`exec.compile`,)
`exec.execute`, `exec.writeback` > `exec.fetch`, `exec.release` (the old
state's handles, the feed and the new state's tuple die here) and, where a
layer registered a counter, `exec.records` (the observer's own one
`device_get` a step). Each is a `TraceAnnotation` on the window's thread
line, so the gaps of chip 0's busy union split by them; siblings do not
overlap, so the six buckets add up to the idle time. A program without the
two new phases (the parent of PR 36) reads 0.0 in their buckets and the
time in `unnamed`.

The clocks. A blocking loop cannot start step N+1's program before
`exec.execute` of step N+1 opened, and `exec.fetch` returns only once the
program has ended. Where the trace shows otherwise the device's line sits
that much early or late against the host's, and every `idle_*_ms` carries
that error.
"""
import statistics

from benchmark import trace_reduce
from benchmark.layer_metrics import _scopes

BUCKET = dict(_scopes.IDLE_BUCKET,
              **{"exec.release": "release", "exec.records": "records"})
BUCKETS = ("feed", "dispatch", "fetch", "release", "records")


def _traced_steps(trace):
    """(the window's host events, lo, hi, chip 0, its step runs), or None
    where the trace has no device plane, no window, no step, or no `exec.*`
    span on the window's thread line (obs off)."""
    window = _scopes.window_of(trace["host"])
    if not trace["devices"] or window is None:
        return None
    line, lo, hi = window
    first = trace["devices"][sorted(trace["devices"])[0]]
    runs = _scopes.step_runs(first["modules"], lo, hi)
    events = [ev for ev in trace["host"][line]
              if ev[0].startswith("exec.") and ev[2] > lo and ev[1] < hi]
    if not runs or not events:
        return None
    return events, lo, hi, first, runs


def idle_account(trace):
    """Per traced step, chip 0's idle time inside the window, whole and
    under each bucket of phases: {"idle_ms", "feed_ms", "dispatch_ms",
    "fetch_ms", "release_ms", "records_ms", "unnamed_ms"}. `unnamed` is
    measured, not inferred: the gaps minus every `exec.*` phase."""
    found = _traced_steps(trace)
    if found is None:
        return None
    events, lo, hi, first, runs = found
    busy = trace_reduce.busy_union([ev[:3] for ev in first["ops"]], lo, hi)
    gaps = trace_reduce.idle_gaps(busy, lo, hi)
    idle = trace_reduce.total(gaps)

    def per_step(ns):
        return ns / len(runs) / 1e6
    out = {"idle_ms": per_step(idle)}
    for bucket in BUCKETS:
        covered = trace_reduce.merge(
            [(s, e) for n, s, e in events if BUCKET.get(n) == bucket])
        out[bucket + "_ms"] = per_step(idle - trace_reduce.total(
            trace_reduce.subtract(gaps, covered)))
    named = trace_reduce.merge([(s, e) for n, s, e in events
                                if n in BUCKET])
    out["unnamed_ms"] = per_step(trace_reduce.total(
        trace_reduce.subtract(gaps, named)))
    return out


def skew_ms(trace):
    """The largest violation, over the traced steps, of the loop's
    causality between the host's line and chip 0's `XLA Modules` line:
    max(0, `exec.execute` start - program start, program end - `exec.fetch`
    end), in ms. A run of the step program is held to the spans of the
    `exec.step` it overlaps most. 0.0 where the clocks agree to within the
    dispatch latency, or where the program has neither span."""
    found = _traced_steps(trace)
    if found is None:
        return None
    events, _lo, _hi, _first, runs = found
    steps = [(s, e) for n, s, e in events if n == "exec.step"]
    worst = 0.0
    for start, end in runs if steps else ():
        lo, hi = max(steps, key=lambda st: min(st[1], end)
                     - max(st[0], start))
        for name, at, _e in events:
            if name == "exec.execute" and lo <= at < hi:
                worst = max(worst, at - start)
        for name, _s, at in events:
            if name == "exec.fetch" and lo < at <= hi:
                worst = max(worst, end - at)
    return worst / 1e6


def step_walls(spans):
    """[(wall seconds, seconds inside `exec.fetch`)] of every `exec.step`
    span of `spans` (obs's dicts), in order; [] where there is none."""
    step_of = {s["id"]: s["parent"] for s in spans
               if s["name"] == "exec.writeback"}
    waited = {}
    for s in spans:
        if s["name"] == "exec.fetch":
            step = step_of.get(s["parent"])
            waited[step] = waited.get(step, 0.0) + s["t1"] - s["t0"]
    return [(s["t1"] - s["t0"], waited.get(s["id"], 0.0))
            for s in spans if s["name"] == "exec.step"]


def worst_step(spans):
    """{"max_over_median", "wait_pct"} of the window's steps: the longest
    `exec.step` over the median one, and of its excess over the median
    step the share inside `exec.fetch` (against the median step's
    `exec.fetch`), held to 0..100: the wait for the device or the runtime
    as against the host's own phases. `wait_pct` is 0.0 under a ratio of
    1.05 (no step stands out). None where there is no `exec.step`."""
    steps = step_walls(spans)
    if not steps:
        return None
    wall = statistics.median(w for w, _f in steps)
    longest, its_wait = max(steps)
    ratio = longest / wall
    wait_pct = 0.0
    if ratio >= 1.05:
        wait = statistics.median(f for _w, f in steps)
        wait_pct = 100.0 * min(1.0, max(0.0, (its_wait - wait)
                                        / (longest - wall)))
    return {"max_over_median": ratio, "wait_pct": wait_pct}


# ---------------------------------------------------------------------------
# on a run's record
# ---------------------------------------------------------------------------

def account_of(record):
    """`idle_account` of the record's traced window, computed once for the
    three readers."""
    if "_account" not in record:
        trace = _scopes.trace_of(record)
        record["_account"] = idle_account(trace) if trace else None
    return record["_account"]


def skew_of(record):
    trace = _scopes.trace_of(record)
    return skew_ms(trace) if trace else None


def worst_step_of(record):
    """`worst_step` of the window's obs spans; None off the TPU too (a
    traced run with no device plane: `test_harness.py` holds such a run to
    the three metrics that read no time of the device's)."""
    if not record.get("traced"):
        return None
    return worst_step(record.get("obs_spans") or [])
