"""From the profiler's trace to numbers: which events are device
operations, the busy union, the idle share, per-step busy time, idle gaps
by what the host was doing, and collective time not hidden behind compute.

The arithmetic works on plain lists of (name, start_ns, end_ns), so it is
checked on hand-made lists; `read_xplane` turns a recorded `.xplane.pb` into
such lists with nothing but JAX.

What a TPU trace looks like (looked at by hand, PERF.md "Layers"): one plane
per chip named `/device:TPU:<n>`, whose line `XLA Ops` holds one event per
executed HLO operation (named by the instruction's whole HLO text:
`%fusion.123 = ... fusion(...)`, `%copy-done.4 = ...`, a Pallas kernel as
`%<jax scope>.N = ... custom-call(...)`) and whose line
`XLA Modules` holds one event per executed program; the plane `/host:CPU`
holds one line per host thread with the runtime's own events and the
benchmark's `TraceAnnotation`s.
"""
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast)(-start|-done)?$")


def op_kind(name):
    """`%fusion.123 = ... fusion(...)` -> `fusion`: the instruction's name
    without its number, so the same operation adds up over layers and
    steps. A custom call (a Pallas kernel among them) is named after the
    JAX scope it was traced in (`%jvp__.21 = ... custom-call(...)`), which
    says nothing of what it is, so it becomes `custom-call:jvp__`."""
    head = name.lstrip("%").split(" ")[0]
    kind = re.sub(r"[.\d]+$", "", head) or head
    if " custom-call(" in name:
        return "custom-call:" + kind
    return kind


def is_collective(name):
    return bool(COLLECTIVE.match(op_kind(name)))


def merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals):
    return sum(e - s for s, e in intervals)


def busy_union(events, lo=None, hi=None):
    """Merged intervals in which any of `events` ran, clipped to lo..hi."""
    merged = merge([(s, e) for _n, s, e in events])
    if lo is not None:
        merged = clip(merged, lo, hi)
    return merged


def idle_gaps(busy, lo, hi):
    """The (start, end) gaps of lo..hi that `busy` (merged) leaves."""
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def subtract(intervals, holes):
    """`intervals` (merged) minus `holes` (merged)."""
    out = []
    for s, e in intervals:
        at = s
        for hs, he in holes:
            if he <= at or hs >= e:
                continue
            if hs > at:
                out.append((at, hs))
            at = max(at, he)
        if at < e:
            out.append((at, e))
    return out


def exposed_collective_ns(events, lo, hi):
    """Time in lo..hi in which a collective operation ran on this device
    and no other operation did."""
    coll = busy_union([ev for ev in events if is_collective(ev[0])], lo, hi)
    comp = busy_union([ev for ev in events if not is_collective(ev[0])],
                      lo, hi)
    return total(subtract(coll, comp))


def per_step_busy_ns(ops, modules):
    """Busy time inside each run of the step program: the program of
    `modules` (events of the XLA Modules line) with the most total time is
    the step; returns one number per run of it, in order."""
    if not modules:
        return []
    by_name = {}
    for n, s, e in modules:
        by_name[n] = by_name.get(n, 0) + (e - s)
    step = max(by_name, key=by_name.get)
    merged = busy_union(ops)
    return [total(clip(merged, s, e)) for n, s, e in sorted(
        modules, key=lambda ev: ev[1]) if n == step]


def attribute_gap(gap, host_events):
    """What the host was doing in `gap`: of the host events that cover its
    middle, the outermost `bench.*` annotation and the innermost event of
    all, as `outer>inner`; `(nothing recorded)` where none covers it."""
    mid = (gap[0] + gap[1]) / 2.0
    covering = [ev for ev in host_events if ev[1] <= mid < ev[2]]
    if not covering:
        return "(nothing recorded)"
    bench = [ev for ev in covering if ev[0].startswith("bench.")]
    inner = min(covering, key=lambda ev: ev[2] - ev[1])
    if not bench:
        return inner[0]
    outer = max(bench, key=lambda ev: ev[2] - ev[1])
    return outer[0] if inner is outer else "%s>%s" % (outer[0], inner[0])


def top_ops(events, lo, hi, n=10):
    """[[kind, seconds]] of the n operation kinds with the most device
    time in lo..hi."""
    by_kind = {}
    for name, s, e in events:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            k = op_kind(name)
            by_kind[k] = by_kind.get(k, 0) + d
    ranked = sorted(by_kind.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def read_xplane(path):
    """{"devices": {chip number: {"ops": [...], "modules": [...]}},
    "host": {thread line: [...]}} with events as (name, start_ns, end_ns).
    `path` is an .xplane.pb file or a directory searched for one."""
    import jax.profiler
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError("no .xplane.pb under %s" % path)
        path = found[-1]
    data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    dev[key].extend(_events(line))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host[line.name] = _events(line)
    return {"devices": devices, "host": host}


def _events(line):
    return [(ev.name, float(ev.start_ns),
             float(ev.start_ns) + float(ev.duration_ns))
            for ev in line.events]


def reduce_trace(trace, span_name="bench.traced", chips=None):
    """The traced window's numbers. The window is the host annotation
    `span_name` (the benchmark wraps the traced steady steps in it); host
    and device events share the trace's clock.

    Returns a dict: window_s, busy_s (mean over chips), idle_share,
    step_busy_ms (median per-step busy time on the busiest chip's list),
    exposed_collective_s (mean over chips), op_seconds ({kind: s}, mean
    over chips), device_ops and idle_gaps for `breakdown`."""
    import statistics
    bench_line, window = None, None
    for name, events in trace["host"].items():
        for ev in events:
            if ev[0] == span_name:
                bench_line, window = name, (ev[1], ev[2])
    if window is None:
        raise ValueError("no %r annotation in the trace" % span_name)
    lo, hi = window
    devices = trace["devices"]
    if chips is not None:
        devices = {k: devices[k] for k in sorted(devices)[:chips]}
    if not devices:
        raise ValueError("no device plane in the trace")
    busy_ns, exposed_ns, steps, op_s = [], [], [], {}
    first = None
    for dev in sorted(devices):
        ops = devices[dev]["ops"]
        busy = busy_union(ops, lo, hi)
        busy_ns.append(total(busy))
        exposed_ns.append(exposed_collective_ns(ops, lo, hi))
        in_window = [m for m in devices[dev]["modules"]
                     if m[1] >= lo and m[2] <= hi]
        steps.append(per_step_busy_ns(ops, in_window))
        for kind, s in top_ops(ops, lo, hi, n=10 ** 6):
            op_s[kind] = op_s.get(kind, 0.0) + s / len(devices)
        if first is None:
            first = (ops, busy)
    host_events = trace["host"].get(bench_line, [])
    gaps = sorted(idle_gaps(first[1], lo, hi), key=lambda g: g[0] - g[1])
    per_step = [s for per_dev in steps for s in per_dev]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": statistics.mean(busy_ns) / 1e9,
        "idle_share": 1.0 - statistics.mean(busy_ns) / (hi - lo),
        "step_busy_ms": (statistics.median(per_step) / 1e6
                         if per_step else None),
        "steps_seen": len(steps[0]) if steps else 0,
        "exposed_collective_s": statistics.mean(exposed_ns) / 1e9,
        "op_seconds": op_s,
        "device_ops": top_ops(first[0], lo, hi, n=10),
        "idle_gaps": [[attribute_gap(g, host_events), (g[1] - g[0]) / 1e9]
                      for g in gaps[:10]],
    }
