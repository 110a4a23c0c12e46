"""Shared by the metrics that read the program's own names out of the
traced window: the role/op scopes on the device's operations
(`fwd_device_ms`, `bwd_device_ms`, `opt_device_ms`, `unscoped_device_pct`)
and the Executor's phase spans on the host's thread line (`idle_feed_ms`,
`idle_dispatch_ms`, `idle_fetch_ms`). Imports nothing of `paddle_tpu`.

What the program writes, and where it lands in the trace (PERF.md
"Layers" records what was seen on the chip):

- `framework/trace.py` lowers every op under
  `jax.named_scope("<role>/<op type>")`, so an operation's `op_name` reads
  `jit(step)/forward/fc/jvp()/dot_general`,
  `jit(step)/backward/fc/transpose(jvp())/dot_general`,
  `jit(step)/optimize/adam/sub`. Ops of a sub-block nest, inside JAX's
  own brackets too: `jit(step)/backward/remat_block/transpose(jvp(forward/
  remat_block))/jvp()/checkpoint/rematted_computation/forward/fc/dot_general`.
  The ROLE of an operation is the outermost scope's (a recomputed forward
  counts under backward), its OP TYPE the innermost scope's. A Pallas
  kernel's `name=` is the path component before `pallas_call`.
- The profiler stores that `op_name` as the stat `tf_op` of each `XLA Ops`
  event's METADATA, which `jax.profiler.ProfileData` does not show; so the
  `.xplane.pb` is read here a second time, by a bare reader of the
  protobuf wire format (the schema's Python module ships only inside
  TensorFlow, which a run does not import). `test_scopes.py` holds it to
  `ProfileData` on the recorded traces: same events, same times.
- While obs is on, an open `exec.*` span is also a `TraceAnnotation`, so it
  sits on the host plane's thread line, under `bench.exe_run`.

A fusion takes the role its own `tf_op` names: where XLA fused operations
of two roles into one fusion, all of it goes to that one (PERF.md says how
much of the BERT step that is).
"""
import glob
import os
import re
import statistics

from benchmark import trace_reduce

TRACE_DIR = ".bench_trace"          # harness.TRACE_DIR
WINDOW = "bench.traced"
ROLE_BUCKET = {"forward": "fwd", "amp": "fwd", "backward": "bwd",
               "optimize": "opt", "lr_sched": "opt"}
SCOPE = re.compile(r"(?:^|[/(;])(%s)/([A-Za-z0-9_]+)"
                   % "|".join(ROLE_BUCKET))
KERNEL = re.compile(r"([A-Za-z0-9_]+)/pallas_call\b")
IDLE_BUCKET = {"exec.prepare": "feed", "exec.feed": "feed",
               "exec.compile": "dispatch", "exec.execute": "dispatch",
               "exec.writeback": "fetch", "exec.fetch": "fetch"}


def parse_scope(tf_op):
    """(role, op type, kernel name) of one operation's `tf_op`; role and
    type are None where the name carries no scope of the program's,
    kernel is None unless the operation is a named Pallas kernel."""
    found = SCOPE.findall(tf_op or "")
    kernel = KERNEL.search(tf_op or "")
    kernel = kernel.group(1) if kernel else None
    if not found:
        return None, None, kernel
    return found[0][0], found[-1][1], kernel


# ---------------------------------------------------------------------------
# the wire-format reader: XSpace > XPlane > XLine > XEvent, with
# XEventMetadata, XStatMetadata and XStat (tsl/profiler/protobuf/xplane.proto)
# ---------------------------------------------------------------------------

def _fields(buf):
    """[(field number, wire type, value)] of one message: varints as int,
    length-delimited fields as memoryview, fixed64/32 as bytes."""
    out, i, n = [], 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = bytes(buf[i:i + 8])
            i += 8
        elif wire == 5:
            value = bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError("wire type %d in an xplane file" % wire)
        out.append((key >> 3, wire, value))
    return out


def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _text(value):
    return bytes(value).decode("utf-8", "replace")


def _stat(buf, stat_names):
    """(stat name, value) of one XStat: a string, or the name a `ref_value`
    points to; None for the numeric kinds, which nothing here reads."""
    name = value = None
    for num, _wire, v in _fields(buf):
        if num == 1:
            name = stat_names.get(v)
        elif num == 5:
            value = _text(v)
        elif num == 7:
            value = stat_names.get(v)
    return name, value


def _plane(buf):
    """{"name", "lines": [{"name", "events": [(name, start_ns, end_ns,
    {metadata stat: value})]}]} of one XPlane."""
    name, lines, event_md, stat_md = "", [], {}, {}
    for num, _wire, v in _fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 3:
            lines.append(v)
        elif num in (4, 5):
            entry = dict((n, val) for n, _w, val in _fields(v))
            (event_md if num == 4 else stat_md)[entry.get(1, 0)] = \
                entry.get(2, b"")
    stat_names = {}
    for key, md in stat_md.items():
        for num, _wire, v in _fields(md):
            if num == 2:
                stat_names[key] = _text(v)
    metadata = {}
    for key, md in event_md.items():
        md_name, stats = "", {}
        for num, _wire, v in _fields(md):
            if num == 2:
                md_name = _text(v)
            elif num == 5:
                stat, value = _stat(v, stat_names)
                stats[stat] = value
        metadata[key] = (md_name, stats)
    return {"name": name,
            "lines": [_line(v, metadata) for v in lines]}


def _line(buf, metadata):
    name, timestamp_ns, events = "", 0, []
    for num, _wire, v in _fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 3:
            timestamp_ns = v
        elif num == 4:
            events.append(v)
    out = []
    for ev in events:
        md = offset_ps = duration_ps = 0
        for num, _wire, v in _fields(ev):
            if num == 1:
                md = v
            elif num == 2:
                offset_ps = v
            elif num == 3:
                duration_ps = v
        md_name, stats = metadata.get(md, ("", {}))
        start = timestamp_ns + offset_ps / 1e3
        out.append((md_name, start, start + duration_ps / 1e3, stats))
    return {"name": name, "events": out}


def find_xplane(path):
    """`path` itself, or the newest .xplane.pb under the directory `path`
    (as `trace_reduce.read_xplane` finds it)."""
    if not os.path.isdir(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % path)
    return found[-1]


def read_planes(path):
    """Every plane of an .xplane.pb, events with their metadata's stats."""
    with open(find_xplane(path), "rb") as f:
        space = memoryview(f.read())
    return [_plane(v) for num, _wire, v in _fields(space) if num == 1]


def read_xplane(path):
    """What `trace_reduce.read_xplane` returns, with each device operation
    as (name, start_ns, end_ns, tf_op)."""
    devices, host = {}, {}
    for plane in read_planes(path):
        m = trace_reduce.DEVICE_PLANE.match(plane["name"])
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"ops": [], "modules": []})
            for line in plane["lines"]:
                if line["name"] == trace_reduce.OPS_LINE:
                    dev["ops"].extend(
                        (n, s, e, stats.get("tf_op") or "")
                        for n, s, e, stats in line["events"])
                elif line["name"] == trace_reduce.MODULES_LINE:
                    dev["modules"].extend(ev[:3] for ev in line["events"])
        elif plane["name"] == "/host:CPU":
            for line in plane["lines"]:
                host[line["name"]] = [ev[:3] for ev in line["events"]]
    return {"devices": devices, "host": host}


# ---------------------------------------------------------------------------
# the arithmetic, on plain event lists
# ---------------------------------------------------------------------------

def exclusive_ns(events, lo, hi):
    """{key: ns} of lo..hi, each instant given to the innermost running
    event of `events` [(key, start, end)] (a `while` holds its body's
    operations): the values add up to the events' busy union."""
    out, stack, at = {}, [], lo
    clipped = sorted((max(s, lo), -min(e, hi), key) for key, s, e in events
                     if min(e, hi) > max(s, lo))

    def advance(to):
        nonlocal at
        while stack:
            end, key = stack[-1]
            upto = min(end, to)
            if upto > at:
                out[key] = out.get(key, 0.0) + upto - at
                at = upto
            if end > to:
                return
            stack.pop()
        at = max(at, to)

    for start, neg_end, key in clipped:
        advance(start)
        at = max(at, start)
        stack.append((-neg_end, key))
    advance(hi)
    return out


def step_runs(modules, lo, hi):
    """(start, end) of each run inside lo..hi of the step program: the
    program of the `XLA Modules` line with the most time, as
    `trace_reduce.per_step_busy_ns` picks it."""
    inside = [m for m in modules if m[1] >= lo and m[2] <= hi]
    by_name = {}
    for n, s, e in inside:
        by_name[n] = by_name.get(n, 0) + (e - s)
    if not by_name:
        return []
    step = max(by_name, key=by_name.get)
    return sorted((s, e) for n, s, e in inside if n == step)


def window_of(host):
    """(thread line, lo, hi) of the `bench.traced` annotation, or None."""
    for line, events in host.items():
        for n, s, e in events:
            if n == WINDOW:
                return line, s, e
    return None


def role_split(trace):
    """Per traced step of the step program, device time by role. Returns
    {"fwd_ms", "bwd_ms", "opt_ms", "unscoped_ms", "step_ms",
    "unscoped_pct", "steps"} (medians over steps and chips), or None where
    the trace has no device plane, no window, no step, or no operation
    that names a role (a program without the scopes)."""
    window = window_of(trace["host"])
    if not trace["devices"] or window is None:
        return None
    _line_name, lo, hi = window
    per_step = []
    for dev in sorted(trace["devices"]):
        ops = [(tf_op, a, b)
               for _n, a, b, tf_op in trace["devices"][dev]["ops"]]
        for s, e in step_runs(trace["devices"][dev]["modules"], lo, hi):
            split = dict.fromkeys(("fwd", "bwd", "opt", "unscoped"), 0.0)
            for tf_op, ns in exclusive_ns(ops, s, e).items():
                role = parse_scope(tf_op)[0]
                split[ROLE_BUCKET.get(role, "unscoped")] += ns
            per_step.append(split)
    if not per_step or not any(s["fwd"] + s["bwd"] + s["opt"]
                               for s in per_step):
        return None
    out = {k + "_ms": statistics.median(s[k] for s in per_step) / 1e6
           for k in ("fwd", "bwd", "opt", "unscoped")}
    totals = [sum(s.values()) for s in per_step]
    out["step_ms"] = statistics.median(totals) / 1e6
    out["unscoped_pct"] = 100.0 * statistics.median(
        s["unscoped"] / t for s, t in zip(per_step, totals) if t)
    out["steps"] = len(per_step)
    return out


def idle_split(trace):
    """Per traced step, the device-idle time (gaps of the first chip's
    busy union inside the window) that falls under the Executor's phase
    spans: {"feed_ms", "dispatch_ms", "fetch_ms", "idle_ms"}; None where
    the trace has no device plane, no window, or no `exec.*` span on the
    window's thread line (obs off, or a program without the mirror)."""
    window = window_of(trace["host"])
    if not trace["devices"] or window is None:
        return None
    line, lo, hi = window
    first = trace["devices"][sorted(trace["devices"])[0]]
    steps = len(step_runs(first["modules"], lo, hi))
    spans = {}
    for n, s, e in trace["host"][line]:
        if n in IDLE_BUCKET and e > lo and s < hi:
            spans.setdefault(IDLE_BUCKET[n], []).append((s, e))
    if not spans or not steps:
        return None
    busy = trace_reduce.busy_union([ev[:3] for ev in first["ops"]], lo, hi)
    gaps = trace_reduce.idle_gaps(busy, lo, hi)
    out = {"idle_ms": trace_reduce.total(gaps) / steps / 1e6}
    for bucket in ("feed", "dispatch", "fetch"):
        covered = trace_reduce.merge(spans.get(bucket, []))
        inside = trace_reduce.total(gaps) - trace_reduce.total(
            trace_reduce.subtract(gaps, covered))
        out[bucket + "_ms"] = inside / steps / 1e6
    return out


# ---------------------------------------------------------------------------
# on a run's record: parse once, keep what was computed
# ---------------------------------------------------------------------------

def _cached(record, key, make):
    cache = record.setdefault("_scopes", {})
    if key not in cache:
        cache[key] = make()
    return cache[key]


def trace_of(record):
    """The traced window's xplane, read with `tf_op`; None where the run
    was not traced or its trace has no device plane."""
    def make():
        if not record.get("traced"):
            return None
        cell = record["cell"]
        path = os.path.join(cell.root, TRACE_DIR, cell.name)
        trace = read_xplane(path)
        return trace if trace["devices"] else None
    return _cached(record, "trace", make)


def roles_of(record):
    def make():
        trace = trace_of(record)
        return role_split(trace) if trace else None
    return _cached(record, "roles", make)


def idle_of(record):
    def make():
        trace = trace_of(record)
        return idle_split(trace) if trace else None
    return _cached(record, "idle", make)
