"""obs — the distributed-tracing spans engine.

Reference parity: the reference stack ships a real profiler
(python/paddle/fluid/profiler.py + tools/timeline.py renders
chrome://tracing timelines of op runs). ``paddle_tpu/profiler.py``
wraps jax.profiler — which sees XLA internals but nothing of OUR
layers — and the system now spans processes (routers, replicas,
replicated CoordServers, elastic pods) where the questions that matter
("where did this request's 800ms go — queue, coalesce, dispatch,
replica step, or retry?") cross process boundaries no per-process
metric can attribute. This module is the layer that can: cheap
in-process spans with DISTRIBUTED trace context.

Design:

  * A **span** is one timed operation: ``(trace, id, parent, name,
    t0, t1, labels, tid)``. Trace/span ids are random hex; parentage
    links spans into one request tree ACROSS processes.
  * **Trace context** rides a thread-local stack in-process and the
    ``x-trace-id: <trace>:<span>`` HTTP header between processes
    (:func:`header` / :func:`parse_header`).
  * Finished spans land in a **bounded per-process ring**
    (``PADDLE_TPU_TRACE_RING``, default 8192); overflow evicts the
    oldest and counts ``dropped_total()`` — exported by
    ``resilience.metrics()`` as ``trace_spans_dropped_total`` so a
    lying (truncated) timeline is loud, never silent.
  * **Near-zero cost when disabled** (the default): :func:`span`
    checks one module flag and returns a shared no-op context
    manager — no allocation, no clock read. Enable with
    ``PADDLE_TPU_TRACE=1`` or :func:`enable`.
  * **Timestamps** are wall-clock anchored monotonic seconds: each
    process pins ``(time.time(), time.monotonic())`` once at import
    and every span time is ``anchor_wall + (mono - anchor_mono)`` —
    monotonic within the process, comparable across same-host
    processes. For multi-host alignment :func:`probe_clock_offset`
    measures this process's offset against the coordination server's
    clock (min-RTT sample of the ``time`` op) and the offset is
    applied at EXPORT time, so all processes land on the
    coordinator's timeline.
  * **Export** is the Chrome trace event format
    (:func:`chrome_trace`): one Perfetto-loadable JSON merging any
    number of per-process :func:`dump_dict` blobs —
    ``tools/traceview.py`` is the CLI (files and/or live
    ``/admin/trace`` pulls).
  * **Mirror into the profiler's trace**: while the engine is enabled
    an open span also enters a ``jax.profiler.TraceAnnotation`` of the
    same name, so that when a ``jax.profiler`` session is running the
    span sits on the host plane's thread line ON THE PROFILER'S CLOCK,
    beside the device's operations — how a device-idle gap is named
    after the Executor phase the host was in (``exec.feed``,
    ``exec.execute``, ``exec.fetch``). It is the only clock bridge: no
    offset is estimated. The benchmark CHECKS the bridge on every traced
    step by the causality a blocking loop guarantees (the step program
    starts no earlier than ``exec.execute`` opens and ends no later than
    ``exec.fetch`` closes): ``host_device_skew_ms``
    (``benchmark/layer_metrics/_account.py``) is the largest violation,
    and with it the error bar of every ``idle_*_ms``. This module never
    imports JAX (coordination servers and routers use it without): the
    annotation class is picked up lazily, and only if ``jax`` is already
    in ``sys.modules``. Retroactive :func:`record` spans stay obs-only.
  * **Phases** (:meth:`_Span.phase`): a span whose work is a sequence
    of named parts opens each as a child that closes when the next one
    opens (or the span itself closes), both at ONE reading of the clock,
    which the caller may hand in. The children then tile the span with
    no time between them: how ``exec.step`` accounts for every instant
    of an ``Executor.run``.

Span taxonomy (what the built-in instrumentation emits) is documented
in PORTING.md "Observability & tracing".
"""
import contextlib
import collections
import json
import os
import random
import sys
import threading
import time

__all__ = [
    "enabled", "enable", "disable", "span", "record", "current",
    "new_trace_id", "header", "parse_header", "spans", "clear",
    "dropped_total", "set_service", "service", "dump_dict", "dump",
    "clock_offset", "set_clock_offset", "probe_clock_offset",
    "chrome_trace", "now", "RING_CAPACITY",
]

RING_CAPACITY = int(os.environ.get("PADDLE_TPU_TRACE_RING", "8192")
                    or 8192)

# one wall anchor per process: span times are monotonic WITHIN the
# process but live on the wall-clock axis, so same-host processes
# already align and the coordinator offset handles the rest
_ANCHOR_WALL = time.time()
_ANCHOR_MONO = time.monotonic()

_state = {
    "enabled": os.environ.get("PADDLE_TPU_TRACE", "") not in ("", "0"),
    "service": os.environ.get("PADDLE_TPU_TRACE_SERVICE") or None,
    "service_env": bool(os.environ.get("PADDLE_TPU_TRACE_SERVICE")),
    "clock_offset": 0.0,
    "dropped": 0,
}
_ring = collections.deque(maxlen=RING_CAPACITY)
_lock = threading.Lock()
_tls = threading.local()
# ids from the process-seeded global RNG would correlate across forked
# workers; a dedicated SystemRandom never collides
_rng = random.SystemRandom()


_annotation = None


def _annotation_class():
    """``jax.profiler.TraceAnnotation`` once the process has imported
    JAX, else None. Never imports JAX itself."""
    global _annotation
    if _annotation is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


def now():
    """The engine's timebase: wall-anchored monotonic seconds. Use for
    retroactive :func:`record` timestamps so they live on the same
    axis as context-manager spans."""
    return _ANCHOR_WALL + (time.monotonic() - _ANCHOR_MONO)


def enabled():
    return _state["enabled"]


def enable(service=None):
    """Turn the spans engine on (idempotent). ``service`` names this
    process in merged timelines (falls back to ``pid<pid>``)."""
    if service is not None:
        set_service(service)
    _state["enabled"] = True


def disable():
    _state["enabled"] = False


def set_service(name, force=True):
    """Name this process for merged timelines. ``force=False`` keeps
    an operator-provided PADDLE_TPU_TRACE_SERVICE (or an earlier
    explicit set) — how ReplicaMember/FleetRouter self-name without
    clobbering deployment config."""
    if not force and (_state["service_env"]
                      or _state["service"] is not None):
        return
    _state["service"] = str(name)


def service():
    return _state["service"] or ("pid%d" % os.getpid())


def new_trace_id():
    return "%016x" % _rng.getrandbits(64)


def _new_span_id():
    return "%08x" % _rng.getrandbits(32)


def current():
    """(trace_id, span_id) of this thread's innermost open span, or
    ``None`` — what child spans and outgoing headers parent under."""
    stack = getattr(_tls, "stack", None)
    if not stack:
        return None
    return stack[-1]


def header(ctx=None):
    """The ``x-trace-id`` header value for the current (or given)
    context: ``"<trace>:<span>"``; None when there is nothing open."""
    ctx = ctx if ctx is not None else current()
    if not ctx:
        return None
    return "%s:%s" % ctx


def parse_header(value):
    """Parse an ``x-trace-id`` header into ``(trace_id,
    parent_span_id)``; ``(None, None)`` for absent/malformed values —
    a bad header degrades to an un-traced request, never a 500."""
    if not value or not isinstance(value, str):
        return None, None
    parts = value.strip().split(":")
    if len(parts) != 2 or not parts[0]:
        return None, None
    return parts[0], (parts[1] or None)


def _push(trace, span_id):
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    stack.append((trace, span_id))


def _pop():
    stack = getattr(_tls, "stack", None)
    if stack:
        stack.pop()


def _commit(entry):
    with _lock:
        if len(_ring) == _ring.maxlen:
            _state["dropped"] += 1
        _ring.append(entry)


class _Span(object):
    """An OPEN span (context manager). ``set(**labels)`` annotates it
    mid-flight (outcome labels land just before close)."""

    __slots__ = ("trace", "id", "parent", "name", "t0", "labels",
                 "mirror", "leaf")

    def __init__(self, name, trace, parent, labels, t0=None):
        self.name = name
        self.trace = trace
        self.id = _new_span_id()
        self.parent = parent
        self.labels = labels
        self.leaf = None
        annotation = _annotation_class()
        self.mirror = None if annotation is None else annotation(name)
        self.t0 = now() if t0 is None else t0

    def set(self, **labels):
        self.labels.update(labels)
        return self

    def phase(self, name, at=None):
        """Close this span's open phase, if it has one, and open the
        child ``name`` (None: open none), both at the one reading ``at``
        of :func:`now` (read here when not handed in). The open phase is
        the thread's innermost span until the next one, so spans opened
        meanwhile parent under it; the span's own close closes it."""
        at = now() if at is None else at
        if self.leaf is not None:
            self.leaf._close(at)
            self.leaf = None
        if name is not None:
            self.leaf = _Span(name, self.trace, self.id, {}, at)
            self.leaf.__enter__()

    def __enter__(self):
        _push(self.trace, self.id)
        if self.mirror is not None:
            self.mirror.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = now()
        if self.leaf is not None:
            self.leaf._close(t1, exc_type)
            self.leaf = None
        self._close(t1, exc_type)
        return False

    def _close(self, t1, exc_type=None):
        if self.mirror is not None:
            self.mirror.__exit__(None, None, None)
        _pop()
        if exc_type is not None and "error" not in self.labels:
            self.labels["error"] = exc_type.__name__
        _commit({"trace": self.trace, "id": self.id,
                 "parent": self.parent, "name": self.name,
                 "t0": self.t0, "t1": t1, "labels": self.labels,
                 "tid": threading.current_thread().name})


class _Noop(object):
    """The disabled path: one shared instance, no allocation."""

    __slots__ = ()
    trace = id = parent = None

    def set(self, **labels):
        return self

    def phase(self, name, at=None):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


def span(name, trace_id=None, parent=None, **labels):
    """Open a span as a context manager.

    With no explicit ``trace_id`` the span joins the thread's current
    trace (starting a fresh one at the root); ``parent`` defaults to
    the innermost open span. Explicit ``trace_id``/``parent`` attach
    to REMOTE context (:func:`parse_header`). A no-op (shared
    singleton, no clock read) while the engine is disabled."""
    if not _state["enabled"]:
        return _NOOP
    if trace_id is None:
        cur = current()
        if cur is not None:
            trace_id = cur[0]
            if parent is None:
                parent = cur[1]
        else:
            trace_id = new_trace_id()
    return _Span(name, trace_id, parent, labels)


def record(name, t0, t1, trace_id=None, parent=None, **labels):
    """Record an ALREADY-FINISHED span retroactively (timestamps from
    :func:`now`) — how the router accounts a request's queue wait
    after the batch cut, without holding an open span per queued
    request. Joins the thread's current trace when no explicit
    ``trace_id`` is given (same defaulting as :func:`span`). Returns
    the span id (None while disabled)."""
    if not _state["enabled"]:
        return None
    if trace_id is None:
        cur = current()
        if cur is not None:
            trace_id = cur[0]
            if parent is None:
                parent = cur[1]
    sid = _new_span_id()
    _commit({"trace": trace_id or new_trace_id(), "id": sid,
             "parent": parent, "name": name, "t0": float(t0),
             "t1": float(t1), "labels": labels,
             "tid": threading.current_thread().name})
    return sid


def spans(trace_id=None, name=None):
    """Snapshot of the ring (optionally filtered)."""
    with _lock:
        out = list(_ring)
    if trace_id is not None:
        out = [s for s in out if s["trace"] == trace_id]
    if name is not None:
        out = [s for s in out if s["name"] == name]
    return out


def dropped_total():
    with _lock:
        return _state["dropped"]


def clear():
    with _lock:
        _ring.clear()
        _state["dropped"] = 0


# ---------------------------------------------------------------------------
# cross-process clock alignment
# ---------------------------------------------------------------------------

def clock_offset():
    return _state["clock_offset"]


def set_clock_offset(seconds):
    _state["clock_offset"] = float(seconds)


def probe_clock_offset(call, samples=5):
    """Estimate this process's clock offset against the coordination
    server and install it (applied to every exported timestamp).

    ``call(cmd)`` is a request function returning the server's
    response dict — e.g. ``lambda cmd: coord._call(cmd)`` against the
    CoordServer ``time`` op (``{"wall": <server time.time()>}``). The
    classic NTP-style midpoint estimate, keeping the MINIMUM-RTT
    sample (least queueing noise): ``offset = server_wall -
    (t0+t1)/2``. Same-host fleets land near zero; multi-host fleets
    land every process on the coordinator's timeline."""
    best = None
    for _ in range(max(1, int(samples))):
        t0 = now()
        resp = call("time")
        t1 = now()
        off = float(resp["wall"]) - (t0 + t1) / 2.0
        rtt = t1 - t0
        if best is None or rtt < best[0]:
            best = (rtt, off)
    set_clock_offset(best[1])
    return best[1]


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def dump_dict():
    """This process's span dump: what ``/admin/trace`` serves and
    ``tools/traceview.py`` merges. Timestamps stay RAW; the recorded
    ``clock_offset_s`` is applied by the merge so re-probing never
    double-shifts."""
    return {"format": "paddle_tpu_trace", "version": 1,
            "service": service(), "pid": os.getpid(),
            "clock_offset_s": clock_offset(),
            "dropped": dropped_total(), "spans": spans()}


def dump(path):
    """Write :func:`dump_dict` to ``path`` (one JSON object)."""
    with open(path, "w") as f:
        json.dump(dump_dict(), f)
    return path


def chrome_trace(dumps=None):
    """Merge per-process span dumps into ONE Chrome-trace-event JSON
    (``{"traceEvents": [...]}``, Perfetto / chrome://tracing
    loadable). ``dumps`` is a list of :func:`dump_dict`-shaped blobs
    (default: this process's own). Every span becomes a complete
    ("X") event carrying its trace/span/parent ids in ``args`` so the
    cross-process parentage survives into the viewer; process and
    thread metadata events name the lanes."""
    if dumps is None:
        dumps = [dump_dict()]
    events = []
    for d in dumps:
        pid = int(d.get("pid") or 0)
        off = float(d.get("clock_offset_s") or 0.0)
        svc = d.get("service") or ("pid%d" % pid)
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "tid": 0, "args": {"name": svc}})
        tids = {}
        for s in d.get("spans", ()):
            tname = s.get("tid") or "main"
            tid = tids.get(tname)
            if tid is None:
                tid = tids[tname] = len(tids) + 1
                events.append({"ph": "M", "name": "thread_name",
                               "pid": pid, "tid": tid,
                               "args": {"name": tname}})
            args = dict(s.get("labels") or {})
            args.update({"trace_id": s["trace"], "span_id": s["id"],
                         "parent_id": s.get("parent"),
                         "service": svc})
            events.append({
                "ph": "X", "cat": "paddle_tpu", "name": s["name"],
                "pid": pid, "tid": tid,
                "ts": round((s["t0"] + off) * 1e6, 3),
                "dur": round(max(0.0, s["t1"] - s["t0"]) * 1e6, 3),
                "args": args})
    return {"traceEvents": events, "displayTimeUnit": "ms"}
