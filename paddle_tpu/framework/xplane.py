"""Read a jax.profiler trace (``.xplane.pb``) for what the program itself
put there: each device operation with the ``op_name`` it was lowered
under.

``framework/trace.py`` lowers every op under
``jax.named_scope("<role>/<op type>")`` and every Pallas kernel carries a
``name=``, so a device operation's ``op_name`` starts
``jit(step)/forward/fc/...``, ``jit(step)/backward/fc/...``,
``jit(step)/optimize/adam/...``. The profiler keeps that name as the stat
``tf_op`` of the event's METADATA, which ``jax.profiler.ProfileData`` does
not expose, and the schema's Python module ships only inside TensorFlow —
so this is a bare reader of the protobuf wire format, of the few messages
needed (XSpace > XPlane > XLine > XEvent, XEventMetadata, XStatMetadata,
XStat; tsl/profiler/protobuf/xplane.proto). The benchmark keeps its own
reader (``benchmark/layer_metrics/_scopes.py``: a yardstick imports
nothing of the program); ``tests/benchmark_suite/test_scopes.py`` holds
the two to each other and to ``ProfileData``.
"""
import glob
import os
import re

__all__ = ["device_ops", "scope_row", "find_xplane"]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ROLES = ("forward", "backward", "optimize", "lr_sched", "amp")
_SCOPE = re.compile(r"(?:^|[/(;])(%s)/([A-Za-z0-9_]+)" % "|".join(ROLES))
_KERNEL = re.compile(r"([A-Za-z0-9_]+)/pallas_call\b")


def scope_row(op_name):
    """``<role>/<op type>`` of a device operation's ``op_name``: the role
    of the outermost scope (a recomputed forward counts under backward),
    the op type of the innermost (a ``remat_block``'s or ``while``'s body
    ops are named themselves), a Pallas kernel by its ``name=``
    (``backward/flash_fwd``); ``unscoped`` where the name carries no scope
    of the program's (copies and layout changes XLA made)."""
    found = _SCOPE.findall(op_name or "")
    kernel = _KERNEL.search(op_name or "")
    kernel = kernel.group(1) if kernel else None
    if not found:
        return kernel or "unscoped"
    return "%s/%s" % (found[0][0], kernel or found[-1][1])


def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """[(field number, value)] of one message: varints as int, length-
    delimited fields as memoryview (fixed-width fields are skipped)."""
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
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError("wire type %d in an xplane file" % wire)
        out.append((key >> 3, value))
    return out


def _text(value):
    return bytes(value).decode("utf-8", "replace")


def find_xplane(path):
    """``path`` itself, or the newest ``.xplane.pb`` under the directory
    ``path`` (where ``jax.profiler.start_trace(path)`` writes it)."""
    if not os.path.isdir(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % path)
    return found[-1]


def device_ops(path):
    """{chip number: [(instruction name, start_ns, end_ns, op_name)]}: the
    ``XLA Ops`` line of every ``/device:TPU:<n>`` plane of the trace at
    ``path`` (a file, or a directory searched for the newest one). Empty
    where the trace has no device plane (a CPU run)."""
    with open(find_xplane(path), "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        fields = _fields(plane)
        m = DEVICE_PLANE.match(next(
            (_text(v) for n, v in fields if n == 2), ""))
        if not m:
            continue
        stat_md, event_md = {}, {}      # by id: the message's fields
        for n, v in fields:
            if n in (4, 5):
                entry = dict(_fields(v))
                (event_md if n == 4 else stat_md)[entry.get(1, 0)] = \
                    _fields(entry.get(2, b""))
        tf_op_ids = {key for key, md in stat_md.items()
                     if _text(dict(md).get(2, b"")) == "tf_op"}
        named = {}
        for key, md in event_md.items():
            op_name = ""
            for n, v in md:
                if n == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in tf_op_ids and 5 in stat:
                        op_name = _text(stat[5])
            named[key] = (_text(dict(md).get(2, b"")), op_name)
        ops = out.setdefault(int(m.group(1)), [])
        for n, line in fields:
            if n != 3:
                continue
            line = _fields(line)
            if next((_text(v) for k, v in line if k == 2), "") != OPS_LINE:
                continue
            t0 = next((v for k, v in line if k == 3), 0)
            for k, ev in line:
                if k != 4:
                    continue
                ev = dict(_fields(ev))
                name, op_name = named.get(ev.get(1, 0), ("", ""))
                start = t0 + ev.get(2, 0) / 1e3
                ops.append((name, start, start + ev.get(3, 0) / 1e3,
                            op_name))
    return out
