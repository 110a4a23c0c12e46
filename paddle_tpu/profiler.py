"""Profiler.

Reference parity: python/paddle/fluid/profiler.py — but TPU profiling goes
through jax.profiler (XPlane traces viewable in TensorBoard/Perfetto).

``stop_profiler`` (and the ``profiler()`` context) print, as the
reference's does, a sorted table — of the FUSED step's real device time:
one row per ``<role>/<op type>`` the program lowered its ops under
(``forward/mul``, ``backward/layer_norm``, ``optimize/adam``; Pallas
kernels under their own names: ``backward/flash_bwd``, or
``backward/flash_bwd_dkv`` and ``backward/flash_bwd_dq`` where a call's
shapes keep the two backward kernels (a window, or rows too long for
VMEM: ``flash_attention.backward_rule``); ``unscoped`` for what XLA added),
with calls, total, average and share, read from the
trace just written (``framework/xplane.py``). A CPU trace has no device
plane and gives no table.

Rides the framework.obs spans engine as well: ``annotate`` opens an obs
span, and while obs is enabled every obs span (the Executor's
``exec.step`` > ``exec.feed``/``prepare``/``compile``/``execute``/
``writeback`` > ``exec.fetch`` among them) is mirrored as a jax
``TraceAnnotation`` — so inside a running profiler session they sit on
the host's thread line on the profiler's clock, beside the device's
operations, and on the cross-process obs timeline
(``tools/traceview.py``) as before.
"""
import contextlib

import jax

from .framework import obs, xplane


DEFAULT_PATH = "/tmp/paddle_tpu_profile"
_SORT_COLUMN = {"calls": 1, "total": 2, "ave": 3, "max": 4, "min": 5}
_session = {"path": None}


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path=DEFAULT_PATH):
    start_profiler(state, profile_path=profile_path)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


def start_profiler(state="All", tracer_option=None,
                   profile_path=DEFAULT_PATH):
    jax.profiler.start_trace(profile_path)
    _session["path"] = profile_path


def stop_profiler(sorted_key=None, profile_path=None):
    """Stop the trace and print the device-time table of what it holds
    (``sorted_key``: "total" (default), "calls", "ave", "max", "min").
    Returns the table's rows."""
    jax.profiler.stop_trace()
    path = profile_path or _session["path"] or DEFAULT_PATH
    rows = op_table(path, sorted_key)
    print_table(rows)
    print_kernel_plans()
    return rows


PLAN_RECORDS = ("flash.plan", "ssm.plan", "head.plan", "kda.plan",
                "ssd.plan", "moe_gmm.plan")


def print_kernel_plans():
    """What the lowerings made while obs was on planned to do, one line a
    record, under the table: `flash.plan` (tiles, tiles visited and skipped
    by causality and by the window, group size, widths, the fused or the
    split backward), `ssm.plan` (chunk length, chunks, VMEM asked),
    `head.plan` (the LM head: rows, vocab, block rows and blocks, weighted
    or per-token form, operand dtype), `kda.plan` (the delta rule: chunk,
    sub-block, chunks a group, heads, which kernels), `ssd.plan` (the
    Mamba-2 scan: heads, groups, state, chunk, chunks, padding, the states
    the backward keeps, which kernels: `ssd_fwd` / `ssd_bwd` with the heads
    a grid step holds and their VMEM, or the XLA form) and `moe_gmm.plan`
    (a grouped matmul: each kernel's tiles, grid, modelled HBM bytes and
    their ratio to the least, VMEM)."""
    for name in PLAN_RECORDS:
        for plan in obs.spans(name=name):
            print("%s %s" % (plan["name"], " ".join(
                "%s=%s" % kv for kv in sorted(plan["labels"].items()))))


def op_table(path, sorted_key=None):
    """Rows ``(name, calls, total_ms, ave_ms, max_ms, min_ms, share_pct)``
    of the device time in the trace at ``path`` (a file or a directory),
    one per ``<role>/<op type>`` / kernel name. Each instant is given to
    the innermost running operation (a ``while`` holds its body's ops), so
    the totals add up to the device-busy time; several chips add up."""
    col = _SORT_COLUMN.get(sorted_key or "total")
    if col is None:
        raise ValueError("sorted_key %r: one of %s"
                         % (sorted_key, sorted(_SORT_COLUMN)))
    acc = {}
    for ops in xplane.device_ops(path).values():
        for (name, ns) in _exclusive_ns(ops):
            row = acc.setdefault(xplane.scope_row(name), [0, 0.0, 0.0, None])
            row[0] += 1
            row[1] += ns
            row[2] = max(row[2], ns)
            row[3] = ns if row[3] is None else min(row[3], ns)
    busy = sum(r[1] for r in acc.values())
    rows = [(name, c, tot / 1e6, tot / c / 1e6, mx / 1e6, mn / 1e6,
             100.0 * tot / busy if busy else 0.0)
            for name, (c, tot, mx, mn) in acc.items()]
    rows.sort(key=lambda r: (-r[col], r[0]))
    return rows


def _exclusive_ns(ops):
    """[(op_name, ns)] per device operation of one chip, each instant
    counted once, for the innermost operation running."""
    events = sorted(ops, key=lambda ev: (ev[1], -ev[2]))
    own = [0.0] * len(events)
    stack, at = [], 0.0     # stack of (end, index)

    def advance(to):
        nonlocal at
        while stack:
            end, i = stack[-1]
            upto = min(end, to)
            if upto > at:
                own[i] += upto - at
                at = upto
            if end > to:
                return
            stack.pop()
        at = max(at, to)

    for i, (_name, start, end, _op) in enumerate(events):
        advance(start)
        at = max(at, start)
        stack.append((end, i))
    advance(float("inf"))
    return [(ev[3], ns) for ev, ns in zip(events, own)]


def print_table(rows, top_k=None):
    if not rows:
        print("profiler: the trace holds no device operations (a CPU "
              "run); no table")
        return
    print("%-44s %8s %12s %12s %8s" % ("Role/Op", "Calls", "Total(ms)",
                                       "Ave(ms)", "Share%"))
    for name, calls, total, ave, _mx, _mn, share in rows[:top_k]:
        print("%-44s %8d %12.3f %12.4f %8.2f" % (name, calls, total, ave,
                                                 share))
    print("%-44s %8d %12.3f" % ("device busy", sum(r[1] for r in rows),
                                sum(r[2] for r in rows)))


def reset_profiler():
    pass


@contextlib.contextmanager
def annotate(name):
    """A named region in the profiler's trace and, while obs is enabled,
    on the obs timeline too (obs mirrors its spans into the trace)."""
    if obs.enabled():
        with obs.span(str(name)):
            yield
    else:
        with jax.profiler.TraceAnnotation(str(name)):
            yield


@contextlib.contextmanager
def cuda_profiler(output_file=None, output_mode=None, config=None):
    """ref profiler.cuda_profiler — no CUDA here; delegates to the XLA
    trace so existing scripts still produce a usable profile."""
    import warnings
    warnings.warn("cuda_profiler on paddle_tpu records a jax.profiler "
                  "trace instead of a CUDA profile")
    jax.profiler.start_trace(output_file or "/tmp/paddle_tpu_profile")
    try:
        yield
    finally:
        jax.profiler.stop_trace()
