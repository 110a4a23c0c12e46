"""Pallas kernels: the least time the delta-rule kernel calls that the
traced step RAN could take, over those calls' device time, in %. The least
time is by call: `flops_kda.call_flops` and `call_bytes` (the recurrent
form's required work; bytes once a tensor) over `benchmark/peaks.json`, the
larger of the two a call, the forward terms for every `kda_fwd*` call seen
and the backward terms for every backward seen (one `kda_bwd*` kernel's
calls, or the most-called one where several kernels share a backward) — so a
set of kernels that covers only the forward is held to the forward's work
and cannot pass 100%. The calls are counted and timed in the traced window
on the device's `XLA Ops` line, by the `name=` the program gives its
`pallas_call`s (`custom-call:kda_*`). Imports nothing of `paddle_tpu`; None
on a program with no such kernel."""
import re

from benchmark import flops, flops_kda, trace_reduce
from benchmark.layer_metrics import _scopes

KDA = re.compile(r"^custom-call:kda_")
FORWARD = re.compile(r"^custom-call:kda_fwd")
BACKWARD = re.compile(r"^custom-call:kda_bwd")


def calls_seen(record):
    """{kernel kind: (calls, device seconds)} of the `kda_*` custom calls
    inside the traced window, mean over chips; {} where there are none."""
    trace = _scopes.trace_of(record)
    if not trace:
        return {}
    window = _scopes.window_of(trace["host"])
    if window is None:
        return {}
    _line, lo, hi = window
    chips = len(trace["devices"])
    found = {}
    for dev in trace["devices"].values():
        for name, start, end, _tf_op in dev["ops"]:
            kind = trace_reduce.op_kind(name)
            if KDA.search(kind) and start >= lo and end <= hi:
                calls, seconds = found.get(kind, (0, 0.0))
                found[kind] = (calls + 1.0 / chips,
                               seconds + (end - start) / 1e9 / chips)
    return found


def least_seconds(record, seen):
    """Least seconds of the calls in `seen`, each by its own roofline."""
    cell = record["cell"]
    s = cell.family.sizes(cell.config)
    shape = (cell.family.batch_rows(cell.traffic), cell.traffic["seq_len"],
             s["heads"][1], s["dk"], s["dk"])
    itemsize = {"bfloat16": 2, "float32": 4}[cell.config["precision"]]
    ops = flops_kda.call_flops(*shape)
    moved = flops_kda.call_bytes(*shape, itemsize)
    forward = sum(n for kind, (n, _s) in seen.items()
                  if FORWARD.search(kind))
    backward = max([n for kind, (n, _s) in seen.items()
                    if BACKWARD.search(kind)], default=0)
    return sum(calls * flops.roofline_seconds(ops[i], moved[i],
                                              record["peaks"])[0]
               for i, calls in enumerate((forward, backward)))


def read(record):
    if not record.get("peaks"):
        return None
    seen = calls_seen(record)
    seconds = sum(s for _n, s in seen.values())
    if not seconds:
        return None
    return 100.0 * least_seconds(record, seen) / seconds
