"""Shared by the metrics of the sparse-expert cells: the device time under
the expert layer's op types (`moe_route`, `moe_dispatch`, `moe_experts`,
`moe_combine`: the scopes `framework/trace.py` lowers the four ops under,
both roles), the grouped-matmul kernels by the `name=` of their
`pallas_call`s (`custom-call:moe_gmm_*`), and the rows the step really
routed, from the `moe.load` spans `Executor.run` records while obs is on
(labels `layer`, `rows_held`, `rows_max`, `rows_mean`). The spans are read
for the steps the profiler covered and no others, so that the rows stand
against the kernel time of the same steps (the expert bias's update holds
the held experts' rows near their share, but they still differ step by
step). Imports nothing of `paddle_tpu`; where a program has no such scope,
kernel or span, every function returns None."""
import re
import statistics

from benchmark import flops_moe
from benchmark.harness import TRACE_WARM_STEPS
from benchmark.layer_metrics import _hybrid

OP_TYPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine")
AROUND = ("moe_route", "moe_dispatch", "moe_combine")
GMM = re.compile(r"^custom-call:moe_gmm_")
SPAN = "moe.load"


def loads(record):
    """{layer: [that layer's `moe.load` labels, one a traced step]}: the
    `trace_steps` steps from window step TRACE_WARM_STEPS on (obs is cleared
    at the window's start, so a layer's n-th span is window step n), or
    None where the record holds none."""
    by_layer = {}
    for span in record.get("obs_spans") or ():
        if span.get("name") == SPAN:
            labels = span.get("labels") or {}
            by_layer.setdefault(labels.get("layer"), []).append(labels)
    steps = int(record["cell"].traffic.get("trace_steps", 0)) \
        if by_layer else 0
    traced = {layer: seen[TRACE_WARM_STEPS:TRACE_WARM_STEPS + steps]
              for layer, seen in by_layer.items()}
    return {layer: seen for layer, seen in traced.items() if seen} or None


def share_pct(record):
    """Device ms a step under the four op types over the step's device
    ms, in %."""
    ms = _hybrid.op_type_ms(record, OP_TYPES)
    step = (record.get("traced") or {}).get("step_busy_ms")
    return None if ms is None or not step else 100.0 * ms / step


def load_max_over_mean(record):
    """The fullest held expert's rows over the mean held expert's, median
    over the steps, of the layer where that is largest."""
    by_layer = loads(record)
    if not by_layer:
        return None
    worst = [statistics.median(
        s["rows_max"] / s["rows_mean"] for s in steps if s["rows_mean"])
        for steps in by_layer.values()
        if any(s["rows_mean"] for s in steps)]
    return max(worst) if worst else None


def gmm_least_seconds(record):
    """Least seconds of one step's grouped-matmul calls at the rows the
    step COUNTED (median over the window's steps, a layer): each call's own
    roofline, the recompute's second forward in the family's counts."""
    cell = record["cell"]
    calls = getattr(cell.family, "gmm_calls", None)
    by_layer = loads(record)
    if calls is None or not by_layer or not record.get("peaks"):
        return None
    itemsize = {"bfloat16": 2, "float32": 4}[cell.config["precision"]]
    total = 0.0
    for call in calls(cell.config, cell.traffic):
        steps = by_layer.get(call["layer"])
        if not steps:
            return None
        rows = statistics.median(s["rows_held"] for s in steps)
        for kernel in flops_moe.KERNELS:
            seconds, _bound = flops_moe.gmm_least_seconds(
                kernel, rows, call["k"], call["n"], call["groups"],
                itemsize, record["peaks"])
            total += call[kernel] * seconds
    return total or None
