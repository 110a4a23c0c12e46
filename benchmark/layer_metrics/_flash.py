"""Shared by the two flash metrics: which device operations are the flash
attention kernels, and the least time their calls could take.

Matching rule (PERF.md "Layers" records the names seen on the chip): the
`pallas_call`s carry no `name=`, so the kernels appear on the XLA Ops line
as custom calls named after the JAX scope they were traced in: `jvp__` (the
forward), `rematted_computation` (the recompute's forward) and `checkpoint`
(the two backward kernels). `trace_reduce.op_kind` turns every custom call
into `custom-call:<scope>`; all of them are counted, because the GPT step
has no other Pallas kernel on by default and the scopes' names are JAX's,
not the program's."""
import re

from benchmark import flops

KERNEL = re.compile(r"^custom-call:")


def kernel_seconds(record):
    """Device seconds of the flash kernels in the traced window (mean over
    chips), or None where the trace has none."""
    traced = record.get("traced")
    if not traced:
        return None
    found = sum(s for kind, s in traced["op_seconds"].items()
                if KERNEL.search(kind))
    return found or None


def least_seconds(record):
    """(least seconds of one step's calls, which bound applies)."""
    cell = record["cell"]
    calls = cell.family.attention_calls(cell.config, cell.traffic)
    if not calls or not record.get("peaks"):
        return None, None
    itemsize = {"bfloat16": 2, "float32": 4}[cell.config["precision"]]
    total_flops = total_bytes = 0
    for kind, b, h, t, d, causal, count in calls:
        which = 0 if kind == "forward" else 1
        total_flops += count * flops.flash_call_flops(b, h, t, d,
                                                      causal)[which]
        total_bytes += count * flops.flash_call_bytes(b, h, t, d,
                                                      itemsize)[which]
    return flops.roofline_seconds(total_flops, total_bytes, record["peaks"])
