"""Shared by the metrics of the delta-rule (KDA) layers: the device time
under the op types a KDA mixer lowers besides its matmuls (`kda_attention`,
the chunked gated delta rule, and what stands around it: `causal_conv1d`,
`head_l2_norm`, `kda_gate`, `kda_out_norm`), both roles, as
`framework/trace.py` scopes them. Imports nothing of `paddle_tpu`; where a
program has no such scope, every function returns None."""
from benchmark.layer_metrics import _hybrid

OP_TYPES = ("kda_attention", "causal_conv1d", "head_l2_norm", "kda_gate",
            "kda_out_norm")


def device_ms(record):
    """Device ms a traced step under OP_TYPES (median over steps)."""
    return _hybrid.op_type_ms(record, OP_TYPES)


def share_pct(record):
    """`device_ms` over the step's device ms, in %."""
    ms = device_ms(record)
    step = (record.get("traced") or {}).get("step_busy_ms")
    return None if ms is None or not step else 100.0 * ms / step
