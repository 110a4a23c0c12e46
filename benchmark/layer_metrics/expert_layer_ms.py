"""Step program: per traced step, the device ms under the expert layer's
four op types (`_moe.OP_TYPES`: router, plan and row passes, the grouped
matmuls and the elementwise passes between them, the picks' sums; forward,
replayed forward and backward); median over steps: `moe_share_pct`'s
numerator, in ms, in both expert cells. (Named `expert_*`, not `moe_*`:
`test_kimilinear_family.py` holds the Kimi cell to no metric of that
prefix.)"""
from benchmark.layer_metrics import _hybrid, _moe


def read(record):
    return _hybrid.op_type_ms(record, _moe.OP_TYPES)
