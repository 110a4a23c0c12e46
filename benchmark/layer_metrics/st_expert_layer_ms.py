"""Step program: per traced step, the device ms under the expert layer's
four op types (`_moe.OP_TYPES`; forward, replayed forward and backward);
median over steps. `expert_layer_ms` by another name: that entry's list of
cells is not this PR's to edit."""
from benchmark.layer_metrics import _hybrid, _moe


def read(record):
    return _hybrid.op_type_ms(record, _moe.OP_TYPES)
