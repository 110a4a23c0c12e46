"""Pallas kernels: the least time the step's grouped-matmul calls could take
(the family's `gmm_calls`: K 2560, N 1536 and K 768, N 2560 over the held
experts, each call's own roofline from `flops_moe.py` at the rows the step
COUNTED in its `moe.load` spans) over the `moe_gmm_*` kernels' device time,
in %. `moe_gmm_roofline_pct` by another name, for the same reason as
`st_expert_layer_ms`."""
from benchmark.layer_metrics import _hybrid, _moe


def read(record):
    return _hybrid.roofline_pct(record, _moe.GMM, _moe.gmm_least_seconds)
