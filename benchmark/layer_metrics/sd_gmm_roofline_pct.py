"""Pallas kernels: the least time the step's grouped-matmul calls could take
(the family's `gmm_calls`: two matrices an expert, K 2048 / N 1536 and
K 768 / N 2048 over the held experts, at the rows the step COUNTED in its
`moe.load` spans) over the `moe_gmm_*` kernels' device time, in %.
`moe_gmm_roofline_pct` by another name."""
from benchmark.layer_metrics.moe_gmm_roofline_pct import read  # noqa: F401
