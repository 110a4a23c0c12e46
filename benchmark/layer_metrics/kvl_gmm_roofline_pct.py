"""Pallas kernels: the least time the step's grouped-matmul calls could take
(the family's `gmm_calls`: K 2048, N 2816 and K 1408, N 2048 over the held
experts, at the rows the step COUNTED in its `moe.load` spans) over the
`moe_gmm_*` kernels' device time, in %: what the tile plan gives at an
expert width of 1408 = 11 x 128, whose only 128-multiple divisor is 128.
`moe_gmm_roofline_pct` by another name."""
from benchmark.layer_metrics.moe_gmm_roofline_pct import read  # noqa: F401
