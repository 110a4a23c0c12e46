"""Pallas kernels: the least time the step's grouped-matmul calls could take
(the family's `gmm_calls`: two matrices an expert, K 2688 / N 1856 and
K 1856 / N 2688 over the held experts at the PUBLISHED width, at the rows the
step COUNTED in its `moe.load` spans) over the `moe_gmm_*` kernels' device
time, in %: what a whole-width tile gives at 1856 = 14.5 x 128.
`moe_gmm_roofline_pct` by another name."""
from benchmark.layer_metrics.moe_gmm_roofline_pct import read  # noqa: F401
