"""Pallas kernels: the least time the step's flash calls could take (the
family's `attention_calls`: 32 query heads on 2 key heads, a group of 16,
D 128, the visible area T^2/2, recompute's second forward in the count)
over the flash kernels' device time, in %. `mla_attn_roofline_pct` by
another name."""
from benchmark.layer_metrics.mla_attn_roofline_pct import read  # noqa: F401
