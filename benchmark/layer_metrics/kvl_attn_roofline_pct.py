"""Pallas kernels: the least time the step's latent-attention flash calls
could take (the family's `attention_calls`: 5 layers x 16 heads, the visible
area T^2/2 at the q/k width 192 and the value width 128, recompute's second
forward in the count) over the flash kernels' device time, in %.
`mla_attn_roofline_pct` by another name."""
from benchmark.layer_metrics.mla_attn_roofline_pct import read  # noqa: F401
