"""Pallas kernels: device time of the flash kernels, found by their names
(`custom-call:flash_*`), over device-busy time in the traced window, in %:
the one attention layer's calls at 16 query heads a key head.
`mla_attn_share_pct` by another name: that entry's list of cells is not a
program PR's to edit."""
from benchmark.layer_metrics.mla_attn_share_pct import read  # noqa: F401
