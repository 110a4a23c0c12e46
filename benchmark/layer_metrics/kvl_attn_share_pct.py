"""Pallas kernels: device time of the flash kernels, found by their names
(`custom-call:flash_*`), over device-busy time in the traced window, in %:
latent attention is every layer's mixer here, so this is the five layers'
calls. `mla_attn_share_pct` by another name: that entry's list of cells is
not a program PR's to edit."""
from benchmark.layer_metrics.mla_attn_share_pct import read  # noqa: F401
