"""Pallas kernels: device time of the flash kernels, found by their names
(`custom-call:flash_*`), over device-busy time in the traced window, in %.
In a delta-rule hybrid the latent-attention layers' calls are the only flash
calls, so this is their share (`attn_share_pct` by another name: that
entry's list of cells is not this PR's to edit)."""
from benchmark.layer_metrics import _hybrid


def read(record):
    return _hybrid.share_pct(record, _hybrid.FLASH)
