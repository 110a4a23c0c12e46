"""Pallas kernels: device time of the flash kernels (`custom-call:flash_*`)
under the `block_diffusion_attention` scope over device-busy time in the
traced window, in %."""
from benchmark.layer_metrics import _bd


def read(record):
    return _bd.share_pct(record)
