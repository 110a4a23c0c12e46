"""Pallas kernels: the least time the step's block-diffusion attention calls
could take (the family's `attention_calls` through `flops_bd.py`: FLOPs over
the T^2 + T L pairs a head's queries see, against q, k, v, o moved once over
their 2T rows, the replay counted, over `peaks.json`) over the flash
kernels' device time under the `block_diffusion_attention` scope, in %: the
same required work whatever implements the mask."""
from benchmark.layer_metrics import _bd


def read(record):
    return _bd.roofline_pct(record)
