"""Pallas kernels: the least time the step's latent-attention flash calls
could take (`_hybrid.attention_least_seconds` over the family's
`attention_calls`: each call's larger of FLOPs over peak FLOP/s and bytes
over peak B/s, FLOPs by the visible area T^2/2 at the q/k width 192 and the
value width 128; recompute's second forward in both terms) over the flash
kernels' device time, in %."""
from benchmark.layer_metrics import _hybrid


def read(record):
    return _hybrid.roofline_pct(record, _hybrid.FLASH,
                                _hybrid.attention_least_seconds)
