"""Step program: per traced step, the device ms under the Mamba-2 layers'
own op types (`_ssd.OP_TYPES`: `mamba2_scan`, the `causal_conv1d` in front of
it and `mamba2_gate_norm` behind it; forward, replayed forward and
backward); median over steps. The mixers' two projections are `mul` and not
in it. None on a program without the ops."""
from benchmark.layer_metrics import _ssd


def read(record):
    return _ssd.device_ms(record)
