"""Step program: the fullest held expert's rows over the mean held expert's
rows (1 = even routing), from the `moe.load` spans; median over the traced
steps, the worst layer. `moe_load_max_over_mean` by another name."""
from benchmark.layer_metrics import _moe


def read(record):
    return _moe.load_max_over_mean(record)
