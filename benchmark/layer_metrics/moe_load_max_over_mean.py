"""Step program: the fullest held expert's rows over the mean held expert's
rows (1 = even routing), from the `moe.load` spans the Executor records a
step and expert layer while obs is on; median over the traced window's
steps, the worst layer. What the grouped matmul's groups looked like when
the other three metrics were read."""
from benchmark.layer_metrics import _moe


def read(record):
    return _moe.load_max_over_mean(record)
