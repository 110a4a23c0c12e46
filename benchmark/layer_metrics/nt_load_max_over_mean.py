"""Step program: the fullest held expert's rows over the mean held expert's
rows (1 = an even fold), from the `moe.load` spans; median over the traced
steps, the worst layer. `moe_load_max_over_mean` by another name."""
from benchmark.layer_metrics.moe_load_max_over_mean import read  # noqa: F401
