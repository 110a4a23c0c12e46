"""Step program: per traced step, the device ms under the expert layer's
four op types (forward, replayed forward and backward) of the three expert
layers; median over steps. The shared expert's MLP is `mul` and not in it.
`expert_layer_ms` by another name: that entry's list of cells is not a
program PR's to edit."""
from benchmark.layer_metrics.expert_layer_ms import read  # noqa: F401
