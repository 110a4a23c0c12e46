"""Step program: per traced step, the device ms under the expert layer's
four op types (forward, replayed forward and backward) of the six expert
layers, each over the 2T rows a document's two copies make; median over
steps. `expert_layer_ms` by another name: that entry's list of cells is not
a program PR's to edit."""
from benchmark.layer_metrics.expert_layer_ms import read  # noqa: F401
