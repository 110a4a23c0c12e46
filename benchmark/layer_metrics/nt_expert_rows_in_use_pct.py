"""Step program: of the expert layers' worst-case row buffers, the share
the step's plans laid out, in %, from the `moe.load` spans' `rows_in_use`
and `rows_buffer`; median over the traced steps.
`expert_rows_in_use_pct` by another name."""
from benchmark.layer_metrics.expert_rows_in_use_pct import read  # noqa: F401
